"""Share of the roofline reached by the int8 wire codec's Pallas kernels on
the device, in %: the least time their calls could take on this chip over
the kernels' own device time (the ``tpu_custom_call`` events of
``_quantize`` and ``_dequantize``).

The least time is bound by bytes: per call, the bytes of the kernel's
arrays that live in HBM (``counts.hbm_bytes``; an operand XLA has already
copied into VMEM is read from there, and its copy is not the kernel's
time), at peak HBM bandwidth, for the rows the call was given.  A call of
more than 256 rows runs on rows padded up to a multiple of 256; the rows
it was given are the input of the ``pad`` that fed it."""
from bench import counts
from bench.trace import arrays


def read(run):
    if not run.trace:
        return None
    padded_from: dict = {}          # (dtype, padded dims) -> rows given
    ops = nbytes = seconds = 0.0
    for name, secs, text in run.trace["kernel_calls"]:
        arrs = arrays(text)
        if name == "pad":
            if len(arrs) >= 2:
                padded_from[arrs[0][:2]] = arrs[1][1][0]
            continue
        kind = counts.codec_kernel(name)
        operand = counts.codec_rows(kind, arrs) if kind else None
        if operand is None:
            continue
        rows = operand[1][0]
        given = padded_from.pop(operand, rows)
        ops += getattr(counts, kind)(given)[0]
        nbytes += counts.hbm_bytes(arrs) * given / rows
        seconds += secs
    return counts.roofline_share(ops, nbytes, seconds, run.device_kind)
