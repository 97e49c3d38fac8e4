"""Share of the roofline reached by the Pallas ``gram_pair`` kernel on the
device, in %: the least time its calls could take on this chip over the
kernel's own device time (its ``tpu_custom_call`` events).

Per call, the operations and the bytes of the arrays that live in HBM come
from the call's shapes (``bench/gram_counts.py``): 4·n·d² operations; X and
Y read once, G and A read and written once.  At d = 2304 the bytes of G and
A bound it."""
from bench import counts, gram_counts
from bench.trace import arrays


def read(run):
    if not run.trace:
        return None
    ops = nbytes = seconds = 0.0
    for name, secs, text in run.trace["kernel_calls"]:
        if name != gram_counts.KERNEL:
            continue
        found = gram_counts.call_counts(arrays(text))
        if found is None:
            continue
        ops += found[0]
        nbytes += found[1]
        seconds += secs
    return counts.roofline_share(ops, nbytes, seconds, run.device_kind)
