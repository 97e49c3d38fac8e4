"""Operations and bytes from shapes, and the peaks table."""
import pytest

from bench import counts


def test_codec_counts_on_known_shapes():
    # one frame of 32 records of 2304 floats: 288 rows of 256
    ops, nbytes = counts.quant(288)
    assert nbytes == 288 * 256 * 4 + 288 * 256 + 288 * 4
    assert ops == 4 * 288 * 256
    ops, nbytes = counts.dequant(288)
    assert nbytes == 288 * 256 + 288 * 4 + 288 * 256 * 4
    assert ops == 288 * 256


def test_peaks_by_exact_device_kind():
    pk = counts.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v5e")
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_roofline_share_is_bound_by_bytes_here():
    _ops, nbytes = counts.quant(288)
    least = nbytes / 819e9
    assert counts.roofline_share(*counts.quant(288), least * 2,
                                 "TPU v5 lite") == pytest.approx(50.0)
    assert counts.roofline_share(1.0, 1.0, 0.0, "TPU v5 lite") is None


def test_codec_kernel_names_and_rows():
    assert counts.codec_kernel("_quantize") == "quant"
    assert counts.codec_kernel("_dequantize") == "dequant"
    assert counts.codec_kernel("fusion") is None
    quant = [("s8", (512, 256), 0), ("f32", (512, 1), 1), ("f32", (512, 256), 0)]
    assert counts.codec_rows("quant", quant) == ("f32", (512, 256))
    dequant = [("f32", (512, 256), 0), ("s8", (512, 256), 1), ("f32", (512, 1), 1)]
    assert counts.codec_rows("dequant", dequant) == ("s8", (512, 256))


def test_hbm_bytes_leave_out_vmem():
    # every array in HBM: the algorithm's own count
    quant = [("s8", (288, 256), 0), ("f32", (288, 1), 0), ("f32", (288, 256), 0)]
    assert counts.hbm_bytes(quant) == counts.quant(288)[1]
    dequant = [("f32", (288, 256), 0), ("s8", (288, 256), 0), ("f32", (288, 1), 0)]
    assert counts.hbm_bytes(dequant) == counts.dequant(288)[1]
    # codes and scales already copied into VMEM: only the output is HBM's
    dequant = [("f32", (288, 256), 0), ("s8", (288, 256), 1), ("f32", (288, 1), 1)]
    assert counts.hbm_bytes(dequant) == 288 * 256 * 4
