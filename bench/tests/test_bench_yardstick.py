"""Bytes, operations and peaks: the yardstick every rate and share uses."""
import pytest

from bench.lib import yardstick


def test_reduce_bytes_are_input_plus_output():
    rows = 2 ** 26 // 16
    assert yardstick.op_bytes("reduce", rows, 16) == 2 ** 28 + 2 ** 24


def test_scan_bytes_are_input_plus_output_of_same_size():
    assert yardstick.op_bytes("scan", 2 ** 22, 16) == 2 ** 29
    assert yardstick.op_bytes("scan", 1024, 65536) == 2 ** 29


@pytest.mark.parametrize("op", ["reduce", "scan"])
def test_least_time_is_bandwidth_bound(op):
    p = yardstick.peaks_for("TPU v5 lite")
    t = yardstick.op_least_s(op, 2 ** 22, 16, p)
    assert t == pytest.approx(yardstick.op_bytes(op, 2 ** 22, 16) / 819e9)


def test_unknown_op_raises():
    with pytest.raises(ValueError):
        yardstick.op_bytes("sort", 4, 4)


def test_v5e_peaks_and_source():
    p = yardstick.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_s) == (197e12, 819e9)
    assert "TPU v5e" in p.source


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        yardstick.peaks_for("TPU v99")

