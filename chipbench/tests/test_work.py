"""The peaks table and the kernels' work functions."""

import json
import pathlib

import pytest

from chipbench import trace, work

RECORDED = pathlib.Path(__file__).parent / "data" / "trace_v5e.json"


def test_peaks_of_a_v5e():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v4"):
        work.peaks("TPU v4")


@pytest.mark.parametrize("kernel, call, nbytes", [
    ("sort", {"n": 1 << 21}, 16_777_216),
    ("sort", {"n": 600_572}, 4_804_576),
    ("partition", {"n": 1 << 17, "d": 2}, 2_621_440),
    ("partition", {"n": 16_384, "d": 3}, 458_752),
])
def test_bytes_of_known_shapes(kernel, call, nbytes):
    assert work.kernel(kernel).bytes_moved(call) == nbytes


class _Record:
    here = work.HERE
    peaks = {"hbm_bytes_per_s": 819e9}

    def __init__(self, hook_calls, reduction):
        self.hook_calls = hook_calls
        self.trace = reduction


def test_roofline_share_on_the_recorded_trace():
    red = trace.reduce(json.loads(RECORDED.read_text()))
    calls = {"sort_keys": [{"n": 1 << 14}] * 2,
             "partition_rows": [{"n": 4096, "d": 2}] * 2}
    rec = _Record(calls, red)
    sort_s = sum(red.modules["jit_remop_sort"])
    assert work.roofline_share(rec, "sort") == pytest.approx(
        100 * 2 * (2 * 4 * (1 << 14)) / 819e9 / sort_s)
    part_s = sum(red.modules["jit__group_by_part"])
    assert work.roofline_share(rec, "partition") == pytest.approx(
        100 * 2 * (4096 * 4 + 2 * 4096 * 2 * 4) / 819e9 / part_s)
    assert 0 < work.roofline_share(rec, "sort") < 100


def test_roofline_share_reads_nothing_without_calls_or_trace():
    red = trace.reduce(json.loads(RECORDED.read_text()))
    assert work.roofline_share(_Record({}, red), "sort") is None
    assert work.roofline_share(_Record({"sort_keys": [{"n": 8}]}, None), "sort") is None
