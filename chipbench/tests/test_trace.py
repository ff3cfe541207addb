"""The trace reduction on a trace recorded on a TPU v5e, and on made-up
intervals whose answers are known."""

import json
import pathlib

import pytest

from chipbench import trace

RECORDED = pathlib.Path(__file__).parent / "data" / "trace_v5e.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def _host(recorded):
    plane = next(p for p in recorded["planes"] if p["name"] == trace.HOST_PLANE)
    return next(ln for ln in plane["lines"] if ln["name"] == "python")["events"]


def test_union_and_complement():
    merged = trace.union([(5, 6), (0, 2), (1, 3), (8, 12)], 0, 10)
    assert merged == [(0, 3), (5, 6), (8, 10)]
    assert trace.complement(merged, 0, 10) == [(3, 5), (6, 8)]
    assert trace.complement([], 2, 4) == [(2, 4)]


def test_innermost_names_each_segment_by_its_deepest_span():
    spans = [("window", 0, 100), ("query", 10, 60), ("hook", 20, 30), ("free", 50, 60)]
    assert trace.innermost(spans, 0, 100) == [
        (0, 10, "window"), (10, 20, "query"), (20, 30, "hook"), (30, 50, "query"),
        (50, 60, "free"), (60, 100, "window")]


def test_idle_gaps_split_by_span():
    segs = trace.innermost([("window", 0, 100), ("query", 10, 60)], 0, 100)
    idle = trace.split_by_segments([(0, 20), (50, 70)], segs)
    assert idle == pytest.approx({"between queries": 20e-9, "query": 20e-9})


def test_recorded_window_modules_and_ops(recorded):
    red = trace.reduce(recorded)
    window = [d for n, s, d in _host(recorded) if n == "window"]
    assert red.window_s == pytest.approx(window[0] * 1e-9)
    assert red.devices == 1
    # Two queries, each one sort call and one partition call.
    assert {k: len(v) for k, v in red.modules.items()} == {
        "jit_remop_sort": 2, "jit__group_by_part": 2}
    assert red.device_ops[0][0] == "jit__group_by_part/%_group_by_part.1"
    assert 0.0 < red.busy_s < red.window_s
    assert red.idle_share > 0.99


def test_recorded_clock_shift_puts_modules_after_dispatch(recorded):
    red = trace.reduce(recorded)
    assert red.clock_shift_s > 0.0
    host = _host(recorded)
    dispatch = sorted(s for n, s, _ in host if n == "PjitFunction(remop_sort)")
    device = next(p for p in recorded["planes"] if trace.DEVICE_PLANE.match(p["name"]))
    runs = sorted(s for n, s, _ in
                  next(ln for ln in device["lines"] if ln["name"] == trace.MODULE_LINE)["events"]
                  if trace.module_name(n) == "jit_remop_sort")
    assert all(d + red.clock_shift_s * 1e9 >= h for h, d in zip(dispatch, runs))


def test_recorded_idle_time_is_accounted_to_host_spans(recorded):
    red = trace.reduce(recorded)
    idle = dict(red.idle_by_span)
    assert sum(idle.values()) + red.busy_s == pytest.approx(red.window_s, rel=1e-9)
    for name in ("hook.sort_keys", "hook.partition_rows", "query", "between queries"):
        assert idle[name] > 0.0


def test_reduce_needs_one_window_and_a_device(recorded):
    with pytest.raises(RuntimeError, match="'no such span' span, found 0 among"):
        trace.reduce(recorded, window="no such span")
    host_only = {"planes": [p for p in recorded["planes"] if p["name"] == trace.HOST_PLANE]}
    with pytest.raises(RuntimeError, match="device plane"):
        trace.reduce(host_only)
