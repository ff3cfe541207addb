"""The program's tracer (``repro.spans``): off it records nothing, on it
nests spans with their self seconds, counts, and lands on the profiler's
host plane where the benchmark's trace reduction names idle time by it."""

import glob
import importlib
import os
import time

import numpy as np
import pytest

from repro import spans
from repro.core import TESTBED
from repro.core.policies import ehj_plan
from repro.remote import RemoteMemory
from repro.remote.simulator import Relation


@pytest.fixture(autouse=True)
def tracer_off():
    spans.disable()
    yield
    spans.disable()


def _busy(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_off_records_nothing_and_returns_the_shared_no_op():
    a, b = spans.span("a"), spans.span("b")
    assert a is b
    with spans.Recorder(0) as r:
        with spans.span("a"):
            spans.count("n", 3)
    assert r.totals == {} and r.counts == {} and r.spans == []


def test_nested_spans_give_self_plus_children_equal_inclusive():
    spans.enable()
    with spans.Recorder(7) as r:
        with spans.span("outer"):
            _busy(0.002)
            with spans.span("inner"):
                _busy(0.003)
            with spans.span("inner"):
                with spans.span("leaf"):
                    _busy(0.001)
    t = r.totals
    assert {n: x.calls for n, x in t.items()} == {"outer": 1, "inner": 2, "leaf": 1}
    assert t["outer"].self_s + t["inner"].inclusive_s == pytest.approx(t["outer"].inclusive_s)
    assert t["inner"].self_s + t["leaf"].inclusive_s == pytest.approx(t["inner"].inclusive_s)
    assert t["leaf"].self_s == pytest.approx(t["leaf"].inclusive_s)
    assert t["outer"].self_s >= 0.002 and t["inner"].self_s >= 0.003
    parents = {(s.name, s.parent) for s in r.spans}
    assert parents == {("outer", None), ("inner", "outer"), ("leaf", "inner")}
    assert {s.query for s in r.spans} == {7}
    outer = next(s for s in r.spans if s.name == "outer")
    assert all(outer.start <= s.start <= s.end <= outer.end for s in r.spans)


def test_counters_add_and_the_recorder_resets_per_query():
    spans.enable()
    with spans.Recorder(0) as first:
        spans.count("rows", 5)
        spans.count("rows", 2)
        spans.count("calls")
        with spans.span("a"):
            pass
    with spans.Recorder(1) as second:
        spans.count("rows", 1)
    assert first.counts == {"rows": 7, "calls": 1}
    assert second.counts == {"rows": 1}
    assert second.totals == {} and second.spans == []
    assert list(first.totals) == ["a"]


def test_a_span_open_around_recorders_is_their_parent_and_theirs_alone():
    spans.enable()
    with spans.Recorder(-1) as outer:
        with spans.span("window"):
            for q in range(2):
                with spans.Recorder(q) as r:
                    with spans.span("query"):
                        _busy(0.001)
                assert [(s.name, s.parent, s.query) for s in r.spans] == [("query", "window", q)]
    assert [s.name for s in outer.spans] == ["window"]
    window = outer.totals["window"]
    assert window.self_s <= window.inclusive_s - 0.002


def test_disable_inside_an_open_span_still_closes_it():
    spans.enable()
    with spans.Recorder(0) as r:
        with spans.span("a"):
            spans.disable()
            with spans.span("b"):  # off now: not recorded
                pass
    assert list(r.totals) == ["a"]


def test_program_spans_land_on_the_profiler_host_plane(tmp_path):
    """Under ``jax.profiler`` on the CPU the spans appear on the host thread
    that holds the ``window`` span, nested, and the benchmark's reduction
    names segments by them."""
    import jax
    import jax.numpy as jnp

    from chipbench import trace

    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    spans.enable(annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("window"):
            with spans.span("query"):
                with spans.span("ehj.join"):
                    _busy(0.002)
                with spans.span("hook.device"):
                    jax.block_until_ready(x @ x)
    finally:
        jax.profiler.stop_trace()
        spans.disable()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    data = trace.load(found[0])
    lines = [ln for p in data["planes"] if p["name"] == trace.HOST_PLANE
             for ln in p["lines"] if any(e[0] == "window" for e in ln["events"])]
    assert len(lines) == 1
    events = {e[0]: (e[1], e[1] + e[2]) for e in lines[0]["events"]
              if e[0] in ("window", "query", "ehj.join", "hook.device")}
    assert set(events) == {"window", "query", "ehj.join", "hook.device"}
    (w0, w1), (q0, q1) = events["window"], events["query"]
    assert w0 <= q0 <= q1 <= w1
    for name in ("ehj.join", "hook.device"):
        assert q0 <= events[name][0] <= events[name][1] <= q1
    segs = trace.innermost([(n, s, e) for n, (s, e) in events.items()], w0, w1)
    named = {n for _, _, n in segs}
    assert {"ehj.join", "hook.device", "query", "window"} <= named
    join = [b - a for a, b, n in segs if n == "ehj.join"]
    assert sum(join) == pytest.approx(events["ehj.join"][1] - events["ehj.join"][0])


@pytest.mark.parametrize("unique", [True, False], ids=["unique", "duplicate"])
def test_the_hash_join_indexes_each_partition_once(monkeypatch, unique):
    """Traced, the external hash join opens one ``ehj.table`` span per build
    partition it indexes (the resident ones in P1, each spilled one in P3),
    probes every probe row once, and takes the one-search path exactly where
    the build keys are unique."""
    ehj = importlib.import_module("repro.remote.ehj")
    indexed, probed = [], []
    build_index, probe_index = ehj.build_index, ehj.probe_index

    def counted_build(rows):
        index = build_index(rows)
        indexed.append(index.unique)
        return index

    def counted_probe(index, rows):
        probed.append(len(rows))
        return probe_index(index, rows)

    monkeypatch.setattr(ehj, "build_index", counted_build)
    monkeypatch.setattr(ehj, "probe_index", counted_probe)

    rng = np.random.default_rng(5)
    n_build, n_probe, rows = 512, 2048, 16
    build_keys = rng.permutation(4096)[:n_build] if unique else rng.integers(0, 64, n_build)
    probe_keys = rng.integers(0, 4096 if unique else 64, n_probe)
    remote = RemoteMemory(TESTBED["remon_tcp"])

    def relation(keys):
        t = np.stack([keys, np.arange(len(keys))], axis=1).astype(np.int64)
        return Relation(remote.put_local([t[i:i + rows] for i in range(0, len(t), rows)]),
                        rows, len(t))

    build, probe = relation(build_keys), relation(probe_keys)
    plan = ehj_plan(b=n_build / rows, q=n_probe / rows, out=n_probe / rows, m_b=12,
                    partitions=8, sigma=0.5)
    spans.enable()
    with spans.Recorder(0) as r:
        res = ehj.ehj(remote, build, probe, plan)
    # Every partition of both sides holds rows at this size: 4 indexed in
    # P1, 4 in P3, and every probe row reaches one of them.
    assert r.totals["ehj.table"].calls == len(indexed) == 8
    assert sum(indexed) == (8 if unique else 0)
    assert sum(probed) == n_probe
    assert r.counts["ehj.join_calls"] == r.totals["ehj.join"].calls == len(probed)
    assert r.counts["ehj.join_rows_out"] == res.output_rows > 0
