"""The program's spans and counters at the tests' size (R 256 x S 4,096 rows,
kernels interpreted): tracing leaves every output and every ledger count as
it was, each layer's spans appear, and the readers of the spans find them in
a traced record and nothing in an untraced one."""

import json

import pytest

from chipbench import harness, program_spans, work
from chipbench.tests import tiny
from chipbench.tests.test_rehearsal import rehearse
from repro import spans

SEED = 2**31 + 11

# The spans and counters one query of the spilling join opens, by layer.
JOIN_SPANS = {
    "task.ehj",
    "ehj.P1", "ehj.P2", "ehj.P3", "ehj.hash", "ehj.join", "ehj.table",
    "pool.add", "pool.flush", "cursor.block",
    "hook.partition_rows", "hook.prepare", "hook.upload", "hook.device",
    "hook.download", "hook.split",
    "tier.write", "tier.read", "tier.check", "tier.put", "tier.pull", "tier.cast",
    "query", "compile_plan", "session.run", "free",
}
JOIN_COUNTS = {"ehj.join_calls", "ehj.join_rows_out", "partition.rows",
               "partition.padded_rows"}
SPAN_METRICS = tuple(program_spans.METRICS)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax

    return tiny.copy(tmp_path_factory.mktemp("bench"), jax.devices()[0].device_kind)


@pytest.fixture(autouse=True)
def tracer_off():
    spans.disable()
    yield
    spans.disable()


def _setup(cell):
    from repro.remote import make_backend

    tables = cell.query.tables(cell.config, SEED)
    backend = make_backend(*[tuple(t) if isinstance(t, list) else t
                             for t in cell.config["tiers"]])
    inputs = cell.query.place(backend, tables, cell.config)
    return backend, inputs, set(backend.resident_ids())


def _query(cell, traced: bool):
    """One query on a fresh backend: (record, result, outputs, backend)."""
    backend, inputs, keep = _setup(cell)
    if not traced:
        rec, result, outputs = harness.run_query(backend, cell, inputs, keep, harness.Spans())
        return rec, result, outputs, backend
    spans.enable()
    try:
        with program_spans.recording(backend, 0) as fields:
            rec, result, outputs = harness.run_query(backend, cell, inputs, keep,
                                                     harness.Spans())
    finally:
        spans.disable()
    rec.update(fields)
    return rec, result, outputs, backend


@pytest.mark.parametrize("name", ["pkfk-spill", "pkfk-inmem"])
def test_tracing_changes_no_output_and_no_ledger_count(root, name):
    cell = harness.load_cell(name, root)
    off_rec, off, off_out, off_backend = _query(cell, traced=False)
    on_rec, on, on_out, on_backend = _query(cell, traced=True)
    assert "spans" not in off_rec and on_rec["spans"]
    assert len(off_out) == len(on_out) == 1
    assert len(off_out[0]) == len(on_out[0]) > 0
    for a, b in zip(off_out[0], on_out[0]):
        assert a.dtype == b.dtype and (a == b).all()
    assert off.total == on.total
    assert [tr.delta for tr in off.per_task] == [tr.delta for tr in on.per_task]
    assert off_backend.snapshot() == on_backend.snapshot()
    assert off_rec["phase_rounds"] == on_rec["phase_rounds"]
    assert off_rec["rounds"] == on_rec["rounds"]
    assert off_rec["kernel_calls"] == on_rec["kernel_calls"] > 0


def test_every_layer_of_the_spilling_join_has_its_spans(root):
    rec, result, _, _ = _query(harness.load_cell("pkfk-spill", root), traced=True)
    assert JOIN_SPANS <= set(rec["spans"])
    assert JOIN_COUNTS <= set(rec["counts"])
    assert rec["spans"]["task.ehj"]["calls"] == 1
    assert all(rec["spans"][f"ehj.P{i}"]["calls"] == 1 for i in (1, 2, 3))
    assert rec["counts"]["ehj.join_calls"] == rec["spans"]["ehj.join"]["calls"]
    assert rec["counts"]["ehj.join_rows_out"] == result.per_task[0].result.output_rows
    assert rec["spans"]["hook.partition_rows"]["calls"] == rec["kernel_calls"]
    assert rec["counts"]["partition.rows"] < rec["counts"]["partition.padded_rows"]
    assert rec["kernel_fallbacks"] == 0 and rec["host_pinned_pages"] == 0
    assert all(s["self_s"] >= 0.0 for s in rec["spans"].values())
    # The spans tile the query: the self seconds of all of them are its span's.
    assert sum(s["self_s"] for s in rec["spans"].values()) == pytest.approx(
        rec["latency_s"], rel=0.05)


def test_the_sort_has_its_spans(root):
    name = tiny.add_sort_cell(root)
    rec, _, _, _ = _query(harness.load_cell(name, root), traced=True)
    assert {"task.ems", "ems.runs", "ems.merge", "hook.sort_keys", "hook.prepare",
            "hook.upload", "hook.device", "hook.download"} <= set(rec["spans"])
    assert rec["counts"]["sort.keys"] <= rec["counts"]["sort.padded_keys"]


def _record(root, queries):
    return harness.Record(here=root / "chipbench", setup_s=1.0, window_s=1.0,
                          queries=queries, hook_calls={}, window_compiles=0, peaks={})


def test_span_readers_read_a_traced_record_and_nothing_else(root):
    cell = harness.load_cell("pkfk-spill", root)
    traced, _, _, _ = _query(cell, traced=True)
    untraced, _, _, _ = _query(cell, traced=False)
    readers = {m: work.load_module(root / "chipbench" / "metrics" / f"{m}.py")
               for m in SPAN_METRICS}
    values = {m: r.read(_record(root, [traced, traced])) for m, r in readers.items()}
    assert all(v is not None and v > 0.0 for v in values.values()), values
    assert values["join_s"] == pytest.approx(traced["spans"]["ehj.join"]["self_s"])
    c = traced["counts"]
    assert values["pad_share"] == pytest.approx(
        100.0 * (1 - c["partition.rows"] / c["partition.padded_rows"]))
    assert all(r.read(_record(root, [untraced])) is None for r in readers.values())
    # The five host shares, the hooks' timed sections and the timed copies
    # cover disjoint stretches of Session.run, and nearly all of it: what is
    # left is glue no span names.
    covered = (sum(values[m] for m in SPAN_METRICS if m != "pad_share")
               + traced["hook_s"] + traced["transfer_s"])
    assert 0.8 * traced["run_s"] <= covered <= traced["run_s"]


def test_the_traced_window_reads_the_program_spans_and_the_untraced_none(root, monkeypatch):
    """``run_cell`` with ``trace`` switches the program's tracer on for its
    window: every query's record carries its spans and counters, and the
    six readers of them report.  The CPU's trace has no device plane, so the
    reduction is replaced by one that checks the host plane and names the
    idle time by its spans."""
    from chipbench import trace

    host_names = set()

    def reduce(loaded, window=trace.WINDOW):
        for plane in loaded["planes"]:
            if plane["name"] == trace.HOST_PLANE:
                for line in plane["lines"]:
                    if any(e[0] == window for e in line["events"]):
                        host_names.update(e[0] for e in line["events"])
        return trace.Reduction(window_s=1.0, busy_s=0.25, devices=1, modules={},
                               device_ops=[], idle_by_span=[("ehj.join", 0.5)],
                               clock_shift_s=0.0)

    monkeypatch.setattr(trace, "reduce", reduce)
    traced, traced_lines = rehearse(root, "pkfk-spill", seconds=0.0, trace=True)
    assert spans.span("after") is spans.span("the window")  # the tracer is off again
    untraced, untraced_lines = rehearse(root, "pkfk-spill", seconds=0.0)

    def queries(lines):
        return [json.loads(s.split(": ", 1)[1]) for s in lines if s.startswith("query ")]

    assert traced["correct"] is True and untraced["correct"] is True
    assert all(q["spans"] and JOIN_COUNTS <= set(q["counts"]) for q in queries(traced_lines))
    assert all("spans" not in q and "counts" not in q for q in queries(untraced_lines))
    # The benchmark's spans and the program's share the window's host thread.
    assert {"window", "query", "session.run", "task.ehj", "ehj.join", "tier.write",
            "hook.partition_rows"} <= host_names
    assert set(SPAN_METRICS) <= set(traced["metrics"])
    assert {"host_op_s", "hook_s", "transfer_s", "rounds", "plan_s"} <= set(traced["metrics"])
    assert traced["metrics"]["device_idle"]["value"] == pytest.approx(75.0)
    assert traced["breakdown"]["idle_gaps"] == [["ehj.join", 0.5]]
    assert set(untraced["metrics"]) == {"query_s", "setup_s"}
