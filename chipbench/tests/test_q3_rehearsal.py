"""The TPC-H Q3 cell through the harness at a tiny scale on the CPU (kernels
interpreted): the window's queries match the query's own reference, the
traced run reads the Q3 cell's per-layer metrics, and the check fails when
the program drops a filter or returns one row too many."""

import json

import pytest

from chipbench import harness
from chipbench.tests import tiny
from chipbench.tests.test_rehearsal import rehearse
from repro import spans

CELL = "q3-spill"
# The configuration cut to a CPU test's size (the copy's file alone changes):
# about 300 customers, 3,000 orders and 12,000 lineitem rows in 16 KiB pages,
# so each of the four operators still spills half its partitions.
SMALL = dict(scale_factor=0.002, page_bytes=16384)
CHECKS = {"join_rows_differing", "agg_groups_differing", "top_rows_differing"}
Q3_METRICS = {"filter_s", "agg_s", "sort_s", "plan_s.q3", "join_s.q3", "hash_s.q3",
              "buffer_s.q3", "tier_host_s.q3", "hook_host_s.q3", "pad_share.q3"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax

    root = tiny.copy(tmp_path_factory.mktemp("bench"), jax.devices()[0].device_kind)
    tiny._edit(root / "chipbench" / "configs" / "tpch-q3.json", **SMALL)
    return root


@pytest.fixture(autouse=True)
def tracer_off():
    spans.disable()
    yield
    spans.disable()


def _queries(lines):
    return [json.loads(s.split(": ", 1)[1]) for s in lines if s.startswith("query ")]


def test_q3_cell_matches_its_reference(root):
    cell = harness.load_cell(CELL, root)
    assert cell.traffic["budget_pages"] == 8 and cell.params["sigma"] == 0.5
    line, lines = rehearse(root, CELL, seed=2**31 + 3, seconds=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"] == {c: {"value": 0, "limit": 0} for c in CHECKS}
    assert set(line["metrics"]) == {"query_s", "setup_s"}
    window = next(s for s in lines if s.startswith("window:"))
    assert window.endswith("0 compile requests inside it")
    queries = _queries(lines)
    assert queries and all(len(q["phase_rounds"]) == 2 for q in queries)
    # Both joins spill: their third phase moves pages.
    assert all(p["P3"] > 0 for q in queries for p in q["phase_rounds"])
    assert all(len(q["merge_passes"]) == 1 for q in queries)


def test_q3_traced_window_reads_its_metrics(root, monkeypatch):
    """The traced run reports the Q3 cell's own readers and its ``.q3``
    entries (the CPU's trace has no device plane, so the reduction is
    replaced and the partition roofline has nothing to read)."""
    from chipbench import trace

    monkeypatch.setattr(trace, "reduce", lambda loaded, window=trace.WINDOW: trace.Reduction(
        window_s=1.0, busy_s=0.25, devices=1, modules={}, device_ops=[],
        idle_by_span=[], clock_shift_s=0.0))
    line, lines = rehearse(root, CELL, seed=11, seconds=0.0, trace=True)
    assert line["correct"] is True
    assert Q3_METRICS <= set(line["metrics"]), sorted(line["metrics"])
    assert {"host_op_s", "transfer_s", "rounds", "hook_s", "device_idle",
            "window_compiles"} <= set(line["metrics"])
    assert "join_s" not in line["metrics"]  # the pkfk cells' entry
    assert all(m["value"] > 0 for n, m in line["metrics"].items() if n in Q3_METRICS
               and n != "pad_share.q3")
    q = _queries(lines)[0]
    assert q["kernel_fallbacks"] == 0 and q["host_pinned_pages"] == 0
    assert {"ehj.filter", "eagg.P1", "eagg.P2", "eagg.hash", "eagg.group",
            "ems.rows"} <= set(q["spans"])
    c = q["counts"]
    assert c["filter.rows_out"] < c["filter.rows_in"]
    assert c["eagg.groups"] > 0 and c["ems.rows_in"] > 0


@pytest.mark.parametrize("fault", ["drop l_shipdate", "limit + 1"])
def test_q3_check_fails_a_broken_program(root, monkeypatch, fault):
    """The program drops lineitem's filter, or its ORDER BY keeps one row too
    many: the query's own check sees it."""
    from repro.engine.plan import LogicalPlan

    if fault == "drop l_shipdate":
        inner = LogicalPlan.filter

        def dropped(self, child, selectivity, name=None, predicate=None, where=None):
            return inner(self, child, selectivity, name=name, predicate=predicate,
                         where=None if name == "l_shipdate" else where)

        monkeypatch.setattr(LogicalPlan, "filter", dropped)
        failing = "join_rows_differing"
    else:
        inner = LogicalPlan.sort
        monkeypatch.setattr(LogicalPlan, "sort",
                            lambda self, child, limit=None, **kw:
                            inner(self, child, limit=limit + 1, **kw))
        failing = "top_rows_differing"
    line, _ = rehearse(root, CELL, seed=5, seconds=0.0)
    assert line["correct"] is False and line["failed"] >= 1
    assert line["checks"][failing]["value"] > 0


def test_q3_control_runs_and_the_program_reads_zero(root):
    """``python3 -m chipbench.control`` on the Q3 cell: the shared controls
    are column-0 ones, whose rows are not Q3's, and Q3's references take
    them as inputs without failing; the program reads 0, the control not."""
    from chipbench import control

    r = control.readings(harness.load_cell(CELL, root), 7, log=lambda s: None)
    assert r["program"] == {c: 0 for c in CHECKS}
    assert set(r["control"]) == CHECKS and min(r["control"].values()) > 0
