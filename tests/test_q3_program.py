"""TPC-H Q3 on the normal path, and the operator features it needs.

Q3 (``chipbench/queries/q3.py``: its generator, plan and numpy reference) at
scale factor 0.005 through ``LogicalPlan`` -> ``compile_plan`` ->
``Session.run`` on the ``ExecutionBackend`` (kernels interpreted) equals the
reference: the final rows in order and every task's output as a multiset.
Then each part on its own against plain numpy: joins on named columns with
duplicate build keys, filters that run where the scan is read (same read
rounds as without them), exact int64 sums, row sorts with a limit (and the
limit's closed form), lossless wide device pages, and the shared partition
hash."""

import json
import pathlib

import numpy as np
import pytest

from chipbench import check, harness, work
from repro.core.cost_model import TESTBED
from repro.core.policies import (
    eagg_plan,
    ehj_plan,
    ems_limit_costs,
    ems_plan,
)
from repro.engine import Session
from repro.engine.plan import LogicalPlan, compile_plan
from repro.engine.registry import WorkloadStats, get
from repro.remote import RemoteMemory, Relation, eagg, ehj, ems_sort, make_hierarchy
from repro.remote.ems import row_order, sort_rows
from repro.remote.rows import partition_of

ROOT = pathlib.Path(__file__).resolve().parents[1]
Q3 = work.load_module(ROOT / "chipbench" / "queries" / "q3.py")
TIER = TESTBED["remon_tcp"]


def _config(scale_factor=0.005, page_bytes=8192):
    config = json.loads((ROOT / "chipbench" / "configs" / "tpch-q3.json").read_text())
    config.update(scale_factor=scale_factor, page_bytes=page_bytes)
    return config


def _relation(remote, rows: np.ndarray, rows_per_page: int) -> Relation:
    pages = [rows[i:i + rows_per_page].copy() for i in range(0, len(rows), rows_per_page)]
    return Relation(page_ids=remote.put_local(pages), rows_per_page=rows_per_page,
                    total_rows=len(rows))


def _rows(remote, page_ids) -> np.ndarray:
    return np.concatenate(remote.peek_batch(page_ids), axis=0)


# --------------------------------------------------------------------------
# Q3 end to end
# --------------------------------------------------------------------------


@pytest.mark.parametrize("sigma", [0.5, 0.0])
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_q3_through_the_session_equals_the_reference(seed, sigma):
    from repro.remote import make_backend

    config = _config()
    tables = Q3.tables(config, seed)
    backend = make_backend("remon_tcp")
    inputs = Q3.place(backend, tables, config)
    session = Session(backend, budget=8)
    result = Q3.run(session, inputs, config, {"sigma": sigma, "partitions": 8},
                    harness.Spans())
    struct = harness.structure(result, inputs)
    assert [op for op, _ in struct] == ["ehj", "ehj", "eagg", "ems"]
    got = [_rows(backend, get(tr.op).output_of(tr.result)) for tr in result.per_task]
    rules = check.rules(Q3, config)
    want = rules.outputs(struct, tables)
    assert rules.count(struct, got, want) == {
        "join_rows_differing": 0, "agg_groups_differing": 0, "top_rows_differing": 0}
    assert len(got[-1]) == 10 and np.array_equal(got[-1], want[-1])
    # The four tasks run one after another, so each was planned (and
    # replanned) with the whole 8-page budget.
    assert [tr.m_pages for tr in result.per_task] == [8.0] * 4
    # Revenue sums pass int32, and their pages still crossed to the device.
    assert got[2][:, 3].max() > np.iinfo(np.int32).max
    assert backend.wall.host_pinned_pages == 0 and backend.wall.kernel_fallbacks == 0
    # With nothing spilled, a join's third phase is at most its last output flush.
    spilled = [tr.result.per_phase_rounds for tr in result.per_task if tr.op == "ehj"]
    assert all((p["P3"] > 1) == (sigma > 0) for p in spilled)


def test_compile_plan_keeps_q3_written_order_and_each_join_its_keys(monkeypatch):
    import repro.engine.plan as plan_mod

    config = _config(scale_factor=0.001)
    remote = make_hierarchy("remon_tcp")
    inputs = Q3.place(remote, Q3.tables(config, 3), config)
    compiled = []

    def capture(session, lp):
        compiled.append(compile_plan(session, lp))
        return compiled[-1]

    monkeypatch.setattr(plan_mod, "compile_plan", capture)
    Q3.run(Session(remote, budget=8), inputs, config, {"sigma": 0.5, "partitions": 8},
           harness.Spans())
    cp = compiled[0]
    first, second, agg, sort = cp.tasks
    c = lambda t, n: Q3.col(config, t, n)  # noqa: E731
    assert first.inputs["build"] is inputs["customer"]
    assert first.inputs["probe"] is inputs["orders"]
    assert first.options["on"] == (c("customer", "c_custkey"), c("orders", "o_custkey"))
    assert second.inputs["build"].task is first
    assert second.inputs["probe"] is inputs["lineitem"]
    assert second.options["on"] == (Q3.CO.index("o_orderkey"), c("lineitem", "l_orderkey"))
    assert "build_where" in first.options and "probe_where" in first.options
    assert "build_where" not in second.options and "probe_where" in second.options
    assert agg.options["by"] == (0, 1, 2) and sort.options["limit"] == 10
    assert cp.pushed_filters == ["c_mktsegment", "o_orderdate", "l_shipdate"]
    assert cp.annotation_filters == [] and cp.join_choices == []


def _aggregate_int32(ins, config):
    """Q3's group-by with revenue summed in int32, the precision below the
    int64 the configuration states: a control in the program's place."""
    rows = ins["rel"]
    cols = [Q3.COL.index(n) for n in Q3.GROUPS]
    keys, inverse, counts = np.unique(rows[:, cols], axis=0, return_inverse=True,
                                      return_counts=True)
    line = (rows[:, Q3.COL.index("l_extendedprice")]
            * (100 - rows[:, Q3.COL.index("l_discount")])).astype(np.int32)
    revenue = np.zeros(len(keys), np.int32)
    np.add.at(revenue, inverse.ravel(), line)
    return np.column_stack([keys, revenue.astype(np.int64), counts]).astype(np.int64)


def test_revenue_summed_in_int32_fails_q3_check():
    """The int32-revenue control in the program's place reads over the limit
    of 0 on the group-by and the top rows, where the program reads 0."""
    from repro.remote import make_backend

    config = _config()
    tables = Q3.tables(config, 3141592653)
    backend = make_backend("remon_tcp")
    inputs = Q3.place(backend, tables, config)
    result = Q3.run(Session(backend, budget=8), inputs, config,
                    {"sigma": 0.5, "partitions": 8}, harness.Spans())
    struct = harness.structure(result, inputs)
    got = [_rows(backend, get(tr.op).output_of(tr.result)) for tr in result.per_task]
    rules = check.rules(Q3, config)
    want = rules.outputs(struct, tables)
    control = rules.outputs(struct, tables, {**rules.reference, "eagg": _aggregate_int32})
    assert max(rules.count(struct, got, want).values()) <= check.LIMIT
    counts = rules.count(struct, control, want)
    assert counts["join_rows_differing"] == 0
    assert counts["agg_groups_differing"] > check.LIMIT
    assert counts["top_rows_differing"] > check.LIMIT


def test_q3_chain_needs_stages_to_plan_at_8_pages(monkeypatch):
    """Q3's four tasks each need 3 pages when they share one budget, so 8
    pages are refused; as stages, one task each, every task gets all 8."""
    import repro.engine.plan as plan_mod

    config = _config(scale_factor=0.001)
    remote = make_hierarchy("remon_tcp")
    inputs = Q3.place(remote, Q3.tables(config, 3), config)
    compiled = []

    def capture(session, lp):
        compiled.append(compile_plan(session, lp))
        return compiled[-1]

    monkeypatch.setattr(plan_mod, "compile_plan", capture)
    session = Session(remote, budget=8)
    Q3.run(session, inputs, config, {"sigma": 0.5, "partitions": 8}, harness.Spans())
    tasks = compiled[0].tasks
    assert Session.stages(tasks) == [[0], [1], [2], [3]]
    with pytest.raises(ValueError, match="below the pipeline floor 12"):
        session.plan(tasks, dag=True)
    assert session.plan(tasks, dag=True, stages=True).budgets == (8.0,) * 4
    with pytest.raises(ValueError, match="stages=True needs"):
        session.run(tasks, stages=True)


def test_stages_share_the_budget_only_among_tasks_that_may_run_together():
    """Two independent joins feed a third, then a sort: the two share a
    stage and split the budget; the join and the sort each get all of it."""
    session = Session(TIER, budget=12)
    stats = WorkloadStats(size_r=8, size_s=32, out=32, partitions=8)
    a = session.task("ehj", stats, label="a")
    b = session.task("ehj", stats, label="b")
    c = session.task("ehj", stats, inputs={"build": a.output, "probe": b.output},
                     label="c")
    d = session.task("ems", WorkloadStats(size_r=32), inputs={"page_ids": c.output},
                     label="d")
    assert Session.stages([a, b, c, d]) == [[0, 1], [2], [3]]
    budgets = session.plan([a, b, c, d], stages=True).budgets
    assert budgets[0] + budgets[1] == 12 and budgets[2:] == (12.0, 12.0)
    # A task off the chain may run beside the chain's tasks: one stage.
    c2 = session.task("ehj", stats, inputs={"build": a.output}, label="c2")
    d2 = session.task("ehj", stats, inputs={"build": c2.output, "probe": b.output},
                      label="d2")
    assert Session.stages([a, c2, b, d2]) == [[0, 1, 2], [3]]
    # Without stages the budget is shared as before.
    assert sum(session.plan([a, b, c, d], dag=True).budgets) == 12


def test_a_where_filter_off_a_scan_is_refused():
    remote = make_hierarchy("remon_tcp")
    rel = _relation(remote, np.arange(64, dtype=np.int64).reshape(32, 2), 8)
    lp = LogicalPlan("q")
    lp.sort(lp.filter(lp.scan("t", rel, rows_per_page=8), 0.5, where=[(0, "<", 9)]))
    with pytest.raises(ValueError, match="where compiles onto an ehj side or an eagg"):
        compile_plan(Session(remote, budget=12), lp)
    with pytest.raises(ValueError, match="comparison"):
        lp.filter(lp.scan("t", rel, rows_per_page=8), 0.5, where=[(0, "~", 9)])


# --------------------------------------------------------------------------
# The external hash join on named columns, with filters
# --------------------------------------------------------------------------


def _join_numpy(build, bkey, bkeep, probe, pkey, pkeep):
    pairs = [(i, j) for i in range(len(build)) for j in np.flatnonzero(
        probe[:, pkey] == build[i, bkey])]
    return np.array([[build[i, bkey], *build[i, list(bkeep)], *probe[j, list(pkeep)]]
                     for i, j in pairs], dtype=np.int64).reshape(len(pairs), -1)


@pytest.mark.parametrize("sigma", [0.0, 0.5, 1.0])
def test_ehj_on_named_columns_with_duplicate_build_keys(sigma):
    rng = np.random.default_rng(int(sigma * 4))
    build = np.stack([rng.integers(0, 5, 300), rng.integers(0, 40, 300),
                      np.arange(300), rng.integers(-9, 9, 300)], axis=1).astype(np.int64)
    probe = np.stack([np.arange(500), rng.integers(0, 50, 500),
                      rng.integers(0, 1000, 500)], axis=1).astype(np.int64)
    remote = RemoteMemory(TIER)
    b, p = _relation(remote, build, 16), _relation(remote, probe, 16)
    plan = ehj_plan(b=19, q=32, out=30, m_b=12, partitions=8, sigma=sigma)
    res = ehj(remote, b, p, plan, on=(1, 1), keep=((3, 2), (2, 0)), page_bytes=1024)
    got = _rows(remote, res.output_page_ids)
    want = _join_numpy(build, 1, (3, 2), probe, 1, (2, 0))
    assert got.shape[1] == 5 and len(want) > len(probe)  # build keys repeat
    assert check.rows_differing(got, want) == 0
    assert res.output_rows == len(want)
    # Output pages hold 1024 bytes of 5-column rows.
    assert max(len(pg) for pg in remote.peek_batch(res.output_page_ids)) == 1024 // 40


def _read_rounds_of(remote, page_ids):
    """Count the read rounds that touch ``page_ids``."""
    ids, rounds = set(page_ids), []
    inner = remote.read_batch

    def counted(batch, prefetched=False):
        if ids & set(batch):
            rounds.append(len(batch))
        return inner(batch, prefetched)

    remote.read_batch = counted
    return rounds


@pytest.mark.parametrize("sigma", [0.0, 0.5])
def test_side_filters_equal_filtering_first_with_the_same_read_rounds(sigma):
    rng = np.random.default_rng(9)
    build = np.stack([rng.permutation(400), rng.integers(0, 10, 400)], axis=1).astype(np.int64)
    probe = np.stack([rng.integers(0, 400, 900), rng.integers(0, 100, 900),
                      np.arange(900)], axis=1).astype(np.int64)
    bw, pw = [(1, "<", 4)], [(1, ">=", 30), (2, "!=", 7)]
    plan = ehj_plan(b=25, q=57, out=20, m_b=12, partitions=8, sigma=sigma)
    kw = dict(on=(0, 0), keep=((1,), (1, 2)))

    remote = RemoteMemory(TIER)
    b, p = _relation(remote, build, 16), _relation(remote, probe, 16)
    reads = _read_rounds_of(remote, b.page_ids + p.page_ids)
    res = ehj(remote, b, p, plan, build_where=bw, probe_where=pw, **kw)
    got = _rows(remote, res.output_page_ids)

    first = RemoteMemory(TIER)
    fb = build[(build[:, 1] < 4)]
    fp = probe[(probe[:, 1] >= 30) & (probe[:, 2] != 7)]
    pre = ehj(first, _relation(first, fb, 16), _relation(first, fp, 16), plan, **kw)
    assert check.rows_differing(got, _rows(first, pre.output_page_ids)) == 0
    assert 0 < len(got) < len(_join_numpy(build, 0, (1,), probe, 0, (1, 2)))

    plain = RemoteMemory(TIER)
    pb, pp = _relation(plain, build, 16), _relation(plain, probe, 16)
    plain_reads = _read_rounds_of(plain, pb.page_ids + pp.page_ids)
    ehj(plain, pb, pp, plan, **kw)
    assert reads == plain_reads and len(reads) > 2


# --------------------------------------------------------------------------
# The external hash aggregation: composite groups, exact int64 sums
# --------------------------------------------------------------------------


@pytest.mark.parametrize("sigma", [0.0, 0.5])
def test_eagg_int64_sums_that_float64_would_round(sigma):
    rng = np.random.default_rng(2)
    n = 4000
    big = 2**53 + rng.integers(1, 1000, n)  # float64 cannot add these exactly
    rows = np.stack([rng.integers(0, 7, n), rng.integers(0, 3, n), big,
                     rng.integers(0, 11, n)], axis=1).astype(np.int64)
    remote = RemoteMemory(TIER)
    rel = _relation(remote, rows, 64)
    plan = eagg_plan(n=63, out=2, m_b=12, partitions=8, sigma=sigma)
    expr = ("*", 3, ("-", ("lit", 100), 1))
    res = eagg(remote, rel, plan, by=(1, 0), sums=(2, expr), where=[(3, "!=", 5)])
    got = _rows(remote, res.output_page_ids)
    keep = rows[rows[:, 3] != 5]
    want = []
    for g1 in range(3):
        for g0 in range(7):
            sel = keep[(keep[:, 1] == g1) & (keep[:, 0] == g0)]
            if len(sel):
                want.append([g1, g0, sum(int(v) for v in sel[:, 2]),
                             int((sel[:, 3] * (100 - sel[:, 1])).sum()), len(sel)])
    assert check.rows_differing(got, np.array(want, dtype=np.int64)) == 0
    # The same sums through float64 would not be exact.
    g = keep[(keep[:, 1] == want[0][0]) & (keep[:, 0] == want[0][1])]
    assert int(np.bincount(np.zeros(len(g), np.int64), weights=g[:, 2].astype(
        np.float64))[0]) != want[0][2]


def test_the_partition_hash_is_the_old_one_on_column_0():
    import importlib

    eagg_mod = importlib.import_module("repro.remote.eagg")
    ehj_mod = importlib.import_module("repro.remote.ehj")
    keys = np.random.default_rng(0).integers(-2**40, 2**40, 5000)
    h = keys.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    old = ((h >> np.uint64(33)) % np.uint64(8)).astype(np.int64)
    assert np.array_equal(partition_of(keys, 8), old)
    assert np.array_equal(partition_of(keys[:, None], 8), old)
    # Both hash operators partition through this one helper.
    assert eagg_mod.partition_of is ehj_mod.partition_of is partition_of
    pairs = np.stack([keys, keys[::-1]], axis=1)
    composite = partition_of(pairs, 8)
    assert composite.min() >= 0 and composite.max() < 8
    assert not np.array_equal(composite, old)


# --------------------------------------------------------------------------
# The external merge sort of rows, with a limit
# --------------------------------------------------------------------------


def _sorted_rows(rows, by):
    keys = [rows[:, c] for c in range(rows.shape[1] - 1, -1, -1) if c not in dict(by)]
    keys += [-rows[:, c] if desc else rows[:, c] for c, desc in reversed(by)]
    return rows[np.lexsort(keys)]


@pytest.mark.parametrize("limit", [None, 10, 500, 10_000])
def test_ems_sorts_rows_on_chosen_columns_with_a_limit(limit):
    rng = np.random.default_rng(4)
    rows = np.stack([np.arange(2048), rng.integers(0, 50, 2048), rng.integers(0, 5, 2048),
                     rng.integers(-2**40, 2**40, 2048)], axis=1).astype(np.int64)
    remote = RemoteMemory(TIER)
    ids = _relation(remote, rows, 32).page_ids
    by = [(1, True), (2, False)]
    plan = ems_plan(len(ids), 8, TIER.tau_pages, k_cap=4)
    res = ems_sort(remote, ids, plan, rows_per_page=32, by=by, limit=limit)
    got = _rows(remote, res.run_page_ids)
    want = _sorted_rows(rows, by)[:limit]
    assert np.array_equal(got, want)
    assert np.array_equal(sort_rows(rows, row_order(by, 4)), _sorted_rows(rows, by))


@pytest.mark.parametrize("limit_rows", [10, 32, 64, 96])
def test_ems_limit_ledger_equals_its_closed_form(limit_rows):
    """Full 32-row pages, 64 of them: the measured rounds and pages of the
    sort with a limit are ``ems_limit_costs``."""
    rows = np.random.default_rng(6).integers(0, 10**6, (64 * 32, 3)).astype(np.int64)
    plan = ems_plan(64, 16, TIER.tau_pages, k_cap=3)
    r_in, r_out = int(plan.input_pages), max(1, int(round(plan.output_pages)))
    limit_pages = -(-limit_rows // 32)
    assert limit_pages <= max(1, r_in // plan.k)
    remote = RemoteMemory(TIER)
    ids = _relation(remote, rows, 32).page_ids
    res = ems_sort(remote, ids, plan, rows_per_page=32, by=[(0, False)], limit=limit_rows)
    d, c, passes = ems_limit_costs(64, int(plan.m), plan.k, r_in, r_out, limit_pages)
    assert (res.d_read + res.d_write, res.c_read + res.c_write, res.passes) == (d, c, passes)
    assert len(_rows(remote, res.run_page_ids)) == limit_rows


# --------------------------------------------------------------------------
# Wide int64 pages on the device
# --------------------------------------------------------------------------


class _LandedMirror:
    """A device mirror whose copy has landed and whose pull is ``pulled``."""

    def __init__(self, pulled):
        self.pulled = pulled

    def is_ready(self):
        return True

    def __array__(self, dtype=None, copy=None):
        return self.pulled


def test_wide_int64_pages_mirror_losslessly():
    from repro.remote import make_backend

    backend = make_backend("remon_tcp")
    wide = np.array([[2**62, -2**62], [2**62 - 1, -2**62 + 1], [3, -(2**40)]],
                    dtype=np.int64)
    flat = np.array([-2**62 - 7, 2**33, 0], dtype=np.int64)
    narrow = np.arange(6, dtype=np.int64).reshape(3, 2)
    ids = backend.put_local([wide, flat, narrow])
    written = backend.write_batch([wide.copy()])
    got = backend.read_batch(ids + written)
    for a, b in zip(got, [wide, flat, narrow, wide]):
        assert a.dtype == np.int64 and np.array_equal(a, b)
    assert backend.wall.host_pinned_pages == 0
    tier = backend.tiers[0]
    assert tier._dev[ids[0]].shape == (3, 4) and tier._dev[ids[2]].shape == (3, 2)
    # A device may hand back a mirror that is not C-contiguous (a TPU does).
    tier._dev[ids[0]] = _LandedMirror(np.asfortranarray(np.asarray(tier._dev[ids[0]])))
    assert np.array_equal(backend.read_batch(ids[:1])[0], wide)
    backend.free(ids)
    assert not tier._wide & set(ids)
