"""External hash aggregation: oracle correctness + exact D/C ledger parity.

The headline contract (ISSUE 2 acceptance): eagg's *simulated* transfer
ledger matches the ceil-exact closed form ``eagg_costs_exact`` on every
Table I / TESTBED tier, including skewed partition sizes, and tracks the
smooth Property-6 round-count closed forms.
"""

import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core import TABLE_I, TESTBED
from repro.core.policies import (
    eagg_costs_exact,
    eagg_data_costs,
    eagg_optimal_round_costs,
    eagg_plan,
    eagg_round_costs,
    eagg_starved,
)
from repro.engine import WorkloadStats, plan_operator
from repro.remote import RemoteMemory, Relation, eagg, eagg_oracle
from repro.remote.rows import partition_of

TIER = TESTBED["remon_tcp"]
ROWS = 8
_TIERS = list(TABLE_I.values()) + list(TESTBED.values())


def _mk_relation(remote, n_pages, domain, seed=0, skew=0.0):
    """Relation with optionally Zipf-skewed keys (skew > 0 concentrates mass)."""
    rng = np.random.default_rng(seed)
    n_rows = n_pages * ROWS
    if skew > 0.0:
        # numpy's zipf needs an exponent > 1; a tiny skew rounds 1 + skew to 1.
        ranks = rng.zipf(max(1.0 + skew, 1.0 + 1e-6), size=n_rows).astype(np.int64)
        keys = np.minimum(ranks - 1, domain - 1)
    else:
        keys = rng.integers(0, domain, size=n_rows, dtype=np.int64)
    payload = np.arange(n_rows, dtype=np.int64)
    rows = np.stack([keys, payload], axis=1)
    pages = [rows[i : i + ROWS] for i in range(0, n_rows, ROWS)]
    ids = remote.put_local(pages)
    return Relation(page_ids=ids, rows_per_page=ROWS, total_rows=n_rows)


def _exact_inputs(remote, rel, plan):
    """Recompute the skew-aware workload detail eagg_costs_exact needs."""
    rows = np.concatenate(remote.peek_batch(rel.page_ids), axis=0)
    parts = partition_of(rows[:, 0], plan.partitions)
    n_spilled = int(round(plan.sigma * plan.partitions))
    spilled = list(range(plan.partitions - n_spilled, plan.partitions))
    spilled_rows = [int((parts == q).sum()) for q in spilled]
    spill_mask = np.isin(parts, spilled)
    resident_groups = len(np.unique(rows[~spill_mask][:, 0]))
    spilled_groups = len(np.unique(rows[spill_mask][:, 0]))
    return spilled_rows, resident_groups, spilled_groups


def test_eagg_output_matches_oracle():
    remote = RemoteMemory(TIER)
    rel = _mk_relation(remote, 120, 96, seed=1)
    plan = eagg_plan(n=120, out=12, m_b=16, partitions=8, sigma=0.5)
    res = eagg(remote, rel, plan)
    want = eagg_oracle(remote, rel)
    got = np.concatenate(remote.peek_batch(res.output_page_ids), axis=0)
    got = got[np.argsort(got[:, 0], kind="stable")]
    assert res.group_rows == len(want)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("tier", _TIERS, ids=[t.name for t in _TIERS])
@pytest.mark.parametrize("skew", [0.0, 1.2], ids=["uniform", "zipf"])
def test_eagg_ledger_matches_exact_closed_form_on_all_tiers(tier, skew):
    """Acceptance: simulated ledger == ceil-exact D/C on every tier, skew incl."""
    remote = RemoteMemory(tier)
    rel = _mk_relation(remote, 160, 512, seed=3, skew=skew)
    stats = WorkloadStats(size_r=160, out=32, partitions=16, sigma=0.5)
    plan = plan_operator("eagg", stats, tier, 20)
    res = eagg(remote, rel, plan)
    d_want, c_want = eagg_costs_exact(160, ROWS, *_exact_inputs(remote, rel, plan),
                                      plan)
    assert res.d_read + res.d_write == d_want
    assert res.c_read + res.c_write == c_want


@settings(max_examples=8, deadline=None)
@given(
    n_pages=st.integers(40, 200), parts=st.sampled_from([4, 8, 16]),
    sigma=st.sampled_from([0.25, 0.5, 0.75]), skew=st.floats(0.0, 1.5),
    seed=st.integers(0, 99),
)
@example(n_pages=40, parts=4, sigma=0.25, skew=2.2e-311, seed=0)
def test_eagg_correct_and_exact_for_any_plan(n_pages, parts, sigma, skew, seed):
    """Property: oracle-identical groups and exact ledger for arbitrary plans."""
    remote = RemoteMemory(TIER)
    rel = _mk_relation(remote, n_pages, 256, seed=seed, skew=skew)
    plan = eagg_plan(n=n_pages, out=n_pages / 8, m_b=12, partitions=parts,
                     sigma=sigma)
    res = eagg(remote, rel, plan)
    want = eagg_oracle(remote, rel)
    assert res.group_rows == len(want)
    got = np.concatenate(remote.peek_batch(res.output_page_ids), axis=0)
    got = got[np.argsort(got[:, 0], kind="stable")]
    np.testing.assert_array_equal(got, want)
    d_want, c_want = eagg_costs_exact(n_pages, ROWS,
                                      *_exact_inputs(remote, rel, plan), plan)
    assert res.d_read + res.d_write == d_want
    assert res.c_read + res.c_write == c_want


def test_eagg_smooth_round_closed_form_tracks_waterfill():
    """Property-6 algebra: waterfill allocation attains the C_i* closed forms."""
    n, out, m_b, parts, sigma = 160.0, 32.0, 20.0, 16, 0.5
    plan = eagg_plan(n, out, m_b, parts, sigma)
    c1, c2 = eagg_round_costs(n, out, plan)
    c1_star, c2_star = eagg_optimal_round_costs(n, out, m_b, parts, sigma)
    assert c1 == pytest.approx(c1_star, rel=1e-9)
    assert c2 == pytest.approx(c2_star, rel=1e-9)
    # And the starved baseline is strictly worse on both phases.
    starved = eagg_starved(m_b, parts, sigma)
    s1, s2 = eagg_round_costs(n, out, starved)
    assert s1 > c1 and s2 > c2


def test_eagg_measured_rounds_track_smooth_closed_form():
    """Simulated rounds within ceil-effect tolerance of the C* algebra.

    Budget and partition count are sized so the per-stream pool slices don't
    all floor to one page — at that point every policy degenerates and the
    smooth model no longer describes the engine's integer slicing.
    """
    remote = RemoteMemory(TIER)
    n_pages, out_pages = 320, 40
    rel = _mk_relation(remote, n_pages, out_pages * ROWS, seed=5)
    plan = eagg_plan(n_pages, out_pages, 32, 8, 0.5)
    res = eagg(remote, rel, plan)
    c_star = sum(eagg_optimal_round_costs(n_pages, out_pages, 32, 8, 0.5))
    assert res.c_read + res.c_write == pytest.approx(c_star, rel=0.2)
    d_star = sum(eagg_data_costs(n_pages, out_pages, 0.5))
    assert res.d_read + res.d_write == pytest.approx(d_star, rel=0.15)


def test_eagg_remop_beats_starved_in_rounds_and_latency():
    remote = RemoteMemory(TIER)
    rel = _mk_relation(remote, 200, 256, seed=7)
    stats = WorkloadStats(size_r=200, out=32, partitions=8, sigma=0.5)
    tau = TIER.tau_pages

    before = remote.ledger.latency_cost(tau)
    res_s = eagg(remote, rel, plan_operator("eagg", stats, TIER, 24,
                                            policy="conventional"))
    mid = remote.ledger.latency_cost(tau)
    res_r = eagg(remote, rel, plan_operator("eagg", stats, TIER, 24))
    after = remote.ledger.latency_cost(tau)
    assert res_r.group_rows == res_s.group_rows
    assert res_r.c_write < res_s.c_write
    assert after - mid < mid - before  # REMOP latency cost strictly lower
