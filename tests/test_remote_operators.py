"""Integration tests: simulated remote-memory operators vs oracles + closed forms.

These validate that the *measured* ledger (D pages, C rounds) of the real
data-plane algorithms matches the paper's §III analysis, and that every
operator produces exactly the oracle output.
"""

import importlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import TESTBED
from repro.core.policies import (
    BNLJPlan, EMSPlan, bnlj_costs_exact, bnlj_plan, ehj_plan, ems_costs_exact,
)
from repro.remote import (
    RemoteMemory, bnlj, bnlj_oracle, ehj, ehj_oracle, ems_sort, ems_oracle,
    make_relation,
)
from repro.remote.bnlj import _block_join
from repro.remote.ehj import build_index, probe_index
from repro.remote.simulator import Relation, make_key_pages, relation_rows

TIER = TESTBED["remon_tcp"]
# The module, not the function ``repro.remote`` exports under its name.
ehj_mod = importlib.import_module("repro.remote.ehj")


def _mk():
    return RemoteMemory(TIER)


# ---------------------------------------------------------------------------
# BNLJ
# ---------------------------------------------------------------------------


def _bnlj_setup(remote, r_pages=20, s_pages=40, rows=32, domain=256, seed=1):
    outer = make_relation(remote, r_pages * rows, rows, domain, seed=seed)
    inner = make_relation(remote, s_pages * rows, rows, domain, seed=seed + 1)
    return outer, inner


def test_bnlj_output_matches_oracle():
    remote = _mk()
    outer, inner = _bnlj_setup(remote)
    plan = BNLJPlan(m=11, r_in=10 / 11, p_r=0.5)
    res = bnlj(remote, outer, inner, plan)
    got = np.concatenate([remote._store[i] for i in res.output_page_ids])
    got = got[np.lexsort((got[:, 2], got[:, 1], got[:, 0]))]
    want = bnlj_oracle(remote, outer, inner)
    assert res.output_rows == len(want)
    np.testing.assert_array_equal(got, want)


def test_bnlj_read_rounds_match_closed_form():
    """Measured C_read/D_read equal the §III-A ceil formulas (zero-output case)."""
    remote = _mk()
    # Disjoint key domains -> no output; isolates the read-side terms.
    outer = make_relation(remote, 500 * 4, 4, 1000, seed=1)
    inner = make_relation(remote, 1000 * 4, 4, 1000, seed=2)
    # Shift inner keys out of range to kill matches.
    for pid in inner.page_ids:
        remote._store[pid][:, 0] += 10_000_000
    for p_r, p_s in [(99, 1), (50, 50), (10, 90)]:
        before_c, before_d = remote.ledger.c_read, remote.ledger.d_read
        plan = BNLJPlan(m=p_r + p_s + 1, r_in=(p_r + p_s) / (p_r + p_s + 1),
                        p_r=p_r / (p_r + p_s))
        res = bnlj(remote, outer, inner, plan)
        d_want, c_want = bnlj_costs_exact(500, 1000, 0, p_r, p_s, 1)
        # closed form counts |R| once and ceil(R/PR)*|S|; ledger counts pages read.
        assert res.c_read == c_want
        assert res.d_read == d_want
        assert res.output_rows == 0


def test_bnlj_worked_example_rounds_on_simulator():
    """§II-C(a) on the live simulator: 6,006 vs 210 read rounds."""
    remote = _mk()
    outer = make_relation(remote, 500, 1, 10, seed=3)
    inner = make_relation(remote, 1000, 1, 10, seed=4)
    for pid in inner.page_ids:
        remote._store[pid][:, 0] += 999_999
    res_conv = bnlj(remote, outer, inner, BNLJPlan(m=101, r_in=100 / 101, p_r=0.99))
    res_eq = bnlj(remote, outer, inner, BNLJPlan(m=101, r_in=100 / 101, p_r=0.5))
    assert res_conv.c_read == 6006
    assert res_eq.c_read == 210
    assert res_eq.d_read / res_conv.d_read == pytest.approx(10500 / 6500, rel=1e-6)


@settings(max_examples=10, deadline=None)
@given(
    r_pages=st.integers(4, 24), s_pages=st.integers(4, 32),
    p_r=st.floats(0.15, 0.85), domain=st.integers(8, 512), seed=st.integers(0, 99),
)
def test_bnlj_correct_for_any_plan(r_pages, s_pages, p_r, domain, seed):
    """Property: output equals oracle for arbitrary buffer plans."""
    remote = _mk()
    outer = make_relation(remote, r_pages * 16, 16, domain, seed=seed)
    inner = make_relation(remote, s_pages * 16, 16, domain, seed=seed + 1)
    plan = BNLJPlan(m=9, r_in=8 / 9, p_r=p_r)
    res = bnlj(remote, outer, inner, plan)
    want = bnlj_oracle(remote, outer, inner)
    assert res.output_rows == len(want)
    if len(want):
        got = np.concatenate([remote._store[i] for i in res.output_page_ids])
        got = got[np.lexsort((got[:, 2], got[:, 1], got[:, 0]))]
        np.testing.assert_array_equal(got, want)


def test_bnlj_remop_beats_conventional_in_latency_cost():
    """The REMOP plan should lower simulated L vs the conventional plan."""
    remote = _mk()
    outer = make_relation(remote, 120 * 8, 8, 64, seed=5)
    inner = make_relation(remote, 240 * 8, 8, 64, seed=6)
    m, tau = 13.0, TIER.tau_pages

    before = remote.ledger.latency_cost(tau)
    res_c = bnlj(remote, outer, inner, BNLJPlan(m=m, r_in=(m - 1) / m, p_r=(m - 2) / (m - 1)))
    mid = remote.ledger.latency_cost(tau)
    res_r = bnlj(remote, outer, inner, bnlj_plan(m, tau, selectivity=1 / 64))
    after = remote.ledger.latency_cost(tau)
    l_conv, l_remop = mid - before, after - mid
    assert res_r.output_rows == res_c.output_rows
    assert l_remop < l_conv
    assert (res_r.c_read + res_r.c_write) < (res_c.c_read + res_c.c_write)


# ---------------------------------------------------------------------------
# EMS
# ---------------------------------------------------------------------------


def test_ems_output_sorted_and_complete():
    remote = _mk()
    ids = make_key_pages(remote, 600, 8, 100000, seed=7)
    plan = EMSPlan(m=24, k=4, r_in=2 / 3)
    res = ems_sort(remote, ids, plan, rows_per_page=8)
    got = np.concatenate([remote._store[i].ravel() for i in res.run_page_ids])
    want = ems_oracle(remote, ids)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=8, deadline=None)
@given(
    n_pages=st.integers(40, 200), k=st.integers(2, 8),
    r_in=st.floats(0.4, 0.9), seed=st.integers(0, 99),
)
def test_ems_correct_for_any_plan(n_pages, k, r_in, seed):
    remote = _mk()
    ids = make_key_pages(remote, n_pages, 8, 10_000, seed=seed)
    plan = EMSPlan(m=10, k=k, r_in=r_in)
    res = ems_sort(remote, ids, plan, rows_per_page=8)
    got = np.concatenate([remote._store[i].ravel() for i in res.run_page_ids])
    np.testing.assert_array_equal(got, ems_oracle(remote, ids))


def test_ems_round_counts_track_closed_form():
    """Merge-phase rounds within ~15% of §III-B's formula (ceil effects)."""
    remote = _mk()
    n_pages, m = 512, 16
    ids = make_key_pages(remote, n_pages, 8, 1 << 30, seed=8)
    k, r_in_pages = 4, 12
    plan = EMSPlan(m=m, k=k, r_in=r_in_pages / m)
    res = ems_sort(remote, ids, plan, rows_per_page=8,
                   count_run_formation=False)
    d_want, c_want, p_want = ems_costs_exact(n_pages, m, k, r_in_pages)
    assert res.passes == p_want
    assert res.d_read + res.d_write == pytest.approx(d_want, rel=0.02)
    assert res.c_read + res.c_write == pytest.approx(c_want, rel=0.15)


def test_ems_k4_beats_duckdb_2way_in_rounds():
    """Paper: RTT-dominated optimum k*=4 uses fewer rounds than 2-way merge."""
    remote = _mk()
    ids = make_key_pages(remote, 256, 8, 1 << 30, seed=9)
    r2 = ems_sort(remote, ids, EMSPlan(m=12, k=2, r_in=2 / 3),
                  rows_per_page=8, count_run_formation=False)
    r4 = ems_sort(remote, ids, EMSPlan(m=12, k=4, r_in=2 / 3),
                  rows_per_page=8, count_run_formation=False)
    assert r4.c_read + r4.c_write < r2.c_read + r2.c_write
    assert r4.passes < r2.passes


# ---------------------------------------------------------------------------
# EHJ
# ---------------------------------------------------------------------------


def test_ehj_output_count_matches_oracle():
    remote = _mk()
    build = make_relation(remote, 64 * 16, 16, 256, seed=10)
    probe = make_relation(remote, 256 * 16, 16, 256, seed=11)
    plan = ehj_plan(b=64, q=256, out=32, m_b=16, partitions=8, sigma=0.5)
    res = ehj(remote, build, probe, plan)
    assert res.output_rows == ehj_oracle(remote, build, probe)


@settings(max_examples=6, deadline=None)
@given(sigma=st.sampled_from([0.25, 0.5, 0.75]), parts=st.sampled_from([4, 8, 16]),
       seed=st.integers(0, 50))
def test_ehj_correct_for_any_plan(sigma, parts, seed):
    remote = _mk()
    build = make_relation(remote, 48 * 8, 8, 128, seed=seed)
    probe = make_relation(remote, 96 * 8, 8, 128, seed=seed + 1)
    plan = ehj_plan(b=48, q=96, out=36, m_b=12, partitions=parts, sigma=sigma)
    res = ehj(remote, build, probe, plan)
    assert res.output_rows == ehj_oracle(remote, build, probe)


def _multiset(rows: np.ndarray) -> np.ndarray:
    """Rows as a sorted array of tuples: equal iff equal as multisets."""
    return np.sort(np.ascontiguousarray(rows, dtype=np.int64).view("i8,i8,i8").ravel())


def _rows(keys) -> np.ndarray:
    keys = np.asarray(keys, dtype=np.int64)
    return np.stack([keys, 1000 + np.arange(len(keys), dtype=np.int64)], axis=1)


_rng = np.random.default_rng(7)
INDEX_CASES = {
    "unique": (_rows(_rng.permutation(300)[:200]), _rows(_rng.integers(0, 300, 700))),
    "duplicate": (_rows(_rng.integers(0, 40, 200)), _rows(_rng.integers(0, 50, 300))),
    "no_matches": (_rows(np.arange(0, 100)), _rows(np.arange(100, 180))),
    "empty_build": (_rows([]), _rows(np.arange(10))),
    "empty_probe": (_rows(np.arange(10)), _rows([])),
    "negative": (_rows(_rng.integers(-30, 30, 150)), _rows(_rng.integers(-40, 40, 400))),
}


@pytest.mark.parametrize("case", sorted(INDEX_CASES))
def test_probe_index_matches_block_join(case):
    build, probe = INDEX_CASES[case]
    index = build_index(build)
    keys = build[:, 0]
    assert index.unique == (len(np.unique(keys)) == len(keys))
    assert (np.diff(index.keys) >= 0).all()
    got, want = probe_index(index, probe), _block_join(build, probe)
    assert got.dtype == np.int64 and got.shape[1:] == (3,)
    assert len(got) == len(want)
    assert (_multiset(got) == _multiset(want)).all()


def _relation(remote, keys, rows_per_page: int) -> Relation:
    rows = _rows(keys)
    pages = [rows[i:i + rows_per_page] for i in range(0, len(rows), rows_per_page)]
    return Relation(page_ids=remote.put_local(pages), rows_per_page=rows_per_page,
                    total_rows=len(rows))


def _ehj_inputs(remote, unique: bool, seed: int):
    """Build and probe relations of 8-row pages: a primary-key build side
    (and probe keys, some matching none) or both sides drawn from a small
    key domain, so build keys repeat."""
    rng = np.random.default_rng(seed)
    if unique:
        build_keys = rng.permutation(1024)[:48 * 8] - 200
        probe_keys = rng.integers(-200, 900, 96 * 8)
    else:
        build_keys = rng.integers(-20, 100, 48 * 8)
        probe_keys = rng.integers(-20, 120, 96 * 8)
    return _relation(remote, build_keys, 8), _relation(remote, probe_keys, 8)


@pytest.mark.parametrize("unique", [True, False], ids=["unique", "duplicate"])
@pytest.mark.parametrize("sigma", [0.0, 0.5, 1.0])
def test_ehj_output_is_the_block_join_with_the_same_rounds(monkeypatch, sigma, unique):
    """The index-probing EHJ emits every matching pair once, and moves the
    same pages in the same rounds as the EHJ that block-joined each call."""
    plan = ehj_plan(b=48, q=96, out=36, m_b=12, partitions=8, sigma=sigma)
    remote = _mk()
    build, probe = _ehj_inputs(remote, unique, seed=int(sigma * 10) + unique)
    res = ehj(remote, build, probe, plan)
    out = relation_rows(remote, Relation(res.output_page_ids, 8, res.output_rows))
    want = _block_join(relation_rows(remote, build), relation_rows(remote, probe))
    assert res.output_rows == len(out) == len(want) > 0
    assert (_multiset(out) == _multiset(want)).all()

    monkeypatch.setattr(ehj_mod, "build_index", lambda rows: rows)
    monkeypatch.setattr(ehj_mod, "probe_index", _block_join)
    remote = _mk()
    build, probe = _ehj_inputs(remote, unique, seed=int(sigma * 10) + unique)
    blocked = ehj(remote, build, probe, plan)
    assert res.per_phase_rounds == blocked.per_phase_rounds
    assert (res.c_read, res.c_write) == (blocked.c_read, blocked.c_write)
    assert len(res.output_page_ids) == len(blocked.output_page_ids)
    assert res.output_rows == blocked.output_rows


def test_ehj_remop_pools_reduce_write_rounds():
    """Enlarged R_w/R_s pools (Property 6) -> fewer flush rounds than 1-page pools."""
    remote = _mk()
    build = make_relation(remote, 128 * 8, 8, 64, seed=12)
    probe = make_relation(remote, 256 * 8, 8, 64, seed=13)
    sigma, parts, m_b = 0.5, 16, 24
    # Baseline: DuckDB-like minimal write pools (1 page each).
    base = ehj_plan(128, 256, 96, m_b, parts, sigma)
    starved = type(base)(m_b=m_b, partitions=parts, sigma=sigma,
                         p1=(m_b - 1, 1.0), p2=(m_b - 2, 1.0, 1.0),
                         p3=(m_b - 1, 1.0))
    res_starved = ehj(remote, build, probe, starved)
    res_remop = ehj(remote, build, probe, base)
    assert res_remop.output_rows == res_starved.output_rows
    assert res_remop.c_write < res_starved.c_write


# ---------------------------------------------------------------------------
# Prefetch (§IV-E)
# ---------------------------------------------------------------------------


def test_prefetch_hides_rounds_and_reduces_latency():
    remote = _mk()
    outer, inner = _bnlj_setup(remote, r_pages=12, s_pages=24)
    plan = BNLJPlan(m=9, r_in=8 / 9, p_r=0.5)
    res = bnlj(remote, outer, inner, plan, prefetch=True)
    led = remote.ledger
    assert led.c_prefetch_hidden > 0
    assert led.latency_seconds(TIER, prefetch=True) < led.latency_seconds(TIER, prefetch=False)
