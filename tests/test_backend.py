"""Execution backend parity: real arrays, real kernels, same answers.

The :class:`repro.remote.backend.ExecutionBackend` is only allowed to exist
because it changes *nothing* the simulator asserts: every test here runs the
same workload against a simulated :class:`MemoryHierarchy` and a backend on
the same hierarchy spec and demands

* byte-identical operator output pages (dtype, shape, values),
* field-for-field equal ledger snapshots (per tier, per op, and in total),
* wall-clock measurements present on the backend and absent on the simulator.

Workloads are deliberately tiny: the Pallas kernels run in interpret mode on
CPU, where every distinct block length is a fresh interpreted program.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro import spans
from repro.core import TABLE_I
from repro.engine import Session, WorkloadStats
from repro.engine.registry import hierarchy_spec
from repro.remote import MemoryHierarchy, make_backend
from repro.remote.backend import BackendTier, ExecutionBackend, _device_page
from repro.remote.simulator import make_key_pages, make_relation

ROWS = 4
THREE = ((TABLE_I["dram"], 16), (TABLE_I["rdma"], 128), TABLE_I["ssd"])
ONE = (TABLE_I["tcp"],)


def _tasks(sess):
    """A tiny EMS + EHJ pipeline exercising both kernel hooks."""
    ids = make_key_pages(sess.remote, 24, ROWS, seed=3)
    build = make_relation(sess.remote, 8 * ROWS, ROWS, 16, seed=4)
    probe = make_relation(sess.remote, 16 * ROWS, ROWS, 16, seed=5)
    return [
        sess.task("ems", WorkloadStats(size_r=24, k_cap=4),
                  inputs={"page_ids": ids}, rows_per_page=ROWS),
        sess.task("ehj", WorkloadStats(size_r=8, size_s=16, out=6,
                                       partitions=4, sigma=0.5),
                  inputs={"build": build, "probe": probe}),
    ]


def _run(remote):
    sess = Session(remote, budget=24.0)
    return sess, sess.run(_tasks(sess))


def _output_ids(op, result):
    return result.run_page_ids if op == "ems" else result.output_page_ids


def _assert_parity(levels):
    sim_sess, sim = _run(MemoryHierarchy(hierarchy_spec(*levels)))
    backend = make_backend(*levels)
    bk_sess, bkr = _run(backend)

    # Wall clock: measured on the backend, absent from the simulator.
    assert sim.wall_seconds is None
    assert bkr.wall_seconds is not None and bkr.wall_seconds > 0.0

    # Ledger parity — field-for-field, per tier, per op, and in total.
    assert dataclasses.asdict(sim.total) == dataclasses.asdict(bkr.total)
    for (op_a, _, da), (op_b, _, db) in zip(sim.per_op, bkr.per_op):
        assert op_a == op_b
        assert dataclasses.asdict(da) == dataclasses.asdict(db)

    # Output parity — byte-identical pages, page for page.
    for (op_a, ra, _), (_, rb, _) in zip(sim.per_op, bkr.per_op):
        pages_a = sim_sess.remote.peek_batch(_output_ids(op_a, ra))
        pages_b = bk_sess.remote.peek_batch(_output_ids(op_a, rb))
        assert len(pages_a) == len(pages_b)
        for pa, pb in zip(pages_a, pages_b):
            assert pa.dtype == pb.dtype
            assert pa.shape == pb.shape
            assert np.array_equal(pa, pb)
    return backend


def test_session_parity_three_tier():
    backend = _assert_parity(THREE)
    # The hooks actually ran on device: no silent numpy fallbacks.
    assert backend.wall.kernel_calls > 0
    assert backend.wall.kernel_fallbacks == 0
    assert backend.wall.host_pinned_pages == 0


def test_session_parity_single_tier():
    backend = _assert_parity(ONE)
    assert backend.wall.kernel_calls > 0
    assert backend.wall.kernel_fallbacks == 0


# -- direct hook parity ------------------------------------------------------


def test_sort_keys_hook_matches_numpy():
    backend = make_backend(*ONE)
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 50, size=37).astype(np.int64)  # duplicates likely
    out = backend.sort_keys(keys)
    assert out.dtype == keys.dtype
    np.testing.assert_array_equal(out, np.sort(keys, kind="stable"))
    assert backend.wall.kernel_calls == 1
    assert backend.wall.kernel_fallbacks == 0


def test_partition_rows_hook_matches_masks():
    backend = make_backend(*ONE)
    rng = np.random.default_rng(11)
    rows = rng.integers(0, 1000, size=(29, 3)).astype(np.int64)
    parts = rng.integers(0, 4, size=29).astype(np.int64)
    got = backend.partition_rows(rows, parts)
    want = [(int(q), rows[parts == q]) for q in np.unique(parts)]
    assert [q for q, _ in got] == [q for q, _ in want]
    for (_, ga), (_, wa) in zip(got, want):
        assert ga.dtype == wa.dtype
        np.testing.assert_array_equal(ga, wa)  # mask order == stable order
    assert backend.wall.kernel_fallbacks == 0


def test_out_of_int32_range_keys_fall_back_but_agree():
    backend = make_backend(*ONE)
    keys = np.array([2**40, 5, 2**35, 5, -1], dtype=np.int64)
    out = backend.sort_keys(keys)
    np.testing.assert_array_equal(out, np.sort(keys, kind="stable"))
    assert backend.wall.kernel_fallbacks == 1
    assert backend.wall.kernel_calls == 0


def test_host_pinned_pages_round_trip_unchanged():
    """Pages of a dtype the device cannot hold losslessly (float64) never get
    a device mirror, yet reads return them bit-exact (the host copy is
    authoritative).  int64 pages beyond int32 are mirrored wide instead."""
    backend = make_backend(*ONE)
    big = np.array([2.0**60 + 0.5, 2.0**-40, 3.0], dtype=np.float64)
    small = np.arange(5, dtype=np.int64)
    ids = backend.put_local([big, small])
    assert backend.wall.host_pinned_pages == 1
    got = backend.read_batch(ids)
    np.testing.assert_array_equal(got[0], big)
    assert got[0].dtype == np.float64
    np.testing.assert_array_equal(got[1], small)
    assert got[1].dtype == np.int64


def test_wall_clock_report_shape():
    backend = make_backend(*THREE)
    report = backend.wall.to_dict()
    assert set(report["tiers"]) == {"dram", "rdma", "ssd"}
    for tier in report["tiers"].values():
        for key in ("h2d_seconds", "h2d_rounds", "h2d_bytes",
                    "d2h_seconds", "d2h_rounds", "d2h_bytes"):
            assert key in tier
    assert "wall_seconds" in report
    assert "kernel_seconds" in report


def test_backend_is_a_hierarchy_and_flagged():
    backend = make_backend(*THREE)
    assert isinstance(backend, MemoryHierarchy)
    assert isinstance(backend, ExecutionBackend)
    assert backend.is_backend is True
    assert getattr(MemoryHierarchy(hierarchy_spec(*THREE)), "is_backend",
                   False) is False


def test_migrate_keeps_device_mirrors_consistent():
    backend = make_backend(*THREE)
    pages = [np.arange(i, i + ROWS, dtype=np.int64) for i in range(0, 12, ROWS)]
    ids = backend.put_local(pages)  # seeds on the bottom tier (ssd)
    backend.promote(ids)
    got = backend.read_batch(ids)
    for page, back in zip(pages, got):
        np.testing.assert_array_equal(page, back)
        assert back.dtype == np.int64


# -- write-behind: a write round returns before its copy lands ---------------

# One page of each mirror kind: int32 as-is, int64 narrowed to int32, int64
# mirrored wide as word pairs, and float64 host-pinned.
KINDS = {
    "narrow_int32": lambda k: np.arange(8, dtype=np.int32).reshape(ROWS, 2) + k,
    "narrow_int64": lambda k: np.arange(8, dtype=np.int64).reshape(ROWS, 2) - k,
    "wide_int64": lambda k: (np.arange(8, dtype=np.int64).reshape(ROWS, 2)
                             * 2**40 - 2**62 + k),
    "host_pinned": lambda k: np.linspace(-1.5, 2.0**60, 8).reshape(ROWS, 2) + k,
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_read_right_after_write_round_is_bit_exact(kind):
    backend = make_backend(*ONE)
    pages = [KINDS[kind](k) for k in range(3)]
    ids = backend.write_batch(pages)
    got = backend.read_batch(ids)
    for page, back in zip(pages, got):
        assert back.dtype == page.dtype
        assert back.shape == page.shape
        assert back.tobytes() == page.tobytes()
    pinned = kind == "host_pinned"
    assert backend.wall.host_pinned_pages == (3 if pinned else 0)
    wall = backend.wall.tiers["tcp"]
    assert wall.h2d_rounds == (0 if pinned else 1)
    assert wall.d2h_rounds == (0 if pinned else 1)


def _every_kind():
    return [make(k) for k, make in enumerate(KINDS.values())]


@pytest.mark.parametrize("then", ["free", "migrate"])
def test_page_in_flight_frees_and_migrates_like_the_simulator(then):
    sim = MemoryHierarchy(hierarchy_spec(*THREE))
    backend = make_backend(*THREE)
    pages = _every_kind()
    for remote in (sim, backend):
        ids = remote.write_batch(pages)  # top tier, copies possibly in flight
        if then == "free":
            remote.free(ids)
            assert remote.resident_ids() == []
        else:
            remote.migrate(ids, "ssd")
            for page, back in zip(pages, remote.read_batch(ids)):
                assert back.dtype == page.dtype
                assert back.tobytes() == page.tobytes()
    assert sim.snapshot() == backend.snapshot()
    mirrored = [t._dev for t in backend.tiers]
    if then == "free":
        assert mirrored == [{}, {}, {}]
    else:  # the three mirrored pages moved with their pages to ssd
        assert [len(m) for m in mirrored] == [0, 0, 3]


class _InFlight:
    """A device array whose copy has not landed: the pull still works."""

    def __init__(self, arr):
        self.arr = arr

    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.arr)


@pytest.mark.parametrize("landing", ["as_it_comes", "in_flight"])
def test_write_behind_counters_are_recorded(landing, monkeypatch):
    if landing == "in_flight":  # every copy still in flight until pulled
        device_put = jax.device_put
        monkeypatch.setattr(jax, "device_put", lambda xs, device: [
            _InFlight(a) for a in device_put(xs, device)])
    backend = make_backend(*ONE)
    pages = _every_kind()
    spans.enable()
    try:
        with spans.Recorder(0) as rec:
            ids = backend.write_batch(pages)
            got = backend.read_batch(ids)
    finally:
        spans.disable()
    for page, back in zip(pages, got):
        assert back.tobytes() == page.tobytes()
    mirrored = len(pages) - 1  # the float64 page is host-pinned
    if landing == "in_flight":
        assert rec.counts["tier.writes_behind"] == mirrored
        assert rec.counts["tier.reads_waited"] == mirrored
    else:
        assert 0 <= rec.counts["tier.writes_behind"] <= mirrored
        assert 0 <= rec.counts["tier.reads_waited"] <= mirrored
    for name in ("tier.write", "tier.put", "tier.read", "tier.pull"):
        assert rec.totals[name].calls == 1


def test_store_and_wide_mirror_keep_the_callers_page():
    """A write round's copy may still read the page's host array after the
    round returns: the store holds the caller's array itself, and an int32
    page's mirror is that array, a wide page's mirror a view of it."""
    backend = make_backend(*ONE)
    narrow = KINDS["narrow_int32"](0)
    wide = KINDS["wide_int64"](0)
    ids = backend.write_batch([narrow, wide])
    stored = backend.peek_batch(ids)
    assert stored[0] is narrow and stored[1] is wide
    assert _device_page(narrow)[0] is narrow
    mirror, is_wide = _device_page(wide)
    assert is_wide and np.shares_memory(mirror, wide)


def test_written_pages_stay_untouched_through_a_session(monkeypatch):
    """No operator touches a page's host array once it is written, so a copy
    still in flight reads the bytes the round wrote."""
    written = []
    write_batch = BackendTier.write_batch

    def spy(self, pages):
        written.extend((p, np.array(p, copy=True)) for p in pages)
        return write_batch(self, pages)

    monkeypatch.setattr(BackendTier, "write_batch", spy)
    _run(make_backend(*THREE))
    assert written
    for page, before in written:
        assert page.dtype == before.dtype
        assert page.tobytes() == before.tobytes()
