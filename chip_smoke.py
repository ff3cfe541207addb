"""Bring-up smoke test: the execution backend's query path on one TPU chip.

    python chip_smoke.py

Runs two phases through ``Session`` on an ``ExecutionBackend`` whose pages
live on the chip and whose sort/partition hooks run the Pallas kernels
compiled (not interpreted):

  (a) a TPC-H Q3-shaped logical plan (lineitem x orders x customer, then a
      group-by and an order-by) at the row counts of scale factor 1, lowered
      by ``compile_plan`` to EHJ, EHJ, EAGG and EMS tasks and run with
      ``replan="measured"``;
  (b) a standalone external merge sort (EMS) of 2**23 keys.

Both use 256 KiB pages (DuckDB's block size) and a 64-page budget, so every
operator spills.  Each phase is checked against a plain numpy reference of
the same semantics, and re-run on the simulated ``MemoryHierarchy`` for
field-for-field ledger parity and byte-identical outputs.  Any mismatch,
kernel fallback, host-pinned page, interpreted kernel or missing TPU ends the
run with a non-zero exit code.

The earlier lines report the device, data sizes, per-phase wall seconds
(cold bring-up times that include compiles and data generation: not
benchmark numbers), kernel calls, fresh compiles and peak HBM.  The last line
is one JSON object naming the device.  JAX's persistent compilation cache is
kept in ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in
``<repo>/.jax_cache``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# TPC-H scale factor 1 row counts.
LINEITEM_ROWS = 6_001_215
ORDERS_ROWS = 1_500_000
CUSTOMER_ROWS = 150_000
PAGE_ROWS = 16_384  # (key, payload) int64 rows: one 256 KiB page
SORT_KEYS = 1 << 23
SORT_PAGE_KEYS = 32_768  # int64 keys: one 256 KiB page
BUDGET_PAGES = 64
TIERS = (("dram", 64), ("rdma", 512), "ssd")
SEED = 0


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


# --------------------------------------------------------------------------
# Phases: each builds its data from a seed on a fresh target and runs it
# --------------------------------------------------------------------------


def q3_phase(target, lineitem_rows: int, orders_rows: int,
             customer_rows: int, page_rows: int, budget: int, seed: int):
    """Q3-shaped plan: (lineitem x orders) x customer, group-by, order-by."""
    from repro.engine import Session
    from repro.engine.plan import LogicalPlan, compile_plan
    from repro.remote import make_relation

    session = Session(target, budget=budget)
    # One key column joins all three tables; its domain is the order keys.
    domain = orders_rows
    tables = {
        name: make_relation(session.remote, rows, page_rows, domain,
                            seed=seed + i)
        for i, (name, rows) in enumerate((("lineitem", lineitem_rows),
                                          ("orders", orders_rows),
                                          ("customer", customer_rows)))
    }
    lp = LogicalPlan("q3")

    def scan(name):
        return lp.scan(name, tables[name], rows_per_page=page_rows)

    j = lp.join(lp.join(scan("lineitem"), scan("orders")),
                lp.filter(scan("customer"), 0.5), sigma=0.5, partitions=8)
    lp.sort(lp.aggregate(j, sigma=0.5, partitions=8), k_cap=8)
    cp = compile_plan(session, lp)
    return session, cp.run(session, replan="measured")


def sort_phase(target, n_keys: int, page_keys: int, budget: int, seed: int):
    """Standalone EMS of ``n_keys`` int64 keys in ``page_keys``-key pages."""
    from repro.engine import Session, WorkloadStats
    from repro.remote.simulator import make_key_pages

    session = Session(target, budget=budget)
    ids = make_key_pages(session.remote, n_keys // page_keys, page_keys,
                         seed=seed)
    task = session.task("ems", WorkloadStats(size_r=len(ids), k_cap=8),
                        inputs={"page_ids": ids}, rows_per_page=page_keys)
    return session, session.run([task])


# --------------------------------------------------------------------------
# Checks: plain numpy reference and simulator parity
# --------------------------------------------------------------------------


def _ref_join(build: np.ndarray, probe: np.ndarray) -> np.ndarray:
    """Equijoin on column 0: (key, build payload, probe payload) rows."""
    order = np.argsort(probe[:, 0], kind="stable")
    pkeys = probe[order, 0]
    lo = np.searchsorted(pkeys, build[:, 0], side="left")
    cnt = np.searchsorted(pkeys, build[:, 0], side="right") - lo
    b_idx = np.repeat(np.arange(len(build)), cnt)
    first = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
    p_idx = order[first + np.arange(len(b_idx))]
    return np.stack([build[b_idx, 0], build[b_idx, 1], probe[p_idx, 1]],
                    axis=1)


def _ref_group(rows: np.ndarray) -> np.ndarray:
    """Group by column 0: (key, sum of column 1, count) rows."""
    rows = rows[np.argsort(rows[:, 0], kind="stable")]
    keys, starts, counts = np.unique(rows[:, 0], return_index=True,
                                     return_counts=True)
    return np.stack([keys, np.add.reduceat(rows[:, 1], starts), counts],
                    axis=1)


def _rows(remote, page_ids) -> np.ndarray:
    return np.concatenate(remote.peek_batch(list(page_ids)), axis=0)


def _multiset(rows: np.ndarray) -> np.ndarray:
    return rows[np.lexsort(rows.T[::-1])]


def check_reference(session, result) -> None:
    """Every task's output equals the numpy reference computed from the
    base tables (joins and group-bys as multisets, the sort exactly)."""
    from repro.engine.registry import get
    from repro.engine.session import TaskOutput

    ref = {}

    def value(v):
        if isinstance(v, TaskOutput):
            return ref[id(v.task)]
        return _rows(session.remote, getattr(v, "page_ids", v))

    for tr in result.per_task:
        ins = {name: value(v) for name, v in tr.task.inputs.items()}
        got = _rows(session.remote, get(tr.op).output_of(tr.result))
        if tr.op == "ehj":
            want = _ref_join(ins["build"], ins["probe"])
        elif tr.op == "eagg":
            want = _ref_group(ins["rel"])
        elif tr.op == "ems":
            want = np.sort(ins["page_ids"].ravel())
        else:
            raise RuntimeError(f"chip_smoke: no reference for {tr.op!r}")
        if tr.op == "ems":
            _require(np.array_equal(got, want), f"{tr.label}: sort differs")
        else:
            _require(got.shape == want.shape
                     and np.array_equal(_multiset(got), _multiset(want)),
                     f"{tr.label}: {got.shape} rows vs reference {want.shape}")
        ref[id(tr.task)] = want


def check_parity(sim, backend) -> None:
    """Simulator and backend runs: equal ledgers, byte-identical outputs."""
    from repro.engine.registry import get

    (s_sess, s_res), (b_sess, b_res) = sim, backend
    _require(dataclasses.asdict(s_res.total) == dataclasses.asdict(b_res.total),
             "total ledger differs from the simulator")
    _require(len(s_res.per_task) == len(b_res.per_task), "task count differs")
    for st, bt in zip(s_res.per_task, b_res.per_task):
        _require(st.op == bt.op and st.label == bt.label,
                 f"task order differs: {st.label} vs {bt.label}")
        _require(dataclasses.asdict(st.delta) == dataclasses.asdict(bt.delta),
                 f"{bt.label}: ledger differs from the simulator")
        sp = s_sess.remote.peek_batch(get(st.op).output_of(st.result))
        bp = b_sess.remote.peek_batch(get(bt.op).output_of(bt.result))
        _require(len(sp) == len(bp)
                 and all(a.dtype == b.dtype and a.shape == b.shape
                         and np.array_equal(a, b) for a, b in zip(sp, bp)),
                 f"{bt.label}: output pages differ from the simulator")


def verify(phase, tiers=TIERS, **sizes):
    """Run ``phase`` on a backend and on the simulator; check both ways.

    Returns the backend and the seconds its run took (data generation,
    compiles and execution: a bring-up time, not a benchmark number).
    """
    from repro.engine.registry import hierarchy_spec
    from repro.remote import MemoryHierarchy, make_backend

    backend = make_backend(*tiers)
    t0 = time.perf_counter()
    b_run = phase(backend, **sizes)
    seconds = time.perf_counter() - t0
    check_reference(*b_run)
    check_parity(phase(MemoryHierarchy(hierarchy_spec(*tiers)), **sizes),
                 b_run)
    wall = backend.wall
    _require(wall.kernel_calls > 0, "no kernel ran")
    _require(wall.kernel_fallbacks == 0,
             f"{wall.kernel_fallbacks} kernel fallbacks to numpy")
    _require(wall.host_pinned_pages == 0,
             f"{wall.host_pinned_pages} host-pinned pages")
    return backend, seconds


# --------------------------------------------------------------------------
# The chip run
# --------------------------------------------------------------------------


def _use_compile_cache(jax) -> None:
    """Persistent cache at $JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class _CompileCounter:
    """Backend compile requests, persistent-cache hits and the seconds spent
    tracing, lowering and compiling, via jax.monitoring."""

    _STEPS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self, jax):
        self.requests = 0
        self.cache_hits = 0
        self.seconds = dict.fromkeys(self._STEPS.values(), 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        step = self._STEPS.get(event)
        if step is not None:
            self.seconds[step] += secs
        if step == "compile":
            self.requests += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def fresh(self) -> int:
        return self.requests - self.cache_hits


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX's backend is "
                         f"{platform!r}")
    _use_compile_cache(jax)
    from repro.core.cost_model import TPU_V5E

    device = jax.devices()[0]
    if device.device_kind != TPU_V5E.device_kind:
        raise SystemExit(
            f"chip_smoke: device kind {device.device_kind!r} is not the "
            f"{TPU_V5E.device_kind!r} the sort planner's constants describe")
    compiles = _CompileCounter(jax)
    print(f"device: {device.platform} {device.device_kind} "
          f"x{len(jax.devices())}; compile cache: "
          f"{jax.config.jax_compilation_cache_dir}")

    phases = (
        ("q3", q3_phase, dict(lineitem_rows=LINEITEM_ROWS,
                              orders_rows=ORDERS_ROWS,
                              customer_rows=CUSTOMER_ROWS,
                              page_rows=PAGE_ROWS, budget=BUDGET_PAGES,
                              seed=SEED)),
        ("ems", sort_phase, dict(n_keys=SORT_KEYS, page_keys=SORT_PAGE_KEYS,
                                 budget=BUDGET_PAGES, seed=SEED)),
    )
    for name, phase, sizes in phases:
        before = compiles.fresh
        backend, seconds = verify(phase, **sizes)
        _require(backend.interpret is False, "kernels ran interpreted")
        w = backend.wall
        print(f"phase {name}: sizes {sizes}; tiers {TIERS}; oracle and "
              f"simulator parity ok; bring-up wall {seconds:.3f} s (cold "
              f"timing, not a benchmark); kernel_calls {w.kernel_calls} "
              f"({w.kernel_seconds:.3f} s); kernel_fallbacks "
              f"{w.kernel_fallbacks}; host_pinned_pages "
              f"{w.host_pinned_pages}; interpret {backend.interpret}; "
              f"fresh compiles {compiles.fresh - before}", flush=True)
    stats = device.memory_stats() or {}
    spent = ", ".join(f"{k} {v:.3f} s" for k, v in compiles.seconds.items())
    print(f"compiles: {compiles.requests} requested, {compiles.cache_hits} "
          f"from the persistent cache, {compiles.fresh} fresh ({spent}); "
          f"peak HBM {stats.get('peak_bytes_in_use', 'not reported')} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
