"""Real Pallas execution backend: pages carry jax arrays, kernels run.

Everywhere else in ``remote/`` the store is a *simulator* — pages are host
numpy arrays, transfers are ledger bookkeeping, and latency comes from
Eq. (1) with assumed Table I constants.  This module is the measured
counterpart: an :class:`ExecutionBackend` is a drop-in
:class:`~repro.remote.simulator.MemoryHierarchy` whose tiers mirror their
pages as device arrays, whose transfer rounds are actual host<->device
copies timed with a wall clock, and whose operator compute hooks run the
repo's Pallas kernels (``kernels/merge_sort`` for EMS merge steps,
``kernels/dispatch`` for EHJ/EAGG partitioning).

Parity is the correctness oracle: every ledger round counts *exactly* as on
the simulator (the overrides delegate to the simulator paths for all D/C
accounting) and every operator output is byte-identical, because

  * device mirrors only hold pages that round-trip losslessly (jax
    canonicalizes 64-bit dtypes to 32-bit with x64 off — flipping
    ``jax_enable_x64`` globally would contaminate every other suite in the
    process, so int64 pages mirror as int32 where every value fits, and as
    their int32 bit view, two words a value, where one does not; any other
    dtype stays host-pinned and is counted),
  * the kernel hooks fall back to the numpy reference whenever a block is
    not losslessly representable (counted in ``wall.kernel_fallbacks``), and
  * the hooks compute the same functions: sorted keys are sorted keys, and
    a stable partition-id argsort groups rows exactly like per-partition
    boolean masks.

This file is the one sanctioned home of wall-clock reads on a simulator
path (the LAY303 carve-out in ``repro.analysis.rules_layering``); the
determinism contract — no unseeded RNG — still applies here.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.cost_model import HierarchySpec, TierSpec
from repro.kernels.dispatch.dispatch import gather_rows
from repro.kernels.merge_sort.ops import argsort_by_key, next_pow2, remop_sort
from repro.kernels.runtime import resolve_interpret
from repro.remote.simulator import MemoryHierarchy, RemoteMemory
from repro.spans import count, span

_I32_MIN = np.iinfo(np.int32).min
_I32_MAX = np.iinfo(np.int32).max


@functools.partial(jax.jit, static_argnames=("max_key", "interpret"))
def _group_by_part(rows, parts, max_key: int, interpret: bool):
    """Rows in stable partition-id order: ``argsort_by_key`` + ``gather_rows``.

    The two scopes name the halves apart in the device trace; the module
    stays ``jit__group_by_part``.
    """
    with jax.named_scope("argsort"):
        order = argsort_by_key(parts, interpret=interpret, max_key=max_key)
    with jax.named_scope("gather_rows"):
        return gather_rows(rows, order, interpret=interpret)


def _as_int32(page: np.ndarray) -> Optional[np.ndarray]:
    """A host block as int32 values, or ``None`` when that is lossy.

    int32/float32 blocks pass as-is; int64 blocks narrow to int32 only when
    every value round-trips exactly.  This is what the kernels compute on.
    """
    page = np.asarray(page)
    if page.dtype == np.int64:
        if page.size and (page.min() < _I32_MIN or page.max() > _I32_MAX):
            return None
        return page.astype(np.int32)
    if page.dtype in (np.int32, np.float32):
        return page
    return None


def _device_page(page: np.ndarray) -> Optional[Tuple[np.ndarray, bool]]:
    """A page's device mirror and whether it is wide, or ``None`` (host-pin).

    A page :func:`_as_int32` takes mirrors as those values.  Any other int64
    page is *wide*: it mirrors as its int32 bit view, two words a value
    (``(rows, 2 * columns)``), which :meth:`BackendTier.read_batch` views
    back as int64 bit for bit.  Other dtypes host-pin — parity with the
    simulator always beats device coverage.
    """
    page = np.asarray(page)
    narrow = _as_int32(page)
    if narrow is not None:
        return narrow, False
    if page.dtype == np.int64:
        return np.ascontiguousarray(page).view(np.int32), True
    return None


# --------------------------------------------------------------------------
# Wall clock: the measured counterpart of the TransferLedger
# --------------------------------------------------------------------------


@dataclasses.dataclass
class TierWall:
    """Measured host<->device transfer time for one tier."""

    h2d_seconds: float = 0.0
    h2d_rounds: int = 0
    h2d_bytes: int = 0
    d2h_seconds: float = 0.0
    d2h_rounds: int = 0
    d2h_bytes: int = 0

    @property
    def seconds(self) -> float:
        return self.h2d_seconds + self.d2h_seconds

    @property
    def rounds(self) -> int:
        return self.h2d_rounds + self.d2h_rounds

    @property
    def bytes(self) -> int:
        return self.h2d_bytes + self.d2h_bytes

    def to_dict(self) -> Dict[str, float]:
        d = dataclasses.asdict(self)
        d.update(seconds=self.seconds, rounds=self.rounds, bytes=self.bytes)
        return d


class WallClock:
    """Per-tier transfer timings + kernel timings for one backend.

    The wall clock is to measured execution what the
    :class:`~repro.core.cost_model.TransferLedger` is to the simulation —
    but unlike the ledger it is *never* regression-gated in CI
    (``scripts/check_regression.py`` gates only deterministic metrics).
    """

    def __init__(self, tier_names: Sequence[str]):
        self.tiers: Dict[str, TierWall] = {n: TierWall() for n in tier_names}
        self.kernel_seconds = 0.0
        self.kernel_calls = 0
        # Blocks routed back to the numpy reference (lossy int32 round-trip).
        self.kernel_fallbacks = 0
        # Pages never mirrored on device (lossy dtype/range): reads of these
        # serve from the host store, so their rounds have no device timing.
        self.host_pinned_pages = 0

    def record_h2d(self, tier: str, seconds: float, nbytes: int) -> None:
        w = self.tiers[tier]
        w.h2d_seconds += seconds
        w.h2d_rounds += 1
        w.h2d_bytes += nbytes

    def record_d2h(self, tier: str, seconds: float, nbytes: int) -> None:
        w = self.tiers[tier]
        w.d2h_seconds += seconds
        w.d2h_rounds += 1
        w.d2h_bytes += nbytes

    def record_kernel(self, seconds: float) -> None:
        self.kernel_seconds += seconds
        self.kernel_calls += 1

    @property
    def transfer_seconds(self) -> float:
        return sum(w.seconds for w in self.tiers.values())

    @property
    def total_seconds(self) -> float:
        """Measured seconds: all transfers + all kernel invocations."""
        return self.transfer_seconds + self.kernel_seconds

    def to_dict(self) -> Dict[str, object]:
        return {
            "tiers": {n: w.to_dict() for n, w in self.tiers.items()},
            "transfer_seconds": self.transfer_seconds,
            "kernel_seconds": self.kernel_seconds,
            "kernel_calls": self.kernel_calls,
            "kernel_fallbacks": self.kernel_fallbacks,
            "host_pinned_pages": self.host_pinned_pages,
            "wall_seconds": self.total_seconds,
        }


# --------------------------------------------------------------------------
# Backend tiers and the hierarchy
# --------------------------------------------------------------------------


class BackendTier(RemoteMemory):
    """A tier whose pages are mirrored as device arrays.

    Every override delegates to :class:`RemoteMemory` first, so the ledger
    accounting (rounds, volumes, prefetch hiding) is byte-identical to the
    simulator; the device mirror rides along.  ``write_batch`` is a timed
    host->device round, ``read_batch`` a timed device->host round, and the
    pages a read returns are the *device round-trips* (cast back to the host
    dtype), so the data operators consume really crossed the boundary.
    """

    def __init__(self, tier: TierSpec, wall: WallClock, device, _alloc=None):
        super().__init__(tier, _alloc=_alloc)
        self._wall = wall
        self._device = device
        self._dev: Dict[int, jax.Array] = {}
        self._wide: set = set()  # pages mirrored as their int32 bit view
        self._in_write = False

    # -- mirroring -----------------------------------------------------------

    def _mirror(self, page_ids: Sequence[int]) -> None:
        views = []
        with span("tier.check"):
            for i in page_ids:
                mirror = _device_page(self._store[i])
                if mirror is None:
                    self._wall.host_pinned_pages += 1
                    continue
                views.append((i, mirror[0]))
                if mirror[1]:
                    self._wide.add(i)
        if not views:
            return
        nbytes = sum(v.nbytes for _, v in views)
        t0 = time.perf_counter()
        with span("tier.put"):
            arrays = jax.device_put([v for _, v in views], self._device)
            jax.block_until_ready(arrays)
        elapsed = time.perf_counter() - t0
        if self._in_write:  # seeding (put_local) is not a transfer round
            self._wall.record_h2d(self.tier.name, elapsed, nbytes)
        for (i, _), arr in zip(views, arrays):
            self._dev[i] = arr

    def put_local(self, pages: Sequence[np.ndarray]) -> List[int]:
        ids = super().put_local(pages)
        self._mirror(ids)
        return ids

    # -- timed transfer rounds ------------------------------------------------

    def write_batch(self, pages: Sequence[np.ndarray]) -> List[int]:
        if not len(pages):
            return []
        with span("tier.write"):
            self._in_write = True
            try:
                return super().write_batch(pages)  # ledger + put_local -> mirror
            finally:
                self._in_write = False

    def read_batch(self, page_ids: Sequence[int], prefetched: bool = False) -> List[np.ndarray]:
        if not page_ids:
            return []
        with span("tier.read"):
            host = super().read_batch(page_ids, prefetched)  # identical ledger
            mirrors = [self._dev.get(i) for i in page_ids]
            fetched: List[Optional[np.ndarray]] = [None] * len(page_ids)
            live = [(k, d) for k, d in enumerate(mirrors) if d is not None]
            if live:
                t0 = time.perf_counter()
                with span("tier.pull"):
                    pulled = [np.asarray(d) for _, d in live]
                elapsed = time.perf_counter() - t0
                self._wall.record_d2h(
                    self.tier.name, elapsed, sum(p.nbytes for p in pulled)
                )
                for (k, _), p in zip(live, pulled):
                    fetched[k] = p
            # A wide mirror's word pairs view back as int64 from a C-ordered
            # copy: a pulled array need not be C-contiguous on every device.
            with span("tier.cast"):
                return [
                    h if f is None
                    else np.array(f, order="C").view(np.int64) if i in self._wide
                    else f.astype(h.dtype, copy=False)
                    for i, h, f in zip(page_ids, host, fetched)
                ]

    def free(self, page_ids: Iterable[int]) -> None:
        ids = list(page_ids)
        super().free(ids)
        for i in ids:
            self._dev.pop(i, None)
            self._wide.discard(i)


class ExecutionBackend(MemoryHierarchy):
    """A :class:`MemoryHierarchy` executing for real: device pages + kernels.

    Drop-in for every ``MemoryHierarchy`` consumer (``Session``, ``Server``,
    the benchmarks): same placement map, same waterfall, same ledgers — the
    parity tests assert snapshot equality field-for-field — plus a
    :attr:`wall` clock of measured seconds and two compute hooks the
    operators discover through their :class:`~repro.engine.scheduler.
    TransferScheduler` (:meth:`sort_keys`, :meth:`partition_rows`).

    ``interpret=None`` auto-detects the Pallas mode (compiled on TPU/GPU,
    interpreter on CPU); ``device`` defaults to jax's first device.
    """

    is_backend = True  # structural marker (duck-typed like is_hierarchy)

    def __init__(self, spec: HierarchySpec, interpret: Optional[bool] = None,
                 device=None):
        super().__init__(spec)
        self.interpret = resolve_interpret(interpret)
        self.device = jax.devices()[0] if device is None else device
        self.wall = WallClock(spec.names)
        # Re-materialize the levels as backend tiers on the shared allocator
        # (no pages exist yet, so swapping the empty stores is safe).
        self.tiers = [
            BackendTier(lv.tier, wall=self.wall, device=self.device,
                        _alloc=self._alloc)
            for lv in spec.levels
        ]

    # -- migration: move the device mirrors with the pages --------------------

    def migrate(
        self,
        page_ids: Sequence[int],
        dst: Union[int, str],
        background: bool = False,
    ) -> None:
        old = {i: self._placement.get(i) for i in page_ids}
        super().migrate(page_ids, dst, background=background)
        # The base class pokes tier stores directly; re-home the mirrors.
        # All tiers share one device, so this is a reference move, not a
        # timed copy (the ledger already charged the migration rounds).
        for i in page_ids:
            src, cur = old[i], self._placement[i]
            if src is None or src == cur:
                continue
            dev = self.tiers[src]._dev.pop(i, None)
            if dev is not None:
                self.tiers[cur]._dev[i] = dev
            if i in self.tiers[src]._wide:
                self.tiers[src]._wide.discard(i)
                self.tiers[cur]._wide.add(i)

    # -- operator compute hooks ------------------------------------------------

    def sort_keys(self, keys: np.ndarray) -> np.ndarray:
        """EMS hook: sort a 1-D key block via the ``merge_sort`` Pallas kernel.

        Byte-identical to ``np.sort(keys, kind="stable")`` — bare keys carry
        no payload, so equal keys are interchangeable.  Blocks that cannot
        round-trip int32 losslessly fall back to numpy (counted).  The block
        is padded with int32-max keys to a power-of-two length before the
        jit boundary, so a run of many block lengths compiles one program
        per power of two; the padding sorts last and is dropped.
        """
        with span("hook.sort_keys"):
            with span("hook.prepare"):
                keys = np.asarray(keys)
                if keys.ndim != 1 or keys.size < 2:
                    return np.sort(keys, kind="stable")
                dev = _as_int32(keys) if keys.dtype.kind in "iu" else None
                if dev is None:
                    self.wall.kernel_fallbacks += 1
                    return np.sort(keys, kind="stable")
                n = len(dev)
                padded = np.full(next_pow2(n), _I32_MAX, np.int32)
                padded[:n] = dev
            count("sort.keys", n)
            count("sort.padded_keys", len(padded))
            t0 = time.perf_counter()
            with span("hook.upload"):
                padded = jnp.asarray(padded)
            with span("hook.device"):
                out, _ = remop_sort(padded, interpret=self.interpret)
                jax.block_until_ready(out)
            self.wall.record_kernel(time.perf_counter() - t0)
            with span("hook.download"):
                return np.asarray(out)[:n].astype(keys.dtype, copy=False)

    def partition_rows(
        self, rows: np.ndarray, parts: np.ndarray
    ) -> List[Tuple[int, np.ndarray]]:
        """EHJ/EAGG hook: group a row block by partition id via ``dispatch``.

        Returns ``[(q, rows_of_q), ...]`` with ``q`` ascending — exactly
        ``[(q, rows[parts == q]) for q in np.unique(parts)]``, because the
        partition-id argsort is *stable* (within-partition row order is
        preserved) and ``gather_rows`` applies the permutation verbatim.
        Like :meth:`sort_keys`, the block is padded to a power-of-two length:
        padded rows get partition ``max_part + 1``, sort after every real row,
        and are dropped after the gather.
        """
        with span("hook.partition_rows"):
            with span("hook.prepare"):
                rows = np.asarray(rows)
                parts = np.asarray(parts)
                if not len(rows):
                    return []
                uniq, counts = np.unique(parts, return_counts=True)
                n = len(parts)
                if n < 2:  # one row is already grouped: no kernel, no fallback
                    return [(int(q), rows[parts == q]) for q in uniq]
                n_pad = next_pow2(n)
                pad_part = int(uniq[-1]) + 1
                # The largest id the int32 composite key admits at this
                # length: a function of n_pad alone, so it adds no program.
                key_bound = (2**31 - 1) // n_pad - 1
                dev_rows = _as_int32(rows) if rows.ndim == 2 else None
                eligible = (
                    dev_rows is not None
                    and parts.dtype.kind in "iu"
                    and int(uniq[0]) >= 0
                    and pad_part <= key_bound
                )
                if eligible:
                    parts_p = np.full(n_pad, pad_part, np.int32)
                    parts_p[:n] = parts
                    rows_p = np.zeros((n_pad, rows.shape[1]), np.int32)
                    rows_p[:n] = dev_rows
            if not eligible:
                self.wall.kernel_fallbacks += 1
                with span("hook.split"):
                    return [(int(q), rows[parts == q]) for q in uniq]
            count("partition.rows", n)
            count("partition.padded_rows", n_pad)
            t0 = time.perf_counter()
            with span("hook.upload"):
                rows_p, parts_p = jnp.asarray(rows_p), jnp.asarray(parts_p)
            with span("hook.device"):
                gathered = _group_by_part(rows_p, parts_p, max_key=key_bound,
                                          interpret=self.interpret)
                jax.block_until_ready(gathered)
            self.wall.record_kernel(time.perf_counter() - t0)
            with span("hook.download"):
                ordered = np.asarray(gathered)[:n].astype(rows.dtype, copy=False)
            with span("hook.split"):
                out: List[Tuple[int, np.ndarray]] = []
                start = 0
                for q, c in zip(uniq, counts):
                    out.append((int(q), ordered[start:start + int(c)]))
                    start += int(c)
                return out


def make_backend(
    *levels: Union[TierSpec, str, Tuple[Union[TierSpec, str], float]],
    interpret: Optional[bool] = None,
    device=None,
) -> ExecutionBackend:
    """Build an :class:`ExecutionBackend` from tier / ``(tier, cap)`` levels.

    The backend twin of :func:`repro.remote.simulator.make_hierarchy` —
    same tier resolution, e.g. ``make_backend(("dram", 64), "rdma", "ssd")``.
    """
    from repro.core.cost_model import hierarchy_spec

    return ExecutionBackend(hierarchy_spec(*levels), interpret=interpret,
                            device=device)
