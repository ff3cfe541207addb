"""External merge sort over simulated remote memory (Algorithm 2).

Run formation sorts M-page chunks in "local memory" and writes them back as
sorted runs; the merge phase merges groups of ``k`` runs through per-run input
buffers of ``floor(R_in/k)`` pages and an ``R_out``-page output buffer.  Each
run streams through a :class:`repro.engine.PageCursor` (one refill = one read
round) and the output region is a :class:`repro.engine.BufferPool` (one slice
flush = one write round), exactly as analysed in §III-B (and the §II-C worked
example).  Pages are bare keys, sorted by the backend's hook, or row blocks
sorted on chosen columns (ORDER BY) on the host; a LIMIT keeps the first rows
of every run and stops the merge once it has emitted them.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.policies import EMSPlan
from repro.engine.buffers import BufferPool, PageCursor
from repro.engine.scheduler import TransferScheduler, stream_tiers
from repro.remote.rows import page_rows
from repro.remote.simulator import RemoteMemory
from repro.spans import count, span


# Typed input signature for the session API: ``engine.registry`` binds named
# task inputs to ``ems_sort``'s positional data-plane arguments through this,
# and maps each input to the WorkloadStats field that estimates its size.
INPUTS = ("page_ids",)
INPUT_STATS = {"page_ids": "size_r"}

# Spill streams this operator writes, in declaration order — the unit of
# fractional placement: intermediate sorted runs vs. the final merged output.
STREAMS = ("runs", "output")


@dataclasses.dataclass
class SortResult:
    run_page_ids: List[int]  # final single sorted run
    passes: int
    d_read: float
    d_write: float
    c_read: int
    c_write: int


def ems_output(result: SortResult) -> List[int]:
    """The operator's output pages — what a downstream task's input binds to."""
    return result.run_page_ids


def ems_measured(stats, result: SortResult):
    """Feed the measured output cardinality back into the workload stats."""
    return dataclasses.replace(stats, out=float(len(result.run_page_ids)))


# A sort key: (column, descending) pairs, the first the most significant.
Order = Tuple[Tuple[int, bool], ...]


def row_order(by: Sequence[Tuple[int, bool]], width: int) -> Order:
    """The total order of rows sorted on ``by``: its columns, then every
    other column ascending in column order, so no two distinct rows tie."""
    by = tuple((int(c), bool(desc)) for c, desc in by)
    named = {c for c, _ in by}
    if len(named) != len(by) or any(not 0 <= c < width for c in named):
        raise ValueError(f"sort columns {by} are not distinct columns of a "
                         f"{width}-column row")
    return by + tuple((c, False) for c in range(width) if c not in named)


def sort_rows(rows: np.ndarray, order: Order) -> np.ndarray:
    """``rows`` in ``order`` (``np.lexsort``; a descending column sorts by its
    bitwise complement, which reverses int64 order without overflow)."""
    return rows[np.lexsort([~rows[:, c] if desc else rows[:, c]
                            for c, desc in reversed(order)])]


def _leading(rows: np.ndarray, bound: np.ndarray, order: Order) -> int:
    """How many rows of a sorted block come no later than the row ``bound``."""
    before = np.zeros(len(rows), dtype=bool)
    tied = np.ones(len(rows), dtype=bool)
    for c, desc in order:
        col, b = rows[:, c], bound[c]
        before |= tied & ((col > b) if desc else (col < b))
        tied &= col == b
    return int(np.count_nonzero(before | tied))


def _merge_group(
    sched: TransferScheduler,
    runs: List[List[int]],
    plan: EMSPlan,
    rows_of,
    prefetch: bool,
    out_tier=None,
    order: Optional[Order] = None,
    limit: Optional[int] = None,
) -> List[int]:
    """Merge up to k runs into one; returns the new run's page ids.

    Keys merge by the sort hook; rows (``order`` given) on the host.  With
    ``limit`` the merge stops once that many have been emitted.
    """
    per_run = max(1, int(plan.input_pages) // max(len(runs), 1))
    r_out = max(1, int(round(plan.output_pages)))
    rows = order is not None
    cursors = [
        PageCursor(sched, r, per_run, prefetch=prefetch, ravel=not rows) for r in runs
    ]
    out_pool = None
    emitted = 0

    while limit is None or emitted < limit:
        for c in cursors:
            c.refill()  # 1 read round per refill; no-op unless buffer is empty
        active = [c for c in cursors if c.buffered > 0]
        if not active:
            break
        # Emit everything provably below every active run's buffered horizon
        # (batched tournament: same refill/flush rounds as tuple-at-a-time).
        if rows:
            bounds = [c.buffer[-1] for c in active if c.more]
            bound = sort_rows(np.stack(bounds), order)[0] if bounds else None
            taken = [c.take(c.buffered if bound is None
                            else _leading(c.buffer, bound, order)) for c in active]
            with span("ems.rows"):
                merged = sort_rows(np.concatenate(taken, axis=0), order)
        else:
            bounds = [b for c in active if (b := c.safe_bound()) is not None]
            bound = min(bounds) if bounds else None
            taken = [c.take_upto(bound) for c in active]
            merged = sched.sort_keys(np.concatenate(taken))
            if len(merged) == 0:
                # Bound excluded everything buffered: force the binding cursor on.
                binding = min(
                    active, key=lambda c: c.safe_bound() or np.iinfo(np.int64).max
                )
                merged = sched.sort_keys(binding.take_upto(None))
        if limit is not None:
            merged = merged[:limit - emitted]
        if out_pool is None:
            out_pool = BufferPool(sched, r_out, rows_of(merged), tier=out_tier)
        out_pool.add(merged)
        emitted += len(merged)
    if out_pool is None:
        return []
    out_pool.flush_all()
    return out_pool.pages()


def ems_sort(
    remote: RemoteMemory,
    page_ids: List[int],
    plan: EMSPlan,
    rows_per_page: int,
    prefetch: bool = False,
    count_run_formation: bool = True,
    tier=None,
    by: Optional[Sequence[Tuple[int, bool]]] = None,
    limit: Optional[int] = None,
    page_bytes: Optional[int] = None,
) -> SortResult:
    """Full external merge sort of the pages' int64 keys under `plan`.

    ``remote`` is a single tier or a :class:`MemoryHierarchy`; on a
    hierarchy, ``tier`` names the placement runs and merge output spill to —
    a scalar, or a per-stream spec over ``STREAMS`` routing intermediate
    runs and the final merged output to different tiers.

    With ``by``, a sequence of ``(column, descending)`` pairs, the pages are
    row blocks and whole rows sort on those columns, ties broken by the
    other columns ascending (:func:`row_order`), on the host.  Without it
    every value of every page is a key, sorted by the backend's hook.
    ``limit`` keeps the first ``limit`` rows (keys) of the order: each run
    keeps that many and the merge stops there.  Output pages hold
    ``rows_per_page`` rows (keys), or with ``page_bytes`` as many rows of
    the input's width as fill that many bytes of int64.
    """
    if hasattr(page_ids, "page_ids"):  # accept a Relation (DAG scan output)
        page_ids = list(page_ids.page_ids)
    if limit is not None and limit < 0:
        raise ValueError(f"limit must be >= 0, got {limit}")
    tiers = stream_tiers(tier, STREAMS)
    sched = TransferScheduler(remote, tier=tiers["output"])
    before = sched.snapshot()
    m_pages = max(1, int(plan.m))
    order: Optional[Order] = None

    def rows_of(block: np.ndarray) -> int:
        """Rows (keys) an output page of ``block``'s width holds."""
        return page_rows(block.shape[1] if block.ndim == 2 else 1, rows_per_page, page_bytes)

    # ---- run formation: sort M-page chunks locally (§III-B a) -------------
    runs: List[List[int]] = []
    with span("ems.runs"):
        for start in range(0, len(page_ids), m_pages):
            ids = page_ids[start : start + m_pages]
            if count_run_formation:
                pages = sched.read(ids)  # 1 round
            else:
                pages = remote.peek_batch(ids)
            if by is None:
                data = sched.sort_keys(np.concatenate([p.ravel() for p in pages]))
            else:
                data = np.concatenate(pages, axis=0)
                if order is None:
                    order = row_order(by, data.shape[1])
                count("ems.rows_in", len(data))
                with span("ems.rows"):
                    data = sort_rows(data, order)
            if limit is not None:
                data = data[:limit]
            rpp = rows_of(data)
            out_pages = [data[i : i + rpp] for i in range(0, len(data), rpp)]
            if count_run_formation:
                runs.append(sched.write(out_pages, tier=tiers["runs"]))  # 1 round
            else:
                runs.append(remote.put_local(out_pages))

    # ---- merge passes (Algorithm 2) ----------------------------------------
    passes = 0
    with span("ems.merge"):
        while len(runs) > 1:
            # The last pass (a single merge group) writes the *output* stream;
            # every earlier pass writes intermediate runs.
            final = len(runs) <= plan.k
            out_tier = tiers["output"] if final else tiers["runs"]
            nxt: List[List[int]] = []
            for g in range(0, len(runs), plan.k):
                group = runs[g : g + plan.k]
                if len(group) == 1:
                    nxt.append(group[0])
                else:
                    nxt.append(
                        _merge_group(
                            sched, group, plan, rows_of, prefetch,
                            out_tier=out_tier, order=order, limit=limit,
                        )
                    )
            runs = nxt
            passes += 1

    d = sched.delta(before)
    return SortResult(
        run_page_ids=runs[0] if runs else [],
        passes=passes,
        d_read=d.d_read,
        d_write=d.d_write,
        c_read=d.c_read,
        c_write=d.c_write,
    )


def ems_oracle(remote: RemoteMemory, page_ids: List[int]) -> np.ndarray:
    """Dense oracle: all keys, fully sorted (no accounting)."""
    return np.sort(np.concatenate([p.ravel() for p in remote.peek_batch(page_ids)]))
