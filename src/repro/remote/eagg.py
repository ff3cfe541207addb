"""External (grace-style) hash aggregation over simulated remote memory.

Two phases under one I/O budget.  P1 scans the input relation through the R_r
read buffer and hash-partitions it into P partitions: resident partitions
aggregate on the fly in local hash tables, spilled partitions (fraction
``sigma``) flush raw tuples through the per-partition-sliced R_w write pool,
and the resident group output flushes through R_o.  P2 re-reads each spilled
partition through R_r, aggregates it in memory (grace assumption: one
partition fits locally), and flushes its groups through R_o.  Every block
read is a :class:`repro.engine.PageCursor` round and every pool flush a
:class:`repro.engine.BufferPool` round, so the measured ledger matches
:func:`repro.core.policies.eagg_costs_exact` exactly (skew included).

Groups are the distinct values of one or more columns, and each sum an int64
expression of columns, added exactly in int64; group rows are ``(group
columns..., sums..., count)``, by default ``(key, sum(column 1), count)``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.core.policies import EAggPlan
from repro.engine.buffers import BufferPool, PageCursor
from repro.engine.scheduler import TransferScheduler, stream_tiers
from repro.remote.rows import (
    SumExpr, Term, check_sum, check_where, evaluate, page_rows, partition_of, select,
)
from repro.remote.simulator import Relation, RemoteMemory, as_relation, relation_rows
from repro.spans import count, span


# Typed input signature for the session API: ``engine.registry`` binds named
# task inputs to ``eagg``'s positional data-plane arguments through this, and
# maps each input to the WorkloadStats field that estimates its size.
INPUTS = ("rel",)
INPUT_STATS = {"rel": "size_r"}

# Spill streams this operator writes, in declaration order — the unit of
# fractional placement: raw spilled partitions vs. the group output
# (resident P1 groups and external P2 groups share the output stream tier).
STREAMS = ("partitions", "output")


@dataclasses.dataclass
class AggResult:
    output_page_ids: List[int]
    group_rows: int
    sigma: float
    d_read: float
    d_write: float
    c_read: int
    c_write: int
    per_phase_rounds: Dict[str, int]
    # How many spilled partitions were partially aggregated at the memory
    # tier in P2 (0 when pushdown was off or no tier was reduce-capable).
    pushdown_partitions: int = 0


def eagg_output(result: AggResult) -> List[int]:
    """The operator's output pages — what a downstream task's input binds to."""
    return result.output_page_ids


def eagg_measured(stats, result: AggResult):
    """Feed the measured output cardinality back into the workload stats."""
    return dataclasses.replace(stats, out=float(len(result.output_page_ids)))


def _aggregate(rows: np.ndarray, keys: int = 1) -> np.ndarray:
    """Group rows by their first ``keys`` columns: ``(key columns..., sum of
    each other column..., count)`` per group, in key order.

    The sums are int64 additions over the rows in key order, so they are
    exact wherever the true sum fits in int64.
    """
    if not len(rows):
        return np.empty((0, rows.shape[1] + 1), dtype=np.int64)
    if keys == 1:
        order = np.argsort(rows[:, 0], kind="stable")
    else:
        order = np.lexsort(rows[:, keys - 1::-1].T)
    rows = rows[order]
    k = rows[:, :keys]
    first = np.ones(len(rows), dtype=bool)
    first[1:] = (k[1:] != k[:-1]).any(axis=1)
    starts = np.flatnonzero(first)
    out = np.empty((len(starts), rows.shape[1] + 1), dtype=np.int64)
    out[:, :keys] = k[starts]
    out[:, keys:-1] = np.add.reduceat(rows[:, keys:], starts, axis=0)
    out[:, -1] = np.diff(np.append(starts, len(rows)))
    return out


def _cut(rows: np.ndarray, by: Tuple[int, ...], sums: Tuple[SumExpr, ...]) -> np.ndarray:
    """Rows cut to ``(group columns..., sum values...)``; a block already in
    that shape comes back as it is."""
    if all(isinstance(s, int) for s in sums):
        return select(rows, (), by + sums)
    out = np.empty((len(rows), len(by) + len(sums)), dtype=np.int64)
    out[:, :len(by)] = rows[:, by]
    for j, s in enumerate(sums):
        out[:, len(by) + j] = evaluate(rows, s)
    return out


def _groups(rows: np.ndarray, keys: int) -> np.ndarray:
    """:func:`_aggregate` of ``keys`` key columns (one key column keeps its
    one-argument call)."""
    with span("eagg.group"):
        out = _aggregate(rows) if keys == 1 else _aggregate(rows, keys)
    count("eagg.groups", len(out))
    return out


def _reduce_partition(pages: List[np.ndarray], keys: int = 1) -> np.ndarray:
    """Tier-side reducer for one spilled partition (grace assumption)."""
    return _groups(np.concatenate(pages, axis=0), keys)


def eagg(
    remote: RemoteMemory,
    rel: Relation,
    plan: EAggPlan,
    rows_per_page: int | None = None,
    prefetch: bool = False,
    tier=None,
    pushdown: bool = False,
    by: Sequence[int] = (0,),
    sums: Sequence[SumExpr] = (1,),
    where: Sequence[Term] = (),
    page_bytes: int | None = None,
) -> AggResult:
    """Run the two-phase external hash aggregation under ``plan``.

    ``remote`` is a single tier or a :class:`MemoryHierarchy`; on a
    hierarchy, ``tier`` names the placement spilled partitions and group
    output are routed to — a scalar, or a per-stream spec over ``STREAMS``.
    ``rel`` accepts a ``Relation`` or a bare page-id list.

    Groups are the distinct values of the columns ``by``; each of ``sums``
    is a column or an int64 expression of columns (``("*", 3, ("-",
    ("lit", 100), 4))`` is ``c3 * (100 - c4)``).  Each block is filtered by
    ``where`` (:mod:`repro.remote.rows`) as it is read and cut to ``(group
    columns..., sum values...)`` before it is hashed, so spilled partitions
    hold those rows.  Output rows are ``(group columns..., sums...,
    count)``; the defaults give ``(key, sum of column 1, count)``.  Pages
    hold ``rows_per_page`` rows, or with ``page_bytes`` as many rows of
    their stream's width as fill that many bytes of int64.

    ``pushdown=True`` lets P2 partially aggregate a spilled partition *at*
    the tier holding it: when every page of the partition is resident on one
    ``"reduce"``-capable tier, a single ``read_reduced`` pushdown round
    ships only the group pages instead of re-reading the raw spill.
    Partitions that waterfalled across tiers, or sit on non-capable tiers,
    fall back to the plain re-read — the group table is identical either
    way (``_aggregate`` is deterministic), only D/C change.
    """
    rel = as_relation(remote, rel)
    tiers = stream_tiers(tier, STREAMS)
    rows_per_page = rows_per_page or rel.rows_per_page
    by = tuple(int(c) for c in by)
    sums = tuple(check_sum(s) for s in sums)
    where = check_where(where)
    n_keys = len(by)
    if not n_keys:
        raise ValueError("an aggregation groups on at least one column")

    def agg_rows(rows: np.ndarray) -> np.ndarray:
        """A block read from the input, filtered and cut to (groups, sums)."""
        if where:
            with span("eagg.filter"):
                kept = select(rows, where, range(rows.shape[1]))
            count("filter.rows_in", len(rows))
            count("filter.rows_out", len(kept))
            rows = kept
        return _cut(rows, by, sums)

    def hash_part(rows: np.ndarray) -> np.ndarray:
        with span("eagg.hash"):
            return partition_of(rows[:, :n_keys], p)

    p = plan.partitions
    n_spilled = int(round(plan.sigma * p))
    spilled = set(range(p - n_spilled, p))  # deterministic spill set
    sched = TransferScheduler(remote, tier=tiers["output"])
    before = sched.snapshot()
    phase_rounds: Dict[str, int] = {}

    out_width = n_keys + len(sums) + 1
    spill_rpp, out_rpp = (page_rows(w, rows_per_page, page_bytes)
                          for w in (out_width - 1, out_width))

    # ---- P1: scan, aggregate resident partitions, spill the rest -----------
    with span("eagg.P1"):
        t0 = sched.snapshot()
        r_r1, r_w1, r_o1 = plan.p1
        spill_pool = BufferPool(sched, r_w1, spill_rpp,
                                n_streams=max(len(spilled), 1),
                                tier=tiers["partitions"])
        resident: Dict[int, List[np.ndarray]] = {
            q: [] for q in range(p) if q not in spilled}
        for rows in PageCursor(sched, rel.page_ids, round(r_r1),
                               prefetch=prefetch).blocks():
            rows = agg_rows(rows)
            parts = hash_part(rows)
            for q, sel in sched.partitions(rows, parts):
                if q in spilled:
                    spill_pool.add(sel, stream=q)
                else:
                    resident[q].append(sel)
        spill_pool.flush_all()
        out_pool = BufferPool(sched, r_o1, out_rpp, tier=tiers["output"])
        group_rows = 0
        for q in sorted(resident):
            if not resident[q]:
                continue
            groups = _groups(np.concatenate(resident[q], axis=0), n_keys)
            group_rows += len(groups)
            out_pool.add(groups)  # single resident-output stream
        out_pool.flush_all()
        phase_rounds["P1"] = sched.delta(t0).c_total

    # ---- P2: re-read each spilled partition, aggregate, flush groups -------
    with span("eagg.P2"):
        t0 = sched.snapshot()
        r_r2, r_o2 = plan.p2
        read_pages = round(r_r2)
        ext_out_pool = BufferPool(sched, r_o2, out_rpp, tier=tiers["output"])
        pushdown_parts = 0
        for q in sorted(spilled):
            ids = spill_pool.pages(q)
            if not ids:
                continue
            pushed = False
            if pushdown and getattr(remote, "is_hierarchy", False):
                homes = {remote.tier_of(i) for i in ids}
                if len(homes) == 1:
                    home = homes.pop()
                    if remote.spec.level(home).can_push("reduce"):
                        group_pages = remote.read_reduced(
                            home, ids,
                            functools.partial(_reduce_partition, keys=n_keys),
                            out_rpp,
                        )
                        groups = (
                            np.concatenate(group_pages, axis=0)
                            if group_pages
                            else np.empty((0, out_width), dtype=np.int64)
                        )
                        pushdown_parts += 1
                        pushed = True
            if not pushed:
                part_rows = PageCursor(sched, ids, read_pages,
                                       prefetch=prefetch).read_all()
                groups = _groups(part_rows, n_keys)
            group_rows += len(groups)
            ext_out_pool.add(groups)
        ext_out_pool.flush_all()
        phase_rounds["P2"] = sched.delta(t0).c_total

    d = sched.delta(before)
    return AggResult(
        output_page_ids=out_pool.pages() + ext_out_pool.pages(),
        group_rows=group_rows,
        sigma=plan.sigma,
        d_read=d.d_read,
        d_write=d.d_write,
        c_read=d.c_read,
        c_write=d.c_write,
        per_phase_rounds=phase_rounds,
        pushdown_partitions=pushdown_parts,
    )


def eagg_oracle(remote: RemoteMemory, rel: Relation, by: Sequence[int] = (0,),
                sums: Sequence[SumExpr] = (1,)) -> np.ndarray:
    """Oracle group table ``(group columns..., sums..., count)``, in group
    order (no accounting)."""
    by, sums = tuple(int(c) for c in by), tuple(check_sum(s) for s in sums)
    return _aggregate(_cut(relation_rows(remote, rel), by, sums), len(by))
