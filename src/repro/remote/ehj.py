"""External (radix-partitioned) hash join over simulated remote memory.

Algorithm 3 / §III-C: both relations are hash-partitioned into P partitions; a
fraction ``sigma`` of partitions spill.  Phase P1 partitions the build side
(resident partitions become in-memory sorted key indexes, spilled tuples
flush through the R_w write pool); P2 partitions the probe side (resident
tuples probe on the fly, spilled tuples stage through R_s, resident output
through R_o); P3 re-reads each spilled pair, indexes its build partition once
and probes it block by block.  A partition's table is a :class:`KeyIndex`:
its keys are sorted once, and each probe row is searched in them once
(``probe_index``), so a partition probed in many blocks is never searched
again per block.  Rows may be of any width: each side names its key column
and the columns it keeps, and may carry a filter that runs on each block as
it is read.  The R_w/R_s/R_o pools are
per-partition-sliced :class:`repro.engine.BufferPool` instances and every
block read is a :class:`repro.engine.PageCursor` round, so the ledger counts
match the Table V terms.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

from repro.core.policies import EHJPlan
from repro.spans import count, span
from repro.engine.buffers import BufferPool, PageCursor
from repro.engine.scheduler import TransferScheduler, stream_tiers
from repro.remote.bnlj import _block_join
from repro.remote.rows import Term, check_where, page_rows, partition_of, select
from repro.remote.simulator import Relation, RemoteMemory, as_relation, relation_rows


# Typed input signature for the session API: ``engine.registry`` binds named
# task inputs to ``ehj``'s positional data-plane arguments through this, and
# maps each input to the WorkloadStats field that estimates its size.
INPUTS = ("build", "probe")
INPUT_STATS = {"build": "size_r", "probe": "size_s"}

# Spill streams this operator writes, in declaration order — the unit of
# fractional placement: spilled build partitions, staged probe tuples, and
# the join output (resident + external rounds share the output stream tier).
STREAMS = ("build", "stage", "output")


@dataclasses.dataclass
class HashJoinResult:
    output_rows: int
    sigma: float
    d_read: float
    d_write: float
    c_read: int
    c_write: int
    per_phase_rounds: Dict[str, int]
    output_page_ids: List[int] = dataclasses.field(default_factory=list)


class KeyIndex(NamedTuple):
    """One build partition's table: its keys sorted once, stably, each other
    column of its rows in the same order (one array a column), and whether
    no key repeats."""

    keys: np.ndarray
    payloads: Tuple[np.ndarray, ...]
    unique: bool


def build_index(rows: np.ndarray) -> KeyIndex:
    """Index a build partition's ``(key, payload, ...)`` rows on column 0.

    ``unique`` is read from the sorted keys (no two neighbours equal), so the
    probe's path follows the data: a primary-key build side takes the
    one-search path of :func:`probe_index`.
    """
    order = np.argsort(rows[:, 0], kind="stable")
    keys = rows[order, 0]
    payloads = tuple(rows[order, c] for c in range(1, rows.shape[1]))
    return KeyIndex(keys, payloads, not (keys[1:] == keys[:-1]).any())


def probe_index(index: KeyIndex, probe_rows: np.ndarray) -> np.ndarray:
    """Equijoin ``probe_rows`` against a build partition's index on column 0.

    Returns ``(key, build payload..., probe payload...)`` rows in probe
    order, the payloads being every column but the key of each side: the
    same pairs as ``_block_join(build_rows, probe_rows)`` on ``(key,
    payload)`` rows, which emits them in build order.  Each probe key is
    searched once: one ``searchsorted`` where the build keys are unique, a
    left and a right one (and a repeat by the match counts) where they are
    not.  Each payload column is gathered on its own.
    """
    keys, pk = index.keys, probe_rows[:, 0]
    nb = len(index.payloads)
    width = nb + probe_rows.shape[1]
    if not len(keys) or not len(pk):
        return np.empty((0, width), dtype=np.int64)
    # Searching the probe keys in ascending order lets each search start from
    # the last one's result; the positions are scattered back to probe order.
    order = np.argsort(pk)
    ascending = pk[order]

    def search(side: str) -> np.ndarray:
        pos = np.empty(len(pk), dtype=np.intp)
        pos[order] = np.searchsorted(keys, ascending, side=side)
        return pos

    if index.unique:
        pos = search("left")
        np.minimum(pos, len(keys) - 1, out=pos)
        probe_idx = np.flatnonzero(keys[pos] == pk)
        build_idx = pos[probe_idx]
    else:
        lo = search("left")
        counts = search("right") - lo
        total = int(counts.sum())
        probe_idx = np.repeat(np.arange(len(pk)), counts)
        # Build position of each pair: its key run's start plus its rank in it.
        build_idx = np.repeat(lo - (np.cumsum(counts) - counts), counts) + np.arange(total)
    out = np.empty((len(probe_idx), width), dtype=np.int64)
    out[:, 0] = pk[probe_idx]
    for c, column in enumerate(index.payloads, start=1):
        out[:, c] = column[build_idx]
    for c in range(1, probe_rows.shape[1]):
        out[:, nb + c] = probe_rows[probe_idx, c]
    return out


def ehj_output(result: HashJoinResult) -> List[int]:
    """The operator's output pages — what a downstream task's input binds to."""
    return result.output_page_ids


def ehj_measured(stats, result: HashJoinResult):
    """Feed the measured output cardinality back into the workload stats.

    This is the ROADMAP's known misestimation case: the planner's ``out``
    estimate can be ~8x off at high selectivity, and the measured page count
    is what ``Session.run(replan="measured")`` re-arbitrates with.
    """
    return dataclasses.replace(stats, out=float(len(result.output_page_ids)))


def ehj(
    remote: RemoteMemory,
    build: Relation,
    probe: Relation,
    plan: EHJPlan,
    rows_per_page: int | None = None,
    prefetch: bool = False,
    tier=None,
    on: Tuple[int, int] = (0, 0),
    keep: Tuple[Sequence[int], Sequence[int]] = ((1,), (1,)),
    build_where: Sequence[Term] = (),
    probe_where: Sequence[Term] = (),
    page_bytes: int | None = None,
) -> HashJoinResult:
    """Run the three-phase external hash join under `plan`.

    ``remote`` is a single tier or a :class:`MemoryHierarchy`; on a
    hierarchy, ``tier`` names the placement spilled partitions and output
    are routed to — a scalar, or a per-stream spec over ``STREAMS`` (e.g.
    spilled build partitions on DRAM, staged probe tuples on SSD).
    ``build`` / ``probe`` accept a ``Relation`` or a bare page-id list.

    The join is ``build[on[0]] == probe[on[1]]``.  Each block is filtered by
    its side's ``*_where`` (a conjunction of ``(column, comparison,
    constant)`` terms, :mod:`repro.remote.rows`) as soon as it is read, then
    cut to ``(key, kept columns...)`` with ``keep`` naming each side's kept
    columns, before it is hashed; a filter adds no round.  Output rows are
    ``(key, build kept columns..., probe kept columns...)``; the defaults
    join ``(key, payload)`` rows on column 0 into ``(key, build payload,
    probe payload)``.  Every stream's pages hold ``rows_per_page`` rows, or
    with ``page_bytes`` as many rows of the stream's width as fill that
    many bytes of int64.
    """
    build = as_relation(remote, build)
    probe = as_relation(remote, probe)
    tiers = stream_tiers(tier, STREAMS)
    rows_per_page = rows_per_page or build.rows_per_page
    build_cols = (int(on[0]), *map(int, keep[0]))
    probe_cols = (int(on[1]), *map(int, keep[1]))
    build_where, probe_where = check_where(build_where), check_where(probe_where)
    out_width = len(build_cols) + len(probe_cols) - 1
    build_rpp, probe_rpp, out_rpp = (page_rows(w, rows_per_page, page_bytes)
                                     for w in (len(build_cols), len(probe_cols), out_width))

    p = plan.partitions
    n_spilled = int(round(plan.sigma * p))
    spilled = set(range(p - n_spilled, p))  # deterministic spill set
    sched = TransferScheduler(remote, tier=tiers["output"])
    before = sched.snapshot()
    phase_rounds: Dict[str, int] = {}

    def side(rows: np.ndarray, where, cols) -> np.ndarray:
        """A block read from one side, filtered and cut to (key, kept...)."""
        if not where:
            return select(rows, where, cols)
        with span("ehj.filter"):
            out = select(rows, where, cols)
        count("filter.rows_in", len(rows))
        count("filter.rows_out", len(out))
        return out

    def hash_part(keys: np.ndarray) -> np.ndarray:
        with span("ehj.hash"):
            return partition_of(keys, p)

    def table(blocks: List[np.ndarray]) -> KeyIndex:
        with span("ehj.table"):
            rows = (np.concatenate(blocks, axis=0) if blocks
                    else np.empty((0, len(build_cols)), dtype=np.int64))
            return build_index(rows)

    def join(index: KeyIndex, probe_rows: np.ndarray) -> np.ndarray:
        with span("ehj.join"):
            matched = probe_index(index, probe_rows)
        count("ehj.join_calls")
        count("ehj.join_rows_out", len(matched))
        return matched

    # ---- P1: partition build, index resident partitions, spill the rest ---
    with span("ehj.P1"):
        t0 = sched.snapshot()
        r_r1, r_w1 = plan.p1
        build_pool = BufferPool(sched, r_w1, build_rpp,
                                n_streams=max(len(spilled), 1),
                                tier=tiers["build"])
        resident_build: Dict[int, List[np.ndarray]] = {
            q: [] for q in range(p) if q not in spilled}
        for rows in PageCursor(sched, build.page_ids, round(r_r1),
                               prefetch=prefetch).blocks():
            rows = side(rows, build_where, build_cols)
            parts = hash_part(rows[:, 0])
            for q, sel in sched.partitions(rows, parts):
                if q in spilled:
                    build_pool.add(sel, stream=q)
                else:
                    resident_build[q].append(sel)
        build_pool.flush_all()
        resident_tables = {q: table(v) for q, v in resident_build.items()}
        phase_rounds["P1"] = sched.delta(t0).c_total

    # ---- P2: partition probe; probe resident, stage spilled ----------------
    with span("ehj.P2"):
        t0 = sched.snapshot()
        r_r2, r_s2, r_o2 = plan.p2
        stage_pool = BufferPool(sched, r_s2, probe_rpp,
                                n_streams=max(len(spilled), 1),
                                tier=tiers["stage"])
        out_pool = BufferPool(sched, r_o2, out_rpp, tier=tiers["output"])
        output_rows = 0
        for rows in PageCursor(sched, probe.page_ids, round(r_r2),
                               prefetch=prefetch).blocks():
            rows = side(rows, probe_where, probe_cols)
            parts = hash_part(rows[:, 0])
            for q, sel in sched.partitions(rows, parts):
                if q in spilled:
                    stage_pool.add(sel, stream=q)
                else:
                    matched = join(resident_tables[q], sel)
                    if len(matched):
                        output_rows += len(matched)
                        out_pool.add(matched)  # single resident-output stream
        stage_pool.flush_all()
        phase_rounds["P2"] = sched.delta(t0).c_total

    # ---- P3: external rounds over spilled pairs ----------------------------
    with span("ehj.P3"):
        t0 = sched.snapshot()
        r_r3, r_o3 = plan.p3
        read_pages = round(r_r3)
        ext_out_pool = BufferPool(sched, r_o3, out_rpp, tier=tiers["output"])
        for q in sorted(spilled):
            b_ids = build_pool.pages(q)
            q_ids = stage_pool.pages(q)
            if not b_ids or not q_ids:
                continue
            b_index = table(list(PageCursor(sched, b_ids, read_pages,
                                            prefetch=prefetch).blocks()))
            for q_rows in PageCursor(sched, q_ids, read_pages,
                                     prefetch=prefetch).blocks():
                matched = join(b_index, q_rows)
                if len(matched):
                    output_rows += len(matched)
                    ext_out_pool.add(matched, stream=q)
        out_pool.flush_all()
        ext_out_pool.flush_all()
        phase_rounds["P3"] = sched.delta(t0).c_total

    d = sched.delta(before)
    output_ids = list(out_pool.pages())
    for q in sorted(spilled):
        output_ids.extend(ext_out_pool.pages(q))
    return HashJoinResult(
        output_rows=output_rows,
        sigma=plan.sigma,
        d_read=d.d_read,
        d_write=d.d_write,
        c_read=d.c_read,
        c_write=d.c_write,
        per_phase_rounds=phase_rounds,
        output_page_ids=output_ids,
    )


def ehj_oracle(remote: RemoteMemory, build: Relation, probe: Relation) -> int:
    """Oracle row count for the equijoin (no accounting)."""
    b = relation_rows(remote, build)
    q = relation_rows(remote, probe)
    return len(_block_join(b, q))
