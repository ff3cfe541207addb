"""External hash aggregation: Property-6 pools vs starved baseline.

Same shape as the EHJ bench (fig6a) for the new fourth operator: write-round
and simulated-latency reduction of the REMOP waterfill allocation vs the
disk-oriented starved plan, across partition counts, plus exact-ledger
verification against ``eagg_costs_exact`` (derived value 1.0 == parity).
"""

from __future__ import annotations

import numpy as np

from repro.core import TABLE_I
from repro.core.policies import eagg_costs_exact
from repro.engine import WorkloadStats, plan_operator, registry
from repro.remote import RemoteMemory, make_relation
from repro.remote.rows import partition_of
from benchmarks.common import Row, timed

TIER = TABLE_I["tcp"]  # paper Table I constants (see bench_bnlj)
EAGG = registry.get("eagg")
N_PAGES, ROWS, DOMAIN = 192, 8, 256


def _run(plan, seed=0):
    remote = RemoteMemory(TIER)
    rel = make_relation(remote, N_PAGES * ROWS, ROWS, DOMAIN, seed=seed)
    res = EAGG.run(remote, rel, plan)
    return res, remote, rel


def _exact_parity(remote, rel, plan, res) -> bool:
    rows = np.concatenate(remote.peek_batch(rel.page_ids), axis=0)
    parts = partition_of(rows[:, 0], plan.partitions)
    n_spilled = int(round(plan.sigma * plan.partitions))
    spilled = list(range(plan.partitions - n_spilled, plan.partitions))
    spill_mask = np.isin(parts, spilled)
    d, c = eagg_costs_exact(
        N_PAGES, ROWS,
        [int((parts == q).sum()) for q in spilled],
        len(np.unique(rows[~spill_mask][:, 0])),
        len(np.unique(rows[spill_mask][:, 0])),
        plan,
    )
    return res.d_read + res.d_write == d and res.c_read + res.c_write == c


def run() -> list[Row]:
    rows_out: list[Row] = []
    m_b, sigma = 24.0, 0.5
    for parts in (4, 8, 16):
        stats = WorkloadStats(size_r=N_PAGES, out=32, partitions=parts,
                              sigma=sigma)
        remop = plan_operator("eagg", stats, TIER, m_b)
        starved = plan_operator("eagg", stats, TIER, m_b, policy="conventional")

        def run_pair(starved=starved, remop=remop):
            res_s, rem_s, _ = _run(starved)
            res_r, rem_r, rel_r = _run(remop)
            assert res_s.group_rows == res_r.group_rows
            parity = _exact_parity(rem_r, rel_r, remop, res_r)
            return (res_s.c_write, res_r.c_write,
                    rem_s.latency_seconds(), rem_r.latency_seconds(), parity)

        us, (w_s, w_r, lat_s, lat_r, parity) = timed(run_pair, repeats=1)
        rows_out.append((f"eagg_P{parts}_write_round_reduction", us,
                         round(1 - w_r / w_s, 4)))
        rows_out.append((f"eagg_P{parts}_sim_latency_reduction", 0.0,
                         round(1 - lat_r / lat_s, 4)))
        rows_out.append((f"eagg_P{parts}_exact_ledger_parity", 0.0,
                         float(parity)))
    return rows_out


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
