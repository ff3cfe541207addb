"""A query whose reference is its own: the equijoin of R and S on column 0,
then a group-by of the join's rows on the key, summing the build payload.

``LogicalPlan`` holds ``aggregate(join(scan R, scan S))``; ``compile_plan``
lowers it to an external hash join (EHJ) task and an external hash
aggregation (EAGG) task over the join's output pages.  The shared entries of
``check.py`` check the join; the aggregation's reference, check name and
comparison are defined here: ``(key, sum of column 1, count)`` per group,
compared as a multiset.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench import check, data

TABLES = ("R", "S")


def tables(config: dict, seed: int) -> Dict[str, np.ndarray]:
    """R: unique keys; S: foreign keys drawn uniformly from R's keys."""
    n_r = config["build_rows"]
    return {"R": data.unique_table(seed, 0, n_r),
            "S": data.table(seed, 1, config["probe_rows"], n_r)}


def place(backend, tables: Dict[str, np.ndarray], config: dict) -> Dict[str, object]:
    from repro.remote.simulator import Relation

    rows = config["page_rows"]
    return {name: Relation(page_ids=backend.put_local(data.pages(t, rows)),
                           rows_per_page=rows, total_rows=len(t))
            for name, t in tables.items()}


def run(session, inputs: Dict[str, object], config: dict, params: dict, span):
    from repro.engine.plan import LogicalPlan, compile_plan

    rows = config["page_rows"]
    with span("compile_plan"):
        lp = LogicalPlan("join_agg")
        joined = lp.join(lp.scan("R", inputs["R"], rows_per_page=rows),
                         lp.scan("S", inputs["S"], rows_per_page=rows),
                         sigma=params["sigma"], partitions=params["partitions"])
        lp.aggregate(joined, sigma=params["sigma"], partitions=params["partitions"])
        plan = compile_plan(session, lp)
    with span("session.run"):
        return plan.run(session, replan="measured")


def aggregate(rows: np.ndarray) -> np.ndarray:
    """``(key, sum of column 1, count)`` per distinct key of column 0."""
    keys, inverse, counts = np.unique(rows[:, 0], return_inverse=True, return_counts=True)
    sums = np.zeros(len(keys), np.int64)
    np.add.at(sums, inverse.ravel(), rows[:, 1])
    return np.stack([keys, sums, counts], axis=1)


REFERENCE = {"eagg": lambda ins, config: aggregate(ins["rel"])}
CHECKS = {"eagg": "agg_groups_differing"}
COMPARE = {"eagg": check.rows_differing}
