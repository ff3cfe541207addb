"""One equijoin of two ``(key, payload)`` relations, R and S, on column 0.

``LogicalPlan`` holds ``join(scan R, scan S)``; ``compile_plan`` lowers it
to one external hash join (EHJ) task, picking which relation it builds on,
and the query runs it with ``replan="measured"``.  The partition count is
the configuration's, the spilled share of partitions (``sigma``) the
traffic's.  The reference (in ``check.py``) is the equijoin as a multiset of
``(key, build payload, probe payload)`` rows.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench import data

TABLES = ("R", "S")


def tables(config: dict, seed: int) -> Dict[str, np.ndarray]:
    """R: unique keys; S: foreign keys drawn uniformly from R's keys."""
    n_r = config["build_rows"]
    return {"R": data.unique_table(seed, 0, n_r),
            "S": data.table(seed, 1, config["probe_rows"], n_r)}


def place(backend, tables: Dict[str, np.ndarray], config: dict) -> Dict[str, object]:
    """Seed each relation's pages on the backend (bottom tier, no transfer
    rounds) and return the relations the plan scans."""
    from repro.remote.simulator import Relation

    rows = config["page_rows"]
    return {name: Relation(page_ids=backend.put_local(data.pages(t, rows)),
                           rows_per_page=rows, total_rows=len(t))
            for name, t in tables.items()}


def run(session, inputs: Dict[str, object], config: dict, params: dict, span):
    from repro.engine.plan import LogicalPlan, compile_plan

    rows = config["page_rows"]
    with span("compile_plan"):
        lp = LogicalPlan("join")
        lp.join(lp.scan("R", inputs["R"], rows_per_page=rows),
                lp.scan("S", inputs["S"], rows_per_page=rows),
                sigma=params["sigma"], partitions=params["partitions"])
        plan = compile_plan(session, lp)
    with span("session.run"):
        return plan.run(session, replan="measured")
