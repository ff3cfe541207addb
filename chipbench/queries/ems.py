"""A standalone external merge sort (EMS) of bare int64 keys.

One ``ems`` task through ``Session.run``: run formation sorts
``int(plan.m)``-page chunks with the backend's sort hook and writes them as
runs, and merge passes of fan-in ``plan.k`` merge them.  The reference (in
``check.py``) is every key, sorted.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench import data

TABLES = ("keys",)


def tables(config: dict, seed: int) -> Dict[str, np.ndarray]:
    return {"keys": data.keys(seed, 0, config["keys"], config["key_domain"])}


def place(backend, tables: Dict[str, np.ndarray], config: dict) -> Dict[str, object]:
    """Seed the key pages on the backend (bottom tier, no transfer rounds)."""
    return {"keys": backend.put_local(data.pages(tables["keys"], config["page_keys"]))}


def run(session, inputs: Dict[str, object], config: dict, params: dict, span):
    from repro.engine import WorkloadStats

    with span("session.run"):
        ids = inputs["keys"]
        task = session.task("ems", WorkloadStats(size_r=len(ids), k_cap=params["k_cap"]),
                            inputs={"page_ids": ids}, rows_per_page=config["page_keys"])
        return session.run([task])
