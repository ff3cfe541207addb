"""Readings of the comparison that decides ``correct``: the program's and the
control's, on as many seeds as asked, at the cell's own size.

    python3 -m chipbench.control --workload <cell> --seeds <n> [<n> ...]

For each seed, in one process: generate the tables, place them, run one
query of the cell through the timed path (a fresh ``Session`` on the
``ExecutionBackend``, as in the window), and compare every task's output with
the reference.  That gives the program's reading.  Then the control takes
the program's place: the reference again, over the same task graph, but
breaking one guarantee the configuration states, and the same comparison
reads it.  A limit has to lie between the two readings.

The reference, the check names and the comparisons are the cell's
(``check.rules``: a query module's own entries merged over the shared ones).
An op with no control keeps its reference, so a control breaks the ops it
names and the tasks downstream of them.  The controls:

* EHJ keeps one output row per key, as a hash table with unique keys would:
  it breaks the multiset (bag) semantics of the join.
* EMS sorts the keys as float32, the TPU's native sort type: keys of 2^24
  and more are rounded, which breaks the exactness of int64 keys.

The benchmark's own runs never run this.  It needs a TPU as they do.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import check


def join_unique_keys(build: np.ndarray, probe: np.ndarray) -> np.ndarray:
    """The join as a hash table with unique keys gives it: one row per key."""
    rows = check.join(build, probe)
    _, first = np.unique(rows[:, 0], return_index=True)
    return rows[np.sort(first)]


def sort_float32(values: np.ndarray) -> np.ndarray:
    """Every value, sorted as float32 and read back as int64."""
    return np.sort(values.ravel().astype(np.float32)).astype(np.int64)


CONTROL = {
    "ehj": lambda ins, config: join_unique_keys(ins["build"], ins["probe"]),
    "ems": lambda ins, config: sort_float32(ins["page_ids"]),
}


def readings(cell, seed: int, *, log) -> Dict[str, Dict[str, int]]:
    """The program's and the control's counts for one seed."""
    from repro.remote import make_backend

    from chipbench import harness

    tables = cell.query.tables(cell.config, seed)
    backend = make_backend(*[tuple(t) if isinstance(t, list) else t
                             for t in cell.config["tiers"]])
    inputs = cell.query.place(backend, tables, cell.config)
    keep = set(backend.resident_ids())
    rec, result, outputs = harness.run_query(backend, cell, inputs, keep, harness.Spans())
    struct = harness.structure(result, inputs)
    del backend, inputs, result
    got = [np.concatenate(pages, axis=0) for pages in outputs]
    rules = check.rules(cell.query, cell.config)
    want = rules.outputs(struct, tables)
    control = rules.outputs(struct, tables, {**rules.reference, **CONTROL})
    out = {"program": rules.count(struct, got, want),
           "control": rules.count(struct, control, want)}
    log(f"seed {seed}: query {json.dumps(rec)}; "
        f"program {json.dumps(out['program'])}; control {json.dumps(out['control'])}")
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="chipbench.control")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    from chipbench import harness, run

    sys.path.insert(0, str(run.ROOT / "src"))
    cell = harness.load_cell(args.workload, run.ROOT)
    import jax

    run.require_chips(jax, cell.chips)
    run.configure_cache(jax)
    t0 = time.perf_counter()
    per_seed: List[dict] = []
    for seed in args.seeds:
        per_seed.append(readings(cell, seed, log=lambda s: print(s, flush=True)))
    summary = {}
    for side in ("program", "control"):
        names = sorted({n for r in per_seed for n in r[side]})
        summary[side] = {n: {"max": max(r[side].get(n, 0) for r in per_seed),
                             "min": min(r[side].get(n, 0) for r in per_seed)}
                         for n in names}
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "seconds": time.perf_counter() - t0, **summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
