"""The plain reference and the comparison that decides ``correct``.

The reference is numpy over the tables the benchmark generated, and imports
nothing of the program.  It follows the task graph the planner chose (which
input a join builds on, which join runs first), and computes each task's
output from its inputs: base tables as generated, and upstream tasks as the
reference computed them, never as the program did.  What the query computes
(key columns, predicates, constants) the reference takes from the cell's
configuration or its own query module, never from the plan: a planner that
lowered the wrong column would otherwise be followed by the reference.

Per operator there are three entries: ``REFERENCE`` (a function of the
task's inputs and the cell's configuration, returning rows), ``CHECKS`` (the name of the
number compared) and ``COMPARE`` (the function that counts what differs).
This module holds the shared ones:

* EHJ: the equijoin on column 0, as ``(key, build payload, probe payload)``
  rows, compared as a multiset;
* EMS: every value of every input page, sorted, compared in exact order.

A query module (``queries/<query>.py``) may define any of the three dicts
itself; ``rules(query)`` merges them over the shared ones, the query's entry
winning for an op it names.  Each comparison gives a count of rows (keys)
that differ, and the limit of every count is 0: the configurations state
exact results.
"""

from __future__ import annotations

import dataclasses
import pathlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# Check name per operator, and the limit of each (exact comparisons).
CHECKS = {"ehj": "join_rows_differing", "ems": "sort_keys_differing"}
LIMIT = 0


def join(build: np.ndarray, probe: np.ndarray) -> np.ndarray:
    """Equijoin on column 0: ``(key, build payload, probe payload)`` rows."""
    order = np.argsort(probe[:, 0], kind="stable")
    pkeys = probe[order, 0]
    lo = np.searchsorted(pkeys, build[:, 0], side="left")
    cnt = np.searchsorted(pkeys, build[:, 0], side="right") - lo
    b_idx = np.repeat(np.arange(len(build)), cnt)
    first = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
    p_idx = order[first + np.arange(len(b_idx))]
    return np.stack([build[b_idx, 0], build[b_idx, 1], probe[p_idx, 1]], axis=1)


def sort(values: np.ndarray) -> np.ndarray:
    """Every value, in ascending order."""
    return np.sort(values.ravel())


REFERENCE: Dict[str, Callable[..., np.ndarray]] = {
    "ehj": lambda ins, config: join(ins["build"], ins["probe"]),
    "ems": lambda ins, config: sort(ins["page_ids"]),
}


def _packed(got: np.ndarray, want: np.ndarray):
    """Both row sets as one uint64 per row, where every column's range fits
    in a share of 64 bits (a bijection, so the comparison stays exact);
    None where it does not."""
    both = np.concatenate([got, want])
    if len(both) == 0:
        return None
    lo = both.min(axis=0)
    widths = [int(w).bit_length() for w in both.max(axis=0) - lo]
    if sum(widths) > 63:
        return None
    packed = np.zeros(len(both), np.uint64)
    for col, width in enumerate(widths):
        packed = (packed << np.uint64(width)) | (both[:, col] - lo[col]).astype(np.uint64)
    return packed[:len(got)], packed[len(got):]


def _multiset(rows: np.ndarray) -> np.ndarray:
    rows = rows.reshape(len(rows), -1)
    return rows[np.lexsort(rows.T[::-1])]


def rows_differing(got: np.ndarray, want: np.ndarray) -> int:
    """Size of the multiset difference of two row sets, both ways."""
    got = got.reshape(len(got), -1).astype(np.int64)
    want = want.reshape(len(want), -1).astype(np.int64)
    if got.shape[1:] != want.shape[1:]:
        return len(got) + len(want)
    packed = _packed(got, want)
    if packed is not None:
        a, b = np.sort(packed[0]), np.sort(packed[1])
        if a.shape == b.shape and np.array_equal(a, b):
            return 0
        both = np.concatenate([a, b])
    else:
        if got.shape == want.shape and np.array_equal(_multiset(got), _multiset(want)):
            return 0
        row = np.dtype((np.void, 8 * got.shape[1]))
        both = np.ascontiguousarray(np.concatenate([got, want])).view(row).ravel()
    _, inverse = np.unique(both, return_inverse=True)
    inverse = inverse.ravel()
    n = int(inverse.max()) + 1
    a = np.bincount(inverse[:len(got)], minlength=n)
    b = np.bincount(inverse[len(got):], minlength=n)
    return int(np.abs(a - b).sum())


def keys_differing(got: np.ndarray, want: np.ndarray) -> int:
    """Positions at which two sequences differ, plus their length gap."""
    got, want = got.ravel(), want.ravel()
    n = min(len(got), len(want))
    return int(np.count_nonzero(got[:n] != want[:n])) + abs(len(got) - len(want))


COMPARE = {"ehj": rows_differing, "ems": keys_differing}

# A task as the check sees it: (op, {input name: ("table", name) or
# ("task", index of an earlier task)}).
Task = Tuple[str, Dict[str, Tuple[str, object]]]


@dataclasses.dataclass(frozen=True)
class Rules:
    """One cell's entries per operator: ``reference``, ``checks`` and
    ``compare``; ``source``, the query file they were merged from; and
    ``config``, the cell's configuration, which every reference is given."""

    reference: Dict[str, Callable[..., np.ndarray]]
    checks: Dict[str, str]
    compare: Dict[str, Callable[[np.ndarray, np.ndarray], int]]
    source: str
    config: dict

    def require(self, structure: Sequence[Task]) -> None:
        """Fail, naming the op and the query file, where a task's op lacks
        an entry."""
        for op, _ in structure:
            for entry in ("REFERENCE", "CHECKS", "COMPARE"):
                if op not in getattr(self, entry.lower()):
                    raise ValueError(
                        f"no {entry}[{op!r}] for the op {op!r}: neither {self.source} "
                        f"nor chipbench/check.py defines it")

    def outputs(self, structure: Sequence[Task], tables: Dict[str, np.ndarray],
                impl: Optional[Dict[str, Callable[..., np.ndarray]]] = None
                ) -> List[np.ndarray]:
        """Every task's output by ``impl`` (the reference unless given), in
        task order."""
        self.require(structure)
        impl = self.reference if impl is None else impl
        out: List[np.ndarray] = []
        for op, inputs in structure:
            ins = {name: tables[src] if kind == "table" else out[src]
                   for name, (kind, src) in inputs.items()}
            out.append(impl[op](ins, self.config))
        return out

    def count(self, structure: Sequence[Task], got: Sequence[np.ndarray],
              want: Sequence[np.ndarray]) -> Dict[str, int]:
        """The worst count per check over the tasks of one query."""
        counts: Dict[str, int] = {}
        for (op, _), g, w in zip(structure, got, want, strict=True):
            name = self.checks[op]
            counts[name] = max(counts.get(name, 0), self.compare[op](g, w))
        return counts


def rules(query=None, config: Optional[dict] = None) -> Rules:
    """The shared entries, with the query module's own ``REFERENCE``,
    ``CHECKS`` and ``COMPARE`` merged over them, for a cell of the
    configuration ``config``."""
    own = {name: getattr(query, name, {}) for name in ("REFERENCE", "CHECKS", "COMPARE")}
    source = getattr(query, "__file__", None)
    return Rules(reference={**REFERENCE, **own["REFERENCE"]},
                 checks={**CHECKS, **own["CHECKS"]},
                 compare={**COMPARE, **own["COMPARE"]},
                 source=f"queries/{pathlib.Path(source).name}" if source else "no query file",
                 config=dict(config or {}))
