"""The plain reference and the comparison that decides ``correct``.

The reference is numpy over the tables the benchmark generated, and imports
nothing of the program.  It follows the task graph the planner chose (which
input a join builds on, which join runs first), and computes each task's
output from its inputs: base tables as generated, and upstream tasks as the
reference computed them, never as the program did.

* EHJ: the equijoin on column 0, as ``(key, build payload, probe payload)``
  rows, compared as a multiset;
* EMS: every value of every input page, sorted, compared in exact order.

Each comparison gives a count of rows (keys) that differ, and the limit of
every count is 0: the configurations state exact results.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

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
    "ehj": lambda ins: join(ins["build"], ins["probe"]),
    "ems": lambda ins: sort(ins["page_ids"]),
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


def reference(structure: Sequence[Task], tables: Dict[str, np.ndarray],
              impl: Dict[str, Callable[..., np.ndarray]] = REFERENCE
              ) -> List[np.ndarray]:
    """Every task's reference output, in task order."""
    out: List[np.ndarray] = []
    for op, inputs in structure:
        ins = {name: tables[src] if kind == "table" else out[src]
               for name, (kind, src) in inputs.items()}
        out.append(impl[op](ins))
    return out


def compare(structure: Sequence[Task], got: Sequence[np.ndarray],
            want: Sequence[np.ndarray]) -> Dict[str, int]:
    """The worst count per check over the tasks of one query."""
    counts: Dict[str, int] = {}
    for (op, _), g, w in zip(structure, got, want, strict=True):
        name = CHECKS[op]
        counts[name] = max(counts.get(name, 0), COMPARE[op](g, w))
    return counts
