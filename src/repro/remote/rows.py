"""Row-block helpers the operators share: the partition hash, filters that
execute, projections, sum expressions and the rows a page holds.

A block is a 2-D int64 array, one row per tuple.  A *where* is a conjunction
of ``(column, comparison, constant)`` terms, the comparison one of
``COMPARISONS``; :func:`select` keeps the rows every term holds for and the
named columns of each, in one pass over the block.  A *sum expression* is a
column, ``("lit", constant)``, or ``(op, expression, expression)`` with ``op``
one of ``+ - *``; :func:`evaluate` computes one over a block in int64.
"""

from __future__ import annotations

import operator
from typing import Sequence, Tuple, Union

import numpy as np

# Multiplicative (Fibonacci) hashing constant: 2^64 / golden ratio.
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)

COMPARISONS = {
    "<": operator.lt, "<=": operator.le, ">": operator.gt, ">=": operator.ge,
    "==": operator.eq, "!=": operator.ne,
}

Term = Tuple[int, str, int]


def partition_of(keys: np.ndarray, p: int) -> np.ndarray:
    """Partition id in ``[0, p)`` of each key: a 1-D key column, or the rows
    of a 2-D block of key columns (a composite key).

    One column hashes as ``(key * golden >> 33) % p``; each further column
    folds in as ``h = (h ^ column) * golden`` before the shift, so a 2-D
    block of one column hashes exactly as that column does.
    """
    keys = np.asarray(keys)
    if keys.ndim == 2:
        h = keys[:, 0].astype(np.uint64) * _GOLDEN
        for c in range(1, keys.shape[1]):
            h = (h ^ keys[:, c].astype(np.uint64)) * _GOLDEN
    else:
        h = keys.astype(np.uint64) * _GOLDEN
    return ((h >> np.uint64(33)) % np.uint64(p)).astype(np.int64)


def page_rows(width: int, rows_per_page: int, page_bytes: int | None) -> int:
    """Rows a page holds: ``rows_per_page``, or with ``page_bytes`` as many
    int64 rows of ``width`` columns as fill that many bytes."""
    return rows_per_page if page_bytes is None else max(1, page_bytes // (8 * width))


def check_where(where: Sequence[Term], width: int | None = None) -> Tuple[Term, ...]:
    """``where`` as a tuple of ``(column, comparison, constant)`` terms;
    raises ``ValueError`` on an unknown comparison or a column outside
    ``width``."""
    terms = []
    for term in where:
        if len(term) != 3:
            raise ValueError(f"a where term is (column, comparison, constant), got {term!r}")
        col, cmp, value = term
        if cmp not in COMPARISONS:
            raise ValueError(f"comparison {cmp!r} is not one of {sorted(COMPARISONS)}")
        if int(col) < 0 or (width is not None and int(col) >= width):
            raise ValueError(f"where column {col} outside a row of {width} columns")
        terms.append((int(col), cmp, int(value)))
    return tuple(terms)


def mask(rows: np.ndarray, where: Sequence[Term]) -> np.ndarray:
    """Whether each row satisfies every term of ``where``."""
    keep = np.ones(len(rows), dtype=bool)
    for col, cmp, value in where:
        keep &= COMPARISONS[cmp](rows[:, col], value)
    return keep


def select(rows: np.ndarray, where: Sequence[Term], cols: Sequence[int]) -> np.ndarray:
    """The rows ``where`` holds for, with the columns ``cols`` in that order.

    With no terms and ``cols`` every column in order, the block itself comes
    back, not a copy.
    """
    cols = list(cols)
    identity = cols == list(range(rows.shape[1]))
    if not where:
        return rows if identity else rows[:, cols]
    idx = np.flatnonzero(mask(rows, where))
    return rows[idx] if identity else rows[np.ix_(idx, cols)]


# A sum: a column index, ("lit", constant), or (op, sum, sum) with op one of
# "+", "-", "*"; evaluated over a block in int64.
SumExpr = Union[int, tuple]
_OPS = {"+": np.add, "-": np.subtract, "*": np.multiply}


def check_sum(expr: SumExpr) -> SumExpr:
    """``expr`` as a well-formed sum expression (tuples of ints), else
    ``ValueError``."""
    if isinstance(expr, (int, np.integer)) and not isinstance(expr, bool):
        if expr < 0:
            raise ValueError(f"sum column {expr} is negative")
        return int(expr)
    if isinstance(expr, (tuple, list)) and len(expr) == 2 and expr[0] == "lit":
        return ("lit", int(expr[1]))
    if isinstance(expr, (tuple, list)) and len(expr) == 3 and expr[0] in _OPS:
        return (expr[0], check_sum(expr[1]), check_sum(expr[2]))
    raise ValueError(f"a sum is a column, ('lit', c) or (op, sum, sum) with op in "
                     f"{sorted(_OPS)}; got {expr!r}")


def evaluate(rows: np.ndarray, expr: SumExpr) -> np.ndarray:
    """One sum expression over a block: an int64 value per row."""
    if isinstance(expr, int):
        return rows[:, expr]
    if expr[0] == "lit":
        return np.full(len(rows), expr[1], dtype=np.int64)
    return _OPS[expr[0]](evaluate(rows, expr[1]), evaluate(rows, expr[2]))
