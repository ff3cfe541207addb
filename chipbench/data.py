"""Input data from a seed: the one generator every configuration uses.

A table is ``rows`` int64 rows of ``(key, payload)``, the payload the row's
index: keys uniform in ``[0, key_domain)`` (``table``), or each of
``0 .. rows-1`` once in an order drawn from the seed (``unique_table``, a
primary key).  A key set is 1-D int64 keys uniform in ``[0, key_domain)``.
Table ``index`` of a configuration draws from ``SeedSequence([seed, index])``,
so any whole number is a seed and every table of one seed is independent of
the others.
"""

from __future__ import annotations

from typing import List

import numpy as np


def _rng(seed: int, index: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(index)])


def table(seed: int, index: int, rows: int, key_domain: int) -> np.ndarray:
    """``(rows, 2)`` int64: uniform keys and the row index as payload."""
    keys = _rng(seed, index).integers(0, key_domain, size=rows, dtype=np.int64)
    return np.stack([keys, np.arange(rows, dtype=np.int64)], axis=1)


def unique_table(seed: int, index: int, rows: int) -> np.ndarray:
    """``(rows, 2)`` int64: the keys ``0 .. rows-1`` permuted, and the row
    index as payload."""
    keys = _rng(seed, index).permutation(rows).astype(np.int64)
    return np.stack([keys, np.arange(rows, dtype=np.int64)], axis=1)


def keys(seed: int, index: int, n: int, key_domain: int) -> np.ndarray:
    """``(n,)`` int64 keys, uniform in ``[0, key_domain)``."""
    return _rng(seed, index).integers(0, key_domain, size=n, dtype=np.int64)


def pages(data: np.ndarray, page_rows: int) -> List[np.ndarray]:
    """Split along the first axis into pages of ``page_rows``; each page is
    its own copy, so nothing the program does to a page reaches ``data``."""
    return [data[i:i + page_rows].copy() for i in range(0, len(data), page_rows)]
