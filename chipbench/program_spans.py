"""The program's spans and counters (``repro.spans``) in a query's record,
and what the metric readers of them share.

``recording(backend, number)`` is opened around one query with the tracer
on; the fields it gives are added to the query's record.  ``self_seconds``
and ``total`` read them back across a window's queries.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Iterable, Optional

# The readers of the program's spans and their units, each a ``per_layer``
# entry in BENCHMARK.json: the harness's traced run switches the program's
# tracer on, and its untraced run leaves it off, so they read nothing there.
METRICS: Dict[str, str] = {"join_s": "s", "hash_s": "s", "buffer_s": "s",
                           "hook_host_s": "s", "tier_host_s": "s", "pad_share": "%"}


@contextlib.contextmanager
def recording(backend, number: int):
    """One query's ``repro.spans.Recorder``.  Yields a dict that, once the
    block ends, holds the record's new fields: ``spans`` (calls and self
    seconds per name), ``counts`` (the counters) and the changes in the
    backend's ``kernel_fallbacks`` and ``host_pinned_pages``."""
    from repro import spans

    wall = backend.wall
    f0, p0 = wall.kernel_fallbacks, wall.host_pinned_pages
    fields: dict = {}
    with spans.Recorder(number) as r:
        yield fields
    fields["spans"] = {n: {"calls": t.calls, "self_s": t.self_s}
                       for n, t in sorted(r.totals.items())}
    fields["counts"] = dict(sorted(r.counts.items()))
    fields["kernel_fallbacks"] = wall.kernel_fallbacks - f0
    fields["host_pinned_pages"] = wall.host_pinned_pages - p0


def self_seconds(record, names: Iterable[str]) -> Optional[float]:
    """Mean self seconds per query of the program's spans ``names``; None
    where no query of the record holds any of them."""
    names = tuple(names)
    per_query = [q.get("spans", {}) for q in record.queries]
    if not any(n in s for s in per_query for n in names):
        return None
    return sum(s[n]["self_s"] for s in per_query for n in names if n in s) / len(per_query)


def total(record, counter: str) -> Optional[int]:
    """The program's counter summed over the record's queries; None where no
    query counted it."""
    found = [q["counts"][counter] for q in record.queries
             if counter in q.get("counts", {})]
    return sum(found) if found else None
