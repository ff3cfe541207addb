"""The partition kernel: ``_group_by_part`` (``argsort_by_key`` from
``kernels/merge_sort`` and ``gather_rows`` from ``kernels/dispatch``), run by
the backend's ``partition_rows`` hook as its own jitted module.

Work of one call on ``n`` real rows of ``d`` int32 columns: read the ``n``
partition ids, read every row and write it once in partition order,
``n * 4 + 2 * n * d * 4`` bytes.  The lanes the gather pads each row to, and
the padding of ``n`` to a power of two, are the implementation's.
"""

MODULE = r"^jit__group_by_part$"
HOOK = "partition_rows"


def bytes_moved(call: dict) -> int:
    return call["n"] * 4 + 2 * call["n"] * call["d"] * 4
