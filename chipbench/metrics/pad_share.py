"""Kernels: percent of the rows the partition program ran on that were
padding, ``100 * (1 - partition.rows / partition.padded_rows)`` over the
window's queries (the program's counters, ``repro.spans``); None where the
queries carry no program counters."""

from chipbench import program_spans


def read(record):
    padded = program_spans.total(record, "partition.padded_rows")
    if not padded:
        return None
    return 100.0 * (1.0 - program_spans.total(record, "partition.rows") / padded)
