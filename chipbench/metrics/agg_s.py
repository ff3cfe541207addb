"""Operators: host seconds per query in the external hash aggregation's
hashing and grouping, the self seconds of the program's ``eagg.hash`` and
``eagg.group`` spans (``repro.spans``); None where the queries carry no such
spans."""

from chipbench import program_spans


def read(record):
    return program_spans.self_seconds(record, ("eagg.hash", "eagg.group"))
