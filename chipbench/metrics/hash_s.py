"""Operators: host seconds per query hashing join keys to partitions, the
self seconds of the program's ``ehj.hash`` spans (``repro.spans``); None
where the queries carry no program spans."""

from chipbench import program_spans


def read(record):
    return program_spans.self_seconds(record, ("ehj.hash",))
