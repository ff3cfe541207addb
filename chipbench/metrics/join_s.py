"""Operators: host seconds per query in the external hash join's block joins,
the self seconds of the program's ``ehj.join`` spans (``repro.spans``);
None where the queries carry no program spans."""

from chipbench import program_spans


def read(record):
    return program_spans.self_seconds(record, ("ehj.join",))
