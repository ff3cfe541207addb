"""Operators: host seconds per query in the external merge sort outside its
hooks and rounds, the self seconds of the program's ``ems.rows`` (the row
sort and limit), ``ems.runs`` and ``ems.merge`` spans (``repro.spans``);
None where the queries carry no such spans."""

from chipbench import program_spans


def read(record):
    return program_spans.self_seconds(record, ("ems.rows", "ems.runs", "ems.merge"))
