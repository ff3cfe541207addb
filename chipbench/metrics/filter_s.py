"""Operators: host seconds per query running filters on the blocks the hash
operators read, the self seconds of the program's ``ehj.filter`` and
``eagg.filter`` spans (``repro.spans``); None where the queries carry no
such spans."""

from chipbench import program_spans


def read(record):
    return program_spans.self_seconds(record, ("ehj.filter", "eagg.filter"))
