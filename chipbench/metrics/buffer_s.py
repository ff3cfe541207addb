"""Spill engine: host seconds per query in the write pools and read cursors,
the self seconds of the program's ``pool.add``, ``pool.flush`` and
``cursor.block`` spans (``repro.spans``: a flush's tier write is its own);
None where the queries carry no program spans."""

from chipbench import program_spans


def read(record):
    return program_spans.self_seconds(record, ("pool.add", "pool.flush", "cursor.block"))
