"""Execution backend hooks: host seconds per query on the hooks' host side,
which ``hook_s`` leaves out, the self seconds of the program's
``hook.prepare``, ``hook.download`` and ``hook.split`` spans
(``repro.spans``); None where the queries carry no program spans."""

from chipbench import program_spans


def read(record):
    return program_spans.self_seconds(record, ("hook.prepare", "hook.download", "hook.split"))
