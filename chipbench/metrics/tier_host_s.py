"""Spill engine: host seconds per query in tier rounds outside their timed
copies, the self seconds of the program's ``tier.read``, ``tier.write``,
``tier.check`` and ``tier.cast`` spans (``repro.spans``); None where the
queries carry no program spans."""

from chipbench import program_spans


def read(record):
    return program_spans.self_seconds(record, ("tier.read", "tier.write", "tier.check",
                                         "tier.cast"))
