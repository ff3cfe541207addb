"""Operators: host seconds per query inside ``Session.run`` that are neither
hook calls nor transfer rounds (host numpy work and the hooks' host side
outside their timed section)."""


def read(record):
    q = record.queries
    return sum(x["run_s"] - x["hook_s"] - x["transfer_s"] for x in q) / len(q)
