"""Spill engine: transfer rounds per query, the ledger's ``c_total`` over the
query's ``Session.run`` (an exact count)."""


def read(record):
    return sum(q["rounds"] for q in record.queries) / len(record.queries)
