"""Spill engine: seconds per query in timed transfer rounds, the change in
the backend's ``WallClock.transfer_seconds``."""


def read(record):
    return sum(q["transfer_s"] for q in record.queries) / len(record.queries)
