"""Seconds per query: the window's wall time (first query's start to the
last query's end) over the queries completed in it."""


def read(record):
    return record.window_s / len(record.queries)
