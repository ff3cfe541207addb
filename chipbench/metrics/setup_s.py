"""Process start to the end of the warm-up query: JAX's start, the tables
generated and placed, and one full untimed query (which compiles)."""


def read(record):
    return record.setup_s
