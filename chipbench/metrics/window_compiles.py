"""Device: compile requests (persistent-cache hits included) inside the
measured window; every program should be warm before it."""


def read(record):
    return record.window_compiles
