"""Device: percent of the traced window in which no op ran on the chip."""


def read(record):
    if record.trace is None:
        return None
    return 100.0 * record.trace.idle_share
