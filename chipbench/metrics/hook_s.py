"""Execution backend hooks: seconds per query inside the kernel hooks'
timed section (upload, device run and the wait for it), the change in
``WallClock.kernel_seconds``."""


def read(record):
    return sum(q["hook_s"] for q in record.queries) / len(record.queries)
