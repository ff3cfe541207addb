"""Sort kernel: percent of the HBM roofline, the bytes ``kernels/sort.py``
gives for the traced window's calls over their device time."""

from chipbench import work


def read(record):
    return work.roofline_share(record, "sort")
