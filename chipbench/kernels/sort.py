"""The sort kernel: ``remop_sort`` (``kernels/merge_sort``), run by the
backend's ``sort_keys`` hook as its own jitted module.

Work of one call on ``n`` real (unpadded) int32 keys: read every key once
and write it once, ``2 * 4 * n`` bytes.  No operation count bounds a sort on
the chip, so the bytes alone set its roofline; the padding to a power of two
that the hook adds is the implementation's, not the work's.
"""

MODULE = r"^jit_remop_sort$"
HOOK = "sort_keys"


def bytes_moved(call: dict) -> int:
    return 2 * 4 * call["n"]
