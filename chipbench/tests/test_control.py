"""The comparison that decides ``correct`` fails what it should: the control
in the program's place, and the timed path broken underneath the harness.

Both at a tiny size on the CPU; ``python3 -m chipbench.control`` reads the
control on the chip at each cell's own size.
"""

import numpy as np
import pytest

from chipbench import control, harness
from chipbench.tests import tiny
from chipbench.tests.test_rehearsal import SEED, rehearse
from repro.remote.backend import ExecutionBackend


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax

    root = tiny.copy(tmp_path_factory.mktemp("bench"), jax.devices()[0].device_kind)
    tiny.add_sort_cell(root)  # the sort's check, for a later sort cell
    tiny.add_join_agg_cell(root)  # a query's own reference, merged as the harness does
    return root


@pytest.mark.parametrize("name", ["pkfk-spill", "pkfk-inmem", "sort-2k-spill",
                                  "join_agg-spill"])
def test_program_reads_zero_and_the_control_fails(root, name):
    r = control.readings(harness.load_cell(name, root), SEED, log=lambda s: None)
    assert r["program"] and all(v == 0 for v in r["program"].values())
    assert max(r["control"].values()) > 0


def test_controls_break_what_they_claim():
    keys = np.array([(1 << 24) + 1, 3, 1 << 29], np.int64)
    assert (control.sort_float32(keys) != np.sort(keys)).any()
    build = np.array([[1, 10], [2, 20]], np.int64)
    probe = np.array([[1, 100], [1, 101], [3, 300]], np.int64)
    for b, p in ((build, probe), (probe, build)):  # whichever side repeats keys
        assert len(control.join_unique_keys(b, p)) == 1
        assert len(control.CONTROL["ehj"]({"build": b, "probe": p}, {})) == 1


_sort, _part = ExecutionBackend.sort_keys, ExecutionBackend.partition_rows


def sort_unchanged(self, keys):
    """A step that returns its state unchanged: the block comes back unsorted."""
    _sort(self, keys)
    return np.asarray(keys)


def sort_half(self, keys):
    """Half of the batch left out."""
    out = _sort(self, keys)
    return out[: (len(out) + 1) // 2]


def sort_altered(self, keys):
    """An answer altered where it is produced: one key off by one."""
    out = _sort(self, keys).copy()
    out[-1] += 1
    return out


def partition_half(self, rows, parts):
    """Half of the batch left out: every other row is dropped."""
    return _part(self, rows[::2], parts[::2])


def partition_altered(self, rows, parts):
    """An answer altered where it is produced: the first partition's
    payloads off by one (column 1: what joins carry and sums add)."""
    out = _part(self, rows, parts)
    q, first = out[0]
    first = first.copy()
    first[:, 1] += 1
    return [(q, first)] + out[1:]


FAULTS = [
    ("pkfk-spill", "partition_rows", partition_half),
    ("pkfk-inmem", "partition_rows", partition_half),
    ("pkfk-spill", "partition_rows", partition_altered),
    ("pkfk-inmem", "partition_rows", partition_altered),
    ("sort-2k-spill", "sort_keys", sort_unchanged),
    ("sort-2k-spill", "sort_keys", sort_half),
    ("sort-2k-spill", "sort_keys", sort_altered),
]


@pytest.mark.parametrize("name, hook, fault", FAULTS,
                         ids=[f"{n}-{f.__name__}" for n, _, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, name, hook, fault):
    monkeypatch.setattr(ExecutionBackend, hook, fault)
    line, _ = rehearse(root, name, seconds=0.2)
    assert line["correct"] is False
    assert line["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
