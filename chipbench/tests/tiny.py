"""A copy of the benchmark at a size a CPU test can hold.

``copy(dst)`` writes ``BENCHMARK.json`` and ``chipbench/`` (without the
tests) under ``dst`` and shrinks every configuration's scale, keeping each
cell's shape: the spilling join still spills half its partitions through
every phase, the in-memory join none.  The CPU is not in the peaks table, so
the copy names it with the TPU's peaks: the rehearsal reads no device metric.
"""

from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

SIZES = {"blanas11-pkfk": dict(build_rows=256, probe_rows=4096, page_rows=64)}
QUERIES = pathlib.Path(__file__).resolve().parent / "data" / "queries"


def _edit(path: pathlib.Path, **changes) -> None:
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data))


def copy(dst: pathlib.Path, device_kind: str = "cpu") -> pathlib.Path:
    shutil.copy(REPO / "BENCHMARK.json", dst)
    shutil.copytree(REPO / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__", ".*"))
    for name, sizes in SIZES.items():
        _edit(dst / "chipbench" / "configs" / f"{name}.json", **sizes)
    peaks = dst / "chipbench" / "peaks.json"
    table = json.loads(peaks.read_text())
    _edit(peaks, **{device_kind: table["TPU v5 lite"]})
    return dst


def add_sort_cell(root: pathlib.Path) -> str:
    """Add an external-merge-sort cell to a copy as new files and entries
    alone, as a later change would: 2^11 keys in 32 pages under an 8-page
    budget, one merge pass.  Returns the cell's name."""
    here = root / "chipbench"
    (here / "configs" / "sort-2k.json").write_text(json.dumps(
        {"name": "sort-2k", "query": "ems", "keys": 1 << 11, "key_domain": 1 << 30,
         "page_keys": 64, "tiers": ["remon_tcp"], "plan": {"k_cap": 8}}))
    (here / "workloads" / "closed1-b8.json").write_text(json.dumps(
        {"budget_pages": 8, "why": "a test"}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "sort-2k", "source": "a test",
                             "file": "chipbench/configs/sort-2k.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "sort-2k-spill", "config": "sort-2k",
                               "traffic": "closed1-b8", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "sort_roofline", "unit": "%", "better": "higher",
                               "source": "device_trace", "layer": "kernels",
                               "moves": "query_s", "workloads": ["sort-2k-spill"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "sort-2k-spill"


def add_join_agg_cell(root: pathlib.Path, reference: bool = True) -> str:
    """Add a cell whose query brings its own reference (``data/queries/
    join_agg.py``: an EHJ, then an EAGG of its output) to a copy, as new
    files and entries alone, under the spilling join's traffic.  Without
    ``reference`` the query file (``join_agg_noref.py``) defines no
    reference for its aggregation.  Returns the cell's name."""
    here = root / "chipbench"
    query = "join_agg" if reference else "join_agg_noref"
    text = (QUERIES / "join_agg.py").read_text()
    if not reference:
        text += "\ndel REFERENCE\n"
    (here / "queries" / f"{query}.py").write_text(text)
    (here / "configs" / f"{query}.json").write_text(json.dumps(
        {"name": query, "query": query, "tiers": ["remon_tcp"], "plan": {"partitions": 8},
         **SIZES["blanas11-pkfk"]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": query, "source": "a test",
                             "file": f"chipbench/configs/{query}.json",
                             "reduced": [], "why": "a test"})
    name = f"{query}-spill"
    bench["workloads"].append({"name": name, "config": query,
                               "traffic": "closed1-b8-s0.5", "chips": 1, "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name
