"""A later change adds a configuration, a traffic mix, a cell and a per-layer
metric as new files and entries alone: the harness finds each by its name,
runs the cell, and no file that was there changes."""

import hashlib
import json
import pathlib

from chipbench import harness
from chipbench.tests import tiny
from chipbench.tests.test_rehearsal import rehearse


def _digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "chipbench").rglob("*")) if p.is_file()}


def test_new_files_are_found_by_name(tmp_path):
    import jax

    root = tiny.copy(tmp_path, jax.devices()[0].device_kind)
    before = _digests(root)
    here = root / "chipbench"

    name = tiny.add_sort_cell(root)
    (here / "metrics" / "queries_done.py").write_text(
        "def read(record):\n    return len(record.queries)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "queries_done", "unit": "queries",
                               "better": "higher", "source": "host_clock",
                               "layer": "operators", "moves": "query_s",
                               "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell(name, root)
    assert cell.config["keys"] == 1 << 11
    assert cell.traffic["budget_pages"] == 8
    assert cell.query.TABLES == ("keys",)
    assert [m["name"] for m in cell.per_layer][-2:] == ["sort_roofline", "queries_done"]
    assert "queries_done" not in [m["name"] for m in
                                  harness.load_cell("pkfk-spill", root).per_layer]
    record = harness.Record(here=here, setup_s=1.0, window_s=2.0,
                            queries=[{"latency_s": 1.0}, {"latency_s": 1.0}],
                            hook_calls={}, window_compiles=0, peaks={})
    metrics = harness.read_metrics(cell.per_layer[-1:] + cell.end_to_end, record)
    assert metrics["queries_done"] == {"value": 2.0, "unit": "queries"}
    assert metrics["query_s"] == {"value": 1.0, "unit": "s"}

    line, lines = rehearse(root, name)
    assert line["correct"] is True and line["checks"] == {
        "sort_keys_differing": {"value": 0, "limit": 0}}
    queries = [json.loads(s.split(": ", 1)[1]) for s in lines if s.startswith("query ")]
    assert queries and all(q["merge_passes"] == [1] for q in queries)

    after = _digests(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "chipbench/configs/sort-2k.json", "chipbench/metrics/queries_done.py",
        "chipbench/workloads/closed1-b8.json"]


def test_a_reader_that_finds_nothing_leaves_its_metric_out(tmp_path):
    root = tiny.copy(tmp_path)
    cell = harness.load_cell("pkfk-spill", root)
    record = harness.Record(here=root / "chipbench", setup_s=1.0, window_s=1.0,
                            queries=[{"latency_s": 1.0, "run_s": 1.0, "hook_s": 0.5,
                                      "transfer_s": 0.2, "rounds": 3}],
                            hook_calls={}, window_compiles=0, peaks={})
    metrics = harness.read_metrics(cell.per_layer, record)
    assert "partition_roofline" not in metrics and "device_idle" not in metrics
    assert "plan_s" not in metrics  # no query of this record planned
    assert metrics["host_op_s"]["value"] == 1.0 - 0.5 - 0.2
