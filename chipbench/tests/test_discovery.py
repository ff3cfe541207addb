"""A later change adds a configuration, a traffic mix, a cell, a per-layer
metric and a query with its own reference as new files and entries alone:
the harness finds each by its name, runs the cell, checks it, and no file
that was there changes."""

import hashlib
import json
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import check, harness
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


@pytest.fixture(scope="module")
def agg_root(tmp_path_factory):
    """A copy with the query that brings its own reference, and one that
    brings none for its aggregation."""
    import jax

    root = tiny.copy(tmp_path_factory.mktemp("bench"), jax.devices()[0].device_kind)
    tiny.add_join_agg_cell(root)
    tiny.add_join_agg_cell(root, reference=False)
    return root


def test_a_query_with_its_own_reference_is_checked(tmp_path):
    import jax

    root = tiny.copy(tmp_path, jax.devices()[0].device_kind)
    before = _digests(root)
    name = tiny.add_join_agg_cell(root)
    assert name == "join_agg-spill"
    assert not hasattr(harness.load_cell("pkfk-spill", root).query, "REFERENCE")

    line, lines = rehearse(root, name)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"] == {"agg_groups_differing": {"value": 0, "limit": 0},
                              "join_rows_differing": {"value": 0, "limit": 0}}
    compared = [json.loads(s.split(": ", 1)[1]) for s in lines
                if s.startswith("compared query ")]
    assert compared and all(c == {"join_rows_differing": 0, "agg_groups_differing": 0}
                            for c in compared)

    after = _digests(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "chipbench/configs/join_agg.json", "chipbench/queries/join_agg.py"]


def test_a_query_s_own_check_fails_its_altered_output(agg_root, monkeypatch):
    """The aggregation's output altered where it is produced: every group's
    sum off by one.  The query's own check fails it; the join stays right."""
    import importlib

    eagg = importlib.import_module("repro.remote.eagg")
    inner = eagg._aggregate

    def altered(rows):
        out = inner(rows).copy()
        out[:, 1] += 1
        return out

    monkeypatch.setattr(eagg, "_aggregate", altered)
    line, _ = rehearse(agg_root, "join_agg-spill", seconds=0.2)
    assert line["correct"] is False and line["failed"] >= 1
    assert line["checks"]["agg_groups_differing"]["value"] > 0
    assert line["checks"]["join_rows_differing"]["value"] == 0


def test_an_op_without_a_reference_names_the_op_and_the_query_file(agg_root):
    with pytest.raises(ValueError, match=r"no REFERENCE\['eagg'\] for the op 'eagg': "
                                         r"neither queries/join_agg_noref.py nor "
                                         r"chipbench/check.py defines it"):
        rehearse(agg_root, "join_agg_noref-spill")


def test_a_query_s_entries_win_over_the_shared_ones_for_its_ops():
    query = SimpleNamespace(
        __file__="/anywhere/queries/q.py",
        REFERENCE={"ehj": lambda ins, config: ins["build"][:, config["column"]]},
        CHECKS={"ehj": "column_differing"})
    rules = check.rules(query, {"column": 1})
    assert rules.source == "queries/q.py"
    assert rules.reference["ems"] is check.REFERENCE["ems"]
    assert rules.compare == check.COMPARE
    assert rules.checks == {"ehj": "column_differing", "ems": "sort_keys_differing"}
    build = np.array([[1, 10], [2, 20]], np.int64)
    struct = [("ehj", {"build": ("table", "R"), "probe": ("table", "S")})]
    want = rules.outputs(struct, {"R": build, "S": build})
    assert [w.tolist() for w in want] == [[10, 20]]
    assert check.rules().source == "no query file"
    with pytest.raises(ValueError, match=r"no REFERENCE\['eagg'\] for the op 'eagg'"):
        rules.require([("eagg", {})])


def test_the_reference_reads_the_cell_s_configuration_and_not_the_plan():
    """The check sees each task's op and inputs and nothing the planner put
    on it; a reference is given the cell's configuration instead."""
    from repro.engine.session import TaskOutput

    r, s = object(), object()
    join = SimpleNamespace(inputs={"build": r, "probe": s},
                           options={"rows_per_page": 64, "key_columns": (1, 0)})
    agg = SimpleNamespace(inputs={"rel": TaskOutput(join)},
                          options={"rows_per_page": 64, "predicate": len})
    result = SimpleNamespace(per_task=[SimpleNamespace(op="ehj", task=join),
                                       SimpleNamespace(op="eagg", task=agg)])
    struct = harness.structure(result, {"R": r, "S": s})
    assert struct == [("ehj", {"build": ("table", "R"), "probe": ("table", "S")}),
                      ("eagg", {"rel": ("task", 0)})]

    seen = []
    query = SimpleNamespace(REFERENCE={op: lambda ins, config: seen.append(config) or
                                       np.zeros((0, 3), np.int64)
                                       for op in ("ehj", "eagg")},
                            CHECKS={"eagg": "agg_groups_differing"},
                            COMPARE={"eagg": check.rows_differing})
    config = {"query": "q", "key_columns": [0, 0]}
    rows = np.zeros((1, 2), np.int64)
    check.rules(query, config).outputs(struct, {"R": rows, "S": rows})
    assert seen == [config, config]


def test_a_later_cell_reads_an_existing_reader_under_an_entry_of_its_own(tmp_path):
    """``join_s.join_agg`` has no file: ``metrics/join_s.py`` reads it, for
    the cell it lists, and the entries that were there keep their lists."""
    import jax

    root = tiny.copy(tmp_path, jax.devices()[0].device_kind)
    name = tiny.add_join_agg_cell(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    bench["per_layer"].append({"name": "join_s.join_agg", "unit": "s", "better": "lower",
                               "source": "host_clock", "layer": "operators",
                               "moves": "query_s", "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell(name, root)
    names = [m["name"] for m in cell.per_layer]
    assert "join_s.join_agg" in names and "join_s" not in names
    assert "join_s.join_agg" not in [m["name"] for m in
                                     harness.load_cell("pkfk-spill", root).per_layer]
    spans = {"ehj.join": {"calls": 2, "self_s": 0.25}}
    record = harness.Record(here=root / "chipbench", setup_s=1.0, window_s=1.0,
                            queries=[{"spans": spans}, {"spans": spans}],
                            hook_calls={}, window_compiles=0, peaks={})
    entry = [m for m in cell.per_layer if m["name"] == "join_s.join_agg"]
    assert harness.read_metrics(entry, record) == {"join_s.join_agg": {"value": 0.25,
                                                                       "unit": "s"}}
    assert {m["name"]: m.get("workloads") for m in json.loads(
        (root / "BENCHMARK.json").read_text())["per_layer"][:-1]} == lists
    with pytest.raises(FileNotFoundError):
        harness.reader(root / "chipbench", "no_such_reader.join_agg")


def test_a_cell_without_a_join_reports_every_metric_it_is_held_to(tmp_path, monkeypatch):
    """A sort cell, added as files and entries alone, runs no planner, join or
    partition hook: the metrics of those list their cells, so its traced line
    carries every per-layer metric that applies to it.  The CPU's trace has
    no device plane, so its reduction is a stand-in; the sort's roofline,
    which needs the device's modules, is left to the chip."""
    import jax

    from chipbench import trace

    root = tiny.copy(tmp_path, jax.devices()[0].device_kind)
    name = tiny.add_sort_cell(root)
    required = {m["name"] for m in harness.load_cell(name, root).per_layer}
    assert not required & {"plan_s", "partition_roofline", "join_s", "hash_s", "buffer_s",
                           "tier_host_s", "hook_host_s", "pad_share"}
    monkeypatch.setattr(trace, "reduce", lambda loaded, window=trace.WINDOW: trace.Reduction(
        window_s=1.0, busy_s=0.5, devices=1, modules={}, device_ops=[],
        idle_by_span=[("ems.merge", 0.5)], clock_shift_s=0.0))
    line, _ = rehearse(root, name, seconds=0.0, trace=True)
    assert line["correct"] is True
    assert required - {"sort_roofline"} <= set(line["metrics"])
