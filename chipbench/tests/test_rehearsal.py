"""Each cell's query through the harness at a tiny size on the CPU (kernels
interpreted), and the command's refusals: no TPU, no program."""

import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import harness, run
from chipbench.compiles import CompileCounter
from chipbench.tests import tiny

# Per cell: whether the external hash join spills (P3 moves pages).
CELLS = {"pkfk-spill": True, "pkfk-inmem": False}
SEED = 2**31 + 7  # more than 32 signed bits hold


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax

    return tiny.copy(tmp_path_factory.mktemp("bench"), jax.devices()[0].device_kind)


def rehearse(root, name, seed=SEED, seconds=0.5, trace=False):
    import jax

    lines = []
    counter = CompileCounter(jax)
    try:
        line = harness.run_cell(harness.load_cell(name, root), seed, seconds, trace,
                                jax=jax, compiles=counter,
                                process_start=time.perf_counter(), log=lines.append)
    finally:
        counter.close()
    return line, lines


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_matches_the_reference(root, name):
    line, lines = rehearse(root, name)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"query_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["checks"] and all(c == {"value": 0, "limit": 0}
                                  for c in line["checks"].values())
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    window = next(s for s in lines if s.startswith("window:"))
    assert window.endswith("0 compile requests inside it")
    queries = [json.loads(s.split(": ", 1)[1]) for s in lines if s.startswith("query ")]
    assert len(queries) == line["attempted"]
    assert all(len(q["phase_rounds"]) == 1 for q in queries)
    assert all((q["phase_rounds"][0]["P3"] > 0) == CELLS[name] for q in queries)
    assert len({q["rounds"] for q in queries}) == 1  # every query does the same work


def test_same_seed_same_inputs(root):
    cell = harness.load_cell("pkfk-spill", root)
    a, b = cell.query.tables(cell.config, SEED), cell.query.tables(cell.config, SEED)
    c = cell.query.tables(cell.config, SEED + 1)
    assert all((a[k] == b[k]).all() for k in a)
    assert any((a[k] != c[k]).any() for k in a)


def test_measuring_refuses_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "pkfk-inmem", "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert "TPU" in str(e.value.code)


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    """A checkout of only BENCHMARK.json and chipbench/ has no program to run."""
    tiny.copy(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "pkfk-inmem",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert '"correct"' not in proc.stdout
