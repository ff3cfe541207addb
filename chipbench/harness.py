"""Set up one cell, run its measured window, check it, and reduce it to metrics.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<config>.json``, which names its query shape ``queries/<query>.py``)
under a traffic mix (``workloads/<traffic>.json``).  Every query of the
window does the same work: it builds a ``Session`` over one
``ExecutionBackend`` with the traffic's budget, runs the query, and frees
every page the query created, so the next one starts from the same
placement.  One client, closed loop: queries run back to back until the
window's seconds have passed; the query in flight then completes and counts.

Set-up is everything before the window: JAX's start, generating the tables
from the seed and placing them in the backend's tiers, and one full untimed
query, which compiles (or loads from the persistent cache) exactly the
programs the window's queries run.

After the window the outputs of a sample of its queries, drawn from the
seed, are compared with the numpy reference (``check.py``, with the query
module's own entries merged over its shared ones).  With ``trace`` the
window runs under the JAX profiler with the program's tracer
(``repro.spans``) on and annotated, so every query records the program's
spans and counters, and the per-layer metrics are read from the record.
Without it the tracer stays off.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
import re
import resource
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import check, program_spans, trace as trace_mod, work

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
# Queries of a window whose outputs are compared with the reference.
COMPARED = 8


# --------------------------------------------------------------------------
# Finding a cell's files by name
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    query: object  # the module queries/<query>.py
    end_to_end: List[dict]
    per_layer: List[dict]
    here: pathlib.Path

    @property
    def params(self) -> dict:
        """The query's plan parameters: the configuration's, and the
        traffic's (such as the share of partitions that spill)."""
        return {**self.config.get("plan", {}), **self.traffic.get("plan", {})}


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has {sorted(cells)}")
    entry = cells[name]
    here = root / "chipbench"
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[entry["config"]]["file"]).read_text())
    traffic = json.loads((here / "workloads" / f"{entry['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(entry["chips"]), config=config, traffic=traffic,
        query=work.load_module(here / "queries" / f"{config['query']}.py"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        here=here,
    )


def reader(here: pathlib.Path, name: str):
    """A metric's reader, ``metrics/<name>.py``.  A name ``<reader>.<part>``
    with no file of its own is read by ``metrics/<reader>.py``: a later cell
    that runs what an existing reader reads brings an entry of its own that
    lists it (``join_s.q3``), and no file or entry that is there changes."""
    path = here / "metrics" / f"{name}.py"
    if not path.is_file() and "." in name:
        path = here / "metrics" / f"{name.split('.', 1)[0]}.py"
    return work.load_module(path)


def read_metrics(metrics: List[dict], record: "Record") -> Dict[str, dict]:
    """Run each metric's reader; a reader that finds nothing to read returns
    None and the metric is left out."""
    out = {}
    for m in metrics:
        value = reader(record.here, m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# Host spans and hook-call shapes
# --------------------------------------------------------------------------


class Spans:
    """Host-clock seconds per span name; each span is also the program's
    ``repro.spans.span``, a no-op unless its tracer is on."""

    def __init__(self):
        from repro import spans

        self._program = spans.span
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            with self._program(name):
                yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t0


def instrument(backend, calls: Dict[str, list]) -> None:
    """Record the size of every hook call that ran a kernel (``n`` rows,
    ``d`` columns), which the roofline readers need."""
    wall = backend.wall

    def wrap(hook: str, shape: Callable) -> None:
        inner = getattr(backend, hook)

        def wrapped(*args, **kwargs):
            seen = wall.kernel_calls
            out = inner(*args, **kwargs)
            if wall.kernel_calls > seen:
                calls.setdefault(hook, []).append(shape(*args))
            return out

        setattr(backend, hook, wrapped)

    wrap("sort_keys", lambda keys: {"n": len(keys)})
    wrap("partition_rows", lambda rows, parts: {"n": len(rows), "d": rows.shape[1]})


# --------------------------------------------------------------------------
# One query
# --------------------------------------------------------------------------


def structure(result, inputs: Dict[str, object]) -> List[check.Task]:
    """The task graph a query ran, as the check sees it: each task's op and
    its inputs."""
    from repro.engine.session import TaskOutput

    table_of = {id(v): k for k, v in inputs.items()}
    index = {id(tr.task): i for i, tr in enumerate(result.per_task)}
    out = []
    for tr in result.per_task:
        ins = {}
        for name, v in tr.task.inputs.items():
            ins[name] = (("task", index[id(v.task)]) if isinstance(v, TaskOutput)
                         else ("table", table_of[id(v)]))
        out.append((tr.op, ins))
    return out


def run_query(backend, cell: Cell, inputs: Dict[str, object], keep: set, span: Spans):
    """One query: a fresh ``Session``, the query, and every page it created
    freed.  Returns (its record, its result, its output pages).

    The record's ``cpu_s`` (the process's CPU seconds, all threads),
    ``minflt`` (minor page faults) and ``nivcsw`` (involuntary context
    switches) tell a query that waited from one that worked."""
    from repro.engine import Session
    from repro.engine.registry import get

    wall = backend.wall
    span.seconds.clear()
    k0, t0s, c0 = wall.kernel_seconds, wall.transfer_seconds, wall.kernel_calls
    u0 = resource.getrusage(resource.RUSAGE_SELF)
    start = time.perf_counter()
    with span("query"):
        session = Session(backend, budget=cell.traffic["budget_pages"])
        result = cell.query.run(session, inputs, cell.config, cell.params, span)
        outputs = [backend.peek_batch(get(tr.op).output_of(tr.result))
                   for tr in result.per_task]
        with span("free"):
            backend.free([i for i in backend.resident_ids() if i not in keep])
    end = time.perf_counter()
    u1 = resource.getrusage(resource.RUSAGE_SELF)
    rec = {
        "latency_s": end - start,
        "run_s": span.seconds.get("session.run", 0.0),
        "hook_s": wall.kernel_seconds - k0,
        "transfer_s": wall.transfer_seconds - t0s,
        "kernel_calls": wall.kernel_calls - c0,
        "rounds": int(result.total.c_total),
        "merge_passes": [int(tr.result.passes) for tr in result.per_task if tr.op == "ems"],
        "phase_rounds": [tr.result.per_phase_rounds for tr in result.per_task
                         if tr.op == "ehj"],
        "cpu_s": u1.ru_utime + u1.ru_stime - u0.ru_utime - u0.ru_stime,
        "minflt": u1.ru_minflt - u0.ru_minflt, "nivcsw": u1.ru_nivcsw - u0.ru_nivcsw,
    }
    if "compile_plan" in span.seconds:
        rec["plan_s"] = span.seconds["compile_plan"]
    return rec, result, outputs


# --------------------------------------------------------------------------
# The run
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    """What the metric readers (``metrics/<name>.py``) read."""

    here: pathlib.Path
    setup_s: float
    window_s: float
    queries: List[dict]
    hook_calls: Dict[str, list]
    window_compiles: int
    peaks: dict
    trace: Optional[trace_mod.Reduction] = None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, jax,
             compiles, process_start: float, log: Callable[[str], None]) -> dict:
    """Set up, measure and check one cell; returns the result line's object."""
    device = jax.devices()[0]
    peaks = work.peaks(device.device_kind, cell.here)
    from repro.remote import make_backend

    t_init = time.perf_counter()
    tables = cell.query.tables(cell.config, seed)
    backend = make_backend(*[tuple(t) if isinstance(t, list) else t
                             for t in cell.config["tiers"]])
    inputs = cell.query.place(backend, tables, cell.config)
    keep = set(backend.resident_ids())
    t_data = time.perf_counter()
    warm, warm_result, _ = run_query(backend, cell, inputs, keep, Spans())
    rules = check.rules(cell.query, cell.config)
    rules.require(structure(warm_result, inputs))
    t_warm = time.perf_counter()
    setup_s = t_warm - process_start
    log(f"device: {device.platform} {device.device_kind} x{len(jax.devices())}; "
        f"interpret {backend.interpret}")
    log(f"setup: {setup_s:.6f} s = init {t_init - process_start:.6f} + data "
        f"{t_data - t_init:.6f} + warm query {t_warm - t_data:.6f}; "
        f"{len(keep)} input pages; compiles {compiles.requests} requested, "
        f"{compiles.cache_hits} from the persistent cache, {compiles.fresh} fresh; "
        + ", ".join(f"{k} {v:.6f} s" for k, v in compiles.seconds.items()))
    log(f"warm query: {json.dumps(warm)}")

    span = Spans()
    hook_calls: Dict[str, list] = {}
    log_dir = None
    if trace:
        from repro import spans

        instrument(backend, hook_calls)
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        spans.enable(annotate=True)
        jax.profiler.start_trace(log_dir, profiler_options=options)

    rng = np.random.default_rng([seed, 0x5eed])
    sample_size = COMPARED
    sampled: List[tuple] = []  # (query index, structure, outputs)
    queries: List[dict] = []
    requests0 = compiles.requests
    try:
        with span("window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while True:
                # With the tracer on, the record gains the program's spans
                # and counters.
                with (program_spans.recording(backend, len(queries)) if trace
                      else contextlib.nullcontext({})) as fields:
                    rec, result, outputs = run_query(backend, cell, inputs, keep, span)
                rec.update(fields)
                queries.append(rec)
                i = len(queries)
                slot = i - 1 if i <= sample_size else int(rng.integers(0, i))
                if slot < sample_size:
                    entry = (i - 1, structure(result, inputs), outputs)
                    if slot < len(sampled):
                        sampled[slot] = entry
                    else:
                        sampled.append(entry)
                if time.perf_counter() >= deadline:
                    break
            window_s = time.perf_counter() - t0
    finally:
        if trace:
            jax.profiler.stop_trace()
            spans.disable()
    window_compiles = compiles.requests - requests0
    stats = device.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    del backend, inputs, warm_result, result

    for i, rec in enumerate(queries):
        log(f"query {i}: {json.dumps(rec)}")
    log(f"window: {len(queries)} queries in {window_s:.6f} s; "
        f"{window_compiles} compile requests inside it")

    # The check: the sampled queries' outputs against the numpy reference.
    t_check = time.perf_counter()
    counts: Dict[str, int] = {}
    failed = 0
    want_by_structure: Dict[str, List[np.ndarray]] = {}
    for qi, struct, outputs in sorted(sampled, key=lambda e: e[0]):
        key = json.dumps(struct)
        if key not in want_by_structure:
            want_by_structure[key] = rules.outputs(struct, tables)
        got = [np.concatenate(pages, axis=0) if pages else np.empty((0,), np.int64)
               for pages in outputs]
        c = rules.count(struct, got, want_by_structure[key])
        if any(v > check.LIMIT for v in c.values()):
            failed += 1
        for name, v in c.items():
            counts[name] = max(counts.get(name, 0), v)
        log(f"compared query {qi}: {json.dumps(c)}")
    log(f"check: {len(sampled)} of {len(queries)} queries compared in "
        f"{time.perf_counter() - t_check:.6f} s")

    record = Record(here=cell.here, setup_s=setup_s, window_s=window_s, queries=queries,
                    hook_calls=hook_calls, window_compiles=window_compiles, peaks=peaks)
    out_device = {"platform": device.platform, "kind": device.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        t_read = time.perf_counter()
        try:
            record.trace = trace_mod.reduce(trace_mod.load(trace_mod.find_xplane(log_dir)))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        red = record.trace
        log(f"trace: read in {time.perf_counter() - t_read:.6f} s; device clock shifted "
            f"{red.clock_shift_s:.9f} s; busy {red.busy_s:.6f} s of {red.window_s:.6f} s")
        out_device.update(busy_s=red.busy_s, window_s=red.window_s)
        for name in sorted(p.stem for p in (cell.here / "kernels").glob("*.py")):
            k = work.kernel(name, cell.here)
            events = sum(len(v) for n, v in red.modules.items()
                         if re.search(k.MODULE, n))
            log(f"kernel {name}: {len(hook_calls.get(k.HOOK, []))} hook calls, "
                f"{events} module events in the traced window")
        breakdown = {"device_ops": [[n, s] for n, s in red.device_ops[:10]],
                     "idle_gaps": [[n, s] for n, s in red.idle_by_span[:10]]}
        metrics = read_metrics(cell.per_layer, record)
    else:
        metrics = read_metrics(cell.end_to_end, record)

    checks = {name: {"value": counts.get(name, 0), "limit": check.LIMIT}
              for name in sorted({rules.checks[op] for struct in want_by_structure
                                  for op, _ in json.loads(struct)})}
    correct = bool(sampled) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": len(queries), "failed": failed,
            "metrics": metrics, "device": out_device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line
