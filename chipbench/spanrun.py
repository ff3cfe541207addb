"""Run one cell with the program's own spans on, under the profiler.

    python3 -m chipbench.spanrun --workload <cell> --seed <n> --seconds <s>

A stopgap until ``harness.run_cell`` switches the program's tracer on in its
``--trace 1`` window; then this module goes (PERF.md, Open questions).

The set-up and the window of ``python3 -m chipbench.run ... --trace 1``, with
every span taken from the program's tracer (``repro.spans``): the ``window``,
``query``, ``compile_plan``, ``session.run`` and ``free`` spans of the
benchmark's own files go through it too, so a query's spans nest in one tree
on the profiler's host plane.  Nothing wraps a method of the program.

Each query line adds to the harness's record the fields of
``program_spans.recording``.  Before the window a line gives what one span
costs on this host, off, on, and on with its trace annotation.  The last
line is one JSON object: ``metrics`` (the cell's per-layer metrics and the
readers of ``program_spans.METRICS`` that find something to read),
``spans_per_query``, ``self_s`` (mean self seconds per query of every span
name) and the ``breakdown`` of the device trace.  The outputs are not
compared with the reference here: ``python3 -m chipbench.run`` does that,
and the tests hold the traced and untraced runs to the same outputs.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Dict

ROOT = pathlib.Path(__file__).resolve().parent.parent

# Per-layer metrics that read what only the harness's traced run gathers:
# the hook-call shapes and the compile counts.
UNREAD = {"partition_roofline", "sort_roofline", "window_compiles"}


class Tracer:
    """The harness's span interface (``span(name)`` and inclusive ``seconds``
    per name) over the program's tracer."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        from repro import spans

        t0 = time.perf_counter()
        try:
            with spans.span(name):
                yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t0


def run_query(backend, cell, inputs, keep: set, tracer: Tracer, number: int):
    """``harness.run_query`` inside ``program_spans.recording``."""
    from chipbench import harness, program_spans

    with program_spans.recording(backend, number) as fields:
        rec, result, outputs = harness.run_query(backend, cell, inputs, keep, tracer, False)
    rec.update(fields)
    return rec, result, outputs


def span_cost(n: int = 200_000) -> Dict[str, float]:
    """Seconds one span adds on this host: off, on, and on with its trace
    annotation (no profiler running), each net of an empty loop."""
    from repro import spans

    def loop(body) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            body()
        return (time.perf_counter() - t0) / n

    def empty():
        pass

    def one():
        with spans.span("cost"):
            pass

    base = loop(empty)
    spans.disable()
    off = loop(one)
    out = {"off_s": off - base}
    for key, annotate in (("on_s", False), ("annotated_s", True)):
        spans.enable(annotate=annotate)
        try:
            with spans.Recorder(-1):
                out[key] = loop(one) - base
        finally:
            spans.disable()
    return out


def measure(cell, seed: int, seconds: float, *, jax, log) -> dict:
    """Set up the cell, then run its window with the program's tracer on and
    the profiler recording; returns the result line's object."""
    from chipbench import harness, program_spans, trace as trace_mod, work
    from repro import spans
    from repro.remote import make_backend

    device = jax.devices()[0]
    tables = cell.query.tables(cell.config, seed)
    backend = make_backend(*[tuple(t) if isinstance(t, list) else t
                             for t in cell.config["tiers"]])
    inputs = cell.query.place(backend, tables, cell.config)
    keep = set(backend.resident_ids())
    harness.run_query(backend, cell, inputs, keep, harness.Spans(annotate=False), False)
    log(f"device: {device.platform} {device.device_kind} x{len(jax.devices())}; "
        f"interpret {backend.interpret}")
    log(f"span cost: {json.dumps(span_cost())}")

    tracer = Tracer()
    queries = []
    log_dir = tempfile.mkdtemp(prefix="chipbench-spans-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    spans.enable(annotate=True)
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        with tracer("window"):
            t0 = time.perf_counter()
            while True:
                rec, _, _ = run_query(backend, cell, inputs, keep, tracer, len(queries))
                queries.append(rec)
                if time.perf_counter() >= t0 + seconds:
                    break
            window_s = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
        spans.disable()
    for i, rec in enumerate(queries):
        log(f"query {i}: {json.dumps(rec)}")
    try:
        red = trace_mod.reduce(trace_mod.load(trace_mod.find_xplane(log_dir)))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    record = harness.Record(here=cell.here, setup_s=0.0, window_s=window_s,
                            queries=queries, hook_calls={}, window_compiles=0,
                            peaks=work.peaks(device.device_kind, cell.here), trace=red)
    read = [m for m in cell.per_layer if m["name"] not in UNREAD]
    read += [{"name": n, "unit": u} for n, u in program_spans.METRICS.items()]
    metrics = harness.read_metrics(read, record)
    names = sorted({n for q in queries for n in q["spans"]})
    return {
        "attempted": len(queries),
        "latency_s": sum(q["latency_s"] for q in queries) / len(queries),
        "spans_per_query": sum(t["calls"] for q in queries
                               for t in q["spans"].values()) / len(queries),
        "metrics": metrics,
        "self_s": {n: program_spans.self_seconds(record, (n,)) for n in names},
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "busy_s": red.busy_s, "window_s": red.window_s},
        "breakdown": {"device_ops": [[n, s] for n, s in red.device_ops[:12]],
                      "idle_gaps": [[n, s] for n, s in red.idle_by_span[:20]]},
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="chipbench.spanrun", description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import harness, run

    cell = harness.load_cell(args.workload, ROOT)
    import jax

    run.require_chips(jax, cell.chips)
    run.configure_cache(jax)
    line = measure(cell, args.seed, args.seconds, jax=jax,
                   log=lambda s: print(s, flush=True))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
