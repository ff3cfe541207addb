"""Reduce a profiler trace to the benchmark's device numbers.

Two steps.  ``load`` turns the XSpace file that ``jax.profiler`` writes into
plain data: ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``, the form of the recorded trace under
``tests/data``.  ``reduce`` takes that data and returns, inside the host span
that brackets the measured window:

* ``busy_s``: the union of the device's op intervals (averaged over devices);
* ``window_s``: the window span's length;
* ``modules``: device seconds and event count per XLA module name;
* ``device_ops``: device seconds per ``module/op``, largest first;
* ``idle_by_span``: idle device seconds, split by the innermost host span
  open at the time (``between queries`` where none but the window is).

The host spans are the events of the host thread that holds the window span:
the benchmark's own trace annotations, and JAX's around dispatch and
transfers on that thread.

The device and host timestamps of one trace share a base but not an exact
clock: on a TPU v5e a module's device start can read a few tenths of a
millisecond before the host dispatched it.  ``clock_shift`` moves the device's
events later by the least amount that puts every module after its dispatch
(the host's ``PjitFunction(<name>)`` event); the split of idle time among
host spans is good to about the dispatch latency that remains.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
OUTSIDE = "between queries"
_MODULE_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def load(xplane_path: str) -> dict:
    """The planes, lines and events of an XSpace file, as plain data."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    return {"planes": [
        {"name": plane.name, "lines": [
            {"name": line.name,
             "events": [[e.name, e.start_ns, e.duration_ns] for e in line.events]}
            for line in plane.lines]}
        for plane in data.planes]}


def find_xplane(log_dir: str) -> str:
    """The one ``*.xplane.pb`` that a profiler session wrote under log_dir."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, found {found}")
    return found[0]


def module_name(event_name: str) -> str:
    """``jit_remop_sort(1738...)`` -> ``jit_remop_sort``."""
    return _MODULE_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%remop_sort.1 = s32[...] custom-call(...)`` -> ``%remop_sort.1``."""
    return event_name.split(" = ", 1)[0]


def clock_shift(host: Sequence[Tuple[str, float, float]],
                modules: Sequence[Tuple[float, float, str]]) -> float:
    """Nanoseconds to add to a device's times so that the k-th run of each
    module starts no earlier than the host's k-th dispatch of it."""
    shift = 0.0
    for name in {m for _, _, m in modules}:
        if not name.startswith("jit_"):
            continue
        calls = sorted(s for n, s, _ in host if n == f"PjitFunction({name[4:]})")
        runs = sorted(s for s, _, m in modules if m == name)
        for h, d in zip(calls, runs):
            shift = max(shift, h - d)
    return shift


def union(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged, sorted intervals clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def complement(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The gaps of sorted, disjoint intervals inside ``[lo, hi]``."""
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost(spans: Iterable[Tuple[str, float, float]], lo: float,
              hi: float) -> List[Tuple[float, float, Optional[str]]]:
    """Cut ``[lo, hi]`` into segments, each named by the innermost of the
    properly nested spans ``(name, start, end)`` open there (``None``: none)."""
    segs: List[Tuple[float, float, Optional[str]]] = []

    def emit(a: float, b: float, name: Optional[str]) -> None:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            segs.append((a, b, name))

    stack: List[Tuple[str, float]] = []
    t = lo
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            emit(t, end, top)
            t = max(t, end)
        emit(t, s, stack[-1][0] if stack else None)
        t = max(t, s)
        stack.append((name, e))
    while stack:
        top, end = stack.pop()
        emit(t, end, top)
        t = max(t, end)
    emit(t, hi, None)
    return segs


def split_by_segments(gaps: Sequence[Interval],
                      segs: Sequence[Tuple[float, float, Optional[str]]]
                      ) -> Dict[str, float]:
    """Seconds of each gap that fall in each named segment (both sorted)."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, name = segs[k]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                key = OUTSIDE if name in (None, WINDOW) else name
                out[key] = out.get(key, 0.0) + overlap * 1e-9
            k += 1
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    devices: int
    modules: Dict[str, List[float]]  # name -> device seconds of each event
    device_ops: List[Tuple[str, float]]  # (module/op, seconds), largest first
    idle_by_span: List[Tuple[str, float]]  # (host span, seconds), largest first
    clock_shift_s: float  # added to the device's times (largest over devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _lines(plane: dict) -> Dict[str, list]:
    return {line["name"]: line["events"] for line in plane["lines"]}


def reduce(trace: dict, window: str = WINDOW) -> Reduction:
    """The device numbers inside the host span named ``window``."""
    planes = trace["planes"]
    host = [line for p in planes if p["name"] == HOST_PLANE for line in p["lines"]
            if any(e[0] == window for e in line["events"])]
    if len(host) != 1:
        names = [line["name"] for p in planes if p["name"] == HOST_PLANE
                 for line in p["lines"]]
        raise RuntimeError(f"expected one host thread with a {window!r} span, found "
                           f"{len(host)} among the host lines {names}")
    spans = [(n, s, s + d) for n, s, d in host[0]["events"]]
    windows = [(s, e) for n, s, e in spans if n == window]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {window!r} host span, found {len(windows)}")
    lo, hi = windows[0]
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise RuntimeError("the trace has no TPU device plane")

    modules: Dict[str, List[float]] = {}
    op_seconds: Dict[str, float] = {}
    busy_ns = 0.0
    shift_ns = 0.0
    idle: Dict[str, float] = {}
    segs = innermost([sp for sp in spans if sp[1] >= lo and sp[2] <= hi], lo, hi)
    for plane in devices:
        lines = _lines(plane)
        mods = sorted((s, s + d, module_name(n)) for n, s, d in lines.get(MODULE_LINE, []))
        shift = clock_shift(spans, mods)
        shift_ns = max(shift_ns, shift)
        mods = [(s + shift, e + shift, n) for s, e, n in mods if lo <= s + shift < hi]
        for s, e, name in mods:
            modules.setdefault(name, []).append((e - s) * 1e-9)
        ops = sorted((s + shift, s + shift + d, op_name(n))
                     for n, s, d in lines.get(OP_LINE, []) if lo <= s + shift < hi)
        j = 0
        for s, e, name in ops:
            while j < len(mods) and mods[j][1] < s:
                j += 1
            owner = mods[j][2] if j < len(mods) and mods[j][0] <= s else "?"
            key = f"{owner}/{name}"
            op_seconds[key] = op_seconds.get(key, 0.0) + (e - s) * 1e-9
        merged = union(((s, e) for s, e, _ in ops), lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        for name, secs in split_by_segments(complement(merged, lo, hi), segs).items():
            idle[name] = idle.get(name, 0.0) + secs / len(devices)
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns * 1e-9 / len(devices),
        devices=len(devices),
        modules=modules,
        device_ops=sorted(op_seconds.items(), key=lambda kv: -kv[1]),
        idle_by_span=sorted(idle.items(), key=lambda kv: -kv[1]),
        clock_shift_s=shift_ns * 1e-9,
    )
