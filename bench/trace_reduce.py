"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The trace holds one plane per TPU (``/device:TPU:<i>``), whose ``XLA Ops``
line has an event per device operation and whose ``XLA Modules`` line has
an event per program run, and host planes whose threads carry the
benchmark's own ``TraceAnnotation`` spans.  Both are on one clock.

The window is the span named ``window`` (the benchmark wraps its measured
window in it); without it, the extent of the device operations.  Within it:

- busy: the union of the intervals in which an operation ran, per device;
- per-operation device time (an operation that encloses others, such as a
  while loop, is charged only the time none of them covers) and
  per-program device time (the union of its operations), summed over
  devices;
- idle gaps: each stretch of the window in which no operation ran on the
  device, split among the host spans that overlap it (the rest is
  ``no span``).

Times in the result are seconds.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "no span"


def find(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float) -> tuple[float, float] | None:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def device_planes(profile) -> list:
    return [p for p in profile.planes if p.name.startswith("/device:TPU:")]


def reduce(profile, window: str = "bench.window", spans: tuple[str, ...] = ()) -> dict | None:
    """Busy and idle time, device time per operation and per program, and
    idle time per host span, inside the window.  None where the trace
    holds no device operation."""
    devices = [p for p in device_planes(profile) if any(True for _ in _events(p, OPS_LINE))]
    if not devices:
        return None
    host_spans: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == window or ev.name in spans:
                    host_spans[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
    if host_spans.get(window):
        lo = min(s for s, _ in host_spans[window])
        hi = max(e for _, e in host_spans[window])
    else:
        ends = [(s, e) for p in devices for _, s, e in _events(p, OPS_LINE)]
        lo, hi = min(s for s, _ in ends), max(e for _, e in ends)
    span_ivs = {}
    for name in spans:
        ivs = _union(host_spans.get(name, []))
        span_ivs[name] = ([iv[0] for iv in ivs], ivs)
    ops: dict[str, float] = defaultdict(float)
    modules: dict[str, float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    busy_total = 0.0
    n_ops = 0
    for plane in devices:
        mods = sorted((s, e, name) for name, s, e in _events(plane, MODULES_LINE))
        mod_starts = [m[0] for m in mods]
        busy = []
        by_module: dict[str, list] = defaultdict(list)
        # an operation such as a while loop encloses the operations of its
        # body on the same line: each is charged its own time only
        stack: list[list] = []  # [end, name, self time]
        for name, s, e in sorted(_events(plane, OPS_LINE), key=lambda ev: (ev[1], -ev[2])):
            iv = _clip(s, e, lo, hi)
            while stack and stack[-1][0] <= s:
                _, done, own = stack.pop()
                ops[done] += own
            if iv is None:
                continue
            n_ops += 1
            busy.append(iv)
            if stack and e <= stack[-1][0]:
                stack[-1][2] -= iv[1] - iv[0]
            stack.append([e, name, iv[1] - iv[0]])
            i = bisect.bisect_right(mod_starts, s) - 1
            owner = mods[i][2] if i >= 0 and e <= mods[i][1] else NO_SPAN
            by_module[owner].append(iv)
        for _, done, own in stack:
            ops[done] += own
        for owner, ivs in by_module.items():
            modules[owner] += sum(e - s for s, e in _union(ivs))
        busy = _union(busy)
        busy_total += sum(e - s for s, e in busy)
        gaps, cursor = [], lo
        for s, e in busy:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < hi:
            gaps.append((cursor, hi))
        for gs, ge in gaps:
            covered = 0.0
            for name, (starts, ivs) in span_ivs.items():
                i = max(0, bisect.bisect_right(starts, gs) - 1)
                while i < len(ivs) and ivs[i][0] < ge:
                    iv = _clip(*ivs[i], gs, ge)
                    if iv is not None:
                        idle[name] += iv[1] - iv[0]
                        covered += iv[1] - iv[0]
                    i += 1
            idle[NO_SPAN] += max(0.0, (ge - gs) - covered)
    ns = 1e-9
    return {
        "devices": len(devices),
        "window_s": (hi - lo) * ns,
        "busy_s": busy_total / len(devices) * ns,
        "n_ops": n_ops,
        "ops": {k: v * ns for k, v in ops.items()},
        "modules": {k: v * ns for k, v in modules.items()},
        "idle_by_span": {k: v / len(devices) * ns for k, v in idle.items()},
    }


def top(table: dict[str, float], k: int = 10) -> list[list]:
    """The ``k`` largest entries of a {name: seconds} table, largest first."""
    return [[name, sec] for name, sec in sorted(table.items(), key=lambda kv: -kv[1])[:k]]


def reduce_file(path: str, window: str = "bench.window", spans: tuple[str, ...] = ()) -> dict | None:
    import jax.profiler

    return reduce(jax.profiler.ProfileData.from_file(path), window, spans)
