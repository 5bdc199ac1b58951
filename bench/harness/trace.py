"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device time.

The traced part of a run is marked by the harness's ``harness.window``
annotation on the host; it starts no earlier than the first operation the
device's tracer recorded (the tracer can start late, which would read as
idle time). Within it:

* busy time is the union of the intervals of the device's ``XLA Ops``
  events (averaged over the devices that ran anything), idle is the rest;
* an op's time is its self time: control-flow ops (``while``) hold their
  bodies' ops on the same line, so each event's children are taken off;
  ops are keyed by the HLO instruction name (the text before `` = ``);
* each jitted program's device time is the sum of its ``XLA Modules``
  events, keyed by the module name without its ``(id)`` suffix;
* each idle gap is tagged with the harness span (``gen.wait``,
  ``engine.submit``, ``engine.step``, ``harness.record``) that held the
  gap's midpoint on the host, or ``none``.

Events are clipped to the window. Times are the profiler's nanoseconds.
"""

from __future__ import annotations

import re
from collections import defaultdict

HARNESS_SPANS = ("gen.wait", "engine.submit", "engine.step", "harness.record")
WINDOW_SPAN = "harness.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(source):
    """ProfileData from a path or from serialized bytes."""
    from jax.profiler import ProfileData

    if isinstance(source, (bytes, bytearray)):
        return ProfileData.from_serialized_xspace(bytes(source))
    return ProfileData.from_file(str(source))


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(s, e, a, b):
    return max(s, a), min(e, b)


def _spans(line):
    for ev in line.events:
        s = float(ev.start_ns)
        yield ev.name, s, s + float(ev.duration_ns)


def module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def self_times(events) -> dict:
    """Name -> summed self time (s) of nested ``(name, start, end)``
    events: each event's time less that of the events it holds."""
    out = defaultdict(float)
    stack = []  # [name, end, duration, children's duration]

    def close(frame):
        out[frame[0]] += (frame[2] - frame[3]) / 1e9

    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([n.split(" = ")[0], e, e - s, 0.0])
    for frame in stack:
        close(frame)
    return out


def reduce(pd, top: int = 10) -> dict:
    host = [(n, s, e) for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for n, s, e in _spans(line)
            if n in HARNESS_SPANS or n == WINDOW_SPAN]
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    anchor, b = windows[0]
    devices = [{line.name: line for line in p.lines} for p in pd.planes
               if p.name.startswith("/device:")]
    devices = [d for d in devices if OPS_LINE in d and d[OPS_LINE].events]
    if not devices:
        raise ValueError("no device operations in the trace")
    a = max(anchor, min(s for d in devices for _, s, _ in _spans(d[OPS_LINE])))
    spans = [(n, s, e) for n, s, e in host if n != WINDOW_SPAN]

    busy_per_device, ops, programs = [], defaultdict(float), {}
    gaps = []
    for lines in devices:
        clipped = [(n,) + _clip(s, e, a, b) for n, s, e in
                   _spans(lines[OPS_LINE])]
        clipped = [c for c in clipped if c[2] > c[1]]
        if not clipped:
            continue
        ivs = [(s, e) for _, s, e in clipped]
        for n, t in self_times(clipped).items():
            ops[n] += t
        merged = union(ivs)
        busy_per_device.append(sum(e - s for s, e in merged) / 1e9)
        edges = [a] + [x for iv in merged for x in iv] + [b]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                tag = next((n for n, hs, he in spans if hs <= mid <= he),
                           "none")
                gaps.append((tag, (e - s) / 1e9))
        modules = lines.get(MODULES_LINE)
        for n, s, e in (_spans(modules) if modules is not None else ()):
            s, e = _clip(s, e, a, b)
            if e <= s:
                continue
            prog = programs.setdefault(module_name(n), {
                "seconds": 0.0, "count": 0, "intervals": []})
            prog["seconds"] += (e - s) / 1e9
            prog["count"] += 1
            prog["intervals"].append((s, e))
    if not busy_per_device:
        raise ValueError("no device operations in the traced window")
    return {
        "anchor_ns": anchor,
        "window_ns": (a, b),
        "window_s": (b - a) / 1e9,
        "busy_s": sum(busy_per_device) / len(busy_per_device),
        "device_ops": sorted(ops.items(), key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda x: -x[1])[:top],
        "programs": programs,
    }


def program_seconds(reduced: dict, fragment: str) -> tuple:
    """(seconds, count, intervals) of the programs whose name holds
    ``fragment``."""
    secs, count, ivs = 0.0, 0, []
    for name, p in reduced["programs"].items():
        if fragment in name:
            secs += p["seconds"]
            count += p["count"]
            ivs += p["intervals"]
    return secs, count, sorted(ivs)
