"""A traced run's device time put down to the program's named scopes, and
its idle gaps put down to the innermost host span.

``reduce(pd, op_maps)`` is ``trace.reduce(pd)`` with two keys changed or
added, over the same window, clipping and self times:

* ``scopes``: ``{program: {label: self seconds}}``. Each op on the device's
  ``XLA Ops`` line belongs to the program whose ``XLA Modules`` run holds
  its midpoint, and takes its label from that program's map of
  instruction -> label (``ServingEngine.op_scopes()``, which reads the
  compiled HLO's metadata with ``repro.obs.scopes``); an op the map lacks
  is ``other``. The trace's ops carry only their HLO text, no op metadata
  (not even with ``enable_hlo_proto = True`` on a TPU v5e), so the map has
  to come from the compiled program.
* ``idle_gaps``: each gap tagged with the innermost span, of the harness's
  and the engine's (``ENGINE_SPANS``, live ``Tracer`` spans that enter a
  ``TraceAnnotation``), that holds the gap's midpoint on the host. Where
  no engine span is recorded the tags are those of ``trace.reduce``.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

from bench.harness import trace

ENGINE_SPANS = ("admit", "prefill_chunk", "decode_chunk", "decode_dispatch",
                "decode_sync", "emit")
SPANS = trace.HARNESS_SPANS + ENGINE_SPANS
OTHER = "other"


def _devices(pd):
    devices = [{line.name: line for line in p.lines} for p in pd.planes
               if p.name.startswith("/device:")]
    return [d for d in devices if trace.OPS_LINE in d
            and d[trace.OPS_LINE].events]


def innermost(spans, t):
    """Name of the innermost ``(name, start, end)`` span holding ``t``: the
    one that starts last, the shortest on a tie; ``none`` if none does."""
    holding = [(s, s - e, n) for n, s, e in spans if s <= t <= e]
    return max(holding)[2] if holding else "none"


def scope_seconds(pd, op_maps: dict, window_ns) -> dict:
    """{program: {label: self seconds}} for the programs in ``op_maps``."""
    a, b = window_ns
    out = defaultdict(lambda: defaultdict(float))
    for lines in _devices(pd):
        modules = lines.get(trace.MODULES_LINE)
        runs = sorted((s, e, trace.module_name(n)) for n, s, e in
                      (trace._spans(modules) if modules is not None else ()))
        starts = [r[0] for r in runs]
        by_program = defaultdict(list)
        for n, s, e in trace._spans(lines[trace.OPS_LINE]):
            s, e = trace._clip(s, e, a, b)
            if e <= s:
                continue
            i = bisect.bisect_right(starts, (s + e) / 2) - 1
            if i >= 0 and (s + e) / 2 <= runs[i][1] \
                    and runs[i][2] in op_maps:
                by_program[runs[i][2]].append((n, s, e))
        for prog, events in by_program.items():
            labels = op_maps[prog]
            for op, secs in trace.self_times(events).items():
                out[prog][labels.get(op.lstrip("%"), OTHER)] += secs
    return {p: dict(v) for p, v in out.items()}


def idle_gaps(pd, window_ns, top: int = 10) -> list:
    """The ``top`` longest device-idle gaps in the window, each tagged with
    the innermost harness or engine span holding its midpoint."""
    a, b = window_ns
    spans = [(n, s, e) for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for n, s, e in trace._spans(line)
             if n in SPANS]
    gaps = []
    for lines in _devices(pd):
        ivs = [trace._clip(s, e, a, b) for _, s, e in
               trace._spans(lines[trace.OPS_LINE])]
        merged = trace.union([iv for iv in ivs if iv[1] > iv[0]])
        if not merged:
            continue
        edges = [a] + [x for iv in merged for x in iv] + [b]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((innermost(spans, (s + e) / 2), (e - s) / 1e9))
    return sorted(gaps, key=lambda x: -x[1])[:top]


def reduce(pd, op_maps: dict, top: int = 10) -> dict:
    """``trace.reduce(pd, top)`` with ``scopes`` added and ``idle_gaps``
    tagged by the innermost span."""
    out = trace.reduce(pd, top)
    out["scopes"] = scope_seconds(pd, op_maps, out["window_ns"])
    out["idle_gaps"] = idle_gaps(pd, out["window_ns"], top)
    return out
