"""Readings shared by metric files whose quantity is split by cell kind
(``decode_step_ms.batch`` and ``.burst`` read the same thing), or that
several metric files read (the decode program's steps and scopes)."""

from __future__ import annotations

from collections import defaultdict

from bench.harness import trace

DECODE = "_decode_chunk_impl"


def decode_programs(run):
    """(device seconds, decode steps, intervals) of the decode-chunk
    programs in the traced part of the window, or None where none ran."""
    if run.trace is None:
        return None
    secs, count, intervals = trace.program_seconds(run.trace, DECODE)
    if not count:
        return None
    return secs, count * run.engine["decode_chunk"], intervals


def decode_step_ms(run):
    """Device time of the decode-chunk program in the traced part of the
    window / the decode steps it ran."""
    programs = decode_programs(run)
    return None if programs is None else 1e3 * programs[0] / programs[1]


def decode_scopes(run):
    """({scope label: device seconds}, decode steps) of the decode-chunk
    programs in the traced part of the window, or None where the trace was
    reduced without the program's scopes."""
    programs = decode_programs(run)
    if programs is None:
        return None
    out = defaultdict(float)
    for prog, labels in run.trace.get("scopes", {}).items():
        if DECODE in prog:
            for label, secs in labels.items():
                out[label] += secs
    return (dict(out), programs[1]) if out else None


def is_mpgemm(label: str) -> bool:
    """A label of mpGEMM work: ``mpgemm`` and its sub-scopes, the LM head's
    among them (``lm_head/mpgemm/table``)."""
    return "mpgemm" in label.split("/")


def decode_chunks(run) -> list:
    """Each decode-chunk program in the traced part of the window, in
    order, as ``((start_ns, end_ns), chunk)``: the window's chunk whose host
    interval holds the program's midpoint, or None. A chunk is taken once."""
    programs = decode_programs(run)
    chunks, out = list(run.chunks), []
    for s, e in programs[2] if programs else ():
        mid = to_perf_counter(run, (s + e) / 2)
        c = next((c for c in chunks if c.t_start <= mid <= c.t_sync), None)
        if c is not None:
            chunks.remove(c)
        out.append(((s, e), c))
    return out


def idle_share(run):
    """100 * (1 - union of device-busy intervals / traced window)."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def to_perf_counter(run, t_ns: float) -> float:
    """A profiler timestamp on the harness's perf_counter clock, aligned at
    the start of the ``harness.window`` annotation."""
    return run.trace_pc[0] + (t_ns - run.trace["anchor_ns"]) / 1e9


def traced_window(run):
    """The traced part of the window on the perf_counter clock, or None."""
    if run.trace is None:
        return None
    return tuple(to_perf_counter(run, t) for t in run.trace["window_ns"])
