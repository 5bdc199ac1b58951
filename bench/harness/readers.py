"""Readings shared by metric files whose quantity is split by cell kind
(``decode_step_ms.batch`` and ``.burst`` read the same thing)."""

from __future__ import annotations

from bench.harness import trace


def decode_step_ms(run):
    """Device time of the decode-chunk program in the traced part of the
    window / the decode steps it ran."""
    if run.trace is None:
        return None
    secs, count, _ = trace.program_seconds(run.trace, "_decode_chunk_impl")
    if not count:
        return None
    return 1e3 * secs / (count * run.engine["decode_chunk"])


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
