"""Device time of the prefill-chunk and cache-merge programs in the traced
part of the window / the requests whose ``admit`` span started in it."""

from bench.harness import trace
from bench.harness.readers import traced_window


def read(run):
    if run.trace is None or not run.admits:
        return None
    a, b = traced_window(run)
    admitted = sum(1 for t in run.admits.values() if a <= t <= b)
    if not admitted:
        return None
    secs = (trace.program_seconds(run.trace, "_prefill_chunk_impl")[0]
            + trace.program_seconds(run.trace, "lambda")[0])
    return 1e3 * secs / admitted
