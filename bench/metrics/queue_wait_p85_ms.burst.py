"""85th percentile over the requests due in the window of the time from a
request's due time to the start of the engine's ``admit`` span for it (the
engine's tracer, same perf_counter clock). A request not admitted by the
window's end enters with its wait."""

from bench.harness import stats


def read(run):
    if not run.admits:
        return None
    waits = []
    for t in run.tracks:
        if run.t0 <= t.due <= run.t_end:
            admit = run.admits.get(t.spec.uid, run.t_end)
            waits.append(min(admit, run.t_end) - t.due)
    return 1e3 * stats.percentile(waits, 85) if waits else None
