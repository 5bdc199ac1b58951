"""85th percentile over the requests due in the window of the time from a
request's due time to the sync that returned its first token (host clock).
A request with no token by the window's end enters with its wait."""

from bench.harness import stats


def read(run):
    waits = [stats.ttft_s(t.due, t.syncs, run.t_end) for t in run.tracks
             if run.t0 <= t.due <= run.t_end]
    return 1e3 * stats.percentile(waits, 85) if waits else None
