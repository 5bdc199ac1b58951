"""85th percentile over the requests due in the window of the time from the
start of a request's admission to the decode sync that returned its first
token, both stamped by the engine on its ``Request`` (``admit_ns``,
``first_token_ns``; the perf_counter clock): the part of the time to first
token that prefill, the cache merge and the first decode chunk take. A
request admitted with no token by the window's end enters with the time
since its admission; one not admitted by then does not enter (its wait is
all queue). Traced runs only; None where the engine stamps no admission."""

from bench.harness import stats


def read(run):
    if run.trace is None:
        return None
    waits = []
    for t in run.tracks:
        admit = getattr(t.req, "admit_ns", None)
        if not run.t0 <= t.due <= run.t_end or admit is None \
                or admit / 1e9 > run.t_end:
            continue
        first = getattr(t.req, "first_token_ns", None)
        end = run.t_end if first is None else min(first / 1e9, run.t_end)
        waits.append(end - admit / 1e9)
    return 1e3 * stats.percentile(waits, 85) if waits else None
