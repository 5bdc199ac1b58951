"""85th percentile over the requests due in the window of (last token's
sync - first token's sync) / (tokens - 1), over the tokens returned by the
window's end (host clock): the gap a streaming user sees."""

from bench.harness import stats


def read(run):
    gaps = [stats.tpot_s(t.syncs, run.t_end) for t in run.tracks
            if run.t0 <= t.due <= run.t_end]
    gaps = [g for g in gaps if g is not None]
    return 1e3 * stats.percentile(gaps, 85) if gaps else None
