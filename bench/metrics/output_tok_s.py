"""Output tokens returned by the syncs in the window / the window's seconds
(host clock): the throughput of an offline batch job."""


def read(run):
    tokens = sum(after - before for c in run.chunks
                 for _, _, before, after in c.emitted)
    return tokens / (run.t_end - run.t0)
