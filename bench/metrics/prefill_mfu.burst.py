"""Prefill against the chip's bf16 peak: FLOPs of the prompts admitted in
the traced part of the window (all but each prompt's last token, which the
first decode step feeds) / (prefill-chunk program device time * peak),
in %."""

from bench.harness import trace
from bench.harness.readers import traced_window


def read(run):
    if run.trace is None or not run.admits:
        return None
    a, b = traced_window(run)
    plen = {t.spec.uid: len(t.spec.prompt) for t in run.tracks}
    flops = sum(run.model.prefill_flops(run.conf, plen[uid] - 1)
                for uid, t in run.admits.items()
                if uid in plen and a <= t <= b)
    secs = trace.program_seconds(run.trace, "_prefill_chunk_impl")[0]
    if not flops or not secs:
        return None
    return 100.0 * flops / (secs * run.peaks["bf16_flops"])
