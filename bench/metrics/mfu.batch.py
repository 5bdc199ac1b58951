"""Whole-step share of the chip's bf16 peak: model FLOPs of the tokens
returned by the syncs in the traced part of the window (2 per weight
applied, plus attention at each token's live length) / (seconds from the
sync before the first of them to the last of them * peak), in %. Whole
decode chunks only, so a traced part that opens mid-chunk reads the same."""

from bench.harness.readers import traced_window


def read(run):
    window = traced_window(run)
    if window is None:
        return None
    a, b = window
    inside = [i for i, c in enumerate(run.chunks) if a <= c.t_sync <= b]
    if not inside or inside[0] == 0:
        return None
    chunks = [run.chunks[i] for i in inside]
    seconds = chunks[-1].t_sync - run.chunks[inside[0] - 1].t_sync
    flops = sum(run.model.flops_per_token(run.conf, plen + j)
                for c in chunks for _, plen, before, after in c.emitted
                for j in range(before, after))
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
