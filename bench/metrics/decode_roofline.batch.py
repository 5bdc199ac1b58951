"""The whole decode step against the chip: least time of the decode steps
in the traced part of the window / their measured device time, in %.

A step's least time is the larger of its FLOPs over the bf16 peak and its
bytes over the HBM bandwidth, counted by the configuration's model module
at the dtypes the configuration states (weights at ``weight_bits`` over
the true K, their scales, the embedding rows, the LM head, the live KV
cache). Each decode program on the device trace is matched to the chunk
whose host interval holds its midpoint; the steps that emitted a token
count.
"""

from bench.harness import trace
from bench.harness.readers import to_perf_counter


def read(run):
    if run.trace is None:
        return None
    _, _, intervals = trace.program_seconds(run.trace, "_decode_chunk_impl")
    conf, peak = run.conf, run.peaks
    least = device = 0.0
    chunks = list(run.chunks)
    for s, e in intervals:
        mid = to_perf_counter(run, (s + e) / 2)
        c = next((c for c in chunks if c.t_start <= mid <= c.t_sync), None)
        if c is None or not c.emitted:
            continue
        chunks.remove(c)
        device += (e - s) / 1e9
        for k in range(max(after - before for _, _, before, after
                           in c.emitted)):
            attended = [plen + before + k for _, plen, before, after
                        in c.emitted if after - before > k]
            flops, nbytes = run.model.decode_step_cost(conf, attended)
            least += max(flops / peak["bf16_flops"], nbytes / peak["hbm_bw"])
    return 100.0 * least / device if device else None
