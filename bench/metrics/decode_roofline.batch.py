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

from bench.harness.readers import decode_chunks


def read(run):
    conf, peak = run.conf, run.peaks
    least = device = 0.0
    for (s, e), c in decode_chunks(run):
        if c is None or not c.emitted:
            continue
        device += (e - s) / 1e9
        for k in range(max(after - before for _, _, before, after
                           in c.emitted)):
            attended = [plen + before + k for _, plen, before, after
                        in c.emitted if after - before > k]
            flops, nbytes = run.model.decode_step_cost(conf, attended)
            least += max(flops / peak["bf16_flops"], nbytes / peak["hbm_bw"])
    return 100.0 * least / device if device else None
