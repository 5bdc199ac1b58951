"""The decode steps' mpGEMM against the chip: least time of the mpGEMM
work of the decode steps in the traced part of the window / the device
time of the decode-chunk program's mpGEMM scopes (as
``mpgemm_ms_per_step.batch`` reads them), in %.

A step's least time is the larger of its FLOPs over the bf16 peak and its
bytes over the HBM bandwidth, at its live rows (``mpgemm_step_cost`` of
the configuration's model module: packed weights over the true K, their
scales, the rows' int8 tables and float32 outputs). Each decode program is
matched to the chunk whose host interval holds its midpoint, and a step's
live rows are the slots that emitted a token at it; a program matched to
no chunk counts every slot at each of its steps.
"""

from bench.harness.readers import decode_chunks, decode_scopes, is_mpgemm


def read(run):
    scopes = decode_scopes(run)
    if scopes is None:
        return None
    device = sum(v for k, v in scopes[0].items() if is_mpgemm(k))
    e, peak = run.engine, run.peaks
    least = 0.0
    for _, c in decode_chunks(run):
        for k in range(e["decode_chunk"]):
            rows = (e["max_batch"] if c is None else
                    sum(1 for *_, before, after in c.emitted
                        if after - before > k))
            if rows:
                flops, nbytes = run.model.mpgemm_step_cost(run.conf, rows)
                least += max(flops / peak["bf16_flops"],
                             nbytes / peak["hbm_bw"])
    return 100.0 * least / device if device else None
