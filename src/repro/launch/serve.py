"""Serving driver: quantize a model to the packed low-bit format and serve a
batch of requests through the device-resident continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --requests 12 --max-new 24 --mode lut_xla \
        --decode-chunk 8 --temperature 0.8 --top-k 40 --top-p 0.95
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core.mpgemm import FUSION_MODES, MPGEMM_MODES
from repro.launch.compile_cache import configure_compile_cache
from repro.models import api
from repro.serving import decoding
from repro.serving.engine import Request, ServingEngine


def parser() -> argparse.ArgumentParser:
    """The serving flags (shared by chip_smoke.py)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="decode steps per device dispatch (host syncs once "
                         "per chunk, not once per token)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="fixed prompt-chunk shape for admission prefill "
                         "(one compiled program for all prompt lengths)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (<=0 greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k (0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-request nucleus mass (>=1 disables)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a slot when it samples this token id")
    ap.add_argument("--decoding", default="greedy",
                    help="per-request decoding mode: greedy | sample | "
                         "beam:W (width-W beam search, W pool slots per "
                         "request) | spec:draftNb (bit-plane self-"
                         "speculation drafting with the top N planes of "
                         "the SAME packed weights)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per speculative verify round")
    ap.add_argument("--cache-block-size", type=int, default=None,
                    help="enable the block-paged KV cache pool with this "
                         "many positions per block (must divide --max-seq)")
    ap.add_argument("--num-cache-blocks", type=int, default=None,
                    help="pool size in blocks incl. the reserved null block "
                         "(default: dense-equivalent capacity)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="hash-based shared-prefix block reuse (requires "
                         "--cache-block-size; identical prefixes prefill "
                         "once and fan out by block reference)")
    ap.add_argument("--mode", default="lut_xla",
                    choices=list(MPGEMM_MODES))
    ap.add_argument("--fusion", default="auto",
                    choices=list(FUSION_MODES),
                    help="lut_pallas precompute placement: fused keeps the "
                         "table in VMEM, staged round-trips it through HBM, "
                         "tuned reads the measured autotune cache")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="persistent kernel-tuning cache (JSON). Activates "
                         "measured dispatch for fusion=tuned; created/"
                         "updated by --pretune")
    ap.add_argument("--pretune", action="store_true",
                    help="before serving, measure-tune every mpGEMM shape "
                         "this engine dispatches and persist the cache "
                         "(lut_pallas only)")
    ap.add_argument("--weight-bits", type=int, default=2)
    ap.add_argument("--mesh", default=None, metavar="DXM",
                    help="serving mesh 'data x model', e.g. 2x4: shards "
                         "packed weights / caches / engine state over a "
                         "jax.sharding mesh (needs data*model devices). "
                         "Default is single-device — the 1x1 no-op plan")
    ap.add_argument("--tp", type=int, default=None, metavar="N",
                    help="tensor-parallel shortcut for --mesh 1xN")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the request "
                         "lifecycle (admit/prefill/decode-chunk spans; open "
                         "at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot as JSON "
                         "('-.prom' suffix writes Prometheus text instead)")
    ap.add_argument("--dispatch-log", default=None, metavar="PATH",
                    help="record every mpGEMM dispatch decision (shape key, "
                         "fusion, tuned-vs-heuristic) traced during this "
                         "serve and write it as JSON")
    return ap


def model_config(args):
    """The served ArchConfig for parsed serving flags."""
    cfg = (registry.get_reduced(args.arch) if args.reduced
           else registry.get_config(args.arch))
    cfg = cfg.replace(activation_dtype=jnp.float32)
    return cfg.with_quant(mpgemm_mode=args.mode,
                          weight_bits=args.weight_bits, fusion=args.fusion)


def load(args, cfg):
    """Random weights (seed 0) in the serving format -> (cfg, params)."""
    quantized = args.mode != "fp16"
    params = api.init_params(jax.random.key(0), cfg,
                             serve_quantized=quantized)
    if not quantized:
        cfg = cfg.replace(quant=None)
    return cfg, params


def build_engine(args, cfg, params, *, tracer=None, ap=None) -> ServingEngine:
    """The ServingEngine exactly as ``main`` serves with these flags."""
    def error(msg):
        if ap is not None:
            ap.error(msg)
        raise ValueError(msg)

    if args.prefix_cache and args.cache_block_size is None:
        error("--prefix-cache requires --cache-block-size")
    try:
        dm = decoding.parse(args.decoding)
    except ValueError as e:
        error(str(e))
    spec_draft_planes = dm.draft_planes if dm.kind == decoding.SPEC else None
    if spec_draft_planes is not None and args.mode == "fp16":
        error("--decoding spec needs a quantized mode: the draft is a "
              "bit-plane slice of the packed weights")
    if args.mesh is not None and args.tp is not None:
        error("--mesh and --tp are mutually exclusive")
    plan = None
    if args.mesh is not None or args.tp is not None:
        from repro.launch.mesh import make_plan, make_serving_mesh
        if args.mesh is not None:
            try:
                d, m = (int(v) for v in args.mesh.lower().split("x"))
            except ValueError:
                error(f"--mesh wants 'DxM' (e.g. 2x4), got {args.mesh!r}")
        else:
            d, m = 1, args.tp
        mesh = make_serving_mesh(data=d, model=m)
        plan = make_plan(mesh, fsdp=False)
        print(f"serving mesh {d}x{m} (data x model) over "
              f"{jax.device_count()} devices")
    return ServingEngine(cfg, params, max_batch=args.max_batch,
                         max_seq=args.max_seq,
                         decode_chunk=args.decode_chunk,
                         prefill_chunk=args.prefill_chunk,
                         eos_id=args.eos_id,
                         tuning_cache=args.tuning_cache,
                         cache_block_size=args.cache_block_size,
                         num_cache_blocks=args.num_cache_blocks,
                         prefix_cache=args.prefix_cache,
                         plan=plan,
                         spec_k=args.spec_k,
                         spec_draft_planes=spec_draft_planes,
                         tracer=tracer)


def make_requests(args, vocab_size: int):
    """``--requests`` prompts of 4-23 random tokens (seed 0)."""
    rng = np.random.default_rng(0)
    out = []
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        out.append(Request(
            uid=i, prompt=rng.integers(0, vocab_size, plen, dtype=np.int32),
            max_new_tokens=args.max_new, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, decoding=args.decoding))
    return out


def main(argv=None):
    ap = parser()
    args = ap.parse_args(argv)
    configure_compile_cache()
    cfg = model_config(args)
    print(f"init + quantize ({args.mode}, W{args.weight_bits}) ...")
    cfg, params = load(args, cfg)

    if args.fusion == "tuned" and args.tuning_cache is None and not args.pretune:
        print("note: fusion=tuned without --tuning-cache falls back to the "
              "auto heuristic on every dispatch")
    tracer = None
    if args.trace_out is not None:
        from repro.obs.trace import Tracer
        tracer = Tracer()
    recorder = None
    if args.dispatch_log is not None:
        from repro.obs import dispatch as dispatch_obs
        recorder = dispatch_obs.enable(dispatch_obs.DispatchRecorder())
    eng = build_engine(args, cfg, params, tracer=tracer, ap=ap)
    if args.pretune:
        if eng.tuning_cache is None:  # tune in-memory for this process
            from repro.core import autotune
            eng.tuning_cache = autotune.configure(None)
        t0 = time.time()
        n = eng.pretune(verbose=True)
        print(f"pretuned {n} mpGEMM shapes in {time.time() - t0:.1f}s "
              f"-> {args.tuning_cache or '(in-memory only)'}")
    for req in make_requests(args, cfg.vocab_size):
        eng.submit(req)
    t0 = time.time()
    chunks = eng.run_to_completion()
    dt = time.time() - t0
    st = eng.stats()
    total_new = st["decode_tokens"]
    print(f"served {args.requests} requests / {total_new} tokens in "
          f"{dt:.2f}s ({chunks} chunk cycles, {total_new/dt:.1f} tok/s, "
          f"continuous batching over {args.max_batch} slots)")
    print(f"host syncs/token {st['host_syncs_per_token']:.4f} "
          f"(decode_chunk={args.decode_chunk}), chunk latency "
          f"p50 {st['p50_chunk_ms']:.1f} ms / p95 {st['p95_chunk_ms']:.1f} ms")
    if "spec" in st:
        sp = st["spec"]
        print(f"self-speculation: K={sp['spec_k']}, draft "
              f"{sp['draft_planes']} planes (+{sp['draft_extra_hbm_bytes']} "
              f"bytes weight HBM), {sp['verify_steps']} verify rounds, "
              f"{sp['mean_accepted_per_step']:.2f} draft tokens accepted / "
              f"round ({sp['mean_emitted_per_step']:.2f} emitted)")
    if st["paged"]:
        line = (f"paged pool: {st['num_cache_blocks']} x "
                f"{st['cache_block_size']}-token blocks, cache HBM "
                f"{st['cache_hbm_bytes'] / 1e6:.2f} MB, occupancy "
                f"{st['slot_occupancy']:.2f}, blocked admissions "
                f"{st['admit_blocked']}/{st['admit_attempts']}")
        if "prefix_cache" in st:
            pc = st["prefix_cache"]
            line += (f", prefix hits {pc['hits']} (reused "
                     f"{st['prefill_tokens_reused']} prompt tokens)")
        print(line)
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"trace: {len(tracer)} events -> {args.trace_out} "
              "(open at ui.perfetto.dev)")
    if args.metrics_out is not None:
        if args.metrics_out.endswith(".prom"):
            with open(args.metrics_out, "w") as f:
                f.write(eng.prometheus_text())
        else:
            import json
            with open(args.metrics_out, "w") as f:
                json.dump(eng.metrics_snapshot(), f, indent=2)
        print(f"metrics -> {args.metrics_out}")
    if recorder is not None:
        import json
        with open(args.dispatch_log, "w") as f:
            json.dump(recorder.summary(), f, indent=2)
        s = recorder.summary()
        print(f"dispatch log: {s['decisions']} mpGEMM decisions "
              f"({s['tuned']} tuned, {s['heuristic']} heuristic, "
              f"{s['forced']} forced) -> {args.dispatch_log}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
