"""Tensor-parallel sharded decode benchmark: single-device dense vs a
(data x model) mesh over forced host devices.

The tentpole claim this bench pins down is STRUCTURAL, not wall-clock: on
``--xla_force_host_platform_device_count`` devices every "device" is a slice
of the same CPU, so sharded tok/s can never beat one device and the ideal
linear-scaling bound (dense tok/s x model-parallel degree) is unreachable
by construction. What the bench verifies and records:

  * the compiled sharded decode program really communicates like a
    tensor-parallel decoder — its scanned layer body carries the
    all-reduce (psum) that completes each row-parallel projection and the
    all-gathers GSPMD inserts around the column-parallel ones (collective
    counts are read from the compiled HLO; ops inside the layer scan
    execute once PER LAYER per decode step);
  * the engine still emits every requested token under the plan (parity);
  * measured sharded tok/s, dense tok/s, and the honest ratio against the
    ideal-scaling bound ``dense * mp`` — on real accelerators the gap is
    interconnect overhead; on forced host devices it also contains the
    core-slicing penalty, which is why the JSON states the bound rather
    than asserting against it.

Each scenario runs in a subprocess with ``JAX_PLATFORMS=cpu`` forced, so
the device count is set before jax initializes. That makes this a CPU
rehearsal of the sharded path, not a chip path: a chip belongs to one
process, and a child cannot reach it while a parent holds it. The chip path
of the same serving code is ``python chip_smoke.py --chips 4``.
``make bench-distributed`` writes ``BENCH_distributed.json``.

    PYTHONPATH=src python benchmarks/bench_distributed.py --smoke
    PYTHONPATH=src python benchmarks/bench_distributed.py \
        --mesh 2x4 --out BENCH_distributed.json
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")


def _child(args) -> int:
    """Runs inside the subprocess: build the engine (sharded or dense),
    compile the decode program, count collectives, serve, report JSON."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import registry
    from repro.models import api
    from repro.serving.engine import Request, ServingEngine

    cfg = registry.get_reduced(args.arch).replace(activation_dtype=jnp.float32)
    cfg = cfg.with_quant(mpgemm_mode=args.mode, weight_bits=args.weight_bits)
    params = api.init_params(jax.random.key(0), cfg, serve_quantized=True)

    plan = None
    d = m = 1
    if args.mesh != "1x1":
        from repro.launch.mesh import make_plan, make_serving_mesh
        d, m = (int(v) for v in args.mesh.split("x"))
        plan = make_plan(make_serving_mesh(data=d, model=m), fsdp=False)

    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_seq=args.max_seq, decode_chunk=args.decode_chunk,
                        prefill_chunk=args.prefill_chunk, plan=plan)

    compiled = eng._decode.lower(eng.params, eng.state).compile()
    hlo = compiled.as_text()
    counts = {}
    for op in COLLECTIVE_OPS:
        # HLO instruction names: "all-reduce", "all-reduce-start", ...
        counts[op] = len(re.findall(rf"= \S+ {op}(?:-start)?\(", hlo))

    def workload(seed=0):
        rng = np.random.default_rng(seed)
        return [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            int(rng.integers(4, 24)),
                                            dtype=np.int32),
                        max_new_tokens=args.max_new)
                for i in range(args.requests)]

    # warmup (compiles prefill/merge paths), then measured reps
    for r in workload(seed=1):
        eng.submit(r)
    eng.run_to_completion()
    best = None
    for _ in range(max(1, args.repeats)):
        eng.reset()
        reqs = workload(seed=0)
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run_to_completion()
        wall = time.perf_counter() - t0
        st = eng.stats()
        assert all(r.done and len(r.output) == args.max_new for r in reqs), \
            "sharded decode dropped tokens"
        rec = {"tok_s": st["decode_tokens"] / wall,
               "decode_tok_s": st["decode_tok_s"],
               "decode_tokens": st["decode_tokens"],
               "host_syncs_per_token": st["host_syncs_per_token"],
               "p50_chunk_ms": st["p50_chunk_ms"],
               "wall_s": wall}
        if best is None or rec["decode_tok_s"] > best["decode_tok_s"]:
            best = rec

    best.update({
        "mesh": {"data": d, "model": m},
        "devices": jax.device_count(),
        "collectives": counts,
        "collectives_total": sum(counts.values()),
        # collectives sit inside the scanned layer body: static count x
        # n_layers executions per decode step
        "n_layers": cfg.n_layers,
    })
    print("BENCH_JSON:" + json.dumps(best))
    return 0


def _run_scenario(args, mesh: str, devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    # forced host devices exist only on the CPU backend; pinning it
    # also skips the accelerator-plugin probe (a sleep-poll loop that
    # starves 1-cpu boxes)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__), "--_child",
           "--mesh", mesh, "--arch", args.arch, "--mode", args.mode,
           "--weight-bits", str(args.weight_bits),
           "--requests", str(args.requests), "--max-new", str(args.max_new),
           "--max-batch", str(args.max_batch), "--max-seq", str(args.max_seq),
           "--decode-chunk", str(args.decode_chunk),
           "--prefill-chunk", str(args.prefill_chunk),
           "--repeats", str(args.repeats)]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f"scenario {mesh} failed:\n{r.stdout}\n"
                           f"{r.stderr[-4000:]}")
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("BENCH_JSON:"))
    return json.loads(line[len("BENCH_JSON:"):])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b",
                    help="reduced config to serve (default: the qwen2-72b "
                         "class the TP plan targets)")
    ap.add_argument("--mesh", default="2x4", metavar="DXM",
                    help="sharded scenario's data x model mesh")
    ap.add_argument("--mode", default="lut_xla")
    ap.add_argument("--weight-bits", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="smallest footprint: fewer requests/tokens")
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests, args.max_new, args.repeats = 4, 8, 1
    if args._child:
        return _child(args)

    d, m = (int(v) for v in args.mesh.split("x"))
    print(f"dense baseline (1 device) ...")
    dense = _run_scenario(args, "1x1", 1)
    print(f"  {dense['decode_tok_s']:.1f} tok/s decode-only, "
          f"collectives {dense['collectives_total']}")
    print(f"sharded {args.mesh} ({d * m} forced host devices) ...")
    shard = _run_scenario(args, args.mesh, d * m)
    cc = shard["collectives"]
    print(f"  {shard['decode_tok_s']:.1f} tok/s decode-only; compiled "
          f"decode HLO: {cc.get('all-reduce', 0)} all-reduce, "
          f"{cc.get('all-gather', 0)} all-gather (inside the layer scan -> "
          f"executed per layer per step)")

    ideal = dense["decode_tok_s"] * m
    result = {
        "bench": "distributed",
        "arch": args.arch,
        "mesh": shard["mesh"],
        "weight_bits": args.weight_bits,
        "mode": args.mode,
        "dense": dense,
        "sharded": shard,
        # one psum (all-reduce) per row-parallel projection per layer is
        # the canonical TP comm structure; the static HLO count sits inside
        # the scanned layer body, so >=1 all-reduce in the decode program
        # means >=1 psum per LAYER at runtime
        "has_per_layer_psum": cc.get("all-reduce", 0) >= 1,
        "ideal_scaling_tok_s": ideal,
        "fraction_of_ideal": shard["decode_tok_s"] / ideal,
        "fraction_of_dense": shard["decode_tok_s"] / dense["decode_tok_s"],
        "note": ("forced host devices time-slice one CPU: fraction_of_ideal "
                 "bounds from below what a real mp-device system would see; "
                 "the structural claims (collectives, parity) are "
                 "device-count faithful"),
    }
    print(f"ideal-scaling bound {ideal:.1f} tok/s (dense x {m}); sharded "
          f"reaches {result['fraction_of_ideal']:.2f} of ideal "
          f"({result['fraction_of_dense']:.2f} of dense) on time-sliced "
          f"host devices")
    if not result["has_per_layer_psum"]:
        print("ASSERTION FAILED: no all-reduce in the sharded decode HLO — "
              "the plan is not producing tensor-parallel computation")
        return 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
