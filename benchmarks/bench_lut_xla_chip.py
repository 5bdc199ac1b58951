"""Chip timing of lut_xla's ``T @ CW`` contraction, one projection shape at a time.

    python benchmarks/bench_lut_xla_chip.py [--out shapes.json]

For every projection shape of paper-bitnet-3b (ternary) and qwen2-72b (W2
on the odd grid), at decode rows (M = 8) and a prefill chunk (M = 32), it
times the contraction of a per-row int8 table against the packed weights
two ways:

  * ``xla``    — the CW matrix built by XLA from the packed planes
                 (``kernels/ref.py:build_cw``), then one int8 ``dot_general``;
  * ``kernel`` — the lookup kernel over the packed planes
                 (``kernels/ops.lut_mpgemm(table=...)``), CW only as VMEM
                 tiles, at the blocks ``ops.resolve_dispatch`` picks and at
                 a few other (bn, bg).

Each timing scans over L stacked random weights in one jitted call, as the
model's layer scan does, so no path's work can be hoisted out of the loop;
it reports the median milliseconds per layer over ``--reps`` calls, each
ended by ``block_until_ready``. Each case also reports the largest absolute
difference between the two paths' outputs on one weight. One JSON line
per case on stdout; ``--out`` also writes them all to one file. Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core import quantize as Q
from repro.core.table import precompute_table
from repro.kernels import ops, ref

SHAPES = {  # config: (scheme, [(K, N), ...])
    "paper-bitnet-3b": ("ternary", [(3200, 3200), (3200, 8640), (8640, 3200),
                                    (3200, 32000)]),
    "qwen2-72b": ("symmetric", [(8192, 8192), (8192, 1024), (8192, 29568),
                                (29568, 8192), (8192, 152064)]),
}
ROWS = (8, 32)
BLOCKS = [(1024, 128), (2048, 128), (512, 512), (1024, 512), (2048, 512)]
STACK_BYTES = 400e6  # packed bytes per timed call (sets L)


def stacked_weight(key, layers, k, n, scheme):
    """L random weights of one shape: every packed byte is a valid code."""
    like = jax.eval_shape(lambda w: Q.quantize(w, 2, 4, scheme),
                          jax.ShapeDtypeStruct((n, k), jnp.float32))
    kp, ks = jax.random.split(key)
    packed = jax.random.bits(kp, (layers,) + like.packed.shape, jnp.uint8)
    scale = jax.random.uniform(ks, (layers, n), jnp.float32, 1e-3, 2e-3)
    return Q.QuantizedWeight(packed, scale, None, like.plane_scales,
                             bits=like.bits, k_group=like.k_group,
                             k_total=k, n=n)


def contraction(path, x, blocks=None):
    if path == "xla":
        return lambda t, w: ref.ref_lut_mpgemm_matmul(
            x, w, table_quant="per_row", table=t)
    bn, bg = blocks or (None, None)
    return lambda t, w: ops.lut_mpgemm(
        x, w, table_quant="per_row", table=t, fusion="staged",
        block_n=bn, block_g=bg)


def time_per_layer(fn, t, w, layers, reps):
    def scanned(t, w):
        return jax.lax.scan(lambda c, wl: (c + fn(t, wl), None),
                            jnp.zeros((t.values.shape[0], w.n), jnp.float32),
                            w)[0]
    f = jax.jit(scanned).lower(t, w).compile()
    f(t, w).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        f(t, w).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / layers * 1e3


def one_layer(w):
    return jax.tree.map(lambda a: a[0], w)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", help="also write every case to this JSON file")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    rows = []
    key = jax.random.key(0)
    for config, (scheme, shapes) in SHAPES.items():
        for k, n in shapes:
            key, kw, kx = jax.random.split(key, 3)
            like = stacked_weight(kw, 1, k, n, scheme)
            layers = int(min(64, max(2, STACK_BYTES // like.packed.nbytes)))
            w = stacked_weight(kw, layers, k, n, scheme)
            for m in ROWS:
                x = jax.random.normal(kx, (m, k), jnp.float32)
                t = jax.jit(lambda x: precompute_table(x, 4, "per_row"))(x)
                picked = ops.resolve_dispatch(m, n, k // 4, 4, 2,
                                              fusion="staged")[2:]
                w0 = one_layer(w)
                y_xla = jax.jit(contraction("xla", x))(t, w0)
                y_ker = jax.jit(contraction("kernel", x))(t, w0)
                row = {"config": config, "k": k, "n": n, "m": m,
                       "layers": layers, "picked_bn_bg": list(picked),
                       "max_abs_diff": float(jnp.max(jnp.abs(y_xla - y_ker))),
                       "max_abs_out": float(jnp.max(jnp.abs(y_xla))),
                       "xla_ms": time_per_layer(contraction("xla", x), t, w,
                                                layers, args.reps),
                       "kernel_ms": {}}
                for blocks in [None] + BLOCKS:
                    label = "picked" if blocks is None else "%dx%d" % blocks
                    try:
                        row["kernel_ms"][label] = time_per_layer(
                            contraction("kernel", x, blocks), t, w, layers,
                            args.reps)
                    except Exception as e:  # a block the chip refuses
                        row["kernel_ms"][label] = f"error: {e!s:.200}"
                print(json.dumps(row), flush=True)
                rows.append(row)
            del w
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
