"""Pallas TPU kernel: LUT table precompute (+ fused INT8 table quantization).

The DFG-transformed precompute operator (§3.1.1) as a standalone kernel:
activations stream HBM→VMEM once, each block is turned into its half-table
block on the VPU (exact ±1 signed sums), and (optionally) quantized to INT8
in-VMEM before the store — so the table that lands in HBM is already
LUT_BIT=8 (Eq. 7's table-size term).

Kernel layout (``ops`` converts to and from it): groups ride the 128 lanes.
The activation block holds, for lane-group sub-chunk j (groups
``j*128 .. j*128+127``), the K group positions as K contiguous 128-lane
tiles; the table block holds the E entries of sub-chunk j as E contiguous
128-lane tiles (entry-major). Every load and store is a ``[bm, 128]`` tile
at a 128-aligned lane offset — no reshape, which Mosaic cannot lower.

Per-row scales are computed from A in closed form (Σ|a_i| per group, maxed
over groups — see table.group_absmax) by the wrapper and passed in, so this
kernel stays a single pass.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lmma import VMEM_BYTES
from repro.core.packing import LANES
from repro.core.table import abs_sum, signed_entries

__all__ = ["table_precompute_pallas"]


def lane_tile(ref, start):
    """``[rows, 128]`` tile of a VMEM block at a 128-aligned lane offset."""
    if not isinstance(start, int):
        start = pl.multiple_of(start, LANES)
    return ref[:, pl.ds(start, LANES)]


def store_lane_tile(ref, start, value):
    if not isinstance(start, int):
        start = pl.multiple_of(start, LANES)
    ref[:, pl.ds(start, LANES)] = value


def for_each(n: int, body):
    """body(i) for i in [0, n): a real loop, so code size is per-iteration."""
    if n == 1:
        body(0)
        return

    def step(i, carry):
        body(i)
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def sub_chunk_entries(a_ref, j, k_group: int):
    """Activation positions and half-table entries of lane sub-chunk j:
    (K x [bm, 128], E x [bm, 128]) float32."""
    parts = [lane_tile(a_ref, (j * k_group + i) * LANES).astype(jnp.float32)
             for i in range(k_group)]
    return parts, signed_entries(parts, k_group)


def group_scale(parts):
    """per_group INT8 scale of one sub-chunk: max_e|T[e]|/127 = Σ|a_i|/127."""
    return jnp.maximum(abs_sum(parts), 1e-30) / 127.0


def quantize_entries(ents, scale):
    return [jnp.clip(jnp.round(t / scale), -127, 127).astype(jnp.int8)
            for t in ents]


def _kernel(a_ref, *refs, k_group: int, nsub: int, mode: Optional[str]):
    e = 1 << (k_group - 1)
    if mode == "per_row":
        rs_ref, tq_ref = refs
    elif mode == "per_group":
        tq_ref, ts_ref = refs
    else:
        (tq_ref,) = refs

    def body(j):
        parts, ents = sub_chunk_entries(a_ref, j, k_group)
        if mode == "per_row":
            ents = quantize_entries(ents, rs_ref[...])
        elif mode == "per_group":
            scale = group_scale(parts)
            store_lane_tile(ts_ref, j * LANES, scale)
            ents = quantize_entries(ents, scale)
        for i, t in enumerate(ents):
            store_lane_tile(tq_ref, (j * e + i) * LANES, t)

    for_each(nsub, body)


def table_precompute_pallas(
    a: jax.Array,             # [M, Gt*K] lane-group layout (pre-padded)
    k_group: int,
    table_quant: Optional[str],
    row_scale: Optional[jax.Array] = None,  # [M, 1] f32, required for per_row
    *,
    block_m: int = 32,
    block_g: int = 128,
    interpret: bool = False,
):
    """Returns (values [M, Gt*E] kernel layout, per-group scale [M, Gt] or
    None). Rowsum is wrapper-side."""
    m, width = a.shape
    g = width // k_group
    e = 1 << (k_group - 1)
    assert m % block_m == 0 and g % block_g == 0 and block_g % LANES == 0, (
        (m, g), (block_m, block_g))
    grid = (m // block_m, g // block_g)
    kern = functools.partial(_kernel, k_group=k_group,
                             nsub=block_g // LANES, mode=table_quant)
    in_specs = [pl.BlockSpec((block_m, block_g * k_group), lambda i, k: (i, k))]
    args = [a]
    if table_quant == "per_row":
        assert row_scale is not None
        in_specs.append(pl.BlockSpec((block_m, 1), lambda i, k: (i, 0)))
        args.append(row_scale.astype(jnp.float32))
    out_specs = [pl.BlockSpec((block_m, block_g * e), lambda i, k: (i, k))]
    out_shape = [jax.ShapeDtypeStruct(
        (m, g * e), jnp.float32 if table_quant is None else jnp.int8)]
    if table_quant == "per_group":
        out_specs.append(pl.BlockSpec((block_m, block_g), lambda i, k: (i, k)))
        out_shape.append(jax.ShapeDtypeStruct((m, g), jnp.float32))
    outs = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="lut_table_precompute",
    )(*args)
    if table_quant == "per_group":
        return outs[0], outs[1]
    return outs[0], None
