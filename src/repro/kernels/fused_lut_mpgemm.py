"""Pallas TPU kernel: fused precompute→lookup mpGEMM (§3.1.1, fused form).

The staged pipeline materializes the ``[M, G·E]`` half-table in HBM between
``table_precompute_pallas`` and ``lut_mpgemm_pallas`` — the indirect,
traffic-bound pattern the paper's DFG analysis says LUT methods must avoid
once the table stops fitting on-chip.  This kernel is the fused alternative:
one ``pallas_call`` whose grid streams **activation** blocks HBM→VMEM
(``bm·bg·K`` elements — an E/K-times smaller footprint than the table
block), rebuilds each ``[bm, 128]`` half-table tile in-VMEM from the same
exact ±1 signed sums as the staged precompute, optionally quantizes it to
INT8 in-register (per-row and per-group modes, §3.1.3), and immediately
contracts it against the combined-lookup weights unpacked from the packed
weight stream.  The table never touches HBM.

Numerical contract (tests enforce it):

  * ``table_quant='per_row'`` — bit-exact with the staged composition: the
    entries are the same exact signed sums, the INT8 quantization uses the
    same wrapper-computed closed-form row scale, and accumulation is exact
    int32.
  * ``table_quant=None | 'per_group'`` — float accumulation in the same
    K-block order as the staged kernel (same ``bg``), so parity holds to
    float tolerance.

Layouts and the ragged-weight-block rule are those of lut_mpgemm.py.

Cost trade: the table is recomputed once per (j, k) grid step instead of
being read back N/bn times; the recompute is a few VPU adds per entry
(depth ``k_group`` ≤ 8) — cheap — while the avoided HBM traffic is the full
table (≥ table_bits/(8·k_group)·E× the activation bytes) per N-tile pass.
The LMMA scheduler (core/lmma.py: ``select_fusion``) picks fused whenever
the in-VMEM working set fits the budget.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lmma import VMEM_BYTES
from repro.core.packing import LANES
from repro.kernels.lut_mpgemm import chunk_layout, lookup_chunk
from repro.kernels.table_precompute import (for_each, group_scale,
                                            quantize_entries,
                                            sub_chunk_entries)

__all__ = ["fused_lut_mpgemm_pallas"]


def _kernel(a_ref, *refs, k_group: int, planes: int, plane_scales,
            nchunk: int, mode: Optional[str]):
    if mode == "per_row":
        rs_ref, pk_ref, ws_ref, o_ref, acc_ref = refs
    else:
        pk_ref, ws_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def table_tiles(j):
        parts, ents = sub_chunk_entries(a_ref, j, k_group)
        if mode == "per_row":
            return quantize_entries(ents, rs_ref[...])
        if mode == "per_group":
            # quantize→dequantize in-register: carries the §3.1.3 error,
            # matching the staged pipeline
            scale = group_scale(parts)
            return [q.astype(jnp.float32) * scale
                    for q in quantize_entries(ents, scale)]
        return ents

    def body(c):
        acc_ref[...] += lookup_chunk(
            c, pk_ref, table_tiles, k_group=k_group, planes=planes,
            plane_scales=plane_scales,
            dtype=jnp.int8 if mode == "per_row" else jnp.float32)

    for_each(nchunk, body)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        if mode == "per_row":
            o_ref[...] = (acc_ref[...].astype(jnp.float32)
                          * rs_ref[...] * ws_ref[...])
        else:
            o_ref[...] = acc_ref[...] * ws_ref[...]


def fused_lut_mpgemm_pallas(
    a: jax.Array,             # [M, Gt*K] activations, lane-group layout
    row_scale: Optional[jax.Array],  # [M, 1] f32 (per_row) | None
    packed: jax.Array,        # [N, Gs*B*k_group/8] uint8 (packing.py)
    wscale: jax.Array,        # [N] f32
    *,
    k_group: int,
    table_quant: Optional[str],
    planes: int,
    plane_scales: Sequence[float],
    block_m: int = 32,
    block_n: int = 256,
    block_g: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Launch the fused kernel -> [M, N] f32. Activations are pre-padded to
    whole blocks; the weights are not."""
    if table_quant not in (None, "per_row", "per_group"):
        raise ValueError(f"unknown table_quant mode {table_quant!r}")
    m, width = a.shape
    g = width // k_group
    n = packed.shape[0]
    nsub, ncol = chunk_layout(k_group, planes)
    cgroups = nsub * LANES
    assert m % block_m == 0 and g % block_g == 0 and block_g % cgroups == 0, (
        (m, g), (block_m, block_g))
    pb_blk = block_g // cgroups * ncol * LANES
    grid = (m // block_m, pl.cdiv(n, block_n), g // block_g)

    in_specs = [pl.BlockSpec((block_m, block_g * k_group),
                             lambda i, j, k: (i, k))]
    args = [a]
    if table_quant == "per_row":
        assert row_scale is not None, "per_row needs the wrapper's row scale"
        in_specs.append(pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)))
        args.append(row_scale.astype(jnp.float32))
    in_specs += [pl.BlockSpec((block_n, pb_blk), lambda i, j, k: (j, k)),
                 pl.BlockSpec((1, block_n), lambda i, j, k: (0, j))]
    args += [packed, wscale.reshape(1, n).astype(jnp.float32)]
    kern = functools.partial(
        _kernel, k_group=k_group, planes=planes,
        plane_scales=tuple(float(s) for s in plane_scales),
        nchunk=block_g // cgroups, mode=table_quant)
    acc_dtype = jnp.int32 if table_quant == "per_row" else jnp.float32
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="fused_lut_mpgemm",
    )(*args)
