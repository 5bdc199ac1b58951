"""Pallas TPU kernel: dequantization-based mpGEMM (paper Fig. 2b baseline).

What a stock MAC datapath must do with low-bit weights: stream the packed
codes, *upcast them to the activation dtype in-core*, then run a dense GEMM.
Weight HBM traffic is identical to the LUT kernel (both stream the packed
B-bit format); the difference is on-chip: this kernel pays the unpack +
sign-reconstruct + int→float convert on the VPU and contracts A directly,
while the LUT kernel amortizes K-element groups through the table.

Shares the folded-storage format (Eq. 6): raw plane bits are recovered as
``bit_i = idx_i XOR sign`` for i < K-1 and ``bit_{K-1} = sign``. Layouts
(lane-group activations, packed chunks, ragged weight blocks) are those of
lut_mpgemm.py: weight position i of lane sub-chunk j is one ``[bn, 128]``
tile, contracted against activation tile ``(j, i)``.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lmma import VMEM_BYTES
from repro.core.packing import LANES
from repro.kernels.lut_mpgemm import chunk_fields, chunk_layout, nt_dot
from repro.kernels.table_precompute import for_each, lane_tile

__all__ = ["dequant_mpgemm_pallas"]


def _weights(fields, *, k_group: int, plane_scales):
    """One lane sub-chunk's fields -> K tiles [bn, 128] of q' (f32)."""
    lowmask = (1 << (k_group - 1)) - 1
    out = [None] * k_group
    for f, ps in zip(fields, plane_scales):
        sign = f >> (k_group - 1)
        idx = f & lowmask
        for i in range(k_group):
            bit = sign if i == k_group - 1 else ((idx >> i) & 1) ^ sign
            term = float(ps) * (2 * bit - 1).astype(jnp.float32)
            out[i] = term if out[i] is None else out[i] + term
    return out


def _kernel(a_ref, pk_ref, ws_ref, o_ref, acc_ref, *, k_group: int,
            planes: int, plane_scales, nchunk: int):
    k = pl.program_id(2)
    nsub, _ = chunk_layout(k_group, planes)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(c):
        fields = chunk_fields(pk_ref, c, k_group=k_group, planes=planes)
        part = None
        for js in range(nsub):
            j = c * nsub + js
            ws = _weights(fields[js], k_group=k_group,
                          plane_scales=plane_scales)
            for i, w in enumerate(ws):
                a = lane_tile(a_ref, (j * k_group + i) * LANES)
                d = nt_dot(a.astype(jnp.float32), w)
                part = d if part is None else part + d
        acc_ref[...] += part

    for_each(nchunk, body)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...] * ws_ref[...]


def dequant_mpgemm_pallas(
    a: jax.Array,            # [M, Gt*K] lane-group layout (pre-padded)
    packed: jax.Array,       # [N, Gs*B*k_group/8] uint8
    wscale: jax.Array,       # [N]
    *,
    k_group: int,
    planes: int,
    plane_scales: Sequence[float],
    block_m: int = 32,
    block_n: int = 256,
    block_g: int = 128,
    interpret: bool = False,
) -> jax.Array:
    m, width = a.shape
    g = width // k_group
    n = packed.shape[0]
    nsub, ncol = chunk_layout(k_group, planes)
    cgroups = nsub * LANES
    assert m % block_m == 0 and g % block_g == 0 and block_g % cgroups == 0
    pb_blk = block_g // cgroups * ncol * LANES
    grid = (m // block_m, pl.cdiv(n, block_n), g // block_g)
    kern = functools.partial(_kernel, k_group=k_group, planes=planes,
                             plane_scales=tuple(map(float, plane_scales)),
                             nchunk=block_g // cgroups)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_g * k_group), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, pb_blk), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="dequant_mpgemm",
    )(a, packed, wscale.reshape(1, n).astype(jnp.float32))
