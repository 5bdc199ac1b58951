"""Pallas TPU kernel: LUT-based mpGEMM (the LUT Tensor Core datapath).

Realizes the paper's LUT array (§3.2) on the TPU memory hierarchy:

  * the per-(row, group) half-table lives in **VMEM** (the analogue of the
    paper's table registers), streamed in [bm, bg·E] blocks;
  * packed B-bit weight codes stream from HBM in their true packed form —
    ``bg·B·k_group/8`` bytes per N-row per K-block — this is the 4–16×
    weight-traffic reduction the co-design banks on;
  * the lookup itself runs on the **MXU**: the packed codes of each
    128-group lane vector are expanded in-VMEM to the combined-lookup
    weights ``CW_e`` (one ``[bn, 128]`` tile per table entry e: one-hot ×
    plane scales × Eq.-6 sign, values in [-15, 15] ⇒ int8) and contracted
    against the matching ``[bm, 128]`` table tile.  With int8 tables (table
    quantization, §3.1.3) the MXU runs at its 2× int8 rate;
  * bit-serial (§3.2.1) is folded into CW: all B planes of a group share
    the table and collapse into one int8 coefficient per entry;
  * the elongated tiling (§3.2.2) appears as bn ≫ bm block shapes chosen
    by the LMMA tile scheduler (lmma.schedule_tiles).

Everything in the kernel body is a 2-D ``[rows, 128]`` tile op — shifts,
masks, compares, selects and ``dot_general`` — because Mosaic cannot lower
reshapes that split the lane dim. The packed format (core/packing.py) and
the table layout (table_precompute.py) exist to make that possible.

Grid: (M/bm, N/bn, G/bg), K innermost with VMEM scratch accumulation. The
weight operands are never padded: a ragged last N or K block reads
undefined bytes, which meet zero table entries (K) or masked output rows
(N). Variants: int path (per-row-quantized int8 tables, int32 accumulate)
and f32 path (float tables, or per-group scales dequantized in-VMEM).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lmma import lookup_vmem_limit
from repro.core.packing import LANES, chunk_groups
from repro.kernels.table_precompute import for_each, lane_tile

__all__ = ["lut_mpgemm_pallas"]


def chunk_layout(k_group: int, planes: int):
    """(sub-chunks, byte columns) of one packing chunk (packing.py)."""
    c = chunk_groups(k_group, planes)
    return c // LANES, c * planes * k_group // (8 * LANES)


def chunk_fields(pk_ref, c, *, k_group: int, planes: int):
    """Fields of packing chunk c of a packed block: per lane sub-chunk, one
    ``[bn, 128]`` int32 tile per plane (slot s = sub*B + b lives in byte
    column s // F at bit offset k_group*(s % F))."""
    nsub, ncol = chunk_layout(k_group, planes)
    fpb = 8 // k_group
    mask = (1 << k_group) - 1
    cols = [lane_tile(pk_ref, (c * ncol + q) * LANES).astype(jnp.int32)
            for q in range(ncol)]
    out = []
    for js in range(nsub):
        fs = []
        for b in range(planes):
            s = js * planes + b
            x = cols[s // fpb]
            sh = k_group * (s % fpb)
            fs.append(((x >> sh) if sh else x) & mask)
        out.append(fs)
    return out


def cw_entries(fields, *, k_group: int, plane_scales, dtype):
    """CW_e = Σ_b ps_b·(1-2·sign_b)·[idx_b == e] for each entry e: E tiles
    ``[bn, 128]`` of one lane sub-chunk (bit-serial planes folded, §3.2.1)."""
    e_count = 1 << (k_group - 1)
    coef, idx = [], []
    for f, ps in zip(fields, plane_scales):
        coef.append(int(ps) * (1 - 2 * (f >> (k_group - 1))))
        idx.append(f & (e_count - 1))
    out = []
    for e in range(e_count):
        cw = None
        for cb, ib in zip(coef, idx):
            term = jnp.where(ib == e, cb, 0)
            cw = term if cw is None else cw + term
        out.append(cw.astype(dtype))
    return out


def nt_dot(t, w):
    """[bm, 128] x [bn, 128]^T on the MXU: int8 -> exact int32, else f32 at
    full precision (CW entries and int8-dequantized tables are exact)."""
    if t.dtype == jnp.int8:
        return jax.lax.dot_general(t, w, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.int32)
    return jax.lax.dot_general(t, w, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def lookup_chunk(c, pk_ref, table_tiles, *, k_group: int, planes: int,
                 plane_scales, dtype):
    """Σ over chunk c's sub-chunks and entries of table_tiles(j)[e] · CW_e^T.

    ``table_tiles(j)`` returns the E ``[bm, 128]`` table tiles of block
    sub-chunk j, in ``dtype`` (int8 for the exact int path)."""
    nsub, _ = chunk_layout(k_group, planes)
    fields = chunk_fields(pk_ref, c, k_group=k_group, planes=planes)
    part = None
    for js in range(nsub):
        tiles = table_tiles(c * nsub + js)
        cws = cw_entries(fields[js], k_group=k_group,
                         plane_scales=plane_scales, dtype=dtype)
        for t, cw in zip(tiles, cws):
            d = nt_dot(t, cw)
            part = d if part is None else part + d
    return part


def _kernel(tv_ref, *refs, k_group: int, planes: int, plane_scales,
            nchunk: int, scale_mode: Optional[str]):
    """scale_mode "per_row": int8 tables, exact int32 accumulation over the
    K grid; "per_group": int8 tables dequantized in-VMEM; None: float."""
    if scale_mode is None:
        pk_ref, ws_ref, o_ref, acc_ref = refs
    else:
        ts_ref, pk_ref, ws_ref, o_ref, acc_ref = refs
    e = 1 << (k_group - 1)
    int_path = scale_mode == "per_row"
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def table_tiles(j):
        tiles = [lane_tile(tv_ref, (j * e + i) * LANES) for i in range(e)]
        if scale_mode == "per_group":
            gs = lane_tile(ts_ref, j * LANES)
            return [t.astype(jnp.float32) * gs for t in tiles]
        return tiles

    def body(c):
        acc_ref[...] += lookup_chunk(
            c, pk_ref, table_tiles, k_group=k_group, planes=planes,
            plane_scales=plane_scales,
            dtype=jnp.int8 if int_path else jnp.float32)

    for_each(nchunk, body)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        if int_path:  # per-row table scale x per-channel weight scale
            o_ref[...] = (acc_ref[...].astype(jnp.float32)
                          * ts_ref[...] * ws_ref[...])
        else:
            o_ref[...] = acc_ref[...] * ws_ref[...]


def lut_mpgemm_pallas(
    tv: jax.Array,            # [M, Gt*E] table values, kernel layout
    ts: Optional[jax.Array],  # [M, 1] per-row | [M, Gt] per-group | None
    packed: jax.Array,        # [N, Gs*B*k_group/8] uint8 (packing.py)
    wscale: jax.Array,        # [N] f32
    *,
    k_group: int,
    planes: int,
    plane_scales: Sequence[float],
    block_m: int = 32,
    block_n: int = 256,
    block_g: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Launch the LUT mpGEMM kernel -> [M, N] f32.

    The table is pre-padded to whole blocks (zero entries); the weights are
    not (see module docstring)."""
    m, ge = tv.shape
    e = 1 << (k_group - 1)
    g = ge // e
    n = packed.shape[0]
    nsub, ncol = chunk_layout(k_group, planes)
    cgroups = nsub * LANES
    assert m % block_m == 0 and g % block_g == 0 and block_g % cgroups == 0, (
        (m, g), (block_m, block_g))
    pb_blk = block_g // cgroups * ncol * LANES
    grid = (m // block_m, pl.cdiv(n, block_n), g // block_g)

    per_row = ts is not None and ts.shape[1] == 1 and tv.dtype == jnp.int8
    scale_mode = None if ts is None else ("per_row" if per_row
                                          else "per_group")
    if ts is not None and not per_row and ts.shape[1] != g:
        raise ValueError("float tables take no per-row scale; pre-scale "
                         "them in the wrapper")
    in_specs = [pl.BlockSpec((block_m, block_g * e), lambda i, j, k: (i, k))]
    args = [tv]
    if scale_mode == "per_row":
        in_specs.append(pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)))
        args.append(ts.astype(jnp.float32))
    elif scale_mode == "per_group":
        in_specs.append(pl.BlockSpec((block_m, block_g),
                                     lambda i, j, k: (i, k)))
        args.append(ts.astype(jnp.float32))
    in_specs += [
        pl.BlockSpec((block_n, pb_blk), lambda i, j, k: (j, k)),       # packed W
        pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),            # wscale
    ]
    args += [packed, wscale.reshape(1, n).astype(jnp.float32)]
    kern = functools.partial(
        _kernel, k_group=k_group, planes=planes,
        plane_scales=tuple(float(s) for s in plane_scales),
        nchunk=block_g // cgroups, scale_mode=scale_mode)
    acc_dtype = jnp.int32 if scale_mode == "per_row" else jnp.float32
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=lookup_vmem_limit(
                block_m, block_n, block_g, k_group, planes,
                1 if scale_mode == "per_row" else 4)),
        interpret=interpret,
        name="lut_mpgemm",
    )(*args)
