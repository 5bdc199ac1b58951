"""Jit'd wrappers around the Pallas kernels.

Handle padding to block multiples, table precompute (fused or supplied),
per-row scale closed-form computation, zero-point correction (rank-1 update
outside the kernel), and block-shape selection via the LMMA tile scheduler.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core import table as table_mod
from repro.obs import dispatch as dispatch_obs
from repro.core.lmma import (SUBLANE, LMMADescriptor, TileSchedule,
                             align_blocks, round_up, schedule_tiles,
                             select_fusion)
from repro.core.packing import LANES
from repro.core.quantize import QuantizedWeight
from repro.core.table import Table
from repro.kernels import ref
from repro.kernels.dequant_mpgemm import dequant_mpgemm_pallas
from repro.kernels.fused_lut_mpgemm import fused_lut_mpgemm_pallas
from repro.kernels.lut_mpgemm import lut_mpgemm_pallas
from repro.kernels.table_precompute import table_precompute_pallas

from repro.core.mpgemm import FUSION_MODES

__all__ = ["table_precompute", "lut_mpgemm", "fused_lut_mpgemm",
           "dequant_mpgemm", "pick_blocks", "auto_fusion", "resolve_dispatch",
           "plan_local_shape", "FUSION_MODES"]


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pick_blocks(m, n, g, k_group, planes, max_bm=256, max_bg=512):
    """Block shapes: scheduler-elongated, capped, tile-aligned. bn is the
    scheduler's, uncapped (up to 2048): on a v5e the lookup kernel at
    (32, 2048, 128) was within 4.4% of the fastest block at every
    projection shape of paper-bitnet-3b and qwen2-72b, at 8 and 32 rows,
    where a cap of 512 was up to 14.5% slower (PERF.md)."""
    desc = LMMADescriptor(m=m, n=n, k=g * k_group, w_bits=planes, k_group=k_group)
    ts = schedule_tiles(desc)
    return align_blocks(m, n, g, k_group, planes, min(ts.bm, max_bm),
                        ts.bn, min(ts.bg, max_bg))


def _closed_form_row_scale(a: jax.Array, g: int, k_group: int) -> jax.Array:
    """[M, 1] per-row INT8 table scale from A alone (table.group_absmax).

    Shared by the staged precompute wrapper and the fused kernel wrapper so
    both paths quantize with the bit-identical scale.
    """
    m = a.shape[0]
    am = table_mod.group_absmax(a.astype(jnp.float32).reshape(m, g, k_group))
    return (jnp.maximum(jnp.max(am, axis=-1), 1e-30) / 127.0)[:, None]


def _clamp_blocks(m, n, g, k_group, planes, block_m, block_n, block_g):
    """Block shapes for one call: the caller's where given, the scheduler's
    otherwise, then rounded up to the tiling rule (lmma.align_blocks) and
    clamped to the padded problem. Shared by every mpGEMM wrapper."""
    if block_m is None or block_n is None or block_g is None:
        bm, bn, bg = pick_blocks(m, n, g, k_group, planes)
    else:
        bm = bn = bg = None  # all supplied; skip the scheduler search
    return align_blocks(m, n, g, k_group, planes, block_m or bm,
                        block_n or bn, block_g or bg)


def auto_fusion(m, n, g, k_group, planes,
                block_m=None, block_n=None, block_g=None) -> str:
    """Resolve ``fusion="auto"`` for one mpGEMM shape: clamp blocks exactly
    the way the wrappers do, then ask the LMMA scheduler whether the fused
    working set fits VMEM. The single source of truth for the auto decision
    — models.layers.resolve_fusion delegates here.
    """
    bm, bn, bg = _clamp_blocks(m, n, g, k_group, planes,
                               block_m, block_n, block_g)
    desc = LMMADescriptor(m=m, n=n, k=g * k_group, w_bits=planes,
                          k_group=k_group)
    return select_fusion(desc, TileSchedule(bm, bn, bg, 0, 0, 0, 0))


def plan_local_shape(m, n):
    """Per-shard (m, n) under the active AxisPlan (trace-time).

    Under tensor-parallel decode the arrays reaching a wrapper are GLOBAL
    (pjit partitions them later), but each device only computes its
    [m/dp, n/mp] tile of a column-parallel projection — block shapes and
    tuned-cache keys must describe that local tile, or the tuner measures
    (and the dispatcher blocks for) work mp·dp times the size any single
    device ever runs. Dims that do not divide stay global, matching the
    replicate fallback in distributed.sharding.resolve_physical_spec.
    """
    from repro.distributed.sharding import current_plan
    plan = current_plan()
    if plan is None:
        return m, n
    dp = plan.axis_size("batch")
    mp = plan.axis_size("model")
    if dp > 1 and m % dp == 0:
        m //= dp
    if mp > 1 and n % mp == 0:
        n //= mp
    return m, n


def resolve_dispatch(m, n, g, k_group, planes, *, fusion="auto",
                     block_m=None, block_n=None, block_g=None,
                     table_quant: Optional[str] = "per_row",
                     requested: Optional[str] = None):
    """Trace-time dispatch decision for one mpGEMM shape.

    Returns the fully-resolved ``(fusion, bm, bn, bg)`` the wrappers will
    run — the single source of truth shared by ``lut_mpgemm`` and the
    round-trip tests. Under an active AxisPlan the decision is made on the
    PER-SHARD local tile (``plan_local_shape``), and the tuned-cache key is
    the local shape — what each device actually executes. Policies:

      * ``"tuned"``  — consult the active autotune cache (core.autotune);
        a hit supplies the measured fusion and fills any block knob the
        caller left unset (caller-pinned blocks always win); a miss — no
        active cache, shape never tuned, or the entry failed sanitation —
        degrades to ``"auto"``.
      * ``"auto"``   — clamp blocks, then the LMMA VMEM-fit heuristic.
      * ``"fused"``/``"staged"`` — forced, blocks clamped as usual.

    ``requested`` names the caller's policy in the dispatch recorder
    (default: ``fusion`` as asked); lut_xla's lookup kernel logs
    ``"lut_xla"``.
    """
    m, n = plan_local_shape(m, n)
    requested = requested or fusion
    source = "forced"
    if fusion == "tuned":
        tc = autotune.lookup_tuned(m, n, g, k_group, planes,
                                   table_quant=table_quant)
        if tc is not None:
            source = "tuned"
            fusion = tc.fusion
            block_m = block_m or tc.block_m
            block_n = block_n or tc.block_n
            block_g = block_g or tc.block_g
        else:
            fusion = "auto"
    bm, bn, bg = _clamp_blocks(m, n, g, k_group, planes,
                               block_m, block_n, block_g)
    if fusion == "auto":
        source = "heuristic"
        fusion = auto_fusion(m, n, g, k_group, planes, bm, bn, bg)
    # trace-time dispatch profiling (obs.dispatch): a no-op unless a
    # recorder is active — a serve run can dump exactly which kernel
    # configs its compiled programs contain
    dispatch_obs.record(
        "dispatch",
        autotune.shape_key(m, n, g, k_group, planes,
                           table_quant=table_quant),
        fusion, requested, source, (bm, bn, bg))
    return fusion, bm, bn, bg


def _check_not_plane_sliced(qw: QuantizedWeight, opname: str):
    """The Pallas kernels unpack the byte stream in-kernel with
    ``num_planes`` as the per-group field stride — a plane-sliced draft view
    (stored_planes != num_planes) would decode the wrong bytes. Sliced views
    run through lut_xla / dequant modes, which go via ``sign_idx()``."""
    if getattr(qw, "is_plane_sliced", False):
        raise NotImplementedError(
            f"{opname}: plane-sliced QuantizedWeight views (planes "
            f"[{qw.plane_start}:{qw.plane_start + qw.num_planes}] of "
            f"{qw.stored_planes} stored) are not supported by the Pallas "
            f"kernels; use mode='lut_xla' or 'dequant' for the draft view")


def _padded_row_scale(a: jax.Array, g: int, k_group: int, bm: int):
    rs = _pad_to(_closed_form_row_scale(a, g, k_group), bm, 0)
    return jnp.where(rs == 0, 1.0, rs)  # padded rows get an inert scale


def _lane_groups(x: jax.Array, gt: int, k_group: int) -> jax.Array:
    """[M, K] -> [M, gt·K] kernel activation layout: column
    ``(j·K + i)·128 + l`` holds position i of group ``j·128 + l`` (groups
    zero-padded to gt, a multiple of 128). Zero groups make zero table
    entries, so padding is inert."""
    m = x.shape[0]
    x = jnp.pad(x, ((0, 0), (0, gt * k_group - x.shape[1])))
    x = jnp.swapaxes(x.reshape(m, gt // LANES, LANES, k_group), 2, 3)
    return x.reshape(m, gt * k_group)


def _table_to_kernel_layout(values: jax.Array, gt: int) -> jax.Array:
    """Logical [M, G, E] table -> kernel layout [M, gt·E] (entry-major per
    128-group lane vector, see table_precompute.py)."""
    m, g, e = values.shape
    v = jnp.pad(values, ((0, 0), (0, gt - g), (0, 0)))
    return jnp.swapaxes(v.reshape(m, gt // LANES, LANES, e), 2, 3).reshape(
        m, gt * e)


def _table_from_kernel_layout(values: jax.Array, g: int, e: int) -> jax.Array:
    m = values.shape[0]
    v = values.reshape(m, -1, e, LANES)
    return jnp.swapaxes(v, 2, 3).reshape(m, -1, e)[:, :g]


def _precompute(x: jax.Array, k_group: int, table_quant: Optional[str],
                bm: int, bg: int, interpret: bool):
    """Staged precompute in the kernel layout, rows padded to bm and groups
    to a multiple of bg -> (values, scale): scale is the [Mp, 1] row scale
    (per_row), the [Mp, Gt] group scale (per_group) or None."""
    g = x.shape[1] // k_group
    xl = _lane_groups(_pad_to(x, bm, 0), round_up(g, bg), k_group)
    row_scale = None
    if table_quant == "per_row":
        row_scale = _padded_row_scale(x, g, k_group, bm)
    values, gscale = table_precompute_pallas(
        xl, k_group, table_quant, row_scale,
        block_m=bm, block_g=bg, interpret=interpret)
    return values, (row_scale if table_quant == "per_row" else gscale)


def table_precompute(a: jax.Array, k_group: int = 4,
                     table_quant: Optional[str] = "per_row",
                     *, block_m: int = 64, block_g: Optional[int] = None,
                     interpret: bool = False) -> Table:
    """Pallas-backed independent precompute operator (§3.1.1)."""
    m, k_total = a.shape
    g = k_total // k_group
    e = 1 << (k_group - 1)
    bm = min(round_up(block_m, SUBLANE), round_up(m, SUBLANE))
    bg = min(round_up(block_g or 512, LANES), round_up(g, LANES))
    values, scale = _precompute(a, k_group, table_quant, bm, bg, interpret)
    values = _table_from_kernel_layout(values[:m], g, e)
    rowsum = jnp.sum(a.astype(jnp.float32), axis=-1)
    if table_quant is None:
        return Table(values, None, rowsum, k_group)
    if table_quant == "per_row":
        return Table(values, scale[:m].reshape(m, 1, 1), rowsum, k_group)
    return Table(values, scale[:m, :g].reshape(m, g, 1), rowsum, k_group)


def fused_lut_mpgemm(x: jax.Array, qw: QuantizedWeight, *,
                     table_quant: Optional[str] = "per_row",
                     block_m: Optional[int] = None,
                     block_n: Optional[int] = None,
                     block_g: Optional[int] = None,
                     interpret: bool = False) -> jax.Array:
    """Single-kernel precompute→lookup mpGEMM: the table never leaves VMEM.

    Streams activation blocks, rebuilds each [bm, 128] table tile in-VMEM
    (quantizing in-register for per_row/per_group), and contracts it
    immediately against CW — the fused DFG of §3.1.1. Bit-exact with the
    staged ``table_precompute`` + ``lut_mpgemm`` composition on the per_row
    int8 path, float-tolerance-equal otherwise.
    """
    _check_not_plane_sliced(qw, "fused_lut_mpgemm")
    m = x.shape[0]
    g = qw.g
    planes = qw.num_planes
    bm, bn, bg = _clamp_blocks(m, qw.n, g, qw.k_group, planes,
                               block_m, block_n, block_g)
    rowsum = jnp.sum(x.astype(jnp.float32), axis=-1)
    row_scale = None
    if table_quant == "per_row":
        row_scale = _padded_row_scale(x, g, qw.k_group, bm)
    xl = _lane_groups(_pad_to(x, bm, 0), round_up(g, bg), qw.k_group)
    out = fused_lut_mpgemm_pallas(
        xl, row_scale, qw.packed, qw.scale, k_group=qw.k_group,
        table_quant=table_quant, planes=planes,
        plane_scales=qw.plane_scales,
        block_m=bm, block_n=bn, block_g=bg, interpret=interpret)
    return ref.zero_point_correction(out[:m], qw, rowsum)


def lut_mpgemm(x: jax.Array, qw: QuantizedWeight, *,
               table_quant: Optional[str] = "per_row",
               table: Optional[Table] = None,
               fusion: str = "auto",
               block_m: Optional[int] = None, block_n: Optional[int] = None,
               block_g: Optional[int] = None,
               requested: Optional[str] = None,
               interpret: bool = False) -> jax.Array:
    """LUT mpGEMM via the Pallas kernels.

    ``fusion`` selects the pipeline: "fused" runs the single-kernel
    precompute→lookup datapath (table stays in VMEM); "staged" runs
    ``table_precompute_pallas`` then ``lut_mpgemm_pallas`` with the table
    round-tripping through HBM; "auto" defers to the LMMA scheduler
    (``core.lmma.select_fusion``), which picks fused whenever the fused
    working set fits the VMEM budget; "tuned" consults the persistent
    measured-time autotune cache (``core.autotune``) and falls back to
    "auto" on a miss. A caller-supplied ``table=`` (the cross-consumer
    amortization of §3.1.1) always implies staged — the table already
    exists. ``requested`` is the policy the dispatch recorder logs
    (``resolve_dispatch``).
    """
    if fusion not in FUSION_MODES:
        raise ValueError(f"fusion {fusion!r} not in {FUSION_MODES}")
    _check_not_plane_sliced(qw, "lut_mpgemm")
    m = x.shape[0]
    g = qw.g
    planes = qw.num_planes
    fusion, bm, bn, bg = resolve_dispatch(
        m, qw.n, g, qw.k_group, planes, fusion=fusion, block_m=block_m,
        block_n=block_n, block_g=block_g, table_quant=table_quant,
        requested=requested)
    if table is None and fusion == "fused":
        return fused_lut_mpgemm(
            x, qw, table_quant=table_quant, block_m=bm, block_n=bn,
            block_g=bg, interpret=interpret)
    if table is None:
        with jax.named_scope("table"):
            tv, ts = _precompute(x, qw.k_group, table_quant, bm, bg,
                                 interpret)
            rowsum = jnp.sum(x.astype(jnp.float32), axis=-1)
    else:
        gt = round_up(g, bg)
        tv = _pad_to(_table_to_kernel_layout(table.values, gt), bm, 0)
        ts = None
        if table.scale is not None:
            ts = table.scale.reshape(m, -1)
            if ts.shape[1] != 1:  # per_group
                ts = jnp.pad(ts, ((0, 0), (0, gt - g)))
            ts = jnp.pad(ts, ((0, tv.shape[0] - m), (0, 0)),
                         constant_values=1.0)
        rowsum = table.rowsum
    out = lut_mpgemm_pallas(
        tv, ts, qw.packed, qw.scale, k_group=qw.k_group, planes=planes,
        plane_scales=qw.plane_scales,
        block_m=bm, block_n=bn, block_g=bg, interpret=interpret)
    return ref.zero_point_correction(out[:m], qw, rowsum)


def dequant_mpgemm(x: jax.Array, qw: QuantizedWeight, *,
                   block_m: int = 64, block_n: int = 256, block_g: int = 64,
                   interpret: bool = False) -> jax.Array:
    _check_not_plane_sliced(qw, "dequant_mpgemm")
    m = x.shape[0]
    g = qw.g
    bm, bn, bg = align_blocks(m, qw.n, g, qw.k_group, qw.num_planes,
                              block_m, block_n, block_g)
    xl = _lane_groups(_pad_to(x, bm, 0), round_up(g, bg), qw.k_group)
    out = dequant_mpgemm_pallas(
        xl, qw.packed, qw.scale, k_group=qw.k_group, planes=qw.num_planes,
        plane_scales=qw.plane_scales, block_m=bm, block_n=bn, block_g=bg,
        interpret=interpret)[:m]
    if qw.zero_prime is not None:
        rowsum = jnp.sum(x.astype(jnp.float32), axis=-1)
        out = ref.zero_point_correction(out, qw, rowsum)
    return out
