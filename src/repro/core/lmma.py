"""LMMA instruction descriptors + memory-size-based tile scheduler (§3.3).

The paper extends MMA to ``lmma.{M}{N}{K}.{A}{W}{Acc}{O}``.  On TPU the
"instruction" becomes a *kernel schedule contract*: an ``LMMADescriptor``
names the tile shape and operand dtypes, and ``schedule_tiles`` picks
BlockSpec block shapes for the Pallas kernels the way §3.3.2 prescribes —
**tiling by memory size, not by shape**, because the A-side (table bytes) and
W-side (packed code bytes) of an mpGEMM tile have wildly different densities.

The scheduler objective mirrors Roller's rTile logic: choose the largest
(bm, bn, bg) whose working set fits the VMEM budget, with bn elongated
(table-reuse, §3.2.2). Every candidate obeys the TPU tiling rule on every
kernel operand (:func:`align_blocks`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.core.packing import LANES as LANE, chunk_groups
from repro.roofline import hw

__all__ = ["LMMADescriptor", "TileSchedule", "schedule_tiles", "lmma_name",
           "fused_tile_bytes", "select_fusion", "align_blocks", "VMEM_BYTES",
           "TILE_BUDGET", "lookup_vmem_limit"]

# the whole VMEM of the target chip (one TensorCore per v5e chip): the
# scoped-VMEM limit of every pallas_call but the lookup kernel's, which asks
# for what its blocks need (lookup_vmem_limit)
VMEM_BYTES = hw.spec(hw.TARGET_KIND).vmem_bytes
# what the scheduler lets the double-buffered blocks take; the other half is
# for in-kernel temporaries (unpacked fields, CW tiles) and compiler scratch
TILE_BUDGET = VMEM_BYTES // 2
# block rows: int8 operands (tables) tile (32, 128), the strictest of the
# kernels' dtypes, so 32 also satisfies f32 (8) and bf16 (16) operands
SUBLANE = 32


def round_up(x: int, mult: int) -> int:
    """Smallest multiple of ``mult`` that is >= max(x, 1)."""
    return -(-max(1, int(x)) // mult) * mult


def align_blocks(m: int, n: int, g: int, k_group: int, planes: int,
                 bm: int, bn: int, bg: int) -> Tuple[int, int, int]:
    """The TPU tiling rule for every kernel operand, as one block choice.

    Operand blocks (last two dims): activations ``(bm, bg·K)``, tables
    ``(bm, bg·E)`` int8/f32, row scale ``(bm, 1)``, group scale
    ``(bm, bg)``, packed weights ``(bn, bg·B·K/8)`` uint8, weight scale
    ``(1, bn)`` and output ``(bm, bn)`` f32. So bm is a multiple of 32 (int8
    rows), bn of 128 lanes, and bg of the packing chunk (a multiple of 128
    groups whose packed bytes fill whole 128-lane columns). Blocks round
    *up* — the wrappers pad the activation side instead of shrinking a block
    below the tile — and are clamped to the padded problem.
    """
    c = chunk_groups(k_group, planes)
    bm = min(round_up(bm, SUBLANE), round_up(m, SUBLANE))
    bn = min(round_up(bn, LANE), round_up(n, LANE))
    bg = min(round_up(bg, c), round_up(g, c))
    return bm, bn, bg


@dataclasses.dataclass(frozen=True)
class LMMADescriptor:
    """lmma.{M}{N}{K}.{A}{W}{Acc}{O} — operand shapes and dtypes."""

    m: int
    n: int
    k: int                      # contraction length (K_total)
    a_dtype: str = "bf16"       # fp16/bf16/fp8/int8 activations
    w_bits: int = 2             # INT1/2/4 weights (ternary -> 2 planes)
    acc_dtype: str = "f32"
    o_dtype: str = "bf16"
    k_group: int = 4
    table_bits: int = 8         # LUT_BIT after table quantization

    def name(self) -> str:
        return (f"lmma.m{self.m}n{self.n}k{self.k}."
                f"a{self.a_dtype}.w int{self.w_bits}".replace(" ", "") +
                f".acc{self.acc_dtype}.o{self.o_dtype}")


def lmma_name(desc: LMMADescriptor) -> str:
    return desc.name()


@dataclasses.dataclass(frozen=True)
class TileSchedule:
    bm: int
    bn: int
    bg: int  # groups per K-block (K elements = bg * k_group)
    table_bytes: int
    weight_bytes: int
    acc_bytes: int
    vmem_bytes: int

    @property
    def bk(self) -> int:
        return self.bg  # alias; K elements per block = bg * k_group


_DTYPE_BYTES = {"fp16": 2, "bf16": 2, "f32": 4, "fp8": 1, "int8": 1, "int32": 4}


def _tile_bytes(bm, bn, bg, desc: LMMADescriptor) -> Tuple[int, int, int]:
    e = 1 << (desc.k_group - 1)
    planes = desc.w_bits if desc.w_bits > 0 else 2
    table = bm * bg * e * (desc.table_bits // 8 or 1)          # Eq. 7
    weights = bn * bg * planes * desc.k_group // 8              # Eq. 8 packed
    cw = bn * bg * e                                            # int8 CW expansion
    acc = bm * bn * _DTYPE_BYTES[desc.acc_dtype]
    return table, weights + cw, acc


def lookup_vmem_limit(bm: int, bn: int, bg: int, k_group: int, planes: int,
                      entry_bytes: int = 1) -> int:
    """Scoped VMEM to ask for one lookup-kernel call: twice the scheduler's
    working set of its blocks (double-buffered table and code blocks, the
    CW expansion, the accumulator), so the in-kernel temporaries fit too;
    at most the chip's VMEM. ``entry_bytes`` is the size of the table and
    CW entries the kernel contracts: 1 on its int8 path, 4 where it works
    in f32 (float or per-group tables).

    A Pallas call holds its whole limit while it runs. Asking for all of
    VMEM left none to the XLA program around the kernel: the decode layer
    scan then moved each layer's KV cache slice out to HBM and back."""
    desc = LMMADescriptor(m=bm, n=bn, k=bg * k_group, w_bits=planes,
                          k_group=k_group, table_bits=8 * entry_bytes)
    t, w, a = _tile_bytes(bm, bn, bg, desc)
    w += (entry_bytes - 1) * bn * bg * (1 << (k_group - 1))
    return min(VMEM_BYTES, 2 * (2 * (t + w) + a))


def schedule_tiles(desc: LMMADescriptor,
                   vmem_budget: int = TILE_BUDGET,
                   elongate: bool = True) -> TileSchedule:
    """Pick (bm, bn, bg) by memory size (§3.3.2) with elongated N (§3.2.2)."""
    g_total = desc.k // desc.k_group
    planes = desc.w_bits if desc.w_bits > 0 else 2
    c = chunk_groups(desc.k_group, planes)
    best: Optional[TileSchedule] = None
    bm_cands = [m for m in (32, 64, 128, 256)
                if m <= round_up(desc.m, SUBLANE)]
    bn_cands = [n for n in (128, 256, 512, 1024, 2048)
                if n <= round_up(desc.n, LANE)]
    bg_cands = [c * f for f in (1, 2, 4, 8) if c * f <= round_up(g_total, c)]
    for bm in bm_cands:
        for bn in bn_cands:
            for bg in bg_cands:
                t, w, a = _tile_bytes(bm, bn, bg, desc)
                tot = 2 * (t + w) + a  # double-buffered inputs
                if tot > vmem_budget:
                    continue
                cand = TileSchedule(bm, bn, bg, t, w, a, tot)
                # score: MACs per byte moved (table reuse over bn — the
                # elongation pressure, §3.2.2), tie-broken toward larger bn.
                if best is None or _score(cand, desc, elongate) > _score(best, desc, elongate):
                    best = cand
    if best is None:
        t, w, a = _tile_bytes(SUBLANE, LANE, c, desc)
        best = TileSchedule(SUBLANE, LANE, c, t, w, a, 2 * (t + w) + a)
    return best


def fused_tile_bytes(bm: int, bn: int, bg: int, desc: LMMADescriptor) -> int:
    """Per-grid-step VMEM working set of the fused precompute→lookup kernel.

    Unlike the staged kernel (whose A-side input is the HBM-resident table
    block), the fused kernel streams the raw activation block and rebuilds
    the table in-VMEM, so its working set carries BOTH the activation block
    and the recomputed [bm, bg·E] table block (f32 entries plus the int8
    quantized copy), alongside the usual packed-weight / CW / accumulator
    terms.
    """
    e = 1 << (desc.k_group - 1)
    planes = desc.w_bits if desc.w_bits > 0 else 2
    a_blk = bm * bg * desc.k_group * _DTYPE_BYTES[desc.a_dtype]
    ent_f32 = bm * bg * e * 4                       # basis-contraction result
    tbl_q = bm * bg * e * (desc.table_bits // 8 or 1)
    weights = bn * bg * planes * desc.k_group // 8
    cw = bn * bg * e
    acc = bm * bn * _DTYPE_BYTES[desc.acc_dtype]
    return 2 * (a_blk + weights) + ent_f32 + tbl_q + cw + acc


def select_fusion(desc: LMMADescriptor,
                  ts: Optional[TileSchedule] = None,
                  vmem_budget: int = TILE_BUDGET) -> str:
    """§3.1.1 fusion decision: 'fused' iff the table block fits VMEM.

    The fused kernel never writes the [M, G·E] table to HBM, but pays an
    in-VMEM recompute per (N-tile, K-block) step; it is profitable exactly
    when its enlarged working set still fits the VMEM budget — which it does
    for every tile the memory-size scheduler emits, EXCEPT when callers pin
    oversized (bm, bg) by hand. Returns "fused" or "staged".
    """
    if ts is None:
        ts = schedule_tiles(desc)
    fusion = ("fused"
              if fused_tile_bytes(ts.bm, ts.bn, ts.bg, desc) <= vmem_budget
              else "staged")
    # trace-time dispatch profiling hook (no-op unless a recorder is active)
    from repro.obs import dispatch as dispatch_obs
    dispatch_obs.record("select_fusion", desc.name(), fusion, "auto",
                        "heuristic", (ts.bm, ts.bn, ts.bg))
    return fusion


def _score(ts: TileSchedule, desc: LMMADescriptor, elongate: bool) -> float:
    e = 1 << (desc.k_group - 1)
    g_total = desc.k / desc.k_group
    macs = ts.bm * ts.bn * ts.bg * e
    score = macs / (ts.table_bytes + ts.weight_bytes
                    + ts.acc_bytes / max(1, (g_total // ts.bg)))
    if elongate:
        score *= (1.0 + 0.1 * (ts.bn / 2048))
    return score
