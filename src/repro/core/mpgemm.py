"""Public mpGEMM API — the paper's contribution as a composable JAX op.

``mpgemm(x, qw, mode=...)`` multiplies high-precision activations with packed
low-bit weights.  Modes:

  * ``"dequant"``     — unpack→upcast→GEMM (paper Fig. 2b baseline; what a
                        stock accelerator must do).
  * ``"lut_xla"``     — LUT-based: DFG-split table precompute by XLA + one
                        ``T @ CW`` contraction (DESIGN.md §2); with
                        ``table_quant='per_row'`` it runs int8. Where CW
                        lives depends on what the call can observe:
                          - TPU, per-row int8 table, packed store, one
                            device: the lookup kernel over the packed planes
                            (``kernels.ops.lut_mpgemm(table=...)``) builds
                            CW only as VMEM tiles;
                          - CPU: the engine hoists CW once at load
                            (``qw.cw``), and the contraction reads it;
                          - a multi-device plan or a plane-sliced draft
                            view: CW is built by XLA from the planes.
                        The arithmetic is the same on every branch.
  * ``"lut_pallas"``  — the Pallas LUT Tensor Core kernel (kernels/); the
                        ``fusion`` knob picks the fused single-kernel
                        precompute→lookup pipeline (table stays in VMEM,
                        §3.1.1) vs the staged two-kernel one.
  * ``"fp16"``        — dense float GEMM on dequantized weights cached as a
                        regular array; reference/upper-precision path.

The DFG transformation (§3.1.1) is first-class: ``precompute_tables`` is an
independent operator whose result can be passed back via ``table=`` so the
framework (or XLA fusion) amortizes it across every consumer — e.g. Q/K/V
projections share one table of their common input.

``mpgemm`` handles arbitrary leading batch dims; the contraction is always
the last axis of ``x`` against ``qw.k_total``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .quantize import QuantizedWeight, dequantize
from .table import Table, precompute_table

__all__ = ["mpgemm", "precompute_tables", "resolve_table_quant",
           "MPGEMM_MODES", "FUSION_MODES"]

MPGEMM_MODES = ("fp16", "dequant", "lut_xla", "lut_pallas")
# lut_pallas precompute placement (owned here, next to the mode it modifies,
# so config/model validation never has to import the kernel stack):
# "auto" = LMMA VMEM heuristic, "tuned" = measured-time autotune cache
# (core.autotune; falls back to "auto" on a cache miss)
FUSION_MODES = ("auto", "fused", "staged", "tuned")


def resolve_table_quant(table_quant: Optional[str]) -> Optional[str]:
    """Map the ``"auto"`` table-precision knob to a concrete mode.

    Per-row INT8 tables are the paper's format — they feed an int8 MXU (or
    the LUT unit's int8 datapath) and halve table bytes. On backends
    without an int8 GEMM fast path (CPU emulation), quantizing the table
    costs extra ops AND accuracy, so ``"auto"`` resolves to float tables
    there. Explicit ``"per_row"``/``"per_group"``/``None`` pass through.
    """
    if table_quant == "auto":
        return "per_row" if jax.default_backend() == "tpu" else None
    return table_quant


def precompute_tables(x, k_group: int = 4, table_quant: Optional[str] = "per_row") -> Table:
    """Independent table-precompute operator (fuse me with your previous op).

    Traced under ``mpgemm/table``: a table shared by several consumers is
    still mpGEMM work."""
    table_quant = resolve_table_quant(table_quant)
    # the table stays flat [M, G, E]; mpgemm reshapes the output
    with jax.named_scope("mpgemm"), jax.named_scope("table"):
        return precompute_table(x.reshape(-1, x.shape[-1]), k_group,
                                table_quant)


def _packed_lookup(qw: QuantizedWeight, table_quant) -> bool:
    """Whether lut_xla's contraction runs as the lookup kernel over the
    packed planes: on the TPU, for the int8 per-row table (whose int32
    accumulation and epilogue the kernel reproduces bit for bit), against a
    packed store (not the CW store the CPU engine hoists, nor a plane-sliced
    view, which the kernel refuses), with no plan of several devices (a
    custom call there would make the partitioner gather the weights)."""
    if jax.default_backend() != "tpu" or table_quant != "per_row":
        return False
    if qw.cw is not None or qw.is_plane_sliced:
        return False
    from repro.distributed.sharding import current_plan
    plan = current_plan()
    return plan is None or plan.mesh.size == 1


def _lut_xla(x2d, qw: QuantizedWeight, table_quant, table: Optional[Table],
             interpret):
    from repro.kernels import ops, ref  # local import to avoid cycles

    if not _packed_lookup(qw, table_quant):
        return ref.ref_lut_mpgemm_matmul(x2d, qw, table_quant=table_quant,
                                         table=table)
    if table is None:
        with jax.named_scope("table"):
            table = precompute_table(x2d, qw.k_group, table_quant)
    return ops.lut_mpgemm(x2d, qw, table_quant=table_quant, table=table,
                          fusion="staged", requested="lut_xla",
                          interpret=bool(interpret))


def _lut_pallas(x2d, qw: QuantizedWeight, table_quant, table: Optional[Table],
                fusion, interpret):
    from repro.kernels import ops

    return ops.lut_mpgemm(x2d, qw, table_quant=table_quant, table=table,
                          fusion=fusion, interpret=interpret)


def mpgemm(
    x: jax.Array,
    qw: QuantizedWeight,
    *,
    mode: str = "lut_xla",
    table_quant: Optional[str] = "per_row",
    table: Optional[Table] = None,
    fusion: str = "auto",
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """y[..., n] = Σ_k x[..., k] · W[n, k] with W stored low-bit packed.

    ``fusion`` (lut_pallas only) picks the precompute placement: "fused"
    computes the table in-VMEM inside the mpGEMM kernel (never hits HBM),
    "staged" materializes it between two kernels, "auto" lets the LMMA tile
    scheduler decide from the VMEM budget, "tuned" uses the persistent
    measured-time autotune cache (auto on a miss). Ignored when ``table=``
    is supplied — a shared table is by definition staged. ``interpret``
    runs the Pallas kernels in interpret mode (default: off the TPU); on
    lut_xla it matters only where the lookup kernel runs.

    Every mode traces under the named scope ``mpgemm`` (sub-scopes ``table``
    and ``cw`` for the table precompute and the CW build), so the compiled
    program's op metadata says which ops are mpGEMM work.
    """
    if mode not in MPGEMM_MODES:
        raise ValueError(f"mode {mode!r} not in {MPGEMM_MODES}")
    table_quant = resolve_table_quant(table_quant)
    if x.shape[-1] != qw.k_total:
        raise ValueError(f"contract dim {x.shape[-1]} != k_total {qw.k_total}")
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    with jax.named_scope("mpgemm"):
        x2d = x.reshape(-1, qw.k_total)
        if mode == "fp16":
            w = dequantize(qw).astype(x.dtype)
            out = jnp.dot(x2d, w.T, preferred_element_type=jnp.float32)
        elif mode == "dequant":
            # Unpack + upcast happen *inside* the jitted graph: HLO parameter
            # bytes stay truly low-bit; the upcast is the baseline's cost.
            w = dequantize(qw).astype(jnp.bfloat16)
            out = jnp.dot(x2d.astype(jnp.bfloat16), w.T,
                          preferred_element_type=jnp.float32)
        elif mode == "lut_xla":
            out = _lut_xla(x2d, qw, table_quant, table, interpret)
        else:  # lut_pallas
            if interpret is None:
                interpret = jax.default_backend() != "tpu"
            out = _lut_pallas(x2d, qw, table_quant, table, fusion, interpret)
        return out.reshape(*lead, qw.n).astype(out_dtype)
