"""Pure-jnp oracles for every Pallas kernel.

Three semantically-equivalent mpGEMM formulations (they must all agree to
float tolerance; tests enforce this):

  * ``ref_dequant_mpgemm``      — A @ dequantize(W).T, the paper's baseline.
  * ``ref_lut_mpgemm_gather``   — the *literal* paper mechanism: per K-group
    table lookup by folded index with MSB sign (Eq. 5-6), bit-serial over
    planes. O(M·G·B·N) gathers — the semantic ground truth.
  * ``ref_lut_mpgemm_matmul``   — the TPU-native reformulation: one GEMM
    ``T[M, G·E] @ CW[G·E, N]`` where CW folds one-hot lookup, per-plane
    2^b scales and the Eq.-6 sign into a static int8 matrix (DESIGN.md §2).

Also: ``ref_table_precompute`` (re-export of the core operator) and
``build_cw`` (the CW expansion used by both the XLA path and the kernel).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import table as table_mod
from repro.core.quantize import QuantizedWeight, dequantize
from repro.core.table import Table, precompute_table

__all__ = [
    "ref_table_precompute",
    "ref_dequant_mpgemm",
    "ref_lut_mpgemm_gather",
    "ref_lut_mpgemm_matmul",
    "build_cw",
    "zero_point_correction",
]

ref_table_precompute = precompute_table


def zero_point_correction(out, qw: QuantizedWeight, rowsum):
    """out[m,n] -= rowsum[m] * scale[n] * z'[n]  (no-op for symmetric)."""
    if qw.zero_prime is None:
        return out
    return out - jnp.outer(rowsum, qw.scale * qw.zero_prime)


def ref_dequant_mpgemm(a, qw: QuantizedWeight, out_dtype=jnp.float32):
    w = dequantize(qw)  # [N, K]
    return jnp.dot(a.astype(jnp.float32), w.T).astype(out_dtype)


def _lookup_plane(tvals, sign, idx):
    """tvals [M,G,E] f32, sign/idx [N,G] -> [M,G,N] looked-up (±)entries."""
    # gather along E with (n, g)-dependent index; ground-truth only (O(MGN)).
    gathered = jnp.take_along_axis(
        tvals[:, :, None, :],  # [M, G, 1, E]
        idx.T[None, :, :, None].astype(jnp.int32),  # [1, G, N, 1]
        axis=-1,
    )[..., 0]  # [M, G, N]
    s = 1.0 - 2.0 * sign.T[None].astype(jnp.float32)  # [1, G, N]
    return gathered * s


def ref_lut_mpgemm_gather(a, qw: QuantizedWeight,
                          table_quant: Optional[str] = None,
                          out_dtype=jnp.float32):
    """Literal per-group lookup, bit-serial over planes (paper Fig. 3/8)."""
    t = precompute_table(a, qw.k_group, table_quant)
    tvals = table_mod.dequantize_table(t)  # [M, G, E] f32
    sign, idx = qw.sign_idx()  # [N, G, B]
    acc = jnp.zeros((a.shape[0], qw.n), jnp.float32)
    ps = jnp.asarray(qw.plane_scales, jnp.float32)
    for b in range(qw.num_planes):  # bit-serial
        lk = _lookup_plane(tvals, sign[:, :, b], idx[:, :, b])  # [M,G,N]
        acc = acc + ps[b] * jnp.sum(lk, axis=1)
    out = acc * qw.scale[None, :]
    out = zero_point_correction(out, qw, t.rowsum)
    return out.astype(out_dtype)


def build_cw(qw: QuantizedWeight, dtype=jnp.int8):
    """Static combined-lookup weights CW [G*E, N].

    CW[(g,e), n] = Σ_b plane_scales[b] · (1-2·sign[n,g,b]) · [idx[n,g,b]==e].
    Integer plane scales (≤ Σ 2^b = 2^B-1 ≤ 15 for B≤4) keep CW exactly
    representable in int8 — this is what unlocks the int8 MXU path. It is
    built in int8 plane by plane: an int32 or one-hot [N, G, B, E]
    intermediate would be 4-16x the size of CW itself (12.8 GiB for
    qwen2-72b's LM head).
    """
    with jax.named_scope("cw"):
        if qw.cw is not None:
            return qw.cw.astype(dtype)
        sign, idx = qw.sign_idx()  # [N, G, B]
        e = 1 << (qw.k_group - 1)
        ent = jnp.arange(e, dtype=idx.dtype)
        cw = None
        for b, ps in enumerate(qw.plane_scales):
            coef = (int(ps) * (1 - 2 * sign[..., b].astype(jnp.int8))).astype(
                jnp.int8)
            term = jnp.where(idx[..., b, None] == ent, coef[..., None],
                             jnp.int8(0))  # [N, G, E]
            cw = term if cw is None else cw + term
        n, g = qw.n, qw.g
        return jnp.transpose(cw, (1, 2, 0)).reshape(g * e, n).astype(dtype)


def ref_lut_mpgemm_matmul(a, qw: QuantizedWeight,
                          table_quant: Optional[str] = None,
                          table: Optional[Table] = None,
                          out_dtype=jnp.float32):
    """T @ CW single-GEMM formulation (accepts a precomputed/fused table)."""
    t = table
    if t is None:
        with jax.named_scope("table"):
            t = precompute_table(a, qw.k_group, table_quant)
    m = a.shape[0]
    e = 1 << (qw.k_group - 1)
    if t.scale is None:
        tv = t.values.reshape(m, -1)
        cw = build_cw(qw, jnp.float32)
        acc = jnp.dot(tv, cw)
    elif t.scale.shape[1] == 1:  # per_row: single int GEMM then row scale
        tv = t.values.reshape(m, -1)
        cw = build_cw(qw, jnp.int8)
        acc = jax.lax.dot_general(
            tv, cw, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32) * t.scale[:, 0, 0][:, None]
    else:  # per_group: dequantize table entries, f32 GEMM
        tv = (t.values.astype(jnp.float32) * t.scale).reshape(m, -1)
        cw = build_cw(qw, jnp.float32)
        acc = jnp.dot(tv, cw)
    out = acc * qw.scale[None, :]
    out = zero_point_correction(out, qw, t.rowsum)
    return out.astype(out_dtype)
