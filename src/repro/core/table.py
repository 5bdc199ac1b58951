"""Lookup-table precompute + symmetrization + table quantization (§3.1).

The half-table for a K-group of activations ``a_0..a_{K-1}`` stores, for every
entry ``e ∈ [0, 2^(K-1))``::

    T[e] = Σ_{i<K-1} a_i * (2*bit_i(e) - 1)  -  a_{K-1}

i.e. the MSB position is pinned to σ = -1 (entries with MSB=+1 are recovered
by oddness, Eq. 4-5).  Precompute is *split out as an independent operator*
(the paper's DFG transformation, §3.1.1) so callers can fuse it with the
preceding element-wise op and share one table across all N output channels.

Table quantization (§3.1.3) converts float entries to INT8 with a dynamic
scale, either per-table (``per_group``, the paper's hardware choice) or
per-activation-row (``per_row``, the TPU/XLA-friendly choice that lets the
whole lookup run as one int8 GEMM — see DESIGN.md §2).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["Table", "signed_entries", "precompute_table", "quantize_table",
           "table_entries"]


class Table(NamedTuple):
    """Precomputed (optionally quantized) lookup tables.

    values:  [M, G, E] float32 or int8, E = 2^(k_group-1)
    scale:   None (float tables) | [M, 1, 1] (per_row) | [M, G, 1] (per_group)
    rowsum:  [M] float32 — Σ_k a[m,k], used for the zero-point correction term
    k_group: group length K
    """

    values: jax.Array
    scale: Optional[jax.Array]
    rowsum: jax.Array
    k_group: int


def signed_entries(parts, k_group: int):
    """Half-table entries from the K group positions, one array per entry.

    ``parts[i]`` holds position i of every group (any common shape);
    returns ``[T[0], ..., T[E-1]]`` with ``T[e] = Σ_i σ_i(e)·parts[i]``
    summed in position order. The ±1 products are exact, so this fixed
    order makes the oracle and the Pallas kernels (which call it on
    ``[bm, 128]`` lane tiles) bit-identical — and keeps the table exact
    float32 on backends whose default matmul precision is lower.
    """
    out = []
    for e in range(1 << (k_group - 1)):
        t = None
        for i, a in enumerate(parts):
            neg = i == k_group - 1 or not (e >> i) & 1
            t = (-a if neg else a) if t is None else (t - a if neg else t + a)
        out.append(t)
    return out


def table_entries(a_groups: jax.Array, k_group: int) -> jax.Array:
    """[..., G, K] activations -> [..., G, E] half-table entries."""
    a = a_groups.astype(jnp.float32)
    parts = [a[..., i] for i in range(k_group)]
    return jnp.stack(signed_entries(parts, k_group), axis=-1)


def abs_sum(parts):
    """Σ_i |parts[i]| in position order (see :func:`group_absmax`)."""
    t = jnp.abs(parts[0])
    for a in parts[1:]:
        t = t + jnp.abs(a)
    return t


def group_absmax(a_groups: jax.Array) -> jax.Array:
    """Closed-form max_e |T[e]| = Σ_i |a_i| per group (oddness ⇒ achievable).

    Using this identity (instead of materializing entries and reducing over
    E) lets the per-row scale be computed from A *before* the table exists —
    the kernel and the oracle share it bit-exactly.
    """
    a = a_groups.astype(jnp.float32)
    return abs_sum([a[..., i] for i in range(a.shape[-1])])  # [..., G]


def precompute_table(
    a: jax.Array,
    k_group: int = 4,
    table_quant: Optional[str] = None,
) -> Table:
    """The independent precompute operator (DFG-transformed, §3.1.1).

    Args:
      a: activations [M, K_total], K_total divisible by k_group.
      table_quant: None | 'per_group' | 'per_row' — INT8 table quantization.
    """
    m, k_total = a.shape
    if k_total % k_group:
        raise ValueError(f"K_total={k_total} not divisible by k_group={k_group}")
    g = k_total // k_group
    af = a.astype(jnp.float32)
    rowsum = jnp.sum(af, axis=-1)
    a_groups = af.reshape(m, g, k_group)
    entries = table_entries(a_groups, k_group)
    if table_quant is None:
        return Table(entries, None, rowsum, k_group)
    absmax = group_absmax(a_groups)  # [M, G]
    return quantize_table(entries, rowsum, k_group, table_quant, absmax=absmax)


def quantize_table(
    entries: jax.Array, rowsum: jax.Array, k_group: int, mode: str,
    absmax: Optional[jax.Array] = None,
) -> Table:
    """INT8 table quantization (§3.1.3) with dynamic absmax scaling."""
    if absmax is None:
        absmax = jnp.max(jnp.abs(entries), axis=-1)  # [M, G]
    if mode == "per_group":
        absmax = absmax[..., None]  # [M,G,1]
    elif mode == "per_row":
        absmax = jnp.max(absmax, axis=-1)[:, None, None]  # [M,1,1]
    else:
        raise ValueError(f"unknown table_quant mode {mode!r}")
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(entries / scale), -127, 127).astype(jnp.int8)
    return Table(q, scale, rowsum, k_group)


def dequantize_table(t: Table) -> jax.Array:
    if t.scale is None:
        return t.values
    return t.values.astype(jnp.float32) * t.scale
