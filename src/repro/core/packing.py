"""Bit-level packing of folded group codes into dense uint8 streams.

Storage format ("packed group codes", PGC): for each output channel n, the
``k_group``-bit fields ``field(g, b) = sign<<(K-1) | idx`` (group g,
bit-plane b) are laid out in **128-group lane vectors**:

  * group ``g = j*128 + l`` sits in lane ``l`` of sub-chunk ``j``;
  * the fields of sub-chunk j are the slots ``s = j*B + b`` (plane-major
    inside the sub-chunk);
  * slot s lives in byte column ``s // F`` (F = 8/k_group fields per byte)
    at bit offset ``k_group * (s % F)``, so byte ``(s // F)*128 + l`` of
    the row holds it.

Every plane of every 128 groups is therefore one contiguous 128-byte lane
vector (shared with the other fields of the byte): a Pallas kernel unpacks
a ``[bn, 128]`` byte tile with one shift and one mask per field, with no
reshape across lanes (Mosaic refuses those).

The group count is padded to a whole :func:`chunk_groups` — the smallest
multiple of 128 groups whose fields fill whole 128-byte columns — so a
K-block of whole chunks is always a lane-aligned byte range. Padded fields
are zero. Storage is ``padded_groups * B * k_group / 8`` bytes per channel:
true ``B``-bit weights up to that padding (12% at K=3200, under 1% at
K=8640 for ternary weights).

k_group ∈ {1, 2, 4, 8} keeps fields byte-aligned (fields never straddle a
byte).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

__all__ = ["pack_group_codes", "unpack_group_codes", "packed_bytes_per_channel",
           "chunk_groups", "padded_groups", "LANES"]

LANES = 128
_SUPPORTED_K = (1, 2, 4, 8)


def chunk_groups(k_group: int, bits: int) -> int:
    """Groups per packing chunk: a multiple of 128 lanes whose ``bits``
    planes of ``k_group``-bit fields fill whole 128-byte columns."""
    bits_per_col = 8 * LANES
    return math.lcm(LANES, bits_per_col // math.gcd(bits_per_col,
                                                    bits * k_group))


def padded_groups(g: int, k_group: int, bits: int) -> int:
    c = chunk_groups(k_group, bits)
    return -(-g // c) * c


def packed_bytes_per_channel(k_total: int, bits: int, k_group: int) -> int:
    return padded_groups(k_total // k_group, k_group, bits) * bits * k_group // 8


def _check(k_group: int):
    if k_group not in _SUPPORTED_K:
        raise ValueError(
            f"k_group={k_group} not byte-aligned; supported: {_SUPPORTED_K}"
        )


def pack_group_codes(sign, idx, k_group: int):
    """Pack (sign, idx) [N, G, B] into uint8 [N, Gp*B*k_group/8]."""
    _check(k_group)
    n, g, b = idx.shape
    field = (sign.astype(jnp.uint32) << (k_group - 1)) | idx.astype(jnp.uint32)
    gp = padded_groups(g, k_group, b)
    field = jnp.pad(field, ((0, 0), (0, gp - g), (0, 0)))
    fpb = 8 // k_group
    # [N, J, 128, B] -> slots s = j*B + b, each a 128-lane vector
    field = jnp.swapaxes(field.reshape(n, gp // LANES, LANES, b), 2, 3)
    field = field.reshape(n, -1, fpb, LANES)
    shifts = (k_group * jnp.arange(fpb, dtype=jnp.uint32))[:, None]
    packed = jnp.sum(field << shifts, axis=2).astype(jnp.uint8)
    return packed.reshape(n, -1)


def unpack_group_codes(packed, k_group: int, g: int, bits: int):
    """Inverse of :func:`pack_group_codes` -> (sign, idx) uint8 [N, G, B]."""
    _check(k_group)
    n = packed.shape[0]
    fpb = 8 // k_group
    mask = (1 << k_group) - 1
    shifts = (k_group * jnp.arange(fpb, dtype=jnp.uint32))[:, None]
    x = packed.reshape(n, -1, 1, LANES).astype(jnp.uint32)
    field = (x >> shifts) & mask                    # [N, cols, fpb, 128]
    field = field.reshape(n, -1, bits, LANES)       # [N, J, B, 128]
    field = jnp.swapaxes(field, 2, 3).reshape(n, -1, bits)[:, :g]
    sign = (field >> (k_group - 1)).astype(jnp.uint8)
    idx = (field & ((1 << (k_group - 1)) - 1)).astype(jnp.uint8)
    return sign, idx
