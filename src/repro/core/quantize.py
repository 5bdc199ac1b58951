"""Weight quantizers producing the framework's low-bit weight format.

``QuantizedWeight`` is the single weight container consumed by every mpGEMM
mode (dequant / lut_xla / lut_pallas) and by the serving stack:

  * ``packed``       uint8 [N, Gp*B*k_group/8] — folded group codes (Eq. 6
                     applied offline), the B-bit HBM format (groups padded
                     to whole packing chunks, see core/packing.py),
  * ``scale``        float32 [N]            — s' = s/2 (reinterpreted),
  * ``zero_prime``   float32 [N] or None    — z' (None ⇒ symmetric, z'=0),
  * ``plane_scales`` float32 [B]            — [1,2,4..] or [1,1] (ternary),
  * ``bits, k_group, k_total, n``           — static metadata.

Quantizers:
  * ``quantize_symmetric``  — absmax onto the odd grid (z'=0). This is the
    reinterpreted form of the paper's Eq. 1-2 with z = (2^B-1)/2.
  * ``quantize_asymmetric`` — min/max affine, reinterpreted via Eq. 2
    (exercises the zero-point correction path).
  * ``quantize_ternary``    — BitNet b1.58 absmean ternary, two ±1 planes.
  * ``fake_quant``          — straight-through-estimator QAT fake-quant for
    the training forward pass (paper §5: applying mpGEMM to training fwd).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import packing, reinterpret

__all__ = [
    "QuantizedWeight",
    "quantize_symmetric",
    "quantize_asymmetric",
    "quantize_ternary",
    "quantize",
    "dequantize",
    "fake_quant",
]


@jax.tree_util.register_pytree_with_keys_class
class QuantizedWeight:
    """Pytree container for packed low-bit weights (see module docstring)."""

    def __init__(self, packed, scale, zero_prime, plane_scales, *, bits, k_group, k_total, n, cw=None,
                 plane_start=0, stored_planes=None):
        self.packed = packed
        self.scale = scale
        self.zero_prime = zero_prime
        # optional offline-expanded combined-lookup matrix CW [G*E, N] int8
        # (the serving format for memory-bound decode: no per-step CW build)
        self.cw = cw
        # plane scales are STATIC metadata (kernels unroll the bit-serial
        # loop over them), never traced arrays.
        self.plane_scales = tuple(float(s) for s in plane_scales)
        self.bits = int(bits)
        self.k_group = int(k_group)
        self.k_total = int(k_total)
        self.n = int(n)
        # plane-sliced execution view (paper §3.1.2: the packed tensor IS a
        # sum of ±1 planes, so a contiguous plane subrange of the SAME
        # buffer is a coarser-precision model for free). ``stored_planes``
        # is the plane count of the underlying packed layout (governs the
        # byte math); ``plane_start`` is where this view's planes begin.
        # A full-precision weight has plane_start == 0 and
        # stored_planes == len(plane_scales).
        self.plane_start = int(plane_start)
        self.stored_planes = (len(self.plane_scales) if stored_planes is None
                              else int(stored_planes))

    # -- pytree protocol ----------------------------------------------------
    # Keyed flattening so tree_flatten_with_path yields NAMED child paths
    # (".../qw/packed", ".../qw/scale", ...) — the sharding-rule regexes in
    # distributed/sharding.py match on these names; with anonymous
    # flattening the paths were numeric indices and no packed-weight rule
    # could ever fire.
    def tree_flatten_with_keys(self):
        children = ((jax.tree_util.GetAttrKey("packed"), self.packed),
                    (jax.tree_util.GetAttrKey("scale"), self.scale),
                    (jax.tree_util.GetAttrKey("zero_prime"), self.zero_prime),
                    (jax.tree_util.GetAttrKey("cw"), self.cw))
        aux = (self.plane_scales, self.bits, self.k_group, self.k_total,
               self.n, self.plane_start, self.stored_planes)
        return children, aux

    def tree_flatten(self):
        children = (self.packed, self.scale, self.zero_prime, self.cw)
        aux = (self.plane_scales, self.bits, self.k_group, self.k_total,
               self.n, self.plane_start, self.stored_planes)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, scale, zero_prime, cw = children
        plane_scales, bits, k_group, k_total, n, plane_start, stored = aux
        return cls(packed, scale, zero_prime, plane_scales,
                   bits=bits, k_group=k_group, k_total=k_total, n=n, cw=cw,
                   plane_start=plane_start, stored_planes=stored)

    # -- helpers -------------------------------------------------------------
    @property
    def num_planes(self) -> int:
        return len(self.plane_scales)

    @property
    def g(self) -> int:
        return self.k_total // self.k_group

    @property
    def is_plane_sliced(self) -> bool:
        return (self.plane_start != 0
                or self.stored_planes != self.num_planes)

    def sign_idx(self):
        """Unpack to (sign, idx) uint8 [N, G, B].

        The packed stream interleaves the planes of each 128-group lane
        vector (slot j*B + b, core/packing.py), so a plane-sliced view
        CANNOT truncate bytes: unpack at the stored plane count, then slice
        this view's plane range.
        """
        sign, idx = packing.unpack_group_codes(
            self.packed, self.k_group, self.g, self.stored_planes)
        if self.is_plane_sliced:
            sl = slice(self.plane_start, self.plane_start + self.num_planes)
            sign, idx = sign[..., sl], idx[..., sl]
        return sign, idx

    def plane_slice(self, keep: int) -> "QuantizedWeight":
        """Top-``keep``-planes draft view of the SAME packed buffer.

        Zero-copy: the returned weight shares ``packed``/``scale``/
        ``zero_prime`` with ``self`` (no extra weight HBM).  Dropping the
        ``B - keep`` low-order planes perturbs each weight by at most
        ``s'·(2^(B-keep) - 1)`` — the sign planes are ±1, never 0, so the
        dropped contribution is mean-zero noise and ``z'`` stays unbiased.
        CW-store weights cannot be sliced (CW bakes all planes in).
        """
        if keep >= self.num_planes:
            return self
        if keep < 1:
            raise ValueError(f"plane_slice(keep={keep}): need >= 1 plane")
        if self.packed is None:
            raise ValueError(
                "plane_slice needs the packed store: the offline CW matrix "
                "bakes every plane into its entries and is not re-sliceable "
                "(pin quant['store']='packed' before converting)")
        start = self.plane_start + (self.num_planes - keep)
        return QuantizedWeight(
            self.packed, self.scale, self.zero_prime,
            self.plane_scales[self.num_planes - keep:],
            bits=self.bits, k_group=self.k_group, k_total=self.k_total,
            n=self.n, cw=None, plane_start=start,
            stored_planes=self.stored_planes)

    def storage_bits_per_weight(self) -> float:
        return self.packed.shape[1] * 8 / self.k_total

    def __repr__(self):
        sl = (f", view=[{self.plane_start}:"
              f"{self.plane_start + self.num_planes}]/{self.stored_planes}"
              if self.is_plane_sliced else "")
        return (f"QuantizedWeight(n={self.n}, k={self.k_total}, bits={self.bits}, "
                f"k_group={self.k_group}, planes={self.num_planes}{sl})")


def _pack_planes(planes, k_group):
    sign, idx = reinterpret.fold_msb_negation(planes, k_group)
    return packing.pack_group_codes(sign, idx, k_group)


def quantize_symmetric(w: jax.Array, bits: int, k_group: int = 4) -> QuantizedWeight:
    """MSE-optimal symmetric quantization onto the odd grid {±1, ±3, ...}·s'.

    w: float [N, K] (output-major). z' = 0 by construction. The per-row
    scale is not plain absmax: a per-row grid search over clip ratios
    r·absmax/qmax (r ∈ [0.6, 1.0], the AWQ/TensorRT-LLM recipe) picks the
    scale minimizing squared reconstruction error — clipping a heavy-tailed
    row's outliers buys a finer grid for the bulk of its mass. Every scale
    on the grid keeps the odd-grid invariant (dequant/scale ratios are odd
    integers ≤ 2^bits − 1), so kernels and tests are agnostic to the
    choice; end-to-end it is what keeps deep stacks with shared quantized
    blocks (zamba2-style) faithful at W4.
    """
    n, k = w.shape
    wf = w.astype(jnp.float32)
    qmax = (1 << bits) - 1
    absmax = jnp.maximum(jnp.max(jnp.abs(wf), axis=1), 1e-30)  # [N]
    ratios = jnp.linspace(0.6, 1.0, 17)

    def _recon_err(r):
        s = absmax * r / qmax
        qr = jnp.clip(jnp.round((wf / s[:, None] + qmax) / 2.0), 0, qmax)
        wr = s[:, None] * (2.0 * qr - qmax)
        return jnp.sum(jnp.square(wf - wr), axis=1)  # [N]

    errs = jax.vmap(_recon_err)(ratios)              # [R, N]
    s_prime = absmax * ratios[jnp.argmin(errs, axis=0)] / qmax
    q = jnp.clip(jnp.round((wf / s_prime[:, None] + qmax) / 2.0), 0, qmax)
    planes = reinterpret.codes_to_sign_planes(q.astype(jnp.uint8), bits)
    return QuantizedWeight(
        _pack_planes(planes, k_group), s_prime, None,
        reinterpret.plane_scales_for(bits),
        bits=bits, k_group=k_group, k_total=k, n=n)


def quantize_asymmetric(w: jax.Array, bits: int, k_group: int = 4) -> QuantizedWeight:
    """Min/max affine quantization, then reinterpretation (Eq. 2)."""
    n, k = w.shape
    wf = w.astype(jnp.float32)
    wmin = jnp.min(wf, axis=1)
    wmax = jnp.max(wf, axis=1)
    qmax = (1 << bits) - 1
    s = jnp.maximum(wmax - wmin, 1e-30) / qmax
    z = -wmin / s
    q = jnp.clip(jnp.round(wf / s[:, None] + z[:, None]), 0, qmax)
    s_prime, z_prime = reinterpret.reinterpret_scale_zero(s, z, bits)
    planes = reinterpret.codes_to_sign_planes(q.astype(jnp.uint8), bits)
    return QuantizedWeight(
        _pack_planes(planes, k_group), s_prime, z_prime,
        reinterpret.plane_scales_for(bits),
        bits=bits, k_group=k_group, k_total=k, n=n)


def quantize_ternary(w: jax.Array, k_group: int = 4) -> QuantizedWeight:
    """BitNet b1.58 absmean ternary: t = clip(round(W/mean|W|), -1, 1)."""
    n, k = w.shape
    wf = w.astype(jnp.float32)
    s = jnp.maximum(jnp.mean(jnp.abs(wf), axis=1), 1e-30)  # [N]
    t = jnp.clip(jnp.round(wf / s[:, None]), -1, 1)
    planes = reinterpret.ternary_to_sign_planes(t)
    # w ≈ s·t = (s/2)·(σ_a + σ_b): plane_scales [1,1], stored scale s/2.
    return QuantizedWeight(
        _pack_planes(planes, k_group), s / 2.0, None,
        reinterpret.plane_scales_for(2, ternary=True),
        bits=2, k_group=k_group, k_total=k, n=n)


def to_cw_format(qw: QuantizedWeight) -> QuantizedWeight:
    """Offline CW expansion (§Perf B1): store the combined-lookup matrix
    CW [G*E, N] int8 instead of packed codes. 4x larger at W2/K=2 (1 byte
    per weight vs 2 bits) but decode reads it ONCE instead of rebuilding it
    every step (packed read + one-hot intermediates + CW write+read)."""
    from repro.kernels.ref import build_cw
    import jax.numpy as _jnp
    cw = build_cw(qw, _jnp.int8)
    return QuantizedWeight(None, qw.scale, qw.zero_prime, qw.plane_scales,
                           bits=qw.bits, k_group=qw.k_group,
                           k_total=qw.k_total, n=qw.n, cw=cw)


def quantize(w, bits: int, k_group: int = 4, scheme: str = "symmetric") -> QuantizedWeight:
    if scheme == "symmetric":
        return quantize_symmetric(w, bits, k_group)
    if scheme == "asymmetric":
        return quantize_asymmetric(w, bits, k_group)
    if scheme == "ternary":
        return quantize_ternary(w, k_group)
    raise ValueError(f"unknown scheme {scheme!r}")


def dequantize(qw: QuantizedWeight) -> jax.Array:
    """Reconstruct float weights [N, K]: s'·(Σ_b ps_b·σ_b − z')."""
    sign, idx = qw.sign_idx()
    planes = reinterpret.unfold_group_codes(sign, idx, qw.k_group)  # [N,K,B] {0,1}
    sigma = 2.0 * planes.astype(jnp.float32) - 1.0
    qp = jnp.einsum("nkb,b->nk", sigma, jnp.asarray(qw.plane_scales, jnp.float32))
    if qw.zero_prime is not None:
        qp = qp - qw.zero_prime[:, None]
    return qw.scale[:, None] * qp


# ---------------------------------------------------------------------------
# QAT fake-quant (straight-through estimator)
# ---------------------------------------------------------------------------

def _fq_symmetric(w, bits):
    qmax = (1 << bits) - 1
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-1, keepdims=True), 1e-30) / qmax
    q = jnp.clip(jnp.round((w / s + qmax) / 2.0), 0, qmax)
    return s * (2.0 * q - qmax)


def _fq_ternary(w):
    s = jnp.maximum(jnp.mean(jnp.abs(w), axis=-1, keepdims=True), 1e-30)
    return s * jnp.clip(jnp.round(w / s), -1, 1)


def fake_quant(w: jax.Array, bits: int, scheme: str = "symmetric") -> jax.Array:
    """STE fake-quant: forward uses the quantized value, gradient passes through."""
    wf = w.astype(jnp.float32)
    if scheme == "ternary":
        wq = _fq_ternary(wf)
    else:
        wq = _fq_symmetric(wf, bits)
    return (w + jax.lax.stop_gradient(wq.astype(w.dtype) - w)).astype(w.dtype)
