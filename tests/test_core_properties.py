"""Property-based tests (hypothesis) for the paper's core invariants:

  * Eq. 2-3: reinterpretation preserves the represented value exactly;
  * exact bit-serial sign-plane decomposition of the odd grid;
  * Eq. 4-5: table oddness LUT[w] = -LUT[~w]; half-table + folded codes
    reproduce every full-table entry;
  * pack/unpack and fold/unfold are bijections;
  * INT8 table quantization error is bounded by scale/2 per entry;
  * ternary = two equal-weight sign planes.
"""

import numpy as np
import pytest
import jax.numpy as jnp

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis; "
    "pip install -r requirements.txt")
from hypothesis import given, settings, strategies as st

from repro.core import packing, quantize as Q, reinterpret as R, table as T
from repro.kernels import ref

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

bits_st = st.sampled_from([1, 2, 3, 4])
kg_st = st.sampled_from([1, 2, 4, 8])


@given(bits=bits_st, data=st.data())
def test_reinterpret_preserves_value(bits, data):
    """s(q-z) == s'(q'-z') for arbitrary s, z, q (Eq. 2-3)."""
    q = data.draw(st.integers(0, (1 << bits) - 1))
    s = data.draw(st.floats(1e-3, 10, allow_nan=False))
    z = data.draw(st.floats(-5, 5, allow_nan=False))
    sp, zp = R.reinterpret_scale_zero(s, z, bits)
    qp = int(np.asarray(R.reinterpret_codes(np.array([q]), bits))[0])
    assert qp == 2 * q - ((1 << bits) - 1)
    # rtol fails spuriously when q ≈ z makes the value ~0; scale the atol by s
    np.testing.assert_allclose(s * (q - z), sp * (qp - zp),
                               rtol=1e-6, atol=s * 1e-6)


@given(bits=bits_st, n=st.integers(1, 5), k=st.sampled_from([8, 16]),
       seed=st.integers(0, 2**31))
def test_sign_plane_decomposition_exact(bits, n, k, seed):
    """q' == Σ_b 2^b (2 plane_b - 1), exactly."""
    rng = np.random.default_rng(seed)
    q = rng.integers(0, 1 << bits, size=(n, k)).astype(np.uint8)
    planes = np.asarray(R.codes_to_sign_planes(q, bits)).astype(np.int64)
    qp = sum((1 << b) * (2 * planes[..., b] - 1) for b in range(bits))
    np.testing.assert_array_equal(qp, 2 * q.astype(np.int64) - ((1 << bits) - 1))


@given(kg=st.sampled_from([2, 3, 4, 5]), seed=st.integers(0, 2**31))
def test_table_oddness(kg, seed):
    """Full table satisfies T[w] == -T[~w] (Eq. 4)."""
    rng = np.random.default_rng(seed)
    a = rng.normal(size=kg).astype(np.float32)
    full = np.zeros(1 << kg)
    for w in range(1 << kg):
        sigma = np.array([2 * ((w >> i) & 1) - 1 for i in range(kg)])
        full[w] = np.dot(a, sigma)
    inv = (~np.arange(1 << kg)) & ((1 << kg) - 1)
    np.testing.assert_allclose(full, -full[inv], atol=1e-5)


@given(kg=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2**31))
def test_half_table_with_folded_codes_covers_full_table(kg, seed):
    """Eq. 5-6: half table + (sign, folded idx) reproduces every entry."""
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(1, kg)).astype(np.float32)
    half = np.asarray(T.table_entries(jnp.asarray(a)[None], kg))[0, 0]  # [E]
    for w in range(1 << kg):
        bits_ = np.array([(w >> i) & 1 for i in range(kg)], np.uint8)
        planes = jnp.asarray(bits_[None, :, None])  # [1, K, 1]
        sign, idx = R.fold_msb_negation(planes, kg)
        s = int(np.asarray(sign)[0, 0, 0])
        e = int(np.asarray(idx)[0, 0, 0])
        sigma = 2 * bits_.astype(np.float32) - 1
        want = float(np.dot(a[0], sigma))
        got = float(half[e]) * (-1.0 if s else 1.0)
        np.testing.assert_allclose(got, want, atol=1e-5)


@given(bits=bits_st, kg=kg_st, n=st.integers(1, 4), g=st.integers(1, 6),
       seed=st.integers(0, 2**31))
def test_pack_unpack_roundtrip(bits, kg, n, g, seed):
    rng = np.random.default_rng(seed)
    sign = jnp.asarray(rng.integers(0, 2, size=(n, g, bits)), jnp.uint8)
    idx = jnp.asarray(rng.integers(0, 1 << (kg - 1), size=(n, g, bits)),
                      jnp.uint8)
    packed = packing.pack_group_codes(sign, idx, kg)
    # true low-bit storage up to one packing chunk of padding, in whole
    # 128-byte lane columns
    assert packed.shape[1] == packing.packed_bytes_per_channel(g * kg, bits, kg)
    assert packed.shape[1] % packing.LANES == 0
    chunk = packing.chunk_groups(kg, bits) * bits * kg // 8
    assert packed.shape[1] < (g * bits * kg + 7) // 8 + chunk
    s2, i2 = packing.unpack_group_codes(packed, kg, g, bits)
    np.testing.assert_array_equal(np.asarray(sign), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i2))


@given(bits=bits_st, kg=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2**31))
def test_fold_unfold_roundtrip(bits, kg, seed):
    rng = np.random.default_rng(seed)
    planes = jnp.asarray(rng.integers(0, 2, size=(3, 2 * kg, bits)), jnp.uint8)
    sign, idx = R.fold_msb_negation(planes, kg)
    back = R.unfold_group_codes(sign, idx, kg)
    np.testing.assert_array_equal(np.asarray(planes), np.asarray(back))


@given(kg=st.sampled_from([2, 4]), mode=st.sampled_from(["per_row", "per_group"]),
       seed=st.integers(0, 2**31))
def test_table_quant_error_bound(kg, mode, seed):
    """|dequant(quant(T)) - T| <= scale/2 per entry (+1 ulp of rounding)."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(4, 4 * kg)), jnp.float32)
    t_fp = ref.ref_table_precompute(a, kg, None)
    t_q = ref.ref_table_precompute(a, kg, mode)
    err = np.abs(np.asarray(T.dequantize_table(t_q)) - np.asarray(t_fp.values))
    bound = np.asarray(t_q.scale) * 0.5 * 1.001 + 1e-6
    assert np.all(err <= bound)


@given(seed=st.integers(0, 2**31))
def test_ternary_two_plane_decomposition(seed):
    rng = np.random.default_rng(seed)
    t = rng.integers(-1, 2, size=(3, 8)).astype(np.int32)
    planes = np.asarray(R.ternary_to_sign_planes(t)).astype(np.int64)
    recon = ((2 * planes[..., 0] - 1) + (2 * planes[..., 1] - 1)) / 2
    np.testing.assert_array_equal(recon, t)


@given(bits=st.sampled_from([1, 2, 4]), kg=st.sampled_from([2, 4]),
       scheme=st.sampled_from(["symmetric", "asymmetric"]),
       seed=st.integers(0, 2**31))
def test_mpgemm_formulations_agree(bits, kg, scheme, seed):
    """dequant == gather-LUT == matmul-LUT on random problems."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(3, 4 * kg)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, 4 * kg)), jnp.float32)
    qw = Q.quantize(w, bits, k_group=kg, scheme=scheme)
    o1 = np.asarray(ref.ref_dequant_mpgemm(a, qw))
    o2 = np.asarray(ref.ref_lut_mpgemm_gather(a, qw))
    o3 = np.asarray(ref.ref_lut_mpgemm_matmul(a, qw))
    np.testing.assert_allclose(o1, o2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(o1, o3, rtol=1e-4, atol=1e-4)


@given(bits=st.sampled_from([1, 2, 4]), seed=st.integers(0, 2**31))
def test_quantize_grid(bits, seed):
    """Symmetric-quantized weights land exactly on the odd grid s'·{±1,±3..}."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    qw = Q.quantize_symmetric(w, bits, k_group=4)
    wd = np.asarray(Q.dequantize(qw))
    ratio = wd / np.asarray(qw.scale)[:, None]
    # ratios must be odd integers within the grid
    np.testing.assert_allclose(ratio, np.round(ratio), atol=1e-4)
    assert np.all(np.abs(ratio) <= (1 << bits) - 1 + 1e-4)
    odd = np.abs(np.round(ratio)) % 2
    assert np.all(odd == 1)
