"""Property-based tests (hypothesis) for the autotune dispatch layer:

  * block pickers (``ops.pick_blocks`` / ``ops._clamp_blocks``) always emit
    kernel-valid blocks — the TPU (sublane, lane) tiling rule on every
    kernel operand's block, within the LMMA VMEM budget — for adversarial
    shapes including odd group counts and non-power-of-two k_group;
  * tuned configs loaded from a foreign/adversarial cache are always either
    rejected or sanitized into valid candidates — ``fusion="tuned"``
    dispatch can never crash because of a cache file.

Deterministic durability/round-trip tests live in test_autotune.py (they
do not need hypothesis and must run even where it is absent).
"""

import pytest
import jax

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis; "
    "pip install -r requirements.txt")
from hypothesis import given, settings, strategies as st

from repro.core import autotune, lmma
from repro.core.autotune import TunedConfig
from repro.core.packing import chunk_groups
from repro.kernels import ops

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

# adversarial shape axes: tiny/odd group counts, non-power-of-two k_group
m_st = st.integers(1, 300)
n_st = st.integers(1, 4096)
g_st = st.integers(1, 1024)
kg_st = st.sampled_from([1, 2, 3, 4, 5, 8])
planes_st = st.integers(1, 4)


def _round_up(x, mult):
    return -(-x // mult) * mult


_SUBLANES = {4: 8, 2: 16, 1: 32}   # TPU tile rows by itemsize; lanes: 128


def _tile_ok(block, array, itemsize):
    """Mosaic's rule on a block's last two dims: a multiple of the
    (sublane, lane) tile of its dtype, or the whole array dim."""
    (br, bc), (ar, ac) = block, array
    return ((br % _SUBLANES[itemsize] == 0 or br == ar)
            and (bc % 128 == 0 or bc == ac))


def _assert_valid_blocks(m, n, g, bm, bn, bg, k_group, planes):
    """Every kernel operand's block obeys the TPU tiling rule."""
    assert isinstance(bm, int) and isinstance(bn, int) and isinstance(bg, int)
    assert bm >= 1 and bn >= 1 and bg >= 1
    # packed-stream alignment: a K-block is whole 128-byte lane columns
    assert (bg * planes * k_group) % (8 * 128) == 0 and bg % 128 == 0
    e = 1 << (k_group - 1)
    mp, gt = _round_up(m, bm), _round_up(g, bg)
    gs = _round_up(g, chunk_groups(k_group, planes))  # stored groups
    operands = [
        ("activations f32", (bm, bg * k_group), (mp, gt * k_group), 4),
        ("activations bf16", (bm, bg * k_group), (mp, gt * k_group), 2),
        ("table int8", (bm, bg * e), (mp, gt * e), 1),
        ("table f32", (bm, bg * e), (mp, gt * e), 4),
        ("row scale", (bm, 1), (mp, 1), 4),
        ("group scale", (bm, bg), (mp, gt), 4),
        ("packed uint8", (bn, bg * planes * k_group // 8),
         (n, gs * planes * k_group // 8), 1),
        ("weight scale", (1, bn), (1, n), 4),
        ("output", (bm, bn), (mp, n), 4),
    ]
    for name, block, array, itemsize in operands:
        assert _tile_ok(block, array, itemsize), (name, block, array)


@given(m=m_st, n=n_st, g=g_st, kg=kg_st, planes=planes_st)
def test_pick_blocks_always_valid(m, n, g, kg, planes):
    """Scheduler-chosen blocks: tile-aligned on every operand, VMEM-feasible."""
    bm, bn, bg = ops.pick_blocks(m, n, g, kg, planes)
    _assert_valid_blocks(m, n, g, bm, bn, bg, kg, planes)
    desc = lmma.LMMADescriptor(m=m, n=n, k=g * kg, w_bits=planes, k_group=kg)
    t, w, a = lmma._tile_bytes(bm, bn, bg, desc)
    assert 2 * (t + w) + a <= lmma.TILE_BUDGET


@given(m=m_st, n=n_st, g=g_st, kg=kg_st, planes=planes_st,
       block_m=st.one_of(st.none(), st.integers(1, 512)),
       block_n=st.one_of(st.none(), st.integers(1, 4096)),
       block_g=st.one_of(st.none(), st.integers(1, 1024)))
def test_clamp_blocks_always_valid(m, n, g, kg, planes,
                                   block_m, block_n, block_g):
    """Caller-pinned or scheduler blocks come out of the clamp valid, and
    auto_fusion resolves them to a real mode without crashing."""
    bm, bn, bg = ops._clamp_blocks(m, n, g, kg, planes,
                                   block_m, block_n, block_g)
    _assert_valid_blocks(m, n, g, bm, bn, bg, kg, planes)
    if block_m is not None:
        # pinned knobs win, rounded up to the int8 tile (never shrunk
        # below it) and clamped to the padded problem
        assert bm == min(_round_up(block_m, 32), _round_up(m, 32))
    assert ops.auto_fusion(m, n, g, kg, planes, bm, bn, bg) in \
        ("fused", "staged")


adversarial_field = st.one_of(
    st.none(), st.booleans(), st.integers(-10, 10_000_000),
    st.floats(allow_nan=True, allow_infinity=True), st.text(max_size=8),
    st.sampled_from(["fused", "staged", "auto", "tuned", ""]))


@given(m=m_st, n=n_st, g=g_st, kg=kg_st, planes=planes_st,
       fusion=adversarial_field, bm=adversarial_field, bn=adversarial_field,
       bg=adversarial_field)
def test_sanitize_foreign_entry_never_invalid(m, n, g, kg, planes,
                                              fusion, bm, bn, bg):
    """Any cache entry — including one written by a different backend with
    arbitrary junk fields — sanitizes to None or a valid dispatch config."""
    cfg = TunedConfig(fusion=fusion, block_m=bm, block_n=bn, block_g=bg)
    out = autotune.sanitize_config(cfg, m, n, g, kg, planes)
    if out is None:
        return
    assert out.fusion in ("fused", "staged")
    _assert_valid_blocks(m, n, g, out.block_m, out.block_n, out.block_g,
                         kg, planes)
    assert out.block_m <= _round_up(m, 32) and out.block_n <= _round_up(n, 128)
    if out.fusion == "fused":
        desc = lmma.LMMADescriptor(m=m, n=n, k=g * kg, w_bits=planes,
                                   k_group=kg)
        assert lmma.fused_tile_bytes(out.block_m, out.block_n, out.block_g,
                                     desc) <= lmma.TILE_BUDGET


@given(m=st.integers(1, 64), n=st.integers(1, 1024), g=st.integers(1, 256),
       kg=kg_st, planes=planes_st, fusion=adversarial_field,
       bm=adversarial_field, bn=adversarial_field, bg=adversarial_field)
def test_tuned_dispatch_never_crashes_on_bad_cache(m, n, g, kg, planes,
                                                   fusion, bm, bn, bg):
    """fusion="tuned" against an adversarial active cache resolves to a
    valid (fusion, blocks) decision — it degrades, never raises."""
    cache = autotune.configure(None)
    try:
        key = autotune.shape_key(m, n, g, kg, planes)
        cache.put(key, TunedConfig(fusion=fusion, block_m=bm,
                                   block_n=bn, block_g=bg))
        rf, rbm, rbn, rbg = ops.resolve_dispatch(m, n, g, kg, planes,
                                                 fusion="tuned")
        assert rf in ("fused", "staged")
        _assert_valid_blocks(m, n, g, rbm, rbn, rbg, kg, planes)
    finally:
        autotune.deactivate()
