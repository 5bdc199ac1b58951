"""Tuning-cache durability + autotuner round-trip tests (no hypothesis
needed — the property-based layer lives in test_autotune_properties.py).

  * durability — corrupt / truncated / version-mismatched cache files warn
    and degrade to heuristic dispatch; a foreign-backend cache is kept but
    re-validated at every lookup; concurrent writers never leave a torn
    file (atomic-rename saves);
  * round trip — tune -> save -> fresh load reproduces the identical
    dispatch decision, and ``fusion="tuned"`` is numerically bit-identical
    to ``fusion="auto"`` on the per_row int8 path.
"""

import json
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.autotune import TunedConfig, TuningCache
from repro.core.quantize import quantize
from repro.kernels import ops


def test_candidate_configs_heuristic_first():
    """Candidate 0 is always the heuristic pick; all candidates are valid
    (positive byte-aligned blocks, real fusion modes)."""
    for (m, n, g, kg, planes) in [(4, 512, 16, 4, 2), (16, 256, 7, 3, 1),
                                  (64, 2048, 256, 8, 3)]:
        cands = autotune.candidate_configs(m, n, g, kg, planes)
        assert cands[0].source == "heuristic"
        assert all(c.source == "measured" for c in cands[1:])
        for c in cands:
            assert c.fusion in ("fused", "staged")
            assert c.block_m >= 1 and c.block_n >= 1 and c.block_g >= 1
            assert (c.block_g * planes * kg) % 8 == 0


# ---------------------------------------------------------------------------
# durability
# ---------------------------------------------------------------------------

def test_corrupt_cache_warns_and_degrades(tmp_path):
    p = tmp_path / "cache.json"
    p.write_text("{garbage not json")
    with pytest.warns(UserWarning, match="unreadable"):
        cache = TuningCache(str(p))
    assert len(cache) == 0 and not cache.foreign


def test_truncated_cache_warns_and_degrades(tmp_path):
    good = tmp_path / "good.json"
    cache = TuningCache(str(good))
    cache.put(autotune.shape_key(4, 512, 16, 4, 2),
              TunedConfig("fused", 8, 256, 16))
    cache.save()
    text = good.read_text()
    trunc = tmp_path / "trunc.json"
    trunc.write_text(text[: len(text) // 2])
    with pytest.warns(UserWarning, match="unreadable"):
        reloaded = TuningCache(str(trunc))
    assert len(reloaded) == 0


def test_version_mismatch_warns_and_degrades(tmp_path):
    p = tmp_path / "cache.json"
    p.write_text(json.dumps({
        "version": 99, "backend": "cpu", "jax_version": jax.__version__,
        "entries": {"m4.n512.g16.kg4.w2.f32.tqper_row":
                    TunedConfig("fused", 8, 256, 16).as_dict()}}))
    with pytest.warns(UserWarning, match="unknown format"):
        cache = TuningCache(str(p))
    assert len(cache) == 0


def test_foreign_backend_kept_but_sanitized(tmp_path):
    """A cache tuned on another backend warns, keeps entries, and every
    lookup re-validates — an absurd block shape cannot reach the kernels."""
    p = tmp_path / "cache.json"
    key = autotune.shape_key(4, 512, 16, 4, 2)
    p.write_text(json.dumps({
        "version": autotune.CACHE_FORMAT_VERSION,
        "backend": "tpu", "jax_version": "9.9.9",
        "entries": {key: TunedConfig("fused", 4096, 1 << 20, 999).as_dict()}}))
    with pytest.warns(UserWarning, match="re-validated"):
        autotune.configure(str(p))
    try:
        assert autotune.get_active().foreign
        tc = autotune.lookup_tuned(4, 512, 16, 4, 2)
        assert tc is not None
        # clamped to the problem padded to one tile: m=4 -> 32 rows (int8
        # tile), g=16 -> one 128-group packing chunk
        assert tc.block_m <= 32 and tc.block_n <= 512
        assert tc.block_g == 128
    finally:
        autotune.deactivate()


def test_malformed_entries_skipped_rest_kept(tmp_path):
    p = tmp_path / "cache.json"
    good_key = autotune.shape_key(4, 512, 16, 4, 2)
    p.write_text(json.dumps({
        "version": autotune.CACHE_FORMAT_VERSION,
        "backend": jax.default_backend(), "jax_version": jax.__version__,
        "entries": {
            good_key: TunedConfig("fused", 8, 256, 16).as_dict(),
            "bad-entry-1": "not a dict",
            "bad-entry-2": {"fusion": "fused", "block_m": "not-an-int",
                            "block_n": 1, "block_g": 1},
        }}))
    cache = TuningCache(str(p))
    assert len(cache) == 1 and cache.lookup(good_key) is not None


def test_concurrent_writers_never_tear_the_file(tmp_path):
    """N threads hammering save() on one path: every interleaved read must
    parse (os.replace is atomic), and the final file is a valid cache."""
    p = str(tmp_path / "cache.json")
    errors = []

    def writer(tid):
        try:
            cache = TuningCache(p, backend="cpu")
            for i in range(20):
                cache.put(f"m{tid}.n{i}.g1.kg4.w2.f32.tqper_row",
                          TunedConfig("staged", 8, 128, 8, steady_ms=i))
                cache.save()
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(e)

    def reader():
        import os
        for _ in range(200):
            if not os.path.exists(p):
                continue
            try:
                with open(p) as f:
                    json.load(f)  # a torn write would raise here
            except json.JSONDecodeError as e:  # pragma: no cover
                errors.append(e)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(4)]
    threads.append(threading.Thread(target=reader))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    final = TuningCache(p, backend="cpu")
    assert len(final) > 0


# ---------------------------------------------------------------------------
# round trip: tune -> persist -> reload -> identical dispatch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_qw():
    w = jax.random.normal(jax.random.key(7), (128, 32))
    return quantize(w, 2, k_group=4)


def test_tune_roundtrip_identical_dispatch(tmp_path, tiny_qw):
    qw, m = tiny_qw, 4
    cache = TuningCache(str(tmp_path / "cache.json"))
    best, measured = autotune.tune_mpgemm(m, qw, cache=cache, repeats=1,
                                          max_candidates=2)
    assert best.source == "measured" and best.steady_ms > 0
    assert best.compile_ms > 0  # compile/steady recorded separately
    # the heuristic is candidate 0 of the same measurement pass, so the
    # winner can only match or beat it
    assert best.steady_ms <= best.heuristic_ms + 1e-9
    cache.save()

    autotune.configure(cache.path)
    try:
        d1 = ops.resolve_dispatch(m, qw.n, qw.g, qw.k_group, qw.num_planes,
                                  fusion="tuned")
    finally:
        autotune.deactivate()
    assert d1 == (best.fusion, best.block_m, best.block_n, best.block_g)

    # fresh process simulation: reload from disk, decision is identical
    autotune.configure(cache.path)
    try:
        d2 = ops.resolve_dispatch(m, qw.n, qw.g, qw.k_group, qw.num_planes,
                                  fusion="tuned")
    finally:
        autotune.deactivate()
    assert d2 == d1


def test_tuned_numerics_match_auto(tmp_path, tiny_qw):
    """fusion="tuned" (cache hit with non-default blocks) is bit-identical
    to fusion="auto" on the per_row int8 path."""
    qw, m = tiny_qw, 4
    x = jax.random.normal(jax.random.key(3), (m, qw.k_total), jnp.float32)
    ref = ops.lut_mpgemm(x, qw, fusion="auto", interpret=True)
    cache = autotune.configure(None)
    try:
        key = autotune.shape_key(m, qw.n, qw.g, qw.k_group, qw.num_planes)
        cache.put(key, TunedConfig("staged", 8, 64, 4))
        out = ops.lut_mpgemm(x, qw, fusion="tuned", interpret=True)
    finally:
        autotune.deactivate()
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_tuned_without_cache_falls_back_to_auto(tiny_qw):
    qw, m = tiny_qw, 4
    autotune.deactivate()
    want = ops.resolve_dispatch(m, qw.n, qw.g, qw.k_group, qw.num_planes,
                                fusion="auto")
    got = ops.resolve_dispatch(m, qw.n, qw.g, qw.k_group, qw.num_planes,
                               fusion="tuned")
    assert got == want
