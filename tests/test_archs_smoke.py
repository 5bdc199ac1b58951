"""Per-architecture smoke tests (reduced configs, CPU).

For every assigned arch: instantiate the reduced config, run one forward
(train-style), one prefill+decode round, and one QAT train-gradient step;
assert output shapes and absence of NaNs. The FULL configs are exercised
only via the dry-run (ShapeDtypeStructs, launch/dryrun.py).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.models import api
from repro.models.transformer import lm_loss

ARCHS = registry.list_archs()


def _batch(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(b, s)), jnp.int32)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_image_tokens, cfg.d_model)) * 0.1,
            jnp.float32)
    if cfg.family == "audio":
        batch["audio_frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_audio_frames, cfg.d_model)) * 0.1,
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nans(arch):
    cfg = registry.get_reduced(arch).replace(activation_dtype=jnp.float32)
    params = api.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg)
    logits, _, aux = jax.jit(
        lambda p, b: api.forward(p, b, cfg))(params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits, np.float32)))
    for v in aux.values():
        assert not np.isnan(float(v))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode_consistent(arch):
    """Prefill+decode must agree with full-sequence forward on the next-token
    logits (cache correctness)."""
    cfg = registry.get_reduced(arch).replace(activation_dtype=jnp.float32)
    params = api.init_params(jax.random.key(1), cfg)
    b, s = 2, 12
    batch = _batch(cfg, b, s, seed=1)

    # full forward over s+1 tokens
    rng = np.random.default_rng(2)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(b, 1)), jnp.int32)
    full_batch = dict(batch, tokens=jnp.concatenate([batch["tokens"], nxt], 1))
    full_logits, _, _ = api.forward(params, full_batch, cfg)

    # prefill s tokens, then decode the next one
    caches = api.init_cache(cfg, b, s + 1, dtype=jnp.float32)
    _, caches, _ = api.forward(params, batch, cfg, caches=caches, cache_pos=0)
    dec_batch = {"tokens": nxt}
    logits1, _, _ = api.forward(params, dec_batch, cfg, caches=caches,
                                cache_pos=s)
    np.testing.assert_allclose(
        np.asarray(logits1[:, 0], np.float32),
        np.asarray(full_logits[:, s], np.float32), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_grad_step(arch):
    """One QAT train-gradient step: finite loss, finite grads."""
    cfg = registry.get_reduced(arch).replace(activation_dtype=jnp.float32)
    if cfg.quant:
        cfg = cfg.with_quant(qat=True)
    params = api.init_params(jax.random.key(2), cfg)
    batch = _batch(cfg, 2, 8, seed=3)
    labels = jnp.roll(batch["tokens"], -1, axis=1)

    def loss_fn(p):
        logits, _, aux = api.forward(p, batch, cfg)
        loss = lm_loss(logits, labels)
        if "lb_loss" in aux:
            loss = loss + 0.01 * aux["lb_loss"] + 0.001 * aux["router_z_loss"]
        return loss

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss)), arch
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g, np.float32))) for g in leaves)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "olmoe-1b-7b",
                                  "falcon-mamba-7b", "zamba2-7b"])
def test_serve_quantized_params(arch):
    """Quantized serving params run and stay close to the fp forward."""
    cfg = registry.get_reduced(arch).replace(activation_dtype=jnp.float32)
    cfg = cfg.with_quant(weight_bits=4)  # W4 keeps the reduced nets sane
    params = api.init_params(jax.random.key(3), cfg)
    qparams = api.init_params(jax.random.key(3), cfg, serve_quantized=True)
    batch = _batch(cfg, 2, 8, seed=5)
    ref_logits, _, _ = api.forward(params, batch, cfg.replace(quant=None))
    q_logits, _, _ = api.forward(qparams, batch, cfg)
    ref = np.asarray(ref_logits, np.float32)
    got = np.asarray(q_logits, np.float32)
    assert np.all(np.isfinite(got))
    # W4 quantization: correlation with the fp forward should be high
    cc = np.corrcoef(ref.ravel(), got.ravel())[0, 1]
    assert cc > 0.95, cc


def test_assigned_arch_count():
    assert len(registry.ASSIGNED) == 10
    assert len(ARCHS) == 11  # + paper-bitnet-3b


@pytest.mark.parametrize("arch", ["paper-bitnet-3b", "olmoe-1b-7b",
                                  "zamba2-7b"])
def test_serving_init_matches_whole_tree_quantize(arch):
    """The serving init, built a few output leaves per program (what fits
    a 3B model on one chip), gives exactly the tree that quantizing the
    whole float tree in one program gives: same structure, every leaf
    bit-identical."""
    from repro.models import quantized
    cfg = registry.get_reduced(arch)
    key = jax.random.key(3)
    want = jax.jit(lambda k: quantized.quantize_params(
        api.get_module(cfg.family).init(k, cfg), cfg.quant))(key)
    got = api.init_params(key, cfg, serve_quantized=True)
    wl, wt = jax.tree_util.tree_flatten(want)
    gl, gt = jax.tree_util.tree_flatten(got)
    assert gt == wt
    for w, g in zip(wl, gl):
        assert w.dtype == g.dtype
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


@pytest.mark.parametrize("arch", ["paper-bitnet-3b", "olmoe-1b-7b",
                                  "zamba2-7b"])
def test_serving_init_matches_eager_quantize(arch):
    """Against the float tree quantized op by op (the serving init before
    it was split into programs): every packed code and other integer leaf
    bit-identical; float leaves within 4 ulp, since XLA fuses the init's
    scaling and the per-channel weight scale's reduction differently in
    one program than op by op (at most 3 ulp seen)."""
    from repro.models import quantized
    cfg = registry.get_reduced(arch)
    key = jax.random.key(3)
    want = quantized.quantize_params(
        api.get_module(cfg.family).init(key, cfg), cfg.quant)
    got = api.init_params(key, cfg, serve_quantized=True)
    wl, wt = jax.tree_util.tree_flatten(want)
    gl, gt = jax.tree_util.tree_flatten(got)
    assert gt == wt
    for w, g in zip(wl, gl):
        w, g = np.asarray(w), np.asarray(g)
        assert w.dtype == g.dtype
        if np.issubdtype(w.dtype, np.floating):
            np.testing.assert_array_max_ulp(w, g, maxulp=4)
        else:
            np.testing.assert_array_equal(w, g)
