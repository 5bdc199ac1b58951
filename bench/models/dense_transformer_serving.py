"""The seeded dense-transformer checkpoint in the program's serving format.

The program's loader takes float latent weights and packs them with its own
quantizer (``repro.models.quantized.quantize_params``). The latent weights
are chosen so that the stated quantizer recovers the benchmark's codes and
scales exactly: on the odd grid, ``codes * scale`` is its own absmax
quantization; for BitNet's absmean ternary, ``codes * scale / mean|codes|``
has absmean ``scale`` and rounds back to ``codes``. The whole tree is made
on the device in one jitted call from the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models import dense_transformer as M


def _latent(conf, codes, scale):
    """Float [k, n] latent (the program's [in, out] layout)."""
    w = M.dequant(codes, scale)
    if conf["quant"]["scheme"] == "ternary":
        frac = jnp.mean(jnp.abs(codes.astype(jnp.float32)), axis=1)
        w = w / jnp.maximum(frac, 1.0 / codes.shape[1])[:, None]
    return w.T


def serving_params(conf, seed: int):
    """The serving parameter tree of the program for ``conf`` and ``seed``."""
    from repro.core.quantize import QuantizedWeight
    from repro.models.quantized import quantize_params

    quant = conf["quant"]
    nb, rows = M.vocab_blocks(conf["vocab_size"])

    def build(lo, hi):
        key = M.root_key(lo, hi)

        def layer(l):
            w = M.layer_weights(conf, key, l)

            def dense(name):
                codes, scale, bias = w[name]
                p = {"w": _latent(conf, codes, scale)}
                if bias is not None:
                    p["b"] = bias
                return p

            tree = {"attn_norm": {"g": w["attn_norm"]},
                    "attn": {n: dense(n) for n in ("wq", "wk", "wv", "wo")},
                    "mlp_norm": {"g": w["mlp_norm"]},
                    "mlp": {n: dense(n) for n in ("gate", "up", "down")}}
            return quantize_params(tree, quant)

        def head(blk):
            codes, scale = M.head_block(conf, key, blk)
            return quantize_params(
                {"lm_head": {"w": _latent(conf, codes, scale)}},
                quant)["lm_head"]["qw"]

        layers = jax.lax.map(layer, jnp.arange(conf["n_layers"]))
        emb = jax.lax.map(lambda b: M.embed_block(conf, key, b),
                          jnp.arange(nb))
        hq = jax.lax.map(head, jnp.arange(nb))  # children lead with [nb, R]
        if hq.zero_prime is not None or hq.cw is not None:
            raise ValueError("expected a symmetric packed LM head")
        lm_head = QuantizedWeight(
            hq.packed.reshape(nb * rows, -1), hq.scale.reshape(-1), None,
            hq.plane_scales, bits=hq.bits, k_group=hq.k_group,
            k_total=hq.k_total, n=nb * rows)
        return {"embed": {"table": emb.reshape(nb * rows, -1)},
                "layers": layers,
                "final_norm": {"g": M.final_norm(conf, key)},
                "lm_head": {"qw": lm_head}}

    return jax.jit(build)(*M.split_seed(seed))
