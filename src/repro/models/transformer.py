"""Decoder-only dense transformer (llama/qwen family) with scan-over-layers.

The stack is the template for every LM family here: embedding → L × block
(lax.scan over stacked params, jax.checkpoint'd body) → final norm → LM head.
Blocks differ per family (dense MLP / MoE / mamba / hybrid); this module
provides the dense one plus the shared embed/head/loss machinery.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain_tree, shard
from repro.models import kvcache, layers as L

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# shared embed / head / loss
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d_model: int, dtype=jnp.float32) -> Params:
    return {"table": (jax.random.normal(key, (vocab, d_model), jnp.float32)
                      * 0.02).astype(dtype)}


def embed_apply(p: Params, tokens: jax.Array) -> jax.Array:
    h = jnp.take(p["table"], tokens, axis=0)
    return shard(h, "batch", "seq", None)


def head_apply(p: Params, h: jax.Array, quant=None) -> jax.Array:
    with jax.named_scope("lm_head"):  # its mpGEMM is lm_head/mpgemm
        logits = L.lut_dense(p, h, quant)
        return shard(logits, "batch", None, "model")  # vocab-sharded logits


def lm_loss(logits: jax.Array, labels: jax.Array,
            mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-mean cross entropy over (possibly vocab-sharded) logits."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# dense block
# ---------------------------------------------------------------------------

def block_init(key, cfg, dtype=jnp.float32) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": L.norm_init(cfg.d_model, dtype),
        "attn": L.attention_init(k1, cfg, dtype=dtype),
        "mlp_norm": L.norm_init(cfg.d_model, dtype),
        "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype),
    }


def block_apply(p: Params, h: jax.Array, cfg, *, cache=None, cache_pos=0,
                window=None, quant=None, page_table=None):
    a, cache = L.attention_apply(
        p["attn"], L.rms_norm(p["attn_norm"], h, cfg.norm_eps), cfg,
        kv_cache=cache, cache_pos=cache_pos, window=window, quant=quant,
        page_table=page_table)
    h = shard(h + a, "batch", "seq", None)
    m = L.mlp_apply(p["mlp"], L.rms_norm(p["mlp_norm"], h, cfg.norm_eps), quant)
    return shard(h + m, "batch", "seq", None), cache


# ---------------------------------------------------------------------------
# stacked layers: init via vmap, apply via scanned+remat'd body
# ---------------------------------------------------------------------------

def stack_init(key, cfg, n_layers: int, block_init_fn=block_init,
               dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: block_init_fn(k, cfg, dtype))(keys)


def stack_apply(stacked: Params, h: jax.Array, cfg, *,
                caches=None, cache_pos=0, window=None, quant=None,
                block_apply_fn=block_apply, page_table=None):
    """lax.scan over the L leading axis of params (+ caches).

    ``page_table`` is closed over, NOT scanned: it has no leading L dim
    (every layer's pool blocks share one per-slot table)."""

    def body(carry, xs):
        hh = carry
        if caches is None:
            lp = constrain_tree(xs)  # §Perf T1: pin layer-slice shardings
            hh, _ = block_apply_fn(lp, hh, cfg, cache=None, cache_pos=cache_pos,
                                   window=window, quant=quant)
            return hh, None
        lp, lc = xs
        lp = constrain_tree(lp)
        hh, nc = block_apply_fn(lp, hh, cfg, cache=lc, cache_pos=cache_pos,
                                window=window, quant=quant,
                                page_table=page_table)
        return hh, nc

    body = jax.checkpoint(body, prevent_cse=False)
    xs = stacked if caches is None else (stacked, caches)
    h, new_caches = jax.lax.scan(body, h, xs)
    return h, new_caches


# ---------------------------------------------------------------------------
# dense LM
# ---------------------------------------------------------------------------

def init(key, cfg, dtype=None) -> Params:
    dtype = dtype or cfg.param_dtype
    k_e, k_l, k_h = jax.random.split(key, 3)
    return {
        "embed": embed_init(k_e, cfg.vocab_size, cfg.d_model, dtype),
        "layers": stack_init(k_l, cfg, cfg.n_layers, dtype=dtype),
        "final_norm": L.norm_init(cfg.d_model, dtype),
        "lm_head": L.dense_init(k_h, cfg.d_model, cfg.vocab_size, dtype=dtype),
    }


def forward(params: Params, batch: Dict[str, jax.Array], cfg, *,
            caches=None, cache_pos=0, window=None,
            token_valid=None, page_table=None) -> Tuple[jax.Array, Any, Dict]:
    # token_valid ([B] real-token counts for right-padded chunked prefill) is
    # accepted for interface uniformity but unused: causal attention already
    # prevents real positions from seeing padded tails, and pad k/v land at
    # cache positions >= the slot's valid length, which every later read
    # masks via kv_valid_len (and decode overwrites them in place).
    del token_valid
    tokens = batch["tokens"]
    h = embed_apply(params["embed"], tokens).astype(cfg.activation_dtype)
    h, new_caches = stack_apply(params["layers"], h, cfg, caches=caches,
                                cache_pos=cache_pos, window=window,
                                quant=cfg.quant, page_table=page_table)
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    logits = head_apply(params["lm_head"], h, cfg.quant)
    return logits, new_caches, {}


def init_cache(cfg, batch: int, s_cache: int, window=None, dtype=jnp.bfloat16):
    return kvcache.attn_cache(cfg.n_layers, batch, s_cache, cfg.n_kv_heads,
                              cfg.head_dim, dtype, window)
