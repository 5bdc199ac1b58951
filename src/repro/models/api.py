"""Model API: family dispatch, param init (float / quantized-serving),
input specs (ShapeDtypeStruct stand-ins for the dry-run), cache init.

``input_specs(cfg, shape)`` follows the shannon/kernels pattern: weak-type-
correct, shardable, zero device allocation.  Modality frontends are stubs —
VLM gets patch embeddings, audio gets frame embeddings (see DESIGN.md §5).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.registry import ArchConfig, ShapeSpec
from repro.core.quantize import QuantizedWeight
from repro.models import audio, hybrid, moe, ssm, transformer, vlm
from repro.models import kvcache, layers as L, quantized
from repro.distributed.sharding import constrain_tree, shard

_FAMILY = {
    "dense": transformer,
    "moe": moe,
    "hybrid": hybrid,
    "vlm": vlm,
    "audio": audio,
}


def get_module(family: str):
    if family == "ssm":
        return _SsmLM
    return _FAMILY[family]


# ---------------------------------------------------------------------------
# SSM LM (falcon-mamba): mamba1 blocks in the standard stack
# ---------------------------------------------------------------------------

class _SsmLM:
    """Namespace-style module matching transformer.py's interface."""

    @staticmethod
    def _block_init(key, cfg, dtype):
        return {"norm": L.norm_init(cfg.d_model, dtype),
                "ssm": ssm.mamba_init(key, cfg, dtype)}

    @staticmethod
    def init(key, cfg, dtype=None):
        dtype = dtype or cfg.param_dtype
        k_e, k_l, k_h = jax.random.split(key, 3)
        keys = jax.random.split(k_l, cfg.n_layers)
        return {
            "embed": transformer.embed_init(k_e, cfg.vocab_size, cfg.d_model, dtype),
            "layers": jax.vmap(lambda k: _SsmLM._block_init(k, cfg, dtype))(keys),
            "final_norm": L.norm_init(cfg.d_model, dtype),
            "lm_head": L.dense_init(k_h, cfg.d_model, cfg.vocab_size, dtype=dtype),
        }

    @staticmethod
    def forward(params, batch, cfg, *, caches=None, cache_pos=0, window=None,
                token_valid=None, page_table=None):
        del page_table  # SSM state is O(1)/slot: nothing to page
        h = transformer.embed_apply(params["embed"], batch["tokens"])
        h = h.astype(cfg.activation_dtype)

        def body(carry, xs):
            hh = carry
            lp = xs if caches is None else xs[0]
            lp = constrain_tree(lp)  # §Perf T1
            lc = None if caches is None else xs[1]
            y, nc = ssm.mamba_apply(lp["ssm"],
                                    L.rms_norm(lp["norm"], hh, cfg.norm_eps),
                                    cfg, cache=lc, quant=cfg.quant,
                                    token_valid=token_valid)
            return hh + y, nc

        body = jax.checkpoint(body, prevent_cse=False)
        xs = params["layers"] if caches is None else (params["layers"], caches)
        h, new_caches = jax.lax.scan(body, h, xs)
        h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
        logits = transformer.head_apply(params["lm_head"], h, cfg.quant)
        return logits, new_caches, {}

    @staticmethod
    def init_cache(cfg, batch, s_cache, window=None, dtype=jnp.bfloat16):
        return kvcache.mamba_cache(cfg.n_layers, batch, cfg.d_inner,
                                   cfg.ssm_state, cfg.d_conv)


# ---------------------------------------------------------------------------
# unified entry points
# ---------------------------------------------------------------------------

# float bytes one serving-init program may generate: a larger leaf gets a
# program of its own, smaller ones share one up to this size
_INIT_GROUP_BYTES = 1 << 30


def init_params(key, cfg: ArchConfig, *, serve_quantized: bool = False):
    """Float params; with serve_quantized=True, projections become packed
    low-bit QuantizedWeights per cfg.quant (the paper's serving format).

    The serving tree is ``quantize_params(init(key))``, built a few output
    nodes (float leaves or QuantizedWeights) per jitted program: XLA drops
    the generation of every other leaf, so a program holds at most one
    large float leaf (or up to 1 GiB of small ones) and its quantizer
    temporaries. (The float tree of paper-bitnet-3b is 13.7 GB in float32;
    a v5e chip has 16 GiB.)
    """
    module = get_module(cfg.family)
    if not (serve_quantized and cfg.quant):
        return module.init(key, cfg)

    def full(k):
        return quantized.quantize_params(module.init(k, cfg), cfg.quant)

    def nodes(tree):
        return jax.tree_util.tree_flatten(tree, is_leaf=_is_qw)

    specs, treedef = nodes(jax.eval_shape(full, key))
    groups, size = [[]], 0
    for i, spec in enumerate(specs):
        b = _float_bytes(spec)
        if groups[-1] and size + b > _INIT_GROUP_BYTES:
            groups.append([])
            size = 0
        groups[-1].append(i)
        size += b
    out = []
    for idx in groups:
        out += jax.jit(lambda k, idx=tuple(idx): [
            nodes(full(k))[0][i] for i in idx])(key)
    return jax.tree_util.tree_unflatten(treedef, out)


def _is_qw(x):
    return isinstance(x, QuantizedWeight)


def _float_bytes(spec) -> int:
    """float32 bytes the init generates for one output node."""
    if _is_qw(spec):
        lead = spec.scale.shape[:-1]
        return math.prod(lead) * spec.n * spec.k_total * 4
    return math.prod(spec.shape) * 4


def forward(params, batch, cfg: ArchConfig, **kw):
    return get_module(cfg.family).forward(params, batch, cfg, **kw)


def init_cache(cfg: ArchConfig, batch: int, s_cache: int, window=None,
               dtype=None):
    if dtype is None:
        dtype = "int8" if cfg.kv_cache_dtype == "int8" else jnp.bfloat16
    return get_module(cfg.family).init_cache(cfg, batch, s_cache,
                                             window=window, dtype=dtype)


# ---------------------------------------------------------------------------
# dry-run specs (no allocation)
# ---------------------------------------------------------------------------

def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def param_specs(cfg: ArchConfig, *, serve_quantized: bool = False):
    fn = functools.partial(init_params, cfg=cfg, serve_quantized=serve_quantized)
    return _sds(jax.eval_shape(fn, jax.random.key(0)))


def input_specs(cfg: ArchConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    b, s = shape.global_batch, shape.seq_len
    tok = jnp.int32
    if shape.kind == "train":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), tok),
                 "labels": jax.ShapeDtypeStruct((b, s), tok)}
    elif shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), tok)}
    else:  # decode: one new token against an s-long cache
        specs = {"tokens": jax.ShapeDtypeStruct((b, 1), tok),
                 "cache_pos": jax.ShapeDtypeStruct((), jnp.int32)}
    if cfg.family == "vlm" and shape.kind != "decode":
        specs["image_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio" and shape.kind != "decode":
        specs["audio_frames"] = jax.ShapeDtypeStruct(
            (b, cfg.n_audio_frames, cfg.d_model), jnp.bfloat16)
    return specs


def cache_specs(cfg: ArchConfig, shape: ShapeSpec):
    """ShapeDtypeStructs of the decode-state for this shape."""
    fn = functools.partial(init_cache, cfg, shape.global_batch, shape.seq_len,
                           window=shape.window)
    return _sds(jax.eval_shape(fn))
