"""Shared model layers: norms, RoPE, LutDense (the paper's integration
point), flash-style chunked attention, and gated MLPs.

Every projection in every architecture goes through :func:`lut_dense`, which
dispatches on the parameter form:

  * float ``{"w": [in, out]}``      — dense GEMM; optional QAT fake-quant of
    the weight in the forward pass (STE), the paper's §5 training story;
  * quantized ``{"qw": QuantizedWeight}`` — mpGEMM via repro.core.mpgemm in
    the configured mode (dequant / lut_xla / lut_pallas).

Projections sharing an input (QKV; gate+up) share one precomputed lookup
table — the DFG-transform + broadcast amortization of §3.1.1.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import mpgemm as mp
from repro.core.quantize import fake_quant
from repro.distributed.sharding import current_plan
from repro.models import kvcache

Params = Dict[str, Any]


def _quantize_kv_slice(x):
    """bf16 [B,S,KV,hd] -> (int8 codes, f32 scales [B,S,KV,1]) absmax."""
    sc = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), -1,
                             keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / sc), -127, 127
                 ).astype(jnp.int8)
    return q, sc


def _flash_decode_ok(plan, kv_cache, b, s, window, per_slot):
    if plan is None or kv_cache is None or s != 1 or window or per_slot:
        return False
    sizes = dict(zip(plan.mesh.axis_names, plan.mesh.devices.shape))
    mp_size = sizes.get(plan.model, 1)
    bsz = 1
    for a in plan.batch:
        bsz *= sizes.get(a, 1)
    s_max = kv_cache[0].shape[1]
    return mp_size > 1 and s_max % mp_size == 0 and b % bsz == 0


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def norm_init(d: int, dtype=jnp.float32, bias: bool = False) -> Params:
    p = {"g": jnp.ones((d,), dtype)}
    if bias:
        p["b"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# LutDense — every matmul in the framework
# ---------------------------------------------------------------------------

def lut_dense(p: Params, x: jax.Array, quant: Optional[dict] = None,
              table=None) -> jax.Array:
    """y = x @ W (+b). See module docstring for the dispatch rule."""
    if "qw" in p:  # packed low-bit weights -> mpGEMM
        q = quant or {}
        y = mp.mpgemm(
            x, p["qw"],
            mode=q.get("mpgemm_mode", "lut_xla"),
            table_quant=q.get("table_quant", "per_row"),
            table=table,
            fusion=q.get("fusion", "auto"),
        )
    else:
        w = p["w"]
        if quant and quant.get("qat"):
            # fake-quant along the input axis per output channel
            w = fake_quant(w.T, quant.get("weight_bits", 2),
                           quant.get("scheme", "symmetric")).T
        y = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
        y = y.astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def resolve_fusion(m: int, k: int, quant: dict) -> str:
    """Resolve the lut_pallas ``fusion`` knob to "fused"/"staged" for a table
    shared across consumers of one [m, k] activation.

    Delegates to ops.auto_fusion (the same clamp + scheduler decision the
    per-call dispatch uses) with one approximation: N differs per consumer,
    so the decision uses the scheduler's maximum elongation (n=2048) —
    ``fused_tile_bytes`` only grows with bn, so fused fitting there implies
    it fits for every real consumer with the same clamped bm/bg. With
    ``fusion="tuned"`` the autotune cache votes first (largest-N entry
    matching this activation shape); a miss falls back to the heuristic.
    """
    fusion = quant.get("fusion", "auto")
    if fusion not in ("auto", "tuned"):
        return fusion
    kg = quant.get("k_group", 4)
    bits = quant.get("weight_bits", 2)
    if fusion == "tuned":
        from repro.core.autotune import lookup_fusion_any
        tuned = lookup_fusion_any(m, max(1, k // kg), kg, bits)
        if tuned is not None:
            return tuned
        # miss: no active cache or shape untuned — same fallback as ops
    from repro.kernels.ops import auto_fusion
    return auto_fusion(m, 2048, max(1, k // kg), kg, bits)


def make_table(x: jax.Array, quant: Optional[dict]):
    """Precompute a shared lookup table for all consumers of ``x`` (§3.1.1).

    Returns None unless the quant config uses a LUT mode — dense and dequant
    paths have no table. Also None when the Pallas path will run the fused
    kernel (``fusion="fused"``, or ``"auto"`` resolving to fused): the fused
    kernel rebuilds the table in-VMEM per consumer (§3.1.1 fused DFG), so a
    shared HBM table would defeat the point — and supplying one would force
    ops.lut_mpgemm onto the staged path, making the knob a no-op. Consumers
    that share an input instead amortize the (cheap, depth-K) MXU recompute.
    """
    if not quant:
        return None
    if quant.get("mpgemm_mode") not in ("lut_xla", "lut_pallas"):
        return None
    if quant.get("mpgemm_mode") == "lut_pallas":
        m = max(1, math.prod(x.shape[:-1]))
        if resolve_fusion(m, x.shape[-1], quant) == "fused":
            return None
    return mp.precompute_tables(
        x, quant.get("k_group", 4), quant.get("table_quant", "per_row"))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["g"].astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["g"].astype(jnp.float32)
    if "b" in p:
        out = out + p["b"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x [B, S, H, hd], positions [B, S] (or [S]) -> rotated."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# flash-style chunked attention (online softmax over KV chunks)
# ---------------------------------------------------------------------------

def chunked_attention(
    q: jax.Array,                # [B, Sq, H, hd]
    k: jax.Array,                # [B, Skv, KV, hd]
    v: jax.Array,                # [B, Skv, KV, hd]
    *,
    q_offset: jax.Array | int = 0,   # global position of q[:, 0]
    kv_offset: jax.Array | int = 0,  # global position of k[:, 0]
    causal: bool = True,
    window: Optional[int] = None,    # sliding window (global positions)
    kv_valid_len: Optional[jax.Array] = None,  # [B] or scalar valid cache len
    chunk: int = 1024,
    k_scale: Optional[jax.Array] = None,  # [B, Skv, KV, 1] int8-cache scales
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Never materializes the [Sq, Skv] score matrix: lax.scan over KV chunks
    with online softmax. Handles GQA by head-grouping (no KV repeat)."""
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    rep = h // kv
    scale = hd ** -0.5
    qg = q.reshape(b, sq, kv, rep, hd)
    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # NOTE: chunks are taken with dynamic_slice inside the scan body — never
    # pre-split/transposed — so the KV cache is streamed once, with no
    # cache-sized temp (§Perf iteration 1).

    q_pos = (jnp.asarray(q_offset) + jnp.arange(sq))  # [Sq] global
    neg = jnp.finfo(jnp.float32).min

    def body(carry, ci):
        m, l, acc = carry
        kci = jax.lax.dynamic_slice_in_dim(k, ci * chunk, chunk, axis=1)
        vci = jax.lax.dynamic_slice_in_dim(v, ci * chunk, chunk, axis=1)
        kcf = kci.astype(jnp.float32)
        vcf = vci.astype(jnp.float32)
        if k_scale is not None:  # int8 cache: dequantize the chunk in-loop
            kcf = kcf * jax.lax.dynamic_slice_in_dim(k_scale, ci * chunk,
                                                     chunk, axis=1)
            vcf = vcf * jax.lax.dynamic_slice_in_dim(v_scale, ci * chunk,
                                                     chunk, axis=1)
        kv_pos = jnp.asarray(kv_offset) + ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("bsgrh,btgh->bsgrt", qg.astype(jnp.float32),
                       kcf) * scale
        mask = jnp.ones((sq, chunk), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - kv_pos[None, :] < window
        mask &= (kv_pos < skv + jnp.asarray(kv_offset))[None, :]  # pad chunk
        s = jnp.where(mask[None, :, None, None, :], s, neg)
        if kv_valid_len is not None:
            vl = jnp.asarray(kv_valid_len)
            cpos = ci * chunk + jnp.arange(chunk)
            if vl.ndim == 2:
                # [B, Sq]: per-query valid length (multi-token speculative
                # decode — query j may read cache written by query j-1)
                vmask = cpos[None, None, :] < vl[:, :, None]  # [B, Sq, chunk]
                s = jnp.where(vmask[:, :, None, None, :], s, neg)
            else:
                vl = vl[:, None] if vl.ndim == 1 else vl.reshape(1, 1)
                vmask = cpos[None, :] < vl  # [B, chunk]
                s = jnp.where(vmask[:, None, None, None, :], s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bsgrt,btgh->bsgrh", p, vcf)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, kv, rep), neg, jnp.float32)
    l0 = jnp.zeros((b, sq, kv, rep), jnp.float32)
    a0 = jnp.zeros((b, sq, kv, rep, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, sq, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash-decode via shard_map: sequence-sharded KV cache over the model axis
# ---------------------------------------------------------------------------

def flash_decode_shardmap(q, cache, pos, plan, *, chunk=1024):
    """Decode attention with the KV cache sharded along SEQUENCE over the
    model axis (§Perf B4, flash-decoding style).

    Under plain pjit the hd-/kv-sharded cache forces a per-chunk all-gather
    of KV into the score einsum (measured: 80 GiB/step on qwen2-72b
    decode_32k). Here each model shard owns S/mp cache positions, updates
    its local slice if the write position falls inside it, runs the local
    online-softmax, and the partial (m, l, acc) merge is ONE tiny all-gather
    per layer.

    q: [B, 1, H, hd]; cache: (k, v) or (k, v, ks, vs) with S-dim sharded
    over plan.model; pos: scalar next-token position.
    Returns (out [B, 1, H, hd], new_cache).
    """
    mesh = plan.mesh
    model_ax = plan.model
    batch_spec = plan.batch if len(plan.batch) > 1 else plan.batch[0]
    int8 = len(cache) == 4
    b, _, h, hd = q.shape
    kv = cache[0].shape[2]
    rep = h // kv

    qspec = P(batch_spec, None, None, None)
    cspec = P(batch_spec, model_ax, None, None)

    def body(q_, pos_, *cache_):
        idx = jax.lax.axis_index(model_ax)
        ck = cache_[0]
        s_loc = ck.shape[1]
        start = idx * s_loc
        # -- local cache write (new token k/v precomputed into q_'s tail? no:
        # the caller writes k/v before sharding; here cache is already
        # updated. This path only READS.)
        qg = q_.reshape(q_.shape[0], 1, kv, rep, hd).astype(jnp.float32)
        scale = hd ** -0.5
        local_pos = start + jnp.arange(s_loc)
        valid = local_pos <= pos_  # causal/validity vs global position

        def attend(kcf, vcf, vmask):
            s = jnp.einsum("bsgrh,btgh->bsgrt", qg, kcf) * scale
            s = jnp.where(vmask[None, None, None, None, :], s,
                          jnp.finfo(jnp.float32).min)
            m = jnp.max(s, axis=-1)
            p = jnp.exp(s - m[..., None])
            l = jnp.sum(p, axis=-1)
            acc = jnp.einsum("bsgrt,btgh->bsgrh", p, vcf)
            return m, l, acc

        kcf = ck.astype(jnp.float32)
        vcf = cache_[1].astype(jnp.float32)
        if int8:
            kcf = kcf * cache_[2]
            vcf = vcf * cache_[3]
        m, l, acc = attend(kcf, vcf, valid)
        # merge partials across the model axis (flash combine)
        mm = jax.lax.all_gather(m, model_ax)          # [mp, ...]
        ll = jax.lax.all_gather(l, model_ax)
        aa = jax.lax.all_gather(acc, model_ax)
        m_glob = jnp.max(mm, axis=0)
        corr = jnp.exp(mm - m_glob[None])
        l_glob = jnp.sum(ll * corr, axis=0)
        acc_glob = jnp.sum(aa * corr[..., None], axis=0)
        out = acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.reshape(q_.shape).astype(q_.dtype)

    in_specs = (qspec, P()) + (cspec,) * len(cache)
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=qspec, check_vma=False)
    return fn(q, jnp.asarray(pos), *cache)


# ---------------------------------------------------------------------------
# attention + MLP blocks (used by dense / hybrid / vlm / audio stacks)
# ---------------------------------------------------------------------------

def attention_init(key, cfg, *, d_model=None, cross=False, dtype=jnp.float32) -> Params:
    d = d_model or cfg.d_model
    hd = cfg.head_dim
    keys = jax.random.split(key, 4)
    bias = getattr(cfg, "qkv_bias", False)
    return {
        "wq": dense_init(keys[0], d, cfg.n_heads * hd, bias=bias, dtype=dtype),
        "wk": dense_init(keys[1], d, cfg.n_kv_heads * hd, bias=bias, dtype=dtype),
        "wv": dense_init(keys[2], d, cfg.n_kv_heads * hd, bias=bias, dtype=dtype),
        "wo": dense_init(keys[3], cfg.n_heads * hd, d, dtype=dtype),
    }


def attention_apply(
    p: Params, x: jax.Array, cfg, *,
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_pos: jax.Array | int = 0,
    xattn_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
    causal: bool = True,
    use_rope: bool = True,
    quant: Optional[dict] = None,
    page_table: Optional[jax.Array] = None,
):
    """Returns (out, new_kv_cache). Handles train/prefill/decode/cross.

    With ``page_table`` ([B, nb] int32) the cache leaves are block-pool
    shaped [num_blocks, block_size, ...]: writes scatter through the table
    and reads gather the slot's blocks into a contiguous [B, nb*bs] view
    (see kvcache.paged_gather/paged_scatter for the exactness argument).
    """
    b, s, d = x.shape
    hd = cfg.head_dim
    tbl = make_table(x, quant)
    q = lut_dense(p["wq"], x, quant, tbl).reshape(b, s, cfg.n_heads, hd)
    if xattn_kv is None:
        k = lut_dense(p["wk"], x, quant, tbl).reshape(b, s, cfg.n_kv_heads, hd)
        v = lut_dense(p["wv"], x, quant, tbl).reshape(b, s, cfg.n_kv_heads, hd)
    else:
        k, v = xattn_kv  # precomputed cross-attention KV (encoder/image)
    # the "attention" scope holds everything between the projections (RoPE,
    # the cache write, scores, softmax, values); the projections are mpGEMMs
    with jax.named_scope("attention"):
        out, new_cache = _attend(
            q, k, v, cfg, kv_cache=kv_cache, cache_pos=cache_pos,
            cross=xattn_kv is not None, positions=positions, window=window,
            causal=causal, use_rope=use_rope, page_table=page_table)
    return lut_dense(p["wo"], out, quant), new_cache


def _attend(q, k, v, cfg, *, kv_cache, cache_pos, cross, positions, window,
            causal, use_rope, page_table):
    """Attention of ``q`` over the fresh ``k``/``v`` and the cache: returns
    (out [B, S, H*hd] before the output projection, new cache)."""
    b, s = q.shape[:2]
    hd = cfg.head_dim
    # per-slot decode (continuous batching): cache_pos is a [B] vector and
    # s == 1; each slot reads/writes its own position.
    per_slot = getattr(jnp.asarray(cache_pos), "ndim", 0) == 1
    xattn_kv = (k, v) if cross else None

    if positions is None:
        if per_slot:
            positions = jnp.asarray(cache_pos)[:, None] + jnp.arange(s)  # [B,S]
        else:
            positions = jnp.asarray(cache_pos) + jnp.arange(s)
    if use_rope and xattn_kv is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    # ---- block-paged cache (pool leaves + page table): one branch covers
    # per-slot decode (cache_pos [B], s == 1) and chunked prefill at a
    # scalar offset — scatter the fresh k/v through the table, gather the
    # slot's logical view, then run the exact chunked_attention call the
    # matching dense branch runs (bit-exact: see kvcache paged helpers).
    if page_table is not None and kv_cache is not None and xattn_kv is None:
        if window is not None:
            raise NotImplementedError(
                "paged KV cache does not support sliding-window attention "
                "(rolling caches have their own fixed-size layout)")
        cp = jnp.asarray(cache_pos)
        base = cp if per_slot else jnp.broadcast_to(cp, (b,))
        pos2d = base[:, None] + jnp.arange(s)[None, :]  # [B, S] global write
        if len(kv_cache) == 4:  # int8 pool: codes + per-(pos, head) scales
            kq, ks_new = _quantize_kv_slice(k)
            vq, vs_new = _quantize_kv_slice(v)
            new_cache = tuple(
                kvcache.paged_scatter(leaf, vals, page_table, pos2d)
                for leaf, vals in zip(kv_cache, (kq, vq, ks_new, vs_new)))
            kg, vg, ksg, vsg = (kvcache.paged_gather(leaf, page_table)
                                for leaf in new_cache)
        else:
            new_cache = tuple(
                kvcache.paged_scatter(leaf, vals, page_table, pos2d)
                for leaf, vals in zip(kv_cache, (k, v)))
            kg, vg = (kvcache.paged_gather(leaf, page_table)
                      for leaf in new_cache)
            ksg = vsg = None
        if per_slot:
            if ksg is None:
                kg, vg = kg.astype(q.dtype), vg.astype(q.dtype)
            # s > 1 is the speculative decode burst: query j of slot b may
            # read every position up to its own write, so the valid length
            # is per-(slot, query) [B, S]. paged_scatter routes any
            # out-of-range pos2d through the null block, so slots near
            # max_seq stay safe.
            vlen = base + 1 if s == 1 else pos2d + 1
            out = chunked_attention(
                q, kg, vg, k_scale=ksg, v_scale=vsg,
                q_offset=0, causal=False, kv_valid_len=vlen,
                chunk=getattr(cfg, "attn_chunk", 1024))
        else:
            out = chunked_attention(
                q, kg, vg, k_scale=ksg, v_scale=vsg,
                q_offset=cp, causal=causal, kv_valid_len=cp + s,
                chunk=getattr(cfg, "attn_chunk", 1024))
        out = out.reshape(b, s, cfg.n_heads * hd)
        return out, new_cache

    if per_slot and kv_cache is not None and xattn_kv is None:
        bi = jnp.arange(b)
        cp = jnp.asarray(cache_pos)
        # s == 1 keeps the exact single-token decode write; s > 1 is the
        # speculative burst: scatter all s fresh positions (mode="drop"
        # silently skips writes past max_seq — those queries are masked off
        # by the engine's budget logic) and give each query its own valid
        # length so query j sees positions <= cp + j.
        pos2d = cp[:, None] + jnp.arange(s)[None, :]  # [B, S]
        vlen = cp + 1 if s == 1 else pos2d + 1
        if len(kv_cache) == 4:  # int8 KV cache: quantize the new token slice
            ck, cv, cks, cvs = kv_cache
            kq, ks_new = _quantize_kv_slice(k)
            vq, vs_new = _quantize_kv_slice(v)
            if s == 1:
                ck = ck.at[bi, cp].set(kq[:, 0])
                cv = cv.at[bi, cp].set(vq[:, 0])
                cks = cks.at[bi, cp].set(ks_new[:, 0])
                cvs = cvs.at[bi, cp].set(vs_new[:, 0])
            else:
                bi2 = bi[:, None]
                ck = ck.at[bi2, pos2d].set(kq, mode="drop")
                cv = cv.at[bi2, pos2d].set(vq, mode="drop")
                cks = cks.at[bi2, pos2d].set(ks_new, mode="drop")
                cvs = cvs.at[bi2, pos2d].set(vs_new, mode="drop")
            out = chunked_attention(
                q, ck, cv, k_scale=cks, v_scale=cvs,
                q_offset=0, causal=False, kv_valid_len=vlen,
                chunk=getattr(cfg, "attn_chunk", 1024))
            out = out.reshape(b, s, cfg.n_heads * hd)
            return out, (ck, cv, cks, cvs)
        ck, cv = kv_cache
        if s == 1:
            ck = ck.at[bi, cp].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[bi, cp].set(v[:, 0].astype(cv.dtype))
        else:
            ck = ck.at[bi[:, None], pos2d].set(k.astype(ck.dtype), mode="drop")
            cv = cv.at[bi[:, None], pos2d].set(v.astype(cv.dtype), mode="drop")
        out = chunked_attention(
            q, ck.astype(q.dtype), cv.astype(q.dtype),
            q_offset=0, causal=False,
            kv_valid_len=vlen,
            chunk=getattr(cfg, "attn_chunk", 1024))
        out = out.reshape(b, s, cfg.n_heads * hd)
        return out, (ck, cv)

    q_off = jnp.asarray(cache_pos)
    plan = current_plan()
    kv_valid = None
    k_scale = v_scale = None

    # ---- prefill fast path: attend over the fresh k/v (never read the
    # possibly-sequence-sharded cache back); cache update is output-only.
    if (kv_cache is not None and xattn_kv is None and s > 1
            and isinstance(cache_pos, int) and cache_pos == 0):
        if len(kv_cache) == 4:
            ck, cv, cks, cvs = kv_cache
            kq, ks_new = _quantize_kv_slice(k)
            vq, vs_new = _quantize_kv_slice(v)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, kq, 0, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, vq, 0, 1)
            cks = jax.lax.dynamic_update_slice_in_dim(cks, ks_new, 0, 1)
            cvs = jax.lax.dynamic_update_slice_in_dim(cvs, vs_new, 0, 1)
            new_cache = (ck, cv, cks, cvs)
        else:
            ck, cv = kv_cache
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), 0, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), 0, 1)
            new_cache = (ck, cv)
        # §Perf P2: sequence-parallel prefill attention. Without this, archs
        # whose head count doesn't divide the model axis (llama3.2-3b: 24
        # heads / 16) make XLA shard the hd CONTRACTION dim — an all-reduce
        # of the full score tensor per chunk (measured 672 GiB/step).
        # Sharding q's sequence over model instead costs one KV all-gather
        # per layer (~0.5 GiB) and keeps scores collective-free.
        if plan is not None:
            sizes = dict(zip(plan.mesh.axis_names, plan.mesh.devices.shape))
            mp_sz = sizes.get(plan.model, 1)
            bspec = plan.batch if len(plan.batch) > 1 else plan.batch[0]
            # only when the head count can't shard cleanly — divisible-head
            # archs already get collective-free head-parallel attention
            if (mp_sz > 1 and s % mp_sz == 0
                    and cfg.n_heads % mp_sz != 0):
                q = jax.lax.with_sharding_constraint(
                    q, NamedSharding(plan.mesh, P(bspec, plan.model, None, None)))
                k = jax.lax.with_sharding_constraint(
                    k, NamedSharding(plan.mesh, P(bspec, None, None, None)))
                v = jax.lax.with_sharding_constraint(
                    v, NamedSharding(plan.mesh, P(bspec, None, None, None)))
        out = chunked_attention(q, k, v, q_offset=0, causal=causal,
                                window=window,
                                chunk=getattr(cfg, "attn_chunk", 1024))
        out = out.reshape(b, s, cfg.n_heads * hd)
        return out, new_cache

    # ---- flash-decode (§Perf B4): sequence-sharded cache over the model
    # axis, local online-softmax per shard, one (m,l,acc) merge per layer.
    if _flash_decode_ok(plan, kv_cache, b, s, window, per_slot) \
            and xattn_kv is None:
        if len(kv_cache) == 4:
            ck, cv, cks, cvs = kv_cache
            kq, ks_new = _quantize_kv_slice(k)
            vq, vs_new = _quantize_kv_slice(v)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, kq, q_off, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, vq, q_off, 1)
            cks = jax.lax.dynamic_update_slice_in_dim(cks, ks_new, q_off, 1)
            cvs = jax.lax.dynamic_update_slice_in_dim(cvs, vs_new, q_off, 1)
            new_cache = (ck, cv, cks, cvs)
        else:
            ck, cv = kv_cache
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), q_off, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), q_off, 1)
            new_cache = (ck, cv)
        out = flash_decode_shardmap(q, new_cache, q_off, plan)
        out = out.reshape(b, s, cfg.n_heads * hd)
        return out, new_cache

    if kv_cache is not None and xattn_kv is None and len(kv_cache) == 4:
        # int8 KV cache (paper §5 direction): quantize the new slice with
        # per-(position, head) absmax scales, dequantize per chunk in-loop.
        ck, cv, cks, cvs = kv_cache
        ks_new = jnp.maximum(jnp.max(jnp.abs(k.astype(jnp.float32)), -1,
                                     keepdims=True), 1e-8) / 127.0
        vs_new = jnp.maximum(jnp.max(jnp.abs(v.astype(jnp.float32)), -1,
                                     keepdims=True), 1e-8) / 127.0
        kq = jnp.clip(jnp.round(k.astype(jnp.float32) / ks_new), -127, 127
                      ).astype(jnp.int8)
        vq = jnp.clip(jnp.round(v.astype(jnp.float32) / vs_new), -127, 127
                      ).astype(jnp.int8)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, kq, q_off, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, vq, q_off, 1)
        cks = jax.lax.dynamic_update_slice_in_dim(cks, ks_new, q_off, 1)
        cvs = jax.lax.dynamic_update_slice_in_dim(cvs, vs_new, q_off, 1)
        out = chunked_attention(
            q, ck, cv, k_scale=cks, v_scale=cvs,
            q_offset=q_off, causal=causal, kv_valid_len=q_off + s,
            window=window, chunk=getattr(cfg, "attn_chunk", 1024))
        out = out.reshape(b, s, cfg.n_heads * hd)
        return out, (ck, cv, cks, cvs)
    if kv_cache is not None and xattn_kv is None:
        ck, cv = kv_cache
        s_max = ck.shape[1]
        if window is not None and s_max == window:
            # rolling sliding-window cache: slot = pos mod window
            slot = (q_off + jnp.arange(s)) % window
            ck = ck.at[:, slot].set(k.astype(ck.dtype))
            cv = cv.at[:, slot].set(v.astype(cv.dtype))
            # (window caches are small; _attend_rolling casts in-einsum)
            # rolling cache: score by *stored global position* per slot
            stored_pos = _rolling_positions(q_off + s, window)
            out = _attend_rolling(q, ck, cv, q_pos=q_off + jnp.arange(s),
                                  stored_pos=stored_pos, window=window)
            out = out.reshape(b, s, cfg.n_heads * hd)
            return out, (ck, cv)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), q_off, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), q_off, 1)
        k, v = ck, cv
        kv_cache = (ck, cv)
        kv_valid = q_off + s
    # §Perf D1: pass k/v in cache dtype — converting the full cache to the
    # activation dtype here materialized an f32 cache copy per layer (and
    # full-cache convert round-trips in the scanned DUS); the chunk body
    # upcasts chunk-sized slices inside its einsums instead.
    out = chunked_attention(
        q, k, v,
        q_offset=q_off, causal=causal and xattn_kv is None,
        window=window, kv_valid_len=kv_valid,
        chunk=getattr(cfg, "attn_chunk", 1024))
    out = out.reshape(b, s, cfg.n_heads * hd)
    return out, kv_cache


def _rolling_positions(next_pos, window):
    """Global position stored in each rolling-cache slot given next write pos."""
    slots = jnp.arange(window)
    # last write to slot i was at the largest p < next_pos with p % window == i
    base = (next_pos - 1 - slots) // window
    return slots + base * window  # may be negative => never written


def _attend_rolling(q, ck, cv, *, q_pos, stored_pos, window):
    """Attention over a rolling cache with per-slot global positions."""
    b, s, h, hd = q.shape
    kv = ck.shape[2]
    rep = h // kv
    qg = q.reshape(b, s, kv, rep, hd).astype(jnp.float32)
    scale = hd ** -0.5
    sres = jnp.einsum("bsgrh,btgh->bsgrt", qg, ck.astype(jnp.float32)) * scale
    valid = (stored_pos[None, :] >= 0) & (stored_pos[None, :] <= q_pos[:, None])
    valid &= q_pos[:, None] - stored_pos[None, :] < window
    sres = jnp.where(valid[None, :, None, None, :], sres,
                     jnp.finfo(jnp.float32).min)
    pr = jax.nn.softmax(sres, axis=-1)
    out = jnp.einsum("bsgrt,btgh->bsgrh", pr, cv.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def mlp_init(key, d_model: int, d_ff: int, *, gated: bool = True,
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    p = {"up": dense_init(ks[1], d_model, d_ff, dtype=dtype),
         "down": dense_init(ks[2], d_ff, d_model, dtype=dtype)}
    if gated:
        p["gate"] = dense_init(ks[0], d_model, d_ff, dtype=dtype)
    return p


def mlp_apply(p: Params, x: jax.Array, quant: Optional[dict] = None) -> jax.Array:
    tbl = make_table(x, quant)
    if "gate" in p:  # SwiGLU
        g = lut_dense(p["gate"], x, quant, tbl)
        u = lut_dense(p["up"], x, quant, tbl)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:  # GELU (whisper-style)
        h = jax.nn.gelu(lut_dense(p["up"], x, quant, tbl).astype(jnp.float32)
                        ).astype(x.dtype)
    return lut_dense(p["down"], h, quant)
