"""Mixture-of-Experts LM (olmoe, kimi-k2) with sort-based EP dispatch.

Routing: token-choice top-k, fp32 router (accuracy-critical, never
quantized — DESIGN.md §5).  Dispatch avoids [T, E] one-hot tensors (E up to
384): the T·k assignments are argsorted by expert id, positions within an
expert come from a cumsum over bincounts, and tokens scatter-add into a
capacity-bucketed [E, C, D] buffer (dropped tokens write zeros; no write
collisions among kept tokens).  Expert FFNs run as one batched einsum with
the expert dim sharded over the EP axis — under pjit the scatter/gather
become the all-to-alls.

Aux outputs: load-balance loss (Switch-style E·Σ f_e·P_e) and router-z loss.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core.quantize import fake_quant
from repro.distributed.sharding import constrain_tree, shard
from repro.models import kvcache, layers as L
from repro.models import transformer as TR

Params = Dict[str, Any]


def _expert_init(key, e: int, d_in: int, d_out: int, dtype):
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (e, d_in, d_out), jnp.float32) * scale).astype(dtype)


def moe_mlp_init(key, cfg, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 5)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": {"w": (jax.random.normal(ks[0], (d, e), jnp.float32) * 0.02)
                   .astype(jnp.float32)},  # router stays fp32
        "experts": {
            "gate": _expert_init(ks[1], e, d, f, dtype),
            "up": _expert_init(ks[2], e, d, f, dtype),
            "down": _expert_init(ks[3], e, f, d, dtype),
        },
    }
    if cfg.n_shared_experts:
        p["shared_mlp"] = L.mlp_init(ks[4], d, f * cfg.n_shared_experts,
                                     dtype=dtype)
    return p


def _capacity(t: int, k: int, e: int, factor: float) -> int:
    c = int(math.ceil(t * k * factor / e))
    return max(8, -(-c // 8) * 8)


def _expert_ffn(w, x, quant):
    """Batched expert einsum with optional QAT fake-quant on expert weights."""
    if "gate_qw" in w:  # packed low-bit experts (serving path)
        from repro.core.mpgemm import mpgemm, precompute_tables
        mode = (quant or {}).get("mpgemm_mode", "lut_xla")
        tq = (quant or {}).get("table_quant", "per_row")
        kg = (quant or {}).get("k_group", 4)
        fusion = (quant or {}).get("fusion", "auto")
        # fused lut_pallas rebuilds tables in-VMEM — sharing one via HBM
        # would force the staged path; resolve auto/tuned the same way
        # layers do (tuned consults the autotune cache, heuristic on miss;
        # x is [E, C, D]: per-expert tables are [C, D]-shaped)
        share = mode == "lut_xla" or (
            mode == "lut_pallas"
            and L.resolve_fusion(x.shape[1], x.shape[2], quant or {})
            == "staged")

        def one(xe, gq, uq, dq):
            tbl = precompute_tables(xe, kg, tq) if share else None
            g = mpgemm(xe, gq, mode=mode, table_quant=tq, table=tbl,
                       fusion=fusion)
            u = mpgemm(xe, uq, mode=mode, table_quant=tq, table=tbl,
                       fusion=fusion)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
            return mpgemm(h, dq, mode=mode, table_quant=tq, fusion=fusion)

        return jax.vmap(one)(x, w["gate_qw"], w["up_qw"], w["down_qw"])
    gate, up, down = w["gate"], w["up"], w["down"]
    if quant and quant.get("qat"):
        bits = quant.get("weight_bits", 2)
        scheme = quant.get("scheme", "symmetric")
        # per-output-channel along the contraction dim
        gate = jnp.swapaxes(fake_quant(jnp.swapaxes(gate, 1, 2), bits, scheme), 1, 2)
        up = jnp.swapaxes(fake_quant(jnp.swapaxes(up, 1, 2), bits, scheme), 1, 2)
        down = jnp.swapaxes(fake_quant(jnp.swapaxes(down, 1, 2), bits, scheme), 1, 2)
    g = jnp.einsum("ecd,edf->ecf", x, gate.astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", x, up.astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, down.astype(x.dtype))


def _moe_mlp_shardmap(p: Params, x: jax.Array, cfg, quant, plan):
    """EP dispatch under shard_map (§Perf A1): routing is LOCAL per data
    shard, experts live on the model axis, and the ONLY collective is the
    final psum of partial outputs over the model axis (plus FSDP weight
    gathers for huge expert stacks).

    Under plain pjit the global scatter/gather dispatch replicates the
    [E·C, D] buffers through all-gathers/all-reduces (measured: olmoe
    train_4k spent 16.8 s/step in collectives — 134x its compute term).
    Dropping is per-(data shard, expert) with capacity T_loc·k·cf/E.
    """
    mesh = plan.mesh
    model_ax = plan.model
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.top_k
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    mp_ = sizes.get(model_ax, 1)
    batch_axes = plan.batch
    dp_ = 1
    for a in batch_axes:
        dp_ *= sizes.get(a, 1)
    e_loc = e // mp_
    t_loc = t // dp_
    cap = _capacity(t_loc, k, e, cfg.capacity_factor)
    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    fsdp_ax = plan.fsdp

    # in_specs: tokens batch-sharded; router replicated; experts E-sharded
    # over model (+ d_model over fsdp when enabled)
    xspec = P(bspec, None)
    espec = P(model_ax, fsdp_ax, None)
    dspec = P(model_ax, None, fsdp_ax)

    def body(xf, rw, gate, up, down, shared):
        # local routing
        logits = jnp.dot(xf.astype(jnp.float32), rw)          # [T_loc, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
        midx = jax.lax.axis_index(model_ax)
        e0 = midx * e_loc
        eid = top_i.reshape(-1)
        mine = (eid >= e0) & (eid < e0 + e_loc)
        eid_loc = jnp.where(mine, eid - e0, e_loc)            # e_loc = trash
        order = jnp.argsort(eid_loc)
        sorted_eid = eid_loc[order]
        counts = jnp.bincount(eid_loc, length=e_loc + 1)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(t_loc * k) - starts[sorted_eid]
        keep = (pos_in_e < cap) & (sorted_eid < e_loc)
        slot = jnp.minimum(sorted_eid, e_loc - 1) * cap + \
            jnp.minimum(pos_in_e, cap - 1)
        tok = order // k
        disp = jnp.zeros((e_loc * cap, d), xf.dtype)
        disp = disp.at[slot].add(jnp.where(keep[:, None], xf[tok], 0))

        if fsdp_ax:  # FSDP: gather this layer's expert shards over data
            gate = jax.lax.all_gather(gate, fsdp_ax, axis=1, tiled=True)
            up = jax.lax.all_gather(up, fsdp_ax, axis=1, tiled=True)
            down = jax.lax.all_gather(down, fsdp_ax, axis=2, tiled=True)
        out = _expert_ffn({"gate": gate, "up": up, "down": down},
                          disp.reshape(e_loc, cap, d), quant)
        out = out.reshape(e_loc * cap, d)

        gathered = jnp.where(keep[:, None], out[slot], 0)
        wsorted = top_p.reshape(-1)[order]
        y = jnp.zeros((t_loc, d), jnp.float32).at[tok].add(
            gathered.astype(jnp.float32) * wsorted[:, None])
        y = jax.lax.psum(y, model_ax)  # combine expert partials

        if shared is not None:
            sh_out = L.mlp_apply(shared, xf[None], quant)[0]
            y = y + sh_out.astype(jnp.float32)

        # aux: pmean the routing statistics BEFORE combining (mean of
        # products != product of means)
        f_e = jax.lax.pmean(jnp.mean(jax.nn.one_hot(top_i, e, dtype=jnp.float32),
                                     axis=(0, 1)) * e, batch_axes)
        p_e = jax.lax.pmean(jnp.mean(probs, axis=0), batch_axes)
        lb = e * jnp.sum(f_e / e * p_e)
        zl = jax.lax.pmean(
            jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
            batch_axes)
        return y.astype(xf.dtype), lb, zl

    # shared-expert MLP weights: replicated (small vs the expert stacks)
    shared = p.get("shared_mlp")
    shared_spec = None if shared is None else jax.tree.map(
        lambda _: P(), shared)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(), espec, espec, dspec, shared_spec),
        out_specs=(xspec, P(), P()),
        check_vma=False)
    y, lb, zl = fn(x.reshape(t, d), p["router"]["w"],
                   p["experts"]["gate"], p["experts"]["up"],
                   p["experts"]["down"], shared)
    return y.reshape(b, s, d), {"lb_loss": lb, "router_z_loss": zl}


def moe_mlp_apply(p: Params, x: jax.Array, cfg, quant=None):
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.top_k

    from repro.distributed.sharding import current_plan
    plan = current_plan()
    if plan is not None and "gate" in p.get("experts", {}):
        sizes = dict(zip(plan.mesh.axis_names, plan.mesh.devices.shape))
        mp_ = sizes.get(plan.model, 1)
        dp_ = 1
        for a in plan.batch:
            dp_ *= sizes.get(a, 1)
        if e % mp_ == 0 and t % dp_ == 0 and mp_ > 1:
            return _moe_mlp_shardmap(p, x, cfg, quant, plan)

    xf = x.reshape(t, d)

    logits = jnp.dot(xf.astype(jnp.float32), p["router"]["w"])  # [T, E] fp32
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)  # [T, k]
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch ------------------------------------------------
    eid = top_i.reshape(-1)                            # [T*k]
    order = jnp.argsort(eid)                           # stable
    sorted_eid = eid[order]
    counts = jnp.bincount(eid, length=e)               # [E]
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k) - starts[sorted_eid]
    cap = _capacity(t, k, e, cfg.capacity_factor)
    keep = pos_in_e < cap
    slot = sorted_eid * cap + jnp.minimum(pos_in_e, cap - 1)
    tok = order // k                                   # source token per assign

    disp = jnp.zeros((e * cap, d), x.dtype)
    disp = disp.at[slot].add(jnp.where(keep[:, None], xf[tok], 0))
    disp = shard(disp.reshape(e, cap, d), "expert", None, None)

    out = _expert_ffn(p["experts"], disp, quant)       # [E, C, D]
    out = shard(out, "expert", None, None).reshape(e * cap, d)

    # ---- combine ------------------------------------------------------------
    gathered = jnp.where(keep[:, None], out[slot], 0)  # [T*k, D]
    wsorted = top_p.reshape(-1)[order]
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(
        gathered.astype(jnp.float32) * wsorted[:, None])

    if "shared_mlp" in p:
        y = y + L.mlp_apply(p["shared_mlp"], xf, quant).astype(jnp.float32)

    # ---- aux losses ---------------------------------------------------------
    f_e = jnp.mean(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=(0, 1)) * e
    p_e = jnp.mean(probs, axis=0)
    lb_loss = e * jnp.sum(f_e / e * p_e)  # Switch-style
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"lb_loss": lb_loss, "router_z_loss": z_loss}
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# MoE block + LM
# ---------------------------------------------------------------------------

def block_init(key, cfg, dtype=jnp.float32) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": L.norm_init(cfg.d_model, dtype),
        "attn": L.attention_init(k1, cfg, dtype=dtype),
        "mlp_norm": L.norm_init(cfg.d_model, dtype),
        "moe": moe_mlp_init(k2, cfg, dtype),
    }


def block_apply(p: Params, h: jax.Array, cfg, *, cache=None, cache_pos=0,
                window=None, quant=None, page_table=None):
    a, cache = L.attention_apply(
        p["attn"], L.rms_norm(p["attn_norm"], h, cfg.norm_eps), cfg,
        kv_cache=cache, cache_pos=cache_pos, window=window, quant=quant,
        page_table=page_table)
    h = shard(h + a, "batch", "seq", None)
    m, aux = moe_mlp_apply(p["moe"], L.rms_norm(p["mlp_norm"], h, cfg.norm_eps),
                           cfg, quant)
    return shard(h + m, "batch", "seq", None), cache, aux


def _scan_block(p, h, cfg, cache, cache_pos, window, quant, page_table=None):
    h, cache, aux = block_apply(p, h, cfg, cache=cache, cache_pos=cache_pos,
                                window=window, quant=quant,
                                page_table=page_table)
    return h, cache, aux


def init(key, cfg, dtype=None) -> Params:
    dtype = dtype or cfg.param_dtype
    k_e, k_d, k_l, k_h = jax.random.split(key, 4)
    params = {
        "embed": TR.embed_init(k_e, cfg.vocab_size, cfg.d_model, dtype),
        "layers": TR.stack_init(k_l, cfg, cfg.n_layers - cfg.first_dense_layers,
                                block_init_fn=block_init, dtype=dtype),
        "final_norm": L.norm_init(cfg.d_model, dtype),
        "lm_head": L.dense_init(k_h, cfg.d_model, cfg.vocab_size, dtype=dtype),
    }
    if cfg.first_dense_layers:
        dcfg_ff = cfg.dense_d_ff or cfg.d_ff
        keys = jax.random.split(k_d, cfg.first_dense_layers)
        params["dense_layers"] = jax.vmap(
            lambda k: {
                "attn_norm": L.norm_init(cfg.d_model, dtype),
                "attn": L.attention_init(jax.random.fold_in(k, 0), cfg, dtype=dtype),
                "mlp_norm": L.norm_init(cfg.d_model, dtype),
                "mlp": L.mlp_init(jax.random.fold_in(k, 1), cfg.d_model,
                                  dcfg_ff, dtype=dtype),
            })(keys)
    return params


def forward(params: Params, batch, cfg, *, caches=None, cache_pos=0,
            window=None, token_valid=None,
            page_table=None) -> Tuple[jax.Array, Any, Dict]:
    del token_valid  # attention-only stack: see transformer.forward
    tokens = batch["tokens"]
    quant = cfg.quant
    h = TR.embed_apply(params["embed"], tokens).astype(cfg.activation_dtype)

    nd = cfg.first_dense_layers
    dense_caches = moe_caches = None
    if caches is not None:
        dense_caches = jax.tree.map(lambda c: c[:nd], caches)
        moe_caches = jax.tree.map(lambda c: c[nd:], caches)

    new_dense = None
    if nd:
        def dbody(carry, xs):
            hh = carry
            lp = xs if dense_caches is None else xs[0]
            lp = constrain_tree(lp)  # §Perf T1
            lc = None if dense_caches is None else xs[1]
            hh, nc = TR.block_apply(lp, hh, cfg, cache=lc, cache_pos=cache_pos,
                                    window=window, quant=quant,
                                    page_table=page_table)
            return hh, nc
        dbody = jax.checkpoint(dbody, prevent_cse=False)
        xs = (params["dense_layers"] if dense_caches is None
              else (params["dense_layers"], dense_caches))
        h, new_dense = jax.lax.scan(dbody, h, xs)

    def body(carry, xs):
        hh, lb, zl = carry
        lp = xs if moe_caches is None else xs[0]
        lp = constrain_tree(lp)  # §Perf T1
        lc = None if moe_caches is None else xs[1]
        hh, nc, aux = _scan_block(lp, hh, cfg, lc, cache_pos, window, quant,
                                  page_table)
        return (hh, lb + aux["lb_loss"], zl + aux["router_z_loss"]), nc

    body = jax.checkpoint(body, prevent_cse=False)
    xs = params["layers"] if moe_caches is None else (params["layers"], moe_caches)
    (h, lb, zl), new_moe = jax.lax.scan(body, (h, 0.0, 0.0), xs)

    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    logits = TR.head_apply(params["lm_head"], h, quant)
    n_moe = cfg.n_layers - nd
    aux = {"lb_loss": lb / n_moe, "router_z_loss": zl / n_moe}
    new_caches = None
    if caches is not None:
        if nd:
            new_caches = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), new_dense, new_moe)
        else:
            new_caches = new_moe
    return logits, new_caches, aux


def init_cache(cfg, batch: int, s_cache: int, window=None, dtype=jnp.bfloat16):
    return kvcache.attn_cache(cfg.n_layers, batch, s_cache, cfg.n_kv_heads,
                              cfg.head_dim, dtype, window)
