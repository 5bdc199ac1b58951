"""Dense decoder-only transformer (BitNet b1.58 / Llama / Qwen2 layout).

Three things the benchmark owns for every configuration of this family,
none of which imports the system under test:

* the seeded quantized checkpoint: integer weight codes and per-output-
  channel scales for every projection (the dequantized weight is
  ``codes * scale``), and the float leaves (embedding, norm gains, QKV
  biases) in the configuration's parameter dtype;
* the plain reference: a float32 ``jax.numpy`` forward pass at
  ``precision="highest"`` over whole sequences, with no cache, chunking or
  batching of the program, and its lower-precision control (int4 per-row
  activations at every projection input);
* the operations and bytes a decode step or a prefill needs, at the dtypes
  the configuration states.

Weights are drawn block by block from ``jax.random`` keys folded from the
seed, so the program's loader and the reference draw the same values
without sharing any array.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# largest vocabulary block drawn at once (embedding rows, LM-head rows)
VOCAB_BLOCK = 8192
_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "bf16": 2, "float16": 2,
                "int8": 1}


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def proj_shapes(conf) -> dict:
    """Projection name -> (n_out, k_in, has_bias)."""
    d, hd, ff = conf["d_model"], conf["head_dim"], conf["d_ff"]
    qd, kvd = conf["n_heads"] * hd, conf["n_kv_heads"] * hd
    bias = bool(conf["qkv_bias"])
    return {"wq": (qd, d, bias), "wk": (kvd, d, bias), "wv": (kvd, d, bias),
            "wo": (d, qd, False), "gate": (ff, d, False),
            "up": (ff, d, False), "down": (d, ff, False)}


def vocab_blocks(vocab: int) -> tuple:
    """(blocks, rows per block): the fewest equal blocks of at most
    ``VOCAB_BLOCK`` rows."""
    nb = next(n for n in range(1, vocab + 1)
              if vocab % n == 0 and vocab // n <= VOCAB_BLOCK)
    return nb, vocab // nb


def layer_weights_count(conf) -> int:
    return sum(n * k for n, k, _ in proj_shapes(conf).values())


def weights_applied(conf) -> int:
    """Weights a decoded token applies: every layer's projections and the
    LM head."""
    return (conf["n_layers"] * layer_weights_count(conf)
            + conf["vocab_size"] * conf["d_model"])


def output_channels(conf) -> int:
    """Output channels of those weights (one float32 scale each)."""
    return (conf["n_layers"] * sum(n for n, _, _ in
                                   proj_shapes(conf).values())
            + conf["vocab_size"])


# ---------------------------------------------------------------------------
# the seeded checkpoint
# ---------------------------------------------------------------------------

def root_key(seed_lo, seed_hi):
    """Key from the two 32-bit halves of ``--seed`` (traced or not)."""
    return jax.random.fold_in(jax.random.key(seed_lo), seed_hi)


def split_seed(seed: int):
    """``--seed`` (any non-negative int below 2**64) -> two uint32 words."""
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32)
                                                      & 0xFFFFFFFF))


def _codes(key, conf, n, k):
    """Integer codes [n, k] int8 and scales [n] float32 of one weight.

    Ternary (BitNet b1.58): codes in {-1, 0, 1}, each a third of the time,
    scale ``u / sqrt(2k/3)``. Symmetric B-bit: the odd grid
    {-(2^B-1), ..., 2^B-1}, scale ``u / sqrt(k E[c^2])``. ``u`` is uniform in
    [0.75, 1.25], so every output has about unit variance for unit inputs.
    """
    q = conf["quant"]
    kc, ks = jax.random.split(key)
    u = jax.random.uniform(ks, (n,), jnp.float32, 0.75, 1.25)
    if q["scheme"] == "ternary":
        codes = jax.random.randint(kc, (n, k), -1, 2, jnp.int8)
        return codes, u / math.sqrt(k * 2.0 / 3.0)
    if q["scheme"] != "symmetric":
        raise ValueError(f"unsupported weight scheme {q['scheme']!r}")
    levels = 1 << q["weight_bits"]
    codes = (2 * jax.random.randint(kc, (n, k), 0, levels, jnp.int8)
             - (levels - 1)).astype(jnp.int8)
    return codes, u / math.sqrt(k * (levels * levels - 1) / 3.0)


def _param(x, conf):
    return x.astype(jnp.dtype(conf["param_dtype"]))


def layer_weights(conf, key_root, layer):
    """One layer: {proj: (codes, scale, bias or None)} and its norm gains."""
    kl = jax.random.fold_in(jax.random.fold_in(key_root, 1), layer)
    out = {}
    for i, (name, (n, k, bias)) in enumerate(proj_shapes(conf).items()):
        kp = jax.random.fold_in(kl, 10 + i)
        codes, scale = _codes(kp, conf, n, k)
        b = (_param(0.1 * jax.random.normal(jax.random.fold_in(kp, 1), (n,)),
                    conf) if bias else None)
        out[name] = (codes, scale, b)
    d = conf["d_model"]
    for j, name in enumerate(("attn_norm", "mlp_norm")):
        out[name] = _param(jax.random.uniform(
            jax.random.fold_in(kl, 1 + j), (d,), jnp.float32, 0.8, 1.2), conf)
    return out


def embed_block(conf, key_root, block):
    """Rows ``[block*R, (block+1)*R)`` of the embedding, in param dtype."""
    _, rows = vocab_blocks(conf["vocab_size"])
    kb = jax.random.fold_in(jax.random.fold_in(key_root, 2), block)
    return _param(jax.random.normal(kb, (rows, conf["d_model"])), conf)


def head_block(conf, key_root, block):
    """Codes and scales of LM-head rows ``[block*R, (block+1)*R)``."""
    _, rows = vocab_blocks(conf["vocab_size"])
    kb = jax.random.fold_in(jax.random.fold_in(key_root, 3), block)
    return _codes(kb, conf, rows, conf["d_model"])


def final_norm(conf, key_root):
    return _param(jax.random.uniform(jax.random.fold_in(key_root, 4),
                                     (conf["d_model"],), jnp.float32, 0.8,
                                     1.2), conf)


def dequant(codes, scale):
    return codes.astype(jnp.float32) * scale[:, None]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _fake_quant_rows(x, bits):
    """Per-row absmax integer quantization of activations (the control)."""
    if bits is None:
        return x
    qmax = (1 << (bits - 1)) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / qmax
    return jnp.round(x / s) * s


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotate-half RoPE over [B, S, H, hd] at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs  # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(conf, w, h, act_bits):
    """One pre-norm block: causal GQA attention, then SwiGLU."""
    b, s, d = h.shape
    hd, nh, nkv = conf["head_dim"], conf["n_heads"], conf["n_kv_heads"]
    eps = conf["norm_eps"]

    def proj(name, x):
        codes, scale, bias = w[name]
        y = _fake_quant_rows(x, act_bits) @ dequant(codes, scale).T
        return y if bias is None else y + bias.astype(jnp.float32)

    x = _rms_norm(h, w["attn_norm"].astype(jnp.float32), eps)
    q = _rope(proj("wq", x).reshape(b, s, nh, hd), conf["rope_theta"])
    k = _rope(proj("wk", x).reshape(b, s, nkv, hd), conf["rope_theta"])
    v = proj("wv", x).reshape(b, s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=2)  # head h reads kv head h // rep
    v = jnp.repeat(v, nh // nkv, axis=2)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, nh * hd)
    h = h + proj("wo", o)
    x = _rms_norm(h, w["mlp_norm"].astype(jnp.float32), eps)
    return h + proj("down", jax.nn.silu(proj("gate", x)) * proj("up", x))


def reference_logits(conf, seed_words, tokens, rows, cols, act_bits=None):
    """Logits [P, V] at positions ``(rows[p], cols[p])`` of ``tokens``.

    ``tokens`` [B, S] int32, right-padded (causal attention never reads the
    padding). ``act_bits`` None is the reference; 4 is its control.
    """
    with jax.default_matmul_precision("highest"):
        key = root_key(*seed_words)
        nb, rows_per = vocab_blocks(conf["vocab_size"])

        def embed(h, blk):
            e = embed_block(conf, key, blk).astype(jnp.float32)
            hit = (tokens // rows_per) == blk
            return h + jnp.where(hit[..., None],
                                 e[jnp.where(hit, tokens % rows_per, 0)],
                                 0.0), None

        h0 = jnp.zeros(tokens.shape + (conf["d_model"],), jnp.float32)
        h, _ = jax.lax.scan(embed, h0, jnp.arange(nb))

        def layer(h, l):
            return _block(conf, layer_weights(conf, key, l), h, act_bits), None

        h, _ = jax.lax.scan(layer, h, jnp.arange(conf["n_layers"]))
        h = _rms_norm(h, final_norm(conf, key).astype(jnp.float32),
                      conf["norm_eps"])
        hp = _fake_quant_rows(h[rows, cols], act_bits)  # [P, d]

        def head(_, blk):
            codes, scale = head_block(conf, key, blk)
            return None, hp @ dequant(codes, scale).T

        _, out = jax.lax.scan(head, None, jnp.arange(nb))  # [nb, P, R]
        return jnp.moveaxis(out, 0, 1).reshape(hp.shape[0], -1)


# ---------------------------------------------------------------------------
# operations and bytes, at the dtypes the configuration states
# ---------------------------------------------------------------------------

def weight_bytes(conf) -> int:
    """Projection and LM-head weights at ``weight_bits`` over the true K."""
    return weights_applied(conf) * conf["quant"]["weight_bits"] // 8


def step_fixed_bytes(conf) -> int:
    """Bytes every decode step reads whatever the batch: weights, their
    float32 per-channel scales, norm gains and biases."""
    pb = _DTYPE_BYTES[conf["param_dtype"]]
    small = conf["n_layers"] * (2 * conf["d_model"]
                                + sum(n for n, _, b in
                                      proj_shapes(conf).values() if b))
    small += conf["d_model"]
    return weight_bytes(conf) + 4 * output_channels(conf) + pb * small


def kv_bytes_per_position(conf) -> int:
    return (conf["n_layers"] * 2 * conf["n_kv_heads"] * conf["head_dim"]
            * _DTYPE_BYTES[conf["kv_cache_dtype"]])


def flops_per_token(conf, attended: int) -> int:
    """Decode FLOPs of one token that attends over ``attended`` positions:
    2 per weight applied (layers and LM head) plus QK^T and PV."""
    attn = (4 * conf["n_layers"] * conf["n_heads"] * conf["head_dim"]
            * attended)
    return 2 * weights_applied(conf) + attn


def decode_step_cost(conf, attended) -> tuple:
    """(FLOPs, bytes) of one decode step whose live tokens attend over
    ``attended`` positions each (one entry per live slot)."""
    flops = sum(flops_per_token(conf, a) for a in attended)
    pb = _DTYPE_BYTES[conf["param_dtype"]]
    kv = kv_bytes_per_position(conf)
    # read the live cache (the new position included), write one position,
    # read one embedding row per live slot
    nbytes = (step_fixed_bytes(conf) + kv * sum(attended)
              + len(attended) * (kv + pb * conf["d_model"]))
    return flops, nbytes


def mpgemm_step_cost(conf, rows: int) -> tuple:
    """(FLOPs, bytes) of one decode step's mpGEMMs (the seven projections
    of every layer and the LM head) over ``rows`` live rows.

    FLOPs: 2 per weight applied per row. Bytes: the weights at
    ``weight_bits`` over the true K, their float32 scales, each row's int8
    tables and its float32 outputs. A row's table over an input of width K
    is the paper's symmetrized half table (section 3.1): K / g groups of
    2^(g-1) int8 entries, g = ``k_group`` (2K bytes at g = 4). Projections
    that read one input share its table (the paper's split-out precompute):
    one for q/k/v, one for gate/up, one each for o, down and the head.
    """
    d, g = conf["d_model"], conf["quant"]["k_group"]
    table_inputs = conf["n_layers"] * (
        2 * d + conf["n_heads"] * conf["head_dim"] + conf["d_ff"]) + d
    outputs = output_channels(conf)
    nbytes = (weight_bytes(conf) + 4 * outputs
              + rows * (table_inputs * (1 << (g - 1)) // g + 4 * outputs))
    return 2 * rows * weights_applied(conf), nbytes


def prefill_flops(conf, n_tokens: int) -> int:
    """FLOPs to prefill ``n_tokens`` prompt tokens from position 0: the
    layers only (no logits are needed for them), causal attention."""
    w = conf["n_layers"] * layer_weights_count(conf)
    pairs = n_tokens * (n_tokens + 1) // 2
    return (2 * w * n_tokens
            + 4 * conf["n_layers"] * conf["n_heads"] * conf["head_dim"] * pairs)
