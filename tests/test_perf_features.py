"""Tests for the §Perf optimization features: int8 KV cache, offline-CW
weight format, flash-decode shard_map, shard_map MoE dispatch."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import quantize as Q
from repro.kernels import ref
from repro.models import api

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_sub(code, devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    # forced host devices exist only on the CPU backend; pinning it also
    # skips the accelerator-plugin probe (a sleep-poll that starves 1-cpu
    # boxes)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, f"OUT:\n{r.stdout}\nERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_int8_kv_cache_close_to_fp():
    cfg = registry.get_reduced("tinyllama-1.1b").replace(
        activation_dtype=jnp.float32).with_quant(weight_bits=4)
    params = api.init_params(jax.random.key(0), cfg, serve_quantized=True)
    b, s = 2, 12
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (b, s + 1)), jnp.int32)

    def run(dtype):
        caches = api.init_cache(cfg, b, s + 1, dtype=dtype)
        _, caches, _ = api.forward(params, {"tokens": toks[:, :s]}, cfg,
                                   caches=caches, cache_pos=0)
        lg, _, _ = api.forward(params, {"tokens": toks[:, s:]}, cfg,
                               caches=caches, cache_pos=s)
        return np.asarray(lg[:, 0], np.float32)

    ref_l, i8_l = run(jnp.float32), run("int8")
    cc = np.corrcoef(ref_l.ravel(), i8_l.ravel())[0, 1]
    assert cc > 0.999, cc


def test_cw_format_exact_vs_packed():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    qw = Q.quantize(w, 2, k_group=2)
    qcw = Q.to_cw_format(qw)
    assert qcw.packed is None and qcw.cw.dtype == jnp.int8
    o1 = ref.ref_lut_mpgemm_matmul(a, qw, table_quant="per_row")
    o2 = ref.ref_lut_mpgemm_matmul(a, qcw, table_quant="per_row")
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_cw_bytes_accounting():
    """CW store at W2/K=2 is exactly 1 byte/weight (4x packed, 2x smaller
    than bf16). K=512 is one whole packing chunk (256 groups), where packed
    storage carries no padding."""
    w = jnp.asarray(np.random.default_rng(1).normal(size=(128, 512)),
                    jnp.float32)
    qw = Q.quantize(w, 2, k_group=2)
    qcw = Q.to_cw_format(qw)
    assert qcw.cw.size == w.size  # [K, N] int8
    assert qw.packed.size * 4 == w.size  # 2 bits/weight


def test_flash_decode_matches_chunked_8dev():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import registry
    from repro.distributed.sharding import AxisPlan, plan_scope
    from repro.models import api
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = registry.get_reduced("qwen2-72b").replace(activation_dtype=jnp.float32)
    params = api.init_params(jax.random.key(0), cfg)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = AxisPlan(mesh=mesh, batch=("data",), fsdp=None)
    b, s_cache = 4, 32  # 32 % 4 == 0 -> flash path eligible
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (b, 9)), jnp.int32)
    caches = api.init_cache(cfg, b, s_cache, dtype=jnp.float32)
    _, caches, _ = api.forward(params, {"tokens": toks[:, :8]}, cfg,
                               caches=caches, cache_pos=0)
    # no-plan decode (chunked path)
    lg_ref, _, _ = api.forward(params, {"tokens": toks[:, 8:]}, cfg,
                               caches=caches, cache_pos=8)
    # plan decode (flash_decode_shardmap path)
    def fn(params, caches, t):
        with plan_scope(plan):
            return api.forward(params, {"tokens": t}, cfg, caches=caches,
                               cache_pos=8)[0]
    lg = jax.jit(fn)(params, caches, toks[:, 8:])
    np.testing.assert_allclose(np.asarray(lg, np.float32),
                               np.asarray(lg_ref, np.float32),
                               rtol=2e-3, atol=2e-3)
    print("OK")
    """
    assert "OK" in _run_sub(code)


def test_moe_shardmap_matches_global_8dev():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import registry
    from repro.distributed.sharding import AxisPlan, plan_scope
    from repro.models import api
    from repro.models.moe import moe_mlp_apply

    # dropless capacity so both dispatch semantics agree exactly
    cfg = registry.get_reduced("olmoe-1b-7b").replace(
        activation_dtype=jnp.float32, capacity_factor=64.0)
    params = api.init_params(jax.random.key(0), cfg)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = AxisPlan(mesh=mesh, batch=("data",), fsdp="data")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8, cfg.d_model)),
                    jnp.float32) * 0.3
    moe_p = jax.tree.map(lambda p: p[0], params["layers"])["moe"]
    y_ref, aux_ref = moe_mlp_apply(moe_p, x, cfg, None)

    def fn(p, x):
        with plan_scope(plan):
            return moe_mlp_apply(p, x, cfg, None)
    y, aux = jax.jit(fn)(moe_p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(float(aux["lb_loss"]), float(aux_ref["lb_loss"]),
                               rtol=1e-4)
    print("OK")
    """
    assert "OK" in _run_sub(code)


def test_hlo_cost_loop_awareness():
    """The roofline cost walker multiplies while bodies by trip counts."""
    from repro.roofline import hlo_cost

    def scanned(x, w):
        def body(c, wi):
            return c @ wi, None
        return jax.lax.scan(body, x, w)[0]

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    c = jax.jit(scanned).lower(x, w).compile()
    cost = hlo_cost.analyze_text(c.as_text())
    assert cost.flops == 8 * 2 * 128 ** 3  # 8 iterations, not 1
