"""Distribution-layer tests.

Sharding-rule unit tests run in-process; anything needing multiple devices
(pjit train step, pipeline parallelism, sharded decode) runs in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the
dry-run owns the 512-device configuration; tests stay small).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.distributed import sharding as SH
from repro.models import api

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_sub(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    # forced host devices exist only on the CPU backend; pinning it
    # also skips the accelerator-plugin probe (a sleep-poll loop that
    # starves 1-cpu boxes)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# in-process: spec rules
# ---------------------------------------------------------------------------

def test_param_spec_rules():
    cfg = registry.get_reduced("tinyllama-1.1b")
    params = api.init_params(jax.random.key(0), cfg)
    specs = SH.param_spec_tree(params)
    # attention qkv column-parallel, o row-parallel, embed vocab-sharded
    assert specs["layers"]["attn"]["wq"]["w"] == (None, "fsdp", "model")
    assert specs["layers"]["attn"]["wo"]["w"] == (None, "model", "fsdp")
    assert specs["embed"]["table"] == ("model", "fsdp")
    assert specs["layers"]["mlp"]["down"]["w"] == (None, "model", "fsdp")
    assert specs["final_norm"]["g"] == (None,)


def test_moe_expert_specs():
    cfg = registry.get_reduced("olmoe-1b-7b")
    params = api.init_params(jax.random.key(0), cfg)
    specs = SH.param_spec_tree(params)
    assert specs["layers"]["moe"]["experts"]["up"] == \
        (None, "expert", "fsdp", None)
    assert specs["layers"]["moe"]["router"]["w"] == (None, None, "expert")


def test_divisibility_fallback_replicates():
    """A dim not divisible by its mesh axis must fall back to replication."""
    code = """
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import AxisPlan, named_sharding_tree
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = AxisPlan(mesh=mesh, batch=("data",), fsdp="data")
    params = {"attn": {"wq": {"w": jnp.zeros((6, 10))}}}  # 10 % 4 != 0
    sh = named_sharding_tree(params, plan)
    assert sh["attn"]["wq"]["w"].spec == P("data", None), sh
    print("OK")
    """
    assert "OK" in _run_sub(code)


# ---------------------------------------------------------------------------
# subprocess: 8-device pjit train step + sharded decode
# ---------------------------------------------------------------------------

def test_pjit_train_step_8dev():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import registry
    from repro.distributed.sharding import AxisPlan, plan_scope
    from repro.training import optimizer as O
    from repro.training.train_loop import (init_train_state, make_train_step,
                                           train_shardings)
    from repro.training.data import SyntheticLM

    cfg = registry.get_reduced("tinyllama-1.1b").replace(
        activation_dtype=jnp.float32)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = AxisPlan(mesh=mesh, batch=("data",), fsdp="data")
    opt = O.make_optimizer("adamw", lr=3e-3)
    state = init_train_state(jax.random.key(0), cfg, opt)
    sh = train_shardings(state, plan)
    state = jax.tree.map(jax.device_put, state, sh)
    step = make_train_step(cfg, opt)

    def fn(state, batch):
        with plan_scope(plan):
            return step(state, batch)

    data = SyntheticLM(cfg.vocab_size, 4, 16)
    batch = jax.tree.map(jnp.asarray, data.batch_at(0))
    jfn = jax.jit(fn, donate_argnums=(0,))
    losses = []
    for s in range(16):
        batch = jax.tree.map(jnp.asarray, data.batch_at(s))
        state, m = jfn(state, batch)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4]), losses
    # params stay sharded
    wq = state["params"]["layers"]["attn"]["wq"]["w"]
    assert not wq.sharding.is_fully_replicated
    print("OK", losses[0], "->", losses[-1])
    """
    out = _run_sub(code)
    assert "OK" in out


def test_sharded_quantized_decode_8dev():
    """Packed low-bit weights shard over the model axis and decode runs
    under pjit — the serving dry-run path at test scale."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import registry
    from repro.distributed.sharding import AxisPlan, named_sharding_tree, plan_scope
    from repro.models import api

    cfg = registry.get_reduced("qwen2-72b").replace(activation_dtype=jnp.float32)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = AxisPlan(mesh=mesh, batch=("data",), fsdp=None)
    params = api.init_params(jax.random.key(0), cfg, serve_quantized=True)
    sh = named_sharding_tree(params, plan)
    params = jax.tree.map(jax.device_put, params, sh)
    caches = api.init_cache(cfg, 4, 32, dtype=jnp.float32)

    def decode(params, caches, tokens, pos):
        with plan_scope(plan):
            logits, nc, _ = api.forward(params, {"tokens": tokens}, cfg,
                                        caches=caches, cache_pos=pos)
            return logits[:, -1], nc

    toks = jnp.zeros((4, 1), jnp.int32)
    logits, caches = jax.jit(decode)(params, caches, toks, 0)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    print("OK")
    """
    assert "OK" in _run_sub(code)


def test_pipeline_parallel_4stage():
    """GPipe pipeline == sequential stack on 4 pp-shards."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipelined_forward, split_stages
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("pp",))
    L, D = 8, 16
    key = jax.random.key(0)
    ws = jax.random.normal(key, (L, D, D)) * (D ** -0.5)

    def layer(w, x):
        return jnp.tanh(x @ w)

    def stage_fn(stage_params, x):
        def body(c, w):
            return layer(w, c), None
        y, _ = jax.lax.scan(body, x, stage_params)
        return y

    x = jax.random.normal(jax.random.key(1), (6, 4, D))  # [n_micro, mb, D]

    # sequential reference
    def seq(x2):
        def body(c, w):
            return layer(w, c), None
        y, _ = jax.lax.scan(body, x2, ws)
        return y
    want = jax.vmap(seq)(x)

    staged = split_stages({"w": ws}, 4)["w"]
    got = pipelined_forward(stage_fn, staged, x, mesh=mesh, n_stages=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    print("OK")
    """
    assert "OK" in _run_sub(code, devices=4)


def test_multipod_mesh_shapes():
    code = """
    import os
    from repro.launch.mesh import make_production_mesh, make_plan
    m1 = make_production_mesh()
    assert m1.devices.shape == (16, 16) and m1.axis_names == ("data", "model")
    m2 = make_production_mesh(multi_pod=True)
    assert m2.devices.shape == (2, 16, 16)
    assert m2.axis_names == ("pod", "data", "model")
    plan = make_plan(m2)
    assert plan.batch == ("pod", "data")
    print("OK")
    """
    assert "OK" in _run_sub(code, devices=512)
