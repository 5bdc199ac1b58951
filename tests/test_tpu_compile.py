"""Compile rehearsals for one TPU v5e chip, at the widths the chip serves.

Nothing here runs on a chip: every test compiles for a *described*
``v5e:2x2`` topology with the TPU compiler that ships with jax, which
refuses what interpret mode never checks — blocks off the (sublane, lane)
tiling, layouts Mosaic cannot lower, more VMEM than the limit, a program
that does not fit the chip's HBM. Shapes are paper-bitnet-3b's (the
paper's evaluation model): 3200->3200 (q/k/v/o), 3200->8640 (gate/up),
8640->3200 (down), at decode rows (a --max-batch of 8) and a 32-row
prefill chunk.

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quantize as Q
from repro.core.mpgemm import mpgemm
from repro.kernels import ops

SHAPES = [(3200, 3200), (3200, 8640), (8640, 3200)]  # (K, N)
QWEN_SHAPES = [(8192, 8192), (8192, 1024), (8192, 29568), (29568, 8192),
               (8192, 152064)]  # q/o, k/v, gate/up, down, LM head
ROWS = [8, 32]
HBM_BYTES = 16 * 2 ** 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: a compile
    for a described device is written to it but can never be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _ternary(sharding, k, n, scheme="ternary"):
    w = jax.ShapeDtypeStruct((n, k), jnp.float32)
    return _on(sharding, jax.eval_shape(
        lambda w: Q.quantize(w, 2, 4, scheme), w))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("k,n", SHAPES)
@pytest.mark.parametrize("kernel", ["table_precompute", "staged", "fused"])
def test_kernel_compiles_for_v5e(one_chip, kernel, k, n, m):
    """Each Pallas LUT kernel, per-row int8 tables, lowers to a Mosaic
    kernel (``tpu_custom_call``) under the chip's compiler."""
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    if kernel == "table_precompute":
        c = _compile(functools.partial(ops.table_precompute, k_group=4,
                                       table_quant="per_row"), x)
    else:
        c = _compile(functools.partial(ops.lut_mpgemm, table_quant="per_row",
                                       fusion=kernel),
                     x, _ternary(one_chip, k, n))
    assert "tpu_custom_call" in c.as_text()


def _lut_xla(one_chip, m, k, n, scheme):
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    return _compile(lambda x, q: mpgemm(x, q, mode="lut_xla",
                                        table_quant="per_row"),
                    x, _ternary(one_chip, k, n, scheme))


def test_lut_xla_mpgemm_compiles_for_v5e(one_chip, monkeypatch):
    """The default mode on the chip: the XLA table precompute, then the
    lookup kernel over the packed planes (a Mosaic custom call) in place of
    an XLA-built CW and int8 GEMM."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = _lut_xla(one_chip, 8, 3200, 8640, "ternary")
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("k,n", QWEN_SHAPES)
def test_lut_xla_qwen_shapes_compile_for_v5e(one_chip, monkeypatch, k, n):
    """qwen2-72b's W2 projections and its 152064-row LM head (a ragged last
    N block) at decode rows: the lookup kernel lowers for the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = _lut_xla(one_chip, 8, k, n, "symmetric")
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("mode", ["lut_xla", "lut_pallas"])
def test_bitnet_decode_step_fits_one_chip(one_chip, monkeypatch, mode):
    """The engine's greedy decode chunk at full paper-bitnet-3b width (26
    layers, 8 slots x 128 positions) compiles for one chip and fits its
    16 GiB; both programs carry the kernels (lut_xla its lookup kernel)."""
    from repro.configs import registry
    from repro.models import api
    from repro.serving.engine import ServingEngine

    # take the chip's branches while tracing (int8 per-row tables, no CPU
    # codeword hoist, no interpret mode): jax here only sees the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = registry.get_config("paper-bitnet-3b").replace(
        activation_dtype=jnp.float32).with_quant(mpgemm_mode=mode)
    params = jax.eval_shape(
        lambda key: api.init_params(key, cfg, serve_quantized=True),
        jax.random.key(0))
    eng = ServingEngine(cfg, params, max_batch=8, max_seq=128,
                        decode_chunk=8, prefill_chunk=32)
    c = eng._decode.lower(_on(one_chip, params),
                          _on(one_chip, eng.state)).compile()
    ma = c.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert live < HBM_BYTES, live
    assert "tpu_custom_call" in c.as_text()
