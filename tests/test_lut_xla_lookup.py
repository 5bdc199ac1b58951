"""lut_xla's T @ CW contraction on the TPU branch: the lookup kernel over the
packed planes (``kernels/ops.lut_mpgemm(table=...)``) in place of the CW
matrix built by XLA.

The branch is taken from what the call can observe (backend, table
precision, store, plane slicing, plan), so these tests pretend to be on a
TPU (``jax.default_backend`` patched, as ``test_tpu_compile.py`` does) and
run the kernel in interpret mode. Each kernel-path output must be
bit-identical to the XLA path's: same table, int32 accumulation, epilogue
``(acc · ts) · ws`` and zero-point correction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import registry
from repro.core import quantize as Q
from repro.core.mpgemm import mpgemm, precompute_tables
from repro.distributed.sharding import AxisPlan, plan_scope
from repro.kernels import ref
from repro.models import api, layers
from repro.obs import dispatch as dispatch_obs
from repro.serving.engine import ServingEngine

K, N = 640, 200  # two packing chunks (the second ragged); N off the 128 grid
QUANT = {"mpgemm_mode": "lut_xla", "table_quant": "per_row", "k_group": 4}


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _weight(scheme):
    w = jax.random.normal(jax.random.key(0), (N, K), jnp.float32)
    return Q.quantize(w, 2, 4, scheme)


def _xla_path(x2d, qw, table):
    return ref.ref_lut_mpgemm_matmul(x2d, qw, table_quant="per_row",
                                     table=table)


@pytest.mark.parametrize("shared", [False, True], ids=["own", "shared"])
@pytest.mark.parametrize("m", [8, 32])
@pytest.mark.parametrize("scheme", ["ternary", "symmetric"])
def test_kernel_path_bit_identical(on_tpu, scheme, m, shared):
    """Ternary (two ±1 planes) and W2 symmetric (odd grid), decode and
    prefill rows, a table built per call and one shared through
    ``layers.make_table``: the kernel path equals the XLA path exactly."""
    qw = _weight(scheme)
    x = jax.random.normal(jax.random.key(1), (2, m // 2, K), jnp.float32)
    table = layers.make_table(x, QUANT) if shared else None
    with dispatch_obs.recording() as rec:
        y = mpgemm(x, qw, mode="lut_xla", table_quant="per_row", table=table,
                   interpret=True)
    assert rec.summary()["lut_xla"] == 1  # the kernel path ran
    x2d = x.reshape(m, K)
    t = table if shared else precompute_tables(x2d, 4, "per_row")
    want = _xla_path(x2d, qw, t).reshape(2, m // 2, N)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


@pytest.mark.parametrize("case", ["cw_store", "plane_sliced", "tp_plan"])
def test_fallbacks_take_xla_path(on_tpu, case):
    """A hoisted CW store, a plane-sliced draft view and a plan of several
    devices keep the XLA-built CW: no kernel decision, same output."""
    qw = _weight("symmetric")
    if case == "cw_store":
        qw = Q.to_cw_format(qw)
    elif case == "plane_sliced":
        qw = qw.plane_slice(1)
    x = jax.random.normal(jax.random.key(2), (8, K), jnp.float32)
    plan = (AxisPlan(AbstractMesh((1, 4), ("data", "model")))
            if case == "tp_plan" else None)
    with dispatch_obs.recording() as rec, plan_scope(plan):
        y = mpgemm(x, qw, mode="lut_xla", table_quant="per_row",
                   interpret=True)
    assert rec.summary()["decisions"] == 0
    want = _xla_path(x, qw, precompute_tables(x, 4, "per_row"))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_engine_counts_kernel_path_decisions(monkeypatch, backend):
    """With a recorder active, the decode and prefill programs of a
    pretend-TPU engine each put the 7 projections of a layer and the LM
    head on the kernel path; the CPU engine (hoisted CW) puts none."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = registry.get_reduced("paper-bitnet-3b").replace(
        activation_dtype=jnp.float32, n_layers=2)
    params = api.init_params(jax.random.key(0), cfg, serve_quantized=True)
    with dispatch_obs.recording():
        eng = ServingEngine(cfg, params, max_batch=2, max_seq=32,
                            decode_chunk=2, prefill_chunk=8)
        tok = jnp.zeros((1, eng.prefill_chunk), jnp.int32)
        eng._decode.trace(eng.params, eng.state)
        eng._prefill.trace(eng.params, eng._zero_slot, tok, np.int32(0),
                           np.int32(1))
        counts = eng.stats()["dispatch"]
    assert counts["lut_xla"] == (16 if backend == "tpu" else 0), counts
