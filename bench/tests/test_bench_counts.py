"""Operations and bytes per configuration, pinned by hand arithmetic."""

import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.models import dense_transformer as M  # noqa: E402


def _conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


BITNET, QWEN = _conf("paper-bitnet-3b"), _conf("qwen2-72b-l16")


def test_bitnet_true_2bit_weight_bytes():
    # 26 x (4 x 3200^2 + 3 x 3200 x 8640) + 32000 x 3200 = 3,323,904,000
    # weights at 2 bits
    assert M.weight_bytes(BITNET) == 830_976_000


def test_bitnet_padded_layout_is_larger():
    """The program stores K in whole 128-group chunks: 910,458,880 bytes of
    packed planes, 9.6% more than the roofline counts."""
    import jax
    from repro.configs import registry
    from repro.models import api

    cfg = registry.get_config("paper-bitnet-3b")
    specs = api.param_specs(cfg, serve_quantized=True)
    packed = sum(math.prod(x.shape) * x.dtype.itemsize
                 for p, x in jax.tree_util.tree_flatten_with_path(specs)[0]
                 if jax.tree_util.keystr(p).endswith(".packed"))
    assert packed == 910_458_880
    assert packed / M.weight_bytes(BITNET) == pytest.approx(1.0956, abs=1e-4)


def test_qwen2_72b_l6_decode_step():
    """The qwen2-72b cut as configured (16 of 80 layers)."""
    # per layer: 2 x 8192^2 (q, o) + 2 x 1024 x 8192 (k, v)
    #            + 3 x 29568 x 8192 (gate, up, down) = 877,658,112 weights
    # 16 layers + the 152064 x 8192 head = 15,288,238,080 weights
    assert QWEN["n_layers"] == 16
    assert M.layer_weights_count(QWEN) == 877_658_112
    assert M.weight_bytes(QWEN) == 15_288_238_080 // 4
    # + float32 scales: 16 x 85,760 + 152,064 channels
    # + bf16 norms and biases: 16 x (2 x 8192 + 10240) + 8192
    assert M.step_fixed_bytes(QWEN) == (3_822_059_520 + 4 * 1_524_224
                                        + 2 * 434_176)
    # KV: 16 layers x (k, v) x 8 heads x 128 x bf16
    assert M.kv_bytes_per_position(QWEN) == 65_536
    flops, nbytes = M.decode_step_cost(QWEN, [100, 200])
    assert flops == 2 * (2 * 15_288_238_080) + 4 * 16 * 64 * 128 * 300
    assert nbytes == (3_829_024_768 + 65_536 * 300
                      + 2 * (65_536 + 2 * 8192))


def test_bitnet_decode_step_is_memory_bound():
    flops, nbytes = M.decode_step_cost(BITNET, [256] * 8)
    t_flop, t_byte = flops / 197e12, nbytes / 819e9
    assert t_byte > 5 * t_flop
    # 8 slots at 256 positions: 2 x 3.32 G weights + attention per token
    assert flops == 8 * (2 * 3_323_904_000 + 4 * 26 * 32 * 100 * 256)


@pytest.mark.parametrize("conf,want", [
    # bitnet: tables over 26 x (3200 q/k/v + 3200 o + 3200 gate/up
    # + 8640 down) + 3200 head = 477,440 inputs, 2 int8 bytes each (4-groups
    # of 8 entries); outputs 26 x 33,280 + 32,000 = 897,280 float32; weights
    # 830,976,000 B at 2 bits over the true K (the program's packed planes,
    # padded to whole chunks, are 910,458,880 B)
    (BITNET, (3_323_904_000, 830_976_000 + 4 * 897_280,
              2 * 477_440 + 4 * 897_280)),
    # qwen-16: 16 x (8192 + 8192 + 8192 + 29568) + 8192 = 874,496 inputs;
    # outputs 16 x 85,760 + 152,064 = 1,524,224
    (QWEN, (15_288_238_080, 3_822_059_520 + 4 * 1_524_224,
            2 * 874_496 + 4 * 1_524_224)),
])
def test_mpgemm_step_cost_by_hand(conf, want):
    weights, fixed, per_row = want
    assert M.mpgemm_step_cost(conf, 0) == (0, fixed)
    assert M.mpgemm_step_cost(conf, 8) == (16 * weights, fixed + 8 * per_row)
    # no rows: the weights and their scales alone
    assert fixed == {BITNET["name"]: 834_565_120,
                     QWEN["name"]: 3_828_156_416}[conf["name"]]
    # at 8 rows bytes bound the step on a v5e
    flops, nbytes = M.mpgemm_step_cost(conf, 8)
    assert nbytes / 819e9 > flops / 197e12


def test_prefill_flops_causal():
    n = 10
    want = (2 * 26 * 123_904_000 * n
            + 4 * 26 * 32 * 100 * (n * (n + 1) // 2))
    assert M.prefill_flops(BITNET, n) == want


def test_vocab_blocks():
    assert M.vocab_blocks(32000) == (4, 8000)
    assert M.vocab_blocks(152064) == (22, 6912)
    assert M.vocab_blocks(512) == (1, 512)
