"""A whole run of the harness on the CPU at a tiny size: the engine as the
window drives it, the reference check, its control, and planted faults.

The look for a chip is skipped (``require_chip=False``); everything else is
the run that ``bench/run.py`` makes.
"""

import copy
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import serving, spec  # noqa: E402

SEED = 2 ** 35 + 17


def tiny_cell(name, scheme):
    """The real cell with widths cut to a CPU size, deep and wide enough
    that the int4 control fails the cell's own limit (its gap grows with
    depth and width: 0.75-1.13 at 2 layers of width 64, 1.5-2.4 at 4 of
    256, on the CPU)."""
    cell = spec.load_cell(name, ROOT)
    conf = copy.deepcopy(cell.config)
    conf.update(n_layers=8, d_model=256, n_heads=8, n_kv_heads=4, head_dim=32,
                d_ff=512, vocab_size=2048, qkv_bias=True)
    conf["quant"]["scheme"] = scheme
    mix = copy.deepcopy(cell.traffic)
    mix["engine"] = {"max_batch": 4, "max_seq": 96, "decode_chunk": 4,
                     "prefill_chunk": 16}
    mix["prompt"].update(median=20, min=8, max=48)
    mix["output"].update(median=16, min=8, max=40)
    if mix["loop"] == "closed":
        mix["clients"] = 4
    else:
        mix["arrivals"]["rate"] = 20.0
    cell.config, cell.traffic = conf, mix
    return cell


def _run(cell, **kw):
    return serving.run_cell(cell, SEED, 1.5, traced=False,
                            t_process=time.perf_counter(),
                            require_chip=False, log=lambda m: None, **kw)


def test_serving_tree_matches_the_program():
    import jax
    from repro.launch import serve
    from repro.models import api

    from bench.models import dense_transformer_serving as MS

    cell = tiny_cell("qwen2-72b-l16-batch", "symmetric")
    args = serve.parser().parse_args(serving.engine_flags(cell.config,
                                                          cell.traffic))
    cfg = serving.program_config(cell.config, args)
    want = api.param_specs(cfg, serve_quantized=True)
    got = jax.eval_shape(lambda: MS.serving_params(cell.config, SEED))
    sd = lambda t: [(jax.tree_util.keystr(p), x.shape, x.dtype)  # noqa
                    for p, x in jax.tree_util.tree_flatten_with_path(t)[0]]
    assert sd(got) == sd(want)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))


@pytest.mark.parametrize("name,scheme", [("bitnet3b-batch", "ternary"),
                                         ("bitnet3b-burst", "symmetric")])
def test_sound_run_is_correct_and_control_is_not(name, scheme):
    cell = tiny_cell(name, scheme)
    result, run, check = _run(cell)
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert check["served_tokens"] > 0
    assert run.compiles_in_window == 0
    limit = cell.check["max_logit_gap"]
    assert check["max_logit_gap"] <= limit
    assert list(result)[-1] == "check"
    json.dumps(result)
    for m in cell.end_to_end:
        assert result["metrics"][m["name"]]["value"] > 0
    # the int4 control in the program's place fails the same check
    result, _, check = _run(cell, control=True)
    assert not result["correct"]
    assert result["check"]["max_logit_gap"]["value"] > limit
    assert check["program_max_logit_gap"] <= limit


def test_altered_token_is_caught():
    """A token altered where the engine produces it fails the check."""
    cell = tiny_cell("bitnet3b-batch", "ternary")
    vocab = cell.config["vocab_size"]
    seen = set()

    def alter(eng):
        for r in eng.slots:
            if r is not None and r.output and r.uid not in seen:
                seen.add(r.uid)
                r.output[-1] = (r.output[-1] + vocab // 2) % vocab

    result, _, check = _run(cell, step_hook=alter)
    assert not result["correct"]
    assert check["max_logit_gap"] > cell.check["max_logit_gap"]
