"""BENCHMARK.json: its names and units, and discovery of cells, mixes and
metrics by name."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import serving, spec, stats  # noqa: E402

BENCH = spec.load_benchmark(ROOT)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "bound", "source", "layer", "moves",
               "workloads"}


def validate(bench: dict) -> list:
    """Names and units outside the allowed characters, as messages."""
    bad = []

    def name(x, what):
        if not isinstance(x, str) or not NAME_RE.match(x):
            bad.append(f"{what}: {x!r}")

    for c in bench["configs"]:
        name(c["name"], "config name")
        for k in c["reduced"]:
            name(k, "reduced key")
    for w in bench["workloads"]:
        for key in ("name", "config", "traffic"):
            name(w[key], f"workload {key}")
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            name(m["name"], f"{group} name")
            if not UNIT_RE.match(m["unit"]):
                bad.append(f"unit of {m['name']}: {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"better of {m['name']}: {m['better']!r}")
            extra = set(m) - METRIC_KEYS
            if extra:
                bad.append(f"keys of {m['name']}: {sorted(extra)}")
    return bad


def test_names_and_units_are_allowed():
    assert validate(BENCH) == []


def test_every_named_file_exists():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        spec.model_module(conf)  # its reference and counts
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        cell = spec.load_cell(w["name"], ROOT)
        assert cell.end_to_end and cell.per_layer
        assert "max_logit_gap" in cell.check
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(spec.metric_reader(m["name"], ROOT))
    names = [m["name"] for m in BENCH["end_to_end"]]
    for m in BENCH["per_layer"]:
        assert m["moves"] in names


def test_each_cell_reports_setup_and_one_more():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in cell.per_layer:  # each moves a metric the cell reports
            assert m["moves"] in e2e


def test_discovers_new_files_by_name(tmp_path):
    """A new configuration, mix, check and metric are files and entries."""
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": "toy", "source": "arXiv:0000.00000",
                     "file": "bench/configs/toy.json", "reduced": [],
                     "why": "a throwaway"}],
        "workloads": [{"name": "toy.trickle", "config": "toy",
                       "traffic": "trickle", "chips": 1, "why": "a test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "toy.answer", "unit": "%", "better": "higher",
                       "source": "program_counter", "layer": "engine",
                       "moves": "setup_s", "workloads": ["toy.trickle"]},
                      {"name": "other.cell", "unit": "%", "better": "higher",
                       "source": "program_counter", "layer": "engine",
                       "moves": "setup_s", "workloads": ["elsewhere"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub, name, body in (
            ("configs", "toy.json", {"name": "toy", "model": "dense_transformer"}),
            ("traffic", "trickle.json", {"loop": "open", "block": 4}),
            ("checks", "toy.trickle.json", {"max_logit_gap": 0.1})):
        (tmp_path / "bench" / sub).mkdir(parents=True)
        (tmp_path / "bench" / sub / name).write_text(json.dumps(body))
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "metrics" / "toy.answer.py").write_text(
        "def read(run):\n    return 42.0 if run == 'r' else None\n")
    cell = spec.load_cell("toy.trickle", tmp_path)
    assert cell.config["model"] == "dense_transformer"
    assert cell.traffic == {"loop": "open", "block": 4}
    assert cell.check == {"max_logit_gap": 0.1}
    assert [m["name"] for m in cell.per_layer] == ["toy.answer"]
    read = spec.metric_reader("toy.answer", tmp_path)
    assert read("r") == 42.0 and read("x") is None
    assert validate(bench) == []
    bench["per_layer"][0]["unit"] = "per cent"
    bench["workloads"][0]["name"] = "toy trickle"
    assert len(validate(bench)) == 2


def test_percentile_matches_numpy():
    np = pytest.importorskip("numpy")
    xs = list(np.random.default_rng(0).exponential(size=201))
    for p in (50, 95, 99):
        assert stats.percentile(xs, p) == pytest.approx(np.percentile(xs, p))
    assert stats.percentile([3.0], 95) == 3.0


def test_ttft_counts_unfinished_requests():
    syncs = [(10.5, 8), (10.7, 16)]
    assert stats.ttft_s(10.0, syncs, 11.0) == pytest.approx(0.5)
    # no token by the window's end: the request enters with its wait
    assert stats.ttft_s(10.0, [(12.0, 8)], 11.0) == pytest.approx(1.0)
    assert stats.ttft_s(10.2, [], 11.0) == pytest.approx(0.8)


def test_tpot_over_tokens_returned_by_the_end():
    syncs = [(1.0, 8), (1.2, 16), (1.4, 20), (9.0, 28)]
    assert stats.tpot_s(syncs, 2.0) == pytest.approx(0.4 / 19)
    assert stats.tpot_s([(1.0, 1)], 2.0) is None
    assert stats.tpot_s([(3.0, 8)], 2.0) is None


def _run(tracks, t0=0.0, t_end=10.0):
    return serving.Run(cell=None, model=None, peaks={}, t_process=-5.0,
                       t_setup_end=-1.0, t0=t0, t_end=t_end, tracks=tracks,
                       chunks=[], counters={}, admits={}, trace=None,
                       trace_pc=None, compiles_in_window=0)


def test_tail_readers_include_requests_unfinished_at_the_end():
    from bench.harness.traffic import RequestSpec
    mk = lambda uid, due, syncs: serving.Track(  # noqa: E731
        RequestSpec(uid, None, 8, due), None, due, syncs)
    tracks = [mk(i, 0.1 * i, [(0.1 * i + 0.5, 4), (0.1 * i + 0.9, 8)])
              for i in range(17)]
    tracks += [mk(17 + i, 9.0, []) for i in range(3)]  # never served: 1 s
    tracks.append(mk(20, 11.0, []))    # due after the window: left out
    ttft = spec.metric_reader("ttft_p85_ms", ROOT)(_run(tracks))
    # rank 0.85 x 19 = 16.15 between the last 500 ms and the first 1000 ms
    assert ttft == pytest.approx(500.0 + 0.15 * 500.0)
    tpot = spec.metric_reader("tpot_p85_ms", ROOT)(_run(tracks))
    assert tpot == pytest.approx(400.0 / 7)
    assert spec.metric_reader("setup_s", ROOT)(_run(tracks)) == 4.0
