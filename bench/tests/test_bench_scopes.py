"""The program's scopes and counters as the metric readers see them: the
mpGEMM and attention readers on a trace recorded on one TPU v5e, every
older reader unchanged by the scoped reduction, the engine's registry over
the window, and an untraced run that never asks for the scopes."""

import gzip
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from bench.harness import attribution, device, readers, serving, spec, trace  # noqa: E402,E501
from bench.models import dense_transformer as M  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
BENCH = spec.load_benchmark(ROOT)
NEW = ("mpgemm_ms_per_step.batch", "mpgemm_roofline.batch",
       "attention_ms_per_step.batch")
OLDER = [m["name"] for m in BENCH["per_layer"] if m["name"] not in NEW]
DECODE = "jit__decode_chunk_impl"
# the recorded decode program's scopes (test_bench_attribution.py reads
# them off the trace): one program of 8 steps
MPGEMM_S = (0.069131218 + 0.140267095 + 0.002772397 + 0.002034895
            + 0.004538014 + 0.000028595)
ATTENTION_S = 0.014153830


def _gz(name):
    return gzip.decompress((DATA / name).read_bytes())


@pytest.fixture(scope="module")
def reduced():
    """source -> (``trace.reduce``, ``attribution.reduce``) of the scoped
    and the unscoped recording and of the synthetic trace."""
    import test_bench_attribution as A

    sources = {
        "scoped": (trace.load(_gz("bitnet_batch_scoped.xplane.pb.gz")),
                   json.loads(_gz("bitnet_batch_scoped.op_scopes.json.gz"))),
        "unscoped": (trace.load(_gz("bitnet_batch.xplane.pb.gz")), {}),
        "synthetic": (A.SYNTH, A.MAPS)}
    return {k: (trace.reduce(pd), attribution.reduce(pd, maps))
            for k, (pd, maps) in sources.items()}


def _run(reduced, cell="bitnet3b-batch", emitted=None, chunks=True):
    """A Run over ``reduced`` with host records that every reader finds
    something in: a chunk around each decode program (and one before the
    traced part), admissions and requests inside it."""
    cell = spec.load_cell(cell, ROOT)
    pc0 = 100.0

    def pc(t_ns):
        return pc0 + (t_ns - reduced["anchor_ns"]) / 1e9

    a, b = (pc(t) for t in reduced["window_ns"])
    emitted = emitted or [(u, 96, 16, 24) for u in range(8)]
    host = [serving.Chunk(a - 0.5, a - 0.4, emitted)]
    _, _, ivs = trace.program_seconds(reduced, "_decode_chunk_impl")
    host += [serving.Chunk(pc(s) - 1e-4, pc(e) + 1e-4, emitted)
             for s, e in ivs]
    req = lambda t: type("R", (), {"admit_ns": int(t * 1e9),  # noqa: E731
                                   "first_token_ns": int((t + .05) * 1e9)})
    tracks = [serving.Track(type("S", (), {"uid": u, "prompt": [0] * 96}),
                            req(a + 0.01 * u), a - 0.1 + 0.01 * u, [])
              for u in range(4)]
    return serving.Run(
        cell=cell, model=M, peaks=device.PEAKS["TPU v5 lite"], t_process=0.0,
        t_setup_end=1.0, t0=a - 1.0, t_end=b + 0.1, tracks=tracks,
        chunks=host if chunks else [],
        counters={"engine_slot_occupancy_ratio": {"count": 4, "total": 3.5}},
        admits={u: a + 0.01 * u for u in range(4)}, trace=reduced,
        trace_pc=(pc0, pc(reduced["window_ns"][1])), compiles_in_window=0)


def _read(name, run):
    return spec.metric_reader(name, ROOT)(run)


@pytest.mark.parametrize("source", ["scoped", "unscoped", "synthetic"])
@pytest.mark.parametrize("name", OLDER)
def test_older_readers_read_the_same_from_the_scoped_reduction(
        reduced, source, name):
    old, new = reduced[source]
    cell = next(m.get("workloads", ["bitnet3b-batch"])[0]
                for m in BENCH["per_layer"] if m["name"] == name)
    assert _read(name, _run(new, cell)) == _read(name, _run(old, cell))


def test_mpgemm_and_attention_per_step_by_hand(reduced):
    red = reduced["scoped"][1]
    run = _run(red)
    assert _read("mpgemm_ms_per_step.batch", run) == pytest.approx(
        1e3 * MPGEMM_S / 8, rel=1e-7)
    assert _read("attention_ms_per_step.batch", run) == pytest.approx(
        1e3 * ATTENTION_S / 8, rel=1e-7)
    # the decode program's scopes add up to its device time
    seconds, steps = readers.decode_scopes(run)
    assert steps == 8
    assert sum(seconds.values()) == pytest.approx(
        red["programs"][DECODE]["seconds"], rel=1e-5)
    per_step = serving.decode_scopes_per_step(run)
    assert [k for k, _ in per_step][:3] == ["other", "mpgemm/cw", "mpgemm"]
    assert per_step[0][1] == pytest.approx(0.174941027 / 8, rel=1e-7)


def test_mpgemm_roofline_by_hand(reduced):
    """Six slots live through the chunk's 8 steps and two for the first 3:
    8 rows at 3 steps, 6 at 5. Bytes bound every step."""
    red = reduced["scoped"][1]
    emitted = ([(u, 96, 16, 24) for u in range(6)]
               + [(u, 96, 16, 19) for u in (6, 7)])
    run = _run(red, emitted=emitted)
    want = (3 * 870_917_120 + 5 * 861_829_120) / 819e9 / MPGEMM_S
    assert _read("mpgemm_roofline.batch", run) == pytest.approx(
        100 * want, rel=1e-7)
    # a program matched to no chunk counts every slot at each step
    run = _run(red, chunks=False)
    want = 8 * 870_917_120 / 819e9 / MPGEMM_S
    assert _read("mpgemm_roofline.batch", run) == pytest.approx(
        100 * want, rel=1e-7)
    assert 0 < _read("mpgemm_roofline.batch", run) <= 100


def test_decode_roofline_by_hand(reduced):
    """The whole step's roofline, over the same chunk matching."""
    red = reduced["scoped"][1]
    emitted = ([(u, 96, 16, 24) for u in range(6)]
               + [(u, 96, 16, 19) for u in (6, 7)])
    conf = spec.load_cell("bitnet3b-batch", ROOT).config
    least = 0.0
    for k in range(8):
        attended = [96 + 16 + k] * (6 if k >= 3 else 8)
        flops, nbytes = M.decode_step_cost(conf, attended)
        least += max(flops / 197e12, nbytes / 819e9)
    want = 100 * least / red["programs"][DECODE]["seconds"]
    assert _read("decode_roofline.batch", _run(red, emitted=emitted)) \
        == pytest.approx(want, rel=1e-12)
    assert _read("decode_roofline.batch", _run(red, chunks=False)) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_without_scopes(reduced, name):
    old, new = reduced["scoped"]
    assert _read(name, _run(new)) is not None
    assert _read(name, _run(old)) is None
    assert _read(name, _run(reduced["unscoped"][1])) is None  # maps {}
    run = _run(new)
    run.trace = None
    assert _read(name, run) is None


def test_occupancy_reads_the_same_from_the_registry():
    """``occupancy.batch`` from ``Run.counters`` against the old field,
    (chunks, summed ratio) of the histogram between the two snapshots."""
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("engine_slot_occupancy_ratio")
    c = reg.counter("engine_tokens_total")
    reg.gauge("engine_queue_depth").set(3)
    for v in (0.25, 0.5):      # before the window
        h.observe(v)
    c.inc(5)
    before, old = reg.snapshot(), (h.count, h.total)
    for v in (1.0, 0.875, 1.0, 0.625):
        h.observe(v)
    c.inc(7)
    reg.counter("engine_new_total").inc(2)
    counters = serving.registry_delta(before, reg.snapshot())
    assert counters == {
        "engine_slot_occupancy_ratio": {"count": 4, "total": 3.5},
        "engine_tokens_total": 7, "engine_new_total": 2}
    chunks, total = h.count - old[0], h.total - old[1]
    run = type("R", (), {"counters": counters})
    assert _read("occupancy.batch", run) == 100.0 * total / chunks == 87.5
    run.counters = {}
    assert _read("occupancy.batch", run) is None


@pytest.fixture(scope="module")
def untraced_run():
    """A tiny untraced run of the batch cell on the CPU, with the engine's
    ``op_scopes`` made to raise: an untraced run never asks for it."""
    import test_bench_harness as H
    from repro.serving.engine import ServingEngine

    def refuse(self):
        raise AssertionError("an untraced run asked for op_scopes")

    mp = pytest.MonkeyPatch()
    mp.setattr(ServingEngine, "op_scopes", refuse)
    try:
        return serving.run_cell(H.tiny_cell("bitnet3b-batch", "ternary"),
                                H.SEED, 1.5, traced=False,
                                t_process=time.perf_counter(),
                                require_chip=False, log=lambda m: None)
    finally:
        mp.undo()


def test_untraced_run_never_asks_for_scopes(untraced_run):
    result, run, _ = untraced_run
    assert result["correct"], result["check"]
    assert run.trace is None and "breakdown" not in result
    assert set(result["metrics"]) == {"output_tok_s", "setup_s"}


def test_untraced_run_counts_the_window_in_the_registry(untraced_run):
    _, run, _ = untraced_run
    occ = run.counters["engine_slot_occupancy_ratio"]
    assert occ["count"] >= len(run.chunks) > 0
    assert _read("occupancy.batch", run) == pytest.approx(
        100.0 * occ["total"] / occ["count"])
    assert 0 < _read("occupancy.batch", run) <= 100
