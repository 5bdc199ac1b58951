"""Device time put down to the program's named scopes, idle gaps put down
to the innermost host span, and the engine's request timestamps as the
harness and the ``admit_to_first_token_p85_ms.burst`` reader see them.

Synthetic traces are built from plain objects with the fields a
``jax.profiler.ProfileData`` exposes (planes, lines, events).
"""

import gzip
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from bench.harness import attribution, spec, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=e - s)
                                 for n, s, e in events])


def _pd(host, modules, ops):
    return NS(planes=[
        NS(name="/host:CPU", lines=[_line("python3", host)]),
        NS(name="/device:TPU:0", lines=[_line(trace.MODULES_LINE, modules),
                                        _line(trace.OPS_LINE, ops)])])


# window 0-100 ms; one decode run 10-60 ms holding a while op (20-50) with
# a body of two ops; a prefill run 70-90 ms; host spans nest three deep
SYNTH = _pd(
    host=[("harness.window", 0, 100 * MS), ("engine.step", 0, 100 * MS),
          ("decode_chunk", 5 * MS, 62 * MS),
          ("decode_dispatch", 5 * MS, 9 * MS),
          ("decode_sync", 9 * MS, 62 * MS), ("emit", 62 * MS, 66 * MS),
          ("admit", 66 * MS, 98 * MS)],
    modules=[("jit__decode_chunk_impl(1)", 10 * MS, 60 * MS),
             ("jit__prefill_chunk_impl(2)", 70 * MS, 90 * MS)],
    ops=[("%copy.1 = f32[8] copy(%p)", 10 * MS, 20 * MS),
         ("%while.2 = (f32[8]) while(%t)", 20 * MS, 50 * MS),
         ("%and_convert_fusion.3 = s8[8] fusion(%a)", 22 * MS, 30 * MS),
         ("%dot_fusion.4 = f32[8] fusion(%b)", 30 * MS, 45 * MS),
         ("%fusion.5 = f32[8] fusion(%c)", 50 * MS, 60 * MS),
         ("%fusion.5 = f32[8] fusion(%d)", 70 * MS, 90 * MS)])
MAPS = {"jit__decode_chunk_impl": {"and_convert_fusion.3": "mpgemm/cw",
                                   "dot_fusion.4": "mpgemm",
                                   "fusion.5": "attention"},
        "jit__prefill_chunk_impl": {"fusion.5": "lm_head/mpgemm"}}


def test_scope_seconds_by_hand():
    red = attribution.reduce(SYNTH, MAPS)
    assert red["window_ns"] == (10 * MS, 100 * MS)  # from the first op
    got = red["scopes"]
    want = {"jit__decode_chunk_impl": {"other": 0.010 + 0.007,
                                       "mpgemm/cw": 0.008, "mpgemm": 0.015,
                                       "attention": 0.010},
            "jit__prefill_chunk_impl": {"lm_head/mpgemm": 0.020}}
    assert set(got) == set(want)
    for prog, scopes in want.items():
        assert got[prog] == pytest.approx(scopes, abs=1e-12)
        assert sum(got[prog].values()) == pytest.approx(
            red["programs"][prog]["seconds"])
    # a program missing from the maps gets no scopes
    assert set(attribution.scope_seconds(
        SYNTH, {"jit__other": {}}, red["window_ns"])) == set()


def test_gaps_take_the_innermost_span():
    red = attribution.reduce(SYNTH, MAPS)
    assert red["idle_gaps"] == [("emit", 0.010), ("admit", 0.010)]
    # the harness's own reduction names the outermost harness span
    assert trace.reduce(SYNTH)["idle_gaps"] == [("engine.step", 0.010),
                                                ("engine.step", 0.010)]
    spans = [("engine.step", 0, 10), ("decode_chunk", 2, 8),
             ("decode_sync", 2, 5), ("emit", 8, 9)]
    assert [attribution.innermost(spans, t)
            for t in (1, 2, 4, 6, 8.5, 11)] == [
        "engine.step", "decode_sync", "decode_sync", "decode_chunk", "emit",
        "none"]


@pytest.fixture(scope="module")
def pd_seed():
    return trace.load(gzip.decompress(
        (DATA / "bitnet_batch.xplane.pb.gz").read_bytes()))


def test_recorded_trace_without_engine_spans_reads_as_before(pd_seed):
    """On the trace recorded before the engine's spans existed, every key
    of the harness's reduction reads as before, gaps included."""
    old = trace.reduce(pd_seed)
    new = attribution.reduce(pd_seed, {})
    assert new.pop("scopes") == {}
    assert new == old


@pytest.fixture(scope="module")
def scoped():
    """0.41 s (one decode chunk) of a traced ``bitnet3b-batch`` run on one
    TPU v5e, with the engine's spans and scopes, and the decode program's
    map of instruction -> scope taken from the same engine."""
    pd = trace.load(gzip.decompress(
        (DATA / "bitnet_batch_scoped.xplane.pb.gz").read_bytes()))
    maps = json.loads(gzip.decompress(
        (DATA / "bitnet_batch_scoped.op_scopes.json.gz").read_bytes()))
    return pd, maps, attribution.reduce(pd, maps, top=40)


def test_recorded_scopes_by_hand(scoped):
    pd, maps, red = scoped
    decode = "jit__decode_chunk_impl"
    got = red["scopes"][decode]
    assert got == pytest.approx({
        "other": 0.174941027, "attention": 0.014153830,
        "mpgemm": 0.069131218, "mpgemm/cw": 0.140267095,
        "mpgemm/table": 0.002772397, "lm_head/mpgemm": 0.002034895,
        "lm_head/mpgemm/cw": 0.004538014,
        "lm_head/mpgemm/table": 0.000028595}, rel=1e-8)
    # every op of the program is in one scope: they add up to its time
    assert sum(got.values()) == pytest.approx(
        red["programs"][decode]["seconds"], rel=1e-5)
    # read off the op list: the two whole-cache copies are no kernel's,
    # the plane unpack is the CW build, the LUT contraction is mpgemm's
    ops = dict(red["device_ops"])
    label = {op: maps[decode][op.lstrip("%")] for op in ops}
    assert label["%copy.467"] == label["%copy.466"] == "other"
    assert ops["%copy.467"] == pytest.approx(0.031951093)
    assert label["%and_convert_fusion.14"] == "mpgemm/cw"
    assert label["%multiply_reduce_fusion.14"] == "mpgemm"
    assert label["%fusion.306"] == "attention"


def test_recorded_gaps_name_engine_spans(scoped):
    """The recorded chunk ends in the engine's sync: the device idles while
    the tokens travel to the host."""
    pd, _, red = scoped
    assert red["idle_gaps"][0] == ("decode_sync", pytest.approx(0.003898903))
    assert trace.reduce(pd)["idle_gaps"][0][0] == "engine.step"
    names = {e.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert set(attribution.ENGINE_SPANS) - {"admit", "prefill_chunk"} \
        <= names


# ---------------------------------------------------------------------------
# admit_to_first_token_p85_ms.burst
# ---------------------------------------------------------------------------

def _run(reqs, traced=True):
    """A Run with the fields the reader reads: window 10-20 s, tracks of
    (due, Request with its stamps in ns)."""
    tracks = [NS(due=due, req=req) for due, req in reqs]
    return NS(t0=10.0, t_end=20.0, tracks=tracks,
              trace={} if traced else None)


def _req(admit=None, first=None):
    return NS(admit_ns=None if admit is None else int(admit * 1e9),
              first_token_ns=None if first is None else int(first * 1e9))


READER = spec.metric_reader("admit_to_first_token_p85_ms.burst", ROOT)


def test_admit_to_first_token_reader_by_hand():
    run = _run([
        (9.0, _req(9.5, 10.5)),     # due before the window: out
        (11.0, _req(11.0, 11.4)),   # 0.4 s
        (12.0, _req(12.5, 13.5)),   # 1.0 s
        (13.0, _req(13.0, 13.2)),   # 0.2 s
        (18.0, _req(19.0)),         # no token by the end: 1.0 s so far
        (19.0, _req()),             # not admitted: out
        (19.5, _req(19.6, 21.0)),   # token after the end: 0.4 s so far
    ])
    # 0.2, 0.4, 0.4, 1.0, 1.0: rank 0.85 * 4 = 3.4 -> 1.0
    assert READER(run) == pytest.approx(1000.0)
    run.tracks = run.tracks[:4]  # 0.2, 0.4, 1.0: rank 1.7 -> 0.4 + 0.7*0.6
    assert READER(run) == pytest.approx(820.0)


def test_admit_to_first_token_reader_reads_nothing_where_nothing_is():
    assert READER(_run([(11.0, _req(11.0, 11.4))], traced=False)) is None
    # a program whose Request carries no stamps (the parent's)
    assert READER(_run([(11.0, NS(uid=1))])) is None
    assert READER(_run([])) is None


def test_request_stamps_bracket_the_harness_times():
    """A whole run of the burst cell at a tiny size: the engine stamps each
    request no earlier than its due time, admits it after it arrives, and
    returns its first token at or before the sync the harness records."""
    import test_bench_harness as H
    from bench.harness import serving

    cell = H.tiny_cell("bitnet3b-burst", "ternary")
    _, run, _ = serving.run_cell(cell, H.SEED, 1.5, traced=False,
                                 t_process=time.perf_counter(),
                                 require_chip=False, log=lambda m: None)
    done = [t for t in run.tracks if t.done_t is not None and t.syncs]
    assert done
    for t in done:
        r = t.req
        first_sync = next(s for s, n in t.syncs if n >= 1)
        assert t.due <= r.arrival_ns / 1e9
        assert r.arrival_ns <= r.admit_ns <= r.first_token_ns
        assert r.first_token_ns / 1e9 <= first_sync
