"""Telemetry-layer tests: metrics registry, Chrome-trace recording and
validation, engine stats schema, dispatch profiling, monotonic clocks.

The contracts under test:
  * the registry's histograms are bounded (reservoir) but keep EXACT
    count/sum/min/max, and percentiles interpolate between closest ranks
    (the nearest-rank bug reported p95 of 3 samples as the max);
  * every trace the engine emits passes the Chrome-trace format invariants
    (X spans nest per track, async b/e balance per request id);
  * ``engine.stats()`` keeps its dict schema — every key present and
    finite on a fresh engine AND after a full serve, across dense/paged/
    spec/beam configurations;
  * telemetry never changes engine behaviour: tokens and sync counts are
    identical with and without a tracer;
  * the engine's spans are live, nest, and open each request's async span
    at its arrival; every request is stamped arrival <= admit <= first
    token;
  * the named scopes label every contraction of the compiled decode
    program and change nothing in it but its metadata;
  * heartbeat/interval math runs on the monotonic clock (wall-clock jumps
    must not fire timeouts).
"""

import contextlib
import json
import math
import re
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.models import api
from repro.obs import dispatch as dispatch_obs
from repro.obs import scopes
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               export_stats)
from repro.obs.trace import Tracer, load_trace, validate_chrome_trace
from repro.serving.engine import Request, ServingEngine


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_and_gauge_basics():
    c = Counter("c_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge("g")
    g.set(2.5)
    g.add(-1.0)
    assert g.value == 1.5
    with pytest.raises(ValueError):
        Counter("0bad name")


def test_histogram_bounded_reservoir_exact_aggregates():
    h = Histogram("h", reservoir_size=64)
    xs = np.arange(5000, dtype=float)
    for x in xs:
        h.observe(x)
    snap = h.snapshot()
    assert len(h._res) <= 64           # bounded however many observations
    assert snap["count"] == 5000       # aggregates stay exact
    assert snap["sum"] == pytest.approx(xs.sum())
    assert snap["min"] == 0.0 and snap["max"] == 4999.0
    assert snap["mean"] == pytest.approx(xs.mean())
    # reservoir percentiles approximate the population (uniform sample)
    assert 1000 < snap["p50"] < 4000


def test_histogram_interpolated_percentiles_match_numpy():
    """Small samples interpolate (numpy 'linear'), not nearest-rank."""
    h = Histogram("h2", reservoir_size=1024)
    for v in (10.0, 20.0, 30.0):
        h.observe(v)
    assert h.percentile(0.50) == pytest.approx(20.0)
    assert h.percentile(0.95) == pytest.approx(
        float(np.percentile([10, 20, 30], 95)))  # 29.0, NOT the max
    assert h.percentile(0.95) < 30.0
    h2 = Histogram("h3")
    for v in (1.0, 2.0, 3.0, 4.0):
        h2.observe(v)
    assert h2.percentile(0.5) == pytest.approx(2.5)
    with pytest.raises(ValueError):
        h2.percentile(1.5)


def test_empty_histogram_is_finite():
    h = Histogram("h4")
    snap = h.snapshot()
    for v in snap.values():
        if isinstance(v, float):
            assert math.isfinite(v)
    assert h.percentile(0.99) == 0.0 and h.mean == 0.0


def test_registry_get_or_create_and_kind_conflict():
    r = MetricsRegistry()
    c1 = r.counter("x_total")
    assert r.counter("x_total") is c1
    with pytest.raises(ValueError):
        r.histogram("x_total")


def test_registry_reset_prefix():
    r = MetricsRegistry()
    r.counter("engine_a").inc(3)
    r.counter("pool_b").inc(7)
    r.reset("engine_")
    assert r.get("engine_a").value == 0
    assert r.get("pool_b").value == 7


def test_prometheus_text_exposition():
    r = MetricsRegistry()
    r.set_common_labels(host="0")
    r.counter("req_total", help="requests").inc(2)
    h = r.histogram("lat_seconds", help="latency")
    h.observe(0.5)
    txt = r.prometheus_text()
    assert "# HELP req_total requests" in txt
    assert "# TYPE req_total counter" in txt
    assert 'req_total{host="0"} 2' in txt
    assert "# TYPE lat_seconds summary" in txt
    assert 'quantile="0.95"' in txt
    assert 'lat_seconds_count{host="0"} 1' in txt
    # snapshot is json-able
    json.dumps(r.snapshot())


def test_registry_thread_safety():
    """Concurrent writers never lose an update (per-instrument locks)."""
    r = MetricsRegistry()
    c = r.counter("n_total")
    h = r.histogram("v", reservoir_size=32)
    n_threads, per = 8, 2000

    def work(t):
        for i in range(per):
            c.inc()
            h.observe(float(i))

    ts = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per
    assert h.count == n_threads * per
    assert len(h._res) <= 32


def test_export_stats_flattens_nested_numbers():
    r = MetricsRegistry()
    n = export_stats(r, {"a": 1, "nested": {"b": 2.5, "skip": "str"},
                         "none": None, "flag": True}, prefix="eng")
    assert n == 2
    assert r.get("eng_a").value == 1.0
    assert r.get("eng_nested_b").value == 2.5
    assert r.get("eng_flag") is None  # bools/strings/None skipped


# ---------------------------------------------------------------------------
# tracer + chrome-trace validation
# ---------------------------------------------------------------------------

def test_tracer_spans_nest_and_validate():
    tr = Tracer(annotate_xla=False)
    with tr.span("outer", a=1):
        with tr.span("inner"):
            pass
        tr.instant("mark")
    tr.async_begin("request", id=7, mode="greedy")
    tr.async_end("request", id=7, tokens=3)
    doc = tr.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    summary = validate_chrome_trace(doc["traceEvents"])
    assert summary["by_phase"]["X"] == 2
    assert summary["by_phase"]["b"] == summary["by_phase"]["e"] == 1
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    # inner nests strictly within outer on the same track
    assert evs["inner"]["ts"] >= evs["outer"]["ts"]
    assert (evs["inner"]["ts"] + evs["inner"]["dur"]
            <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1e-3)


def test_tracer_multithreaded_tracks():
    tr = Tracer(annotate_xla=False)
    # keep all threads alive until each has recorded: OS thread ids are
    # reused after exit, which would merge tracks
    barrier = threading.Barrier(3)

    def work():
        with tr.span("thread_span"):
            barrier.wait(timeout=10)

    ts = [threading.Thread(target=work) for _ in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    with tr.span("main_span"):
        pass
    summary = validate_chrome_trace(tr.chrome_trace()["traceEvents"])
    assert summary["tracks"] == 4  # one per thread


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        tr.instant("y")
    tr.async_begin("request", id=1)
    tr.async_end("request", id=1)
    assert len(tr) == 0


def test_validate_rejects_unbalanced_async():
    base = {"pid": 1, "tid": 1, "ts": 0.0}
    with pytest.raises(ValueError, match="begin without end"):
        validate_chrome_trace([
            dict(base, name="r", ph="b", cat="request", id=1)])
    with pytest.raises(ValueError, match="without begin"):
        validate_chrome_trace([
            dict(base, name="r", ph="e", cat="request", id=1)])


def test_validate_rejects_partial_overlap_and_missing_dur():
    base = {"pid": 1, "tid": 1, "cat": "c"}
    with pytest.raises(ValueError, match="must nest"):
        validate_chrome_trace([
            dict(base, name="a", ph="X", ts=0.0, dur=10.0),
            dict(base, name="b", ph="X", ts=5.0, dur=10.0)])
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace([dict(base, name="a", ph="X", ts=0.0)])
    with pytest.raises(ValueError, match="missing or mistyped"):
        validate_chrome_trace([{"name": "a", "ph": "X"}])


def test_trace_save_load_roundtrip(tmp_path):
    tr = Tracer(annotate_xla=False)
    with tr.span("s", k="v"):
        pass
    p = tr.save(str(tmp_path / "t.json"))
    evs = load_trace(p)
    validate_chrome_trace(evs)
    assert any(e["name"] == "s" and e["args"] == {"k": "v"} for e in evs)
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([1, 2]))
    with pytest.raises(ValueError, match="traceEvents"):
        load_trace(str(bad))


# ---------------------------------------------------------------------------
# dispatch profiling
# ---------------------------------------------------------------------------

def test_dispatch_recorder_dedup_and_summary():
    rec = dispatch_obs.DispatchRecorder()
    rec.record("dispatch", "k1", "fused", "auto", "heuristic", (8, 64, 4))
    rec.record("dispatch", "k1", "fused", "auto", "heuristic", (8, 64, 4))
    rec.record("dispatch", "k2", "staged", "tuned", "tuned", (8, 32, 4))
    rec.record("select_fusion", "d1", "fused", "auto", "heuristic")
    s = rec.summary()
    assert s["decisions"] == 2
    assert s["tuned"] == 1 and s["heuristic"] == 1 and s["forced"] == 0
    r1 = next(r for r in rec.records("dispatch") if r.key == "k1")
    assert r1.count == 2 and r1.block_n == 64


def test_recording_context_restores_previous():
    assert dispatch_obs.get_active() is None or True  # env-agnostic
    prev = dispatch_obs.get_active()
    with dispatch_obs.recording() as rec:
        assert dispatch_obs.get_active() is rec
        dispatch_obs.record("dispatch", "k", "fused", "auto", "heuristic")
        assert len(rec) == 1
    assert dispatch_obs.get_active() is prev


def test_resolve_dispatch_records_decision():
    from repro.kernels import ops
    with dispatch_obs.recording() as rec:
        fusion, bm, bn, bg = ops.resolve_dispatch(8, 64, 16, 4, 2)
    recs = rec.records("dispatch")
    assert len(recs) == 1
    r = recs[0]
    assert r.fusion == fusion and r.requested == "auto"
    assert r.source == "heuristic"
    assert (r.block_m, r.block_n, r.block_g) == (bm, bn, bg)
    # forced policy recorded as such
    with dispatch_obs.recording() as rec:
        ops.resolve_dispatch(8, 64, 16, 4, 2, fusion="staged")
    assert rec.records("dispatch")[0].source == "forced"


# ---------------------------------------------------------------------------
# engine integration: stats schema, trace schema, behaviour invariance
# ---------------------------------------------------------------------------

BASE_KEYS = {
    "decode_chunk", "prefill_chunk", "decode_syncs", "decode_tokens",
    "host_syncs_per_token", "prefill_dispatches", "p50_chunk_ms",
    "p95_chunk_ms", "decode_tok_s", "paged", "mesh", "cache_hbm_bytes",
    "slot_occupancy", "peak_active_slots", "admit_attempts", "admit_blocked",
    "admission_blocked_rate", "prefill_s", "prefill_tokens",
    "prefill_tokens_reused",
}


def _assert_finite(obj, path="stats"):
    if isinstance(obj, dict):
        for k, v in obj.items():
            _assert_finite(v, f"{path}.{k}")
    elif isinstance(obj, bool) or obj is None or isinstance(obj, str):
        pass
    elif isinstance(obj, (int, float, np.integer, np.floating)):
        assert np.isfinite(obj), f"non-finite {path} = {obj!r}"


def _cfg():
    cfg = registry.get_reduced("tinyllama-1.1b").replace(
        activation_dtype=jnp.float32)
    # packed store so the SAME params serve the spec-decoding config too
    return cfg.with_quant(mpgemm_mode="lut_xla", weight_bits=4,
                          store="packed", skip="lm_head")


@pytest.fixture(scope="module")
def tl():
    cfg = _cfg()
    params = api.init_params(jax.random.key(0), cfg, serve_quantized=True)
    return cfg, params


def _prompts(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(4, 12)),
                         dtype=np.int32) for _ in range(n)]


ENGINE_CONFIGS = {
    "dense": (dict(), "greedy", set()),
    "paged": (dict(cache_block_size=8, prefix_cache=True), "greedy",
              {"cache_block_size", "num_cache_blocks", "blocks_in_use",
               "prefix_cache"}),
    "spec": (dict(spec_k=3, spec_draft_planes=2), "spec:draft2b", {"spec"}),
    "beam": (dict(), "beam:2", set()),
}


@pytest.mark.parametrize("name", sorted(ENGINE_CONFIGS))
def test_stats_schema_fresh_and_post_retire(tl, name):
    """Every stats key present and finite on a FRESH engine and after a
    full serve, for each engine configuration."""
    cfg, params = tl
    kw, dec, extra = ENGINE_CONFIGS[name]
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, decode_chunk=4,
                        prefill_chunk=4, **kw)
    fresh = eng.stats()
    assert BASE_KEYS <= set(fresh), BASE_KEYS - set(fresh)
    _assert_finite(fresh)
    assert fresh["decode_tok_s"] == 0.0 and fresh["p50_chunk_ms"] == 0.0

    for i, p in enumerate(_prompts(cfg, 3)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=6, decoding=dec))
    eng.run_to_completion()
    st = eng.stats()
    assert (BASE_KEYS | extra) <= set(st), (BASE_KEYS | extra) - set(st)
    _assert_finite(st)
    assert st["decode_tokens"] > 0 and st["decode_tok_s"] > 0
    assert 0 < st["slot_occupancy"] <= 1.0
    if name == "spec":
        assert st["spec"]["verify_steps"] > 0
    if name == "paged":
        # only the prefix cache's own refs survive retirement
        assert st["blocks_in_use"] == len(eng._prefix)


def test_beam_group_visible_in_stats_mid_run(tl):
    cfg, params = tl
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, decode_chunk=2,
                        prefill_chunk=4)
    eng.submit(Request(uid=0, prompt=_prompts(cfg, 1)[0], max_new_tokens=8,
                       decoding="beam:2"))
    assert eng.step()  # admit + first chunk: group active
    st = eng.stats()
    assert st["beam"]["active_groups"] == 1
    _assert_finite(st)
    eng.run_to_completion()


def test_percentiles_interpolate_in_stats(tl):
    """The stats() percentile fix: p95 of 3 chunk latencies interpolates
    instead of snapping to the slowest chunk."""
    cfg, params = tl
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    for v in (0.010, 0.020, 0.030):
        eng._h_chunk_s.observe(v)
    st = eng.stats()
    assert st["p50_chunk_ms"] == pytest.approx(20.0)
    assert st["p95_chunk_ms"] == pytest.approx(29.0)  # nearest-rank gave 30


def test_engine_trace_schema(tl):
    """The trace a serve emits passes format validation and carries the
    span taxonomy: balanced per-request async spans, decode_chunk spans
    with occupancy attributes, prefill/admit spans."""
    cfg, params = tl
    tracer = Tracer(annotate_xla=False)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, decode_chunk=4,
                        prefill_chunk=4, cache_block_size=8,
                        prefix_cache=True, tracer=tracer)
    n_req = 3
    for i, p in enumerate(_prompts(cfg, n_req)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
    eng.submit(Request(uid=99, prompt=_prompts(cfg, 1)[0],
                       max_new_tokens=0))  # retires at admission
    eng.run_to_completion()

    evs = tracer.chrome_trace()["traceEvents"]
    summary = validate_chrome_trace(evs)
    # one balanced async request span per submitted request (incl. the
    # zero-budget one), matched by uid
    assert summary["by_phase"]["b"] == n_req + 1
    assert summary["by_phase"]["e"] == n_req + 1
    uids = {e["id"] for e in evs if e["ph"] == "b"}
    assert uids == {0, 1, 2, 99}
    chunks = [e for e in evs if e["name"] == "decode_chunk"]
    assert len(chunks) == eng.stats()["decode_syncs"]
    for c in chunks:
        assert 0 < c["args"]["occupancy"] <= 1.0
        assert c["args"]["active_slots"] >= 1
        assert c["args"]["steps"] == 4
    admits = [e for e in evs if e["name"] == "admit"]
    assert len(admits) == n_req + 1
    assert all(a["args"]["paged"] for a in admits)
    assert any(e["name"] == "prefill_chunk" for e in evs)


def test_tracing_does_not_change_behaviour(tl):
    """Same tokens, same sync count, with and without a tracer."""
    cfg, params = tl

    def serve(tracer):
        eng = ServingEngine(cfg, params, max_batch=2, max_seq=64,
                            decode_chunk=4, prefill_chunk=4, tracer=tracer)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(_prompts(cfg, 3))]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        return [r.output for r in reqs], eng.stats()

    out_off, st_off = serve(None)
    out_on, st_on = serve(Tracer(annotate_xla=False))
    assert out_on == out_off
    assert st_on["decode_syncs"] == st_off["decode_syncs"]
    assert st_on["host_syncs_per_token"] == st_off["host_syncs_per_token"]


def test_engine_reset_zeroes_metric_series(tl):
    cfg, params = tl
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    for i, p in enumerate(_prompts(cfg, 2)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    eng.run_to_completion()
    assert eng._h_chunk_s.count > 0
    eng.reset()
    assert eng._h_chunk_s.count == 0
    st = eng.stats()
    assert st["decode_tok_s"] == 0.0 and st["slot_occupancy"] == 0.0


def test_tuning_cache_counters_in_stats(tl, tmp_path):
    cfg, params = tl
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64,
                        tuning_cache=str(tmp_path / "tc.json"))
    tc = eng.stats()["tuning_cache"]
    for k in ("entries", "hits", "misses", "sanitized", "foreign"):
        assert k in tc
    eng.tuning_cache.lookup("nonexistent-shape")
    assert eng.stats()["tuning_cache"]["misses"] >= 1


def test_metrics_snapshot_and_prometheus(tl):
    cfg, params = tl
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64,
                        cache_block_size=8, prefix_cache=True)
    for i, p in enumerate(_prompts(cfg, 2)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    eng.run_to_completion()
    snap = eng.metrics_snapshot()
    m = snap["metrics"]
    assert m["engine_decode_chunk_seconds"]["count"] == eng.decode_syncs
    assert m["blockpool_blocks_granted_total"]["value"] > 0
    assert m["prefix_cache_misses_total"]["value"] >= 0
    # stats() mirrored in as engine_* gauges
    assert m["engine_decode_tokens"]["value"] == eng.decode_tokens
    txt = eng.prometheus_text()
    assert "engine_decode_chunk_seconds_count" in txt
    assert "blockpool_blocks_in_use" in txt
    json.dumps(snap)  # json-able end to end


ENGINE_SPANS = {"admit", "prefill_chunk", "decode_chunk", "decode_dispatch",
                "decode_sync", "emit"}


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"] + 1e-3 and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-3)


@pytest.mark.parametrize("paged", [False, True])
def test_engine_spans_nest_and_requests_open_at_arrival(tl, paged):
    """Live engine spans: dispatch and sync inside their decode chunk,
    prefill dispatches inside their admission, one emit per chunk; each
    request's one async span opens at its arrival, before its admission."""
    cfg, params = tl
    tracer = Tracer(annotate_xla=False)
    kw = dict(cache_block_size=8, prefix_cache=True) if paged else {}
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, decode_chunk=4,
                        prefill_chunk=4, tracer=tracer, **kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(_prompts(cfg, 3))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()

    evs = tracer.chrome_trace()["traceEvents"]
    summary = validate_chrome_trace(evs)
    assert summary["by_phase"]["b"] == summary["by_phase"]["e"] == len(reqs)
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in spans} == ENGINE_SPANS
    by = {n: [e for e in spans if e["name"] == n] for n in ENGINE_SPANS}
    assert (len(by["decode_dispatch"]) == len(by["decode_sync"])
            == len(by["emit"]) == len(by["decode_chunk"])
            == eng.decode_syncs)
    for child, parent in (("decode_dispatch", "decode_chunk"),
                          ("decode_sync", "decode_chunk"),
                          ("prefill_chunk", "admit")):
        for c in by[child]:
            assert any(_inside(c, p) for p in by[parent]), (child, c)
    for e in by["emit"]:  # after its chunk's sync, never inside a chunk
        assert not any(_inside(e, c) for c in by["decode_chunk"])
    admits = {e["args"]["uid"]: e for e in by["admit"]}
    assert set(admits) == {r.uid for r in reqs}
    for r in reqs:
        b = next(e for e in evs if e["ph"] == "b" and e["id"] == r.uid)
        assert b["ts"] == pytest.approx(tracer._us(r.arrival_ns))
        assert b["ts"] <= admits[r.uid]["ts"]
        assert admits[r.uid]["ts"] == pytest.approx(
            tracer._us(r.admit_ns), abs=1e3)


@pytest.mark.parametrize("name", sorted(ENGINE_CONFIGS))
def test_request_timestamps_are_ordered(tl, name):
    """arrival <= admit <= first token for every finished request, in every
    engine configuration; a caller's own arrival time is kept."""
    cfg, params = tl
    kw, dec, _ = ENGINE_CONFIGS[name]
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, decode_chunk=4,
                        prefill_chunk=4, **kw)
    given = time.perf_counter_ns() - 10**6
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6, decoding=dec)
            for i, p in enumerate(_prompts(cfg, 3))]
    reqs[0].arrival_ns = given
    for r in reqs:
        eng.submit(r)
    t_end = time.perf_counter_ns()
    eng.run_to_completion()
    assert reqs[0].arrival_ns == given
    for r in reqs:
        assert r.done and r.output
        assert r.arrival_ns <= t_end
        assert r.arrival_ns <= r.admit_ns <= r.first_token_ns


@pytest.mark.parametrize("paged", [False, True])
def test_scopes_label_every_contraction_of_the_decode_program(tl, paged):
    """In the compiled decode program every dot is in a scope: the seven
    projections' contractions in ``mpgemm`` (one scanned layer body), the
    LM head's in ``lm_head`` (a float head: the configuration leaves it
    unquantized), the scores and values in ``attention``; the CW build and
    the tables are in their sub-scopes. The engine maps its prefill
    program too, dense or paged."""
    cfg, params = tl
    kw = dict(cache_block_size=8) if paged else {}
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, decode_chunk=2,
                        prefill_chunk=4, **kw)
    text = eng._decode.lower(eng.params, eng.state).compile().as_text()
    labels = scopes.op_scopes(text)
    maps = eng.op_scopes()
    assert maps["jit__decode_chunk_impl"] == labels
    prefill = "jit__paged_prefill_impl" if paged else "jit__prefill_chunk_impl"
    assert "mpgemm" in maps[prefill].values()
    dots = [m.group(1) for m in re.finditer(
        r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = \S+ dot\(", text, re.M)]
    got = sorted(labels[d] for d in dots)
    assert got == sorted(["mpgemm"] * 7 + ["lm_head"]
                         + ["attention"] * 2), got
    assert {"mpgemm/cw", "mpgemm/table", "other"} <= set(labels.values())


HLO_FUSION = """HloModule jit_f, is_scheduled=true

%fused_computation (p0: f32[4], p1: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %p1 = f32[4]{0} parameter(1)
  %convert.1 = f32[4]{0} convert(%p0), metadata={op_name="jit(f)/while/body/checkpoint/mpgemm/cw/convert_element_type"}
  ROOT %add.2 = f32[4]{0} add(%convert.1, %p1), metadata={op_name="jit(f)/while/body/add"}
}

%fused_computation.1 (p0.1: f32[4]) -> f32[4] {
  %p0.1 = f32[4]{0} parameter(0)
  %exp.3 = f32[4]{0} exponential(%p0.1), metadata={op_name="jit(f)/attention/exp"}
  ROOT %mul.4 = f32[4]{0} multiply(%exp.3, %exp.3), metadata={op_name="jit(f)/lm_head/mul"}
}

%fused_computation.2 (p0.2: f32[4]) -> f32[4] {
  %p0.2 = f32[4]{0} parameter(0)
  %fusion.9 = f32[4]{0} fusion(%p0.2), kind=kLoop, calls=%fused_computation.1
  ROOT %dot.5 = f32[4]{0} multiply(%fusion.9, %p0.2), metadata={op_name="jit(f)/lm_head/mpgemm/dot_general"}
}

ENTRY %main (a: f32[4], b: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %b = f32[4]{0} parameter(1)
  %add_fusion = f32[4]{0} fusion(%a, %b), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/add"}
  %mul_fusion = f32[4]{0} fusion(%add_fusion), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/lm_head/mul"}
  %outer_fusion = f32[4]{0} fusion(%mul_fusion), kind=kOutput, calls=%fused_computation.2
  ROOT %copy.6 = f32[4]{0} copy(%outer_fusion)
}
"""


def test_fusion_takes_the_scope_of_what_it_fuses():
    """A fusion's metadata is its root's: it is labelled by everything it
    fuses, nested fusions included, mpGEMM over attention over the LM
    head's other ops."""
    labels = scopes.op_scopes(HLO_FUSION)
    assert labels["add_fusion"] == "mpgemm/cw"        # root outside a scope
    assert labels["mul_fusion"] == "attention"        # attention over lm_head
    assert labels["outer_fusion"] == "lm_head/mpgemm"  # through a nested one
    assert labels["copy.6"] == "other"
    assert labels["convert.1"] == "mpgemm/cw" and labels["add.2"] == "other"
    assert scopes.module_name(HLO_FUSION) == "jit_f"
    assert scopes.scope_label("jit(f)/lm_head/mpgemm/table/sub") \
        == "lm_head/mpgemm/table"


def _canonical(text):
    """Compiled HLO without metadata, frame tables and name numbering."""
    text = "\n".join(line for line in text.splitlines() if not re.match(
        r"(\d+ |FileNames$|FunctionNames$|FileLocations$|StackFrames$)",
        line))
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_scopes_leave_the_compiled_program_unchanged(tl, monkeypatch,
                                                     program):
    """The scopes are metadata: the optimized program is the same without
    them once metadata and instruction numbering are set aside."""
    cfg, params = tl

    def compiled():
        eng = ServingEngine(cfg, params, max_batch=2, max_seq=64,
                            decode_chunk=2, prefill_chunk=4)
        if program == "decode":
            low = eng._decode.lower(eng.params, eng.state)
        else:
            low = eng._prefill.lower(eng.params, eng._zero_slot,
                                     jnp.zeros((1, 4), jnp.int32),
                                     np.int32(0), np.int32(1))
        return low.compile().as_text()

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert "mpgemm/" in scoped and "mpgemm/" not in bare
    assert _canonical(scoped) == _canonical(bare)


# ---------------------------------------------------------------------------
# monotonic-clock satellites
# ---------------------------------------------------------------------------

def test_heartbeat_uses_monotonic_not_wall_clock(monkeypatch):
    """A wall-clock jump must not fire heartbeat timeouts: the manager's
    default ``now`` comes from time.monotonic."""
    from repro.training import fault_tolerance as ft
    t = {"mono": 1000.0}
    monkeypatch.setattr(ft.time, "monotonic", lambda: t["mono"])
    # a wildly wrong wall clock must be irrelevant to interval math
    monkeypatch.setattr(ft.time, "time", lambda: 1e18)
    mgr = ft.FaultToleranceManager(2, heartbeat_timeout=10.0)
    assert mgr.dead_hosts() == []
    t["mono"] += 5.0
    mgr.heartbeat(0)
    t["mono"] += 7.0   # host 0 heartbeat 7s ago, host 1 12s ago
    assert mgr.dead_hosts() == [1]
    assert mgr.hosts[0].last_heartbeat == 1005.0


def test_checkpoint_manifest_wall_time_and_monotonic_duration(tmp_path):
    from repro.training import checkpoint as ck
    tree = {"w": jnp.ones((2, 2))}
    d = ck.save(str(tmp_path), 3, tree)
    with open(f"{d}/MANIFEST.json") as f:
        man = json.load(f)
    # wall-clock stays as metadata; the duration field is monotonic-derived
    assert man["time"] > 0
    assert man["write_seconds"] >= 0.0 and math.isfinite(man["write_seconds"])
    restored, step = ck.restore(str(tmp_path), tree)
    assert step == 3
