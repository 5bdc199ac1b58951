"""One run of a serving cell: set-up, warm-up, the window, the trace and
the correctness check.

The system under test is ``ServingEngine`` built as ``repro.launch.serve``
builds it (``model_config``, then ``build_engine``), dense cache, greedy.
The window drives its ``submit`` and ``step``; everything else here is the
benchmark's own.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import json
import shutil
import tempfile
import time

import numpy as np

from bench.harness import attribution
from bench.harness import device as D
from bench.harness import readers
from bench.harness import traffic as T
from bench.harness import trace as TR

# the profiler traces the last seconds of the window (stopping it stalls
# the host for seconds, so that happens after the window has closed): at
# least TRACE_SECONDS and TRACE_CHUNKS decode chunks at the pace of the
# window's first quarter, at most half the window. The traced part opens
# a lead after the profiler starts, as the device's tracer can start late
TRACE_SECONDS = 4.0
TRACE_CHUNKS = 3
TRACE_LEAD = 3.0
ARCH_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab_size", "qkv_bias", "rope_theta", "norm_eps",
             "kv_cache_dtype")
_COMPILES = []


@dataclasses.dataclass
class Track:
    spec: T.RequestSpec
    req: object           # the engine's Request
    due: float
    syncs: list           # [(time, tokens so far)]
    done_t: float | None = None


@dataclasses.dataclass
class Chunk:
    t_start: float
    t_sync: float
    emitted: list         # [(uid, prompt_len, tokens before, tokens after)]


@dataclasses.dataclass
class Run:
    """What a metric reader sees (``bench/metrics/<name>.py``)."""
    cell: object
    model: object          # the configuration's model module (counts)
    peaks: dict
    t_process: float
    t_setup_end: float
    t0: float
    t_end: float
    tracks: list
    chunks: list
    counters: dict         # registry_delta(...) of the engine over the window
    admits: dict           # uid -> admission start (traced runs)
    trace: dict | None     # attribution.reduce(...) of the traced part
    trace_pc: tuple | None  # the traced part on the perf_counter clock
    compiles_in_window: int

    @property
    def conf(self):
        return self.cell.config

    @property
    def engine(self):
        return self.cell.traffic["engine"]


def _count_compiles():
    if _COMPILES:
        return
    import jax.monitoring as mon

    _COMPILES.append(0)

    def on(name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            _COMPILES[0] += 1

    mon.register_event_duration_secs_listener(on)


def engine_flags(conf, mix) -> list:
    q, e = conf["quant"], mix["engine"]
    return ["--arch", conf["arch"], "--mode", q["mpgemm_mode"],
            "--weight-bits", str(q["weight_bits"]),
            "--fusion", q.get("fusion", "auto"),
            "--max-batch", str(e["max_batch"]), "--max-seq", str(e["max_seq"]),
            "--decode-chunk", str(e["decode_chunk"]),
            "--prefill-chunk", str(e["prefill_chunk"])]


def program_config(conf, args):
    """``serve.model_config`` with every size of the configuration file."""
    import jax.numpy as jnp
    from repro.launch import serve

    return serve.model_config(args).replace(
        **{k: conf[k] for k in ARCH_KEYS},
        param_dtype=jnp.dtype(conf["param_dtype"]),
        activation_dtype=jnp.dtype(conf["activation_dtype"]),
        quant=dict(conf["quant"]))


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class _Loop:
    """The window: submits, steps and records, on one thread."""

    def __init__(self, eng, cell, requests, traced, step_hook):
        self.eng, self.cell, self.traced = eng, cell, traced
        self.requests = list(requests)
        self.step_hook = step_hook
        self.tracks, self.live, self.chunks = {}, {}, []
        self.next = 0

    def submit(self, spec, due):
        from repro.serving.engine import Request

        r = Request(uid=spec.uid, prompt=spec.prompt,
                    max_new_tokens=spec.max_new, temperature=0.0)
        with _annotate(self.traced, "engine.submit"):
            self.eng.submit(r)
        self.tracks[spec.uid] = self.live[spec.uid] = Track(spec, r, due, [])
        self.next += 1

    def busy(self):
        return bool(self.eng.queue) or any(s is not None
                                           for s in self.eng.slots)

    def step(self):
        t_a = time.perf_counter()
        with _annotate(self.traced, "engine.step"):
            self.eng.step()
            if self.step_hook is not None:
                self.step_hook(self.eng)
        t = time.perf_counter()
        done = []
        with _annotate(self.traced, "harness.record"):
            emitted = []
            for uid, tr in self.live.items():
                n = len(tr.req.output)
                prev = tr.syncs[-1][1] if tr.syncs else 0
                if n > prev:
                    tr.syncs.append((t, n))
                    emitted.append((uid, len(tr.spec.prompt), prev, n))
                if tr.req.done:
                    tr.done_t = t
                    done.append(uid)
            for uid in done:
                del self.live[uid]
            self.chunks.append(Chunk(t_a, t, emitted))
        return t, len(done)


def _start_trace(tmp):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host annotations only: no per-call events
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    return time.perf_counter()


def _open_window():
    ann = _annotate(True, TR.WINDOW_SPAN)
    ann.__enter__()
    return ann, time.perf_counter()


def _stop_trace(ann):
    import jax

    t = time.perf_counter()
    ann.__exit__(None, None, None)
    jax.profiler.stop_trace()
    return t


def drive(eng, cell, requests, seconds, *, traced=False, step_hook=None):
    """Run the window. Returns (loop, t0, t_end, trace dir, traced part)."""
    mix = cell.traffic
    loop = _Loop(eng, cell, requests, False, step_hook)
    closed = mix["loop"] == "closed"
    if closed:
        for _ in range(mix["clients"]):
            loop.submit(loop.requests[loop.next], time.perf_counter())
        while eng.queue or sum(s is not None for s in eng.slots) \
                < mix["clients"]:
            loop.step()
    loop.chunks.clear()  # chunks before the window
    loop.traced = traced
    t0 = time.perf_counter()
    t_stop = t0 + seconds
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    lead = min(TRACE_LEAD, 0.25 * seconds)
    trace_at = started = ann = trace_pc = None
    now = t0
    while now < t_stop:
        if traced and trace_at is None and now >= t0 + 0.25 * seconds:
            pace = (now - t0) / max(1, len(loop.chunks))
            span = max(TRACE_SECONDS, TRACE_CHUNKS * pace)
            trace_at = t_stop - min(span, 0.5 * seconds) - lead
        if trace_at is not None and started is None and now >= trace_at:
            started = _start_trace(tmp)
        if started is not None and ann is None and now >= started + lead:
            ann, a = _open_window()
        if not closed:
            while (loop.next < len(loop.requests)
                   and t0 + loop.requests[loop.next].due <= now):
                loop.submit(loop.requests[loop.next],
                            t0 + loop.requests[loop.next].due)
        if loop.busy():
            now, n_done = loop.step()
            if closed:
                for _ in range(min(n_done, len(loop.requests) - loop.next)):
                    loop.submit(loop.requests[loop.next], now)
            continue
        nxt = (t0 + loop.requests[loop.next].due
               if not closed and loop.next < len(loop.requests) else t_stop)
        with _annotate(loop.traced, "gen.wait"):
            time.sleep(max(0.0, min(nxt, t_stop) - time.perf_counter()))
        now = time.perf_counter()
    if ann is not None:
        trace_pc = (a, _stop_trace(ann))
    return loop, t0, now, tmp, trace_pc


# ---------------------------------------------------------------------------
# correctness: the served tokens against the plain reference
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _reference(model_name: str, conf_json: str, act_bits):
    import jax
    from bench.harness import spec as S

    conf = json.loads(conf_json)
    model = S.model_module(conf)
    return jax.jit(functools.partial(model.reference_logits, conf,
                                     act_bits=act_bits))


def sample_for_check(tracks, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, the longest among them."""
    done = sorted((t for t in tracks if t.done_t is not None),
                  key=lambda t: t.spec.uid)
    if not done:
        return []
    longest = max(done, key=lambda t: (len(t.req.output), -t.spec.uid))
    rest = [t for t in done if t is not longest]
    rng = T.rng_for(seed, 0xC4)
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return sorted([longest] + [rest[i] for i in pick],
                  key=lambda t: t.spec.uid)


def served_gaps(conf, seed, sample, max_seq, positions, control=False):
    """Widest gap by which a checked token's reference logit lies below the
    reference's best. The checked tokens are the served ones; with
    ``control``, the int4 control takes the program's place and the checked
    token at each served position is the one the control puts first."""
    from bench.harness import spec as S

    toks = np.zeros((len(sample), max_seq), np.int32)
    rows = np.zeros(positions, np.int32)
    cols = np.zeros(positions, np.int32)
    served = []
    for i, tr in enumerate(sample):
        out = np.asarray(tr.req.output, np.int32)
        seq = np.concatenate([np.asarray(tr.spec.prompt, np.int32), out])
        toks[i, :len(seq)] = seq
        base = len(tr.spec.prompt) - 1
        for j, tok in enumerate(out):
            rows[len(served)], cols[len(served)] = i, base + j
            served.append(int(tok))
    p = len(served)
    if p > positions:
        raise ValueError(f"{p} served tokens exceed {positions} positions")
    words = S.model_module(conf).split_seed(seed)
    conf_json = json.dumps(conf, sort_keys=True)
    ref = np.asarray(_reference(conf["model"], conf_json, None)(
        words, toks, rows, cols))[:p]
    best = ref.max(-1)

    def gap(picked):
        return float(np.max(best - ref[np.arange(p), picked]))

    out = {"served_tokens": p,
           "argmax_share": float(np.mean(ref.argmax(-1) == served))}
    checked = served
    if control:
        out["program_max_logit_gap"] = gap(served)
        checked = np.asarray(_reference(conf["model"], conf_json, 4)(
            words, toks, rows, cols))[:p].argmax(-1)
    out["max_logit_gap"] = gap(checked)
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def build(cell, seed: int, *, traced: bool, log=print):
    """Set-up: the serving weights from the seed, the engine as
    ``repro.launch.serve`` builds it, and one request through prefill,
    merge and the decode chunk (the only shapes the window uses). Logs
    the seconds of each phase."""
    import jax

    from bench.harness import spec as S
    from repro.launch import serve
    from repro.obs.trace import Tracer
    from repro.serving.engine import Request

    conf, mix = cell.config, cell.traffic
    t = [time.perf_counter()]

    def phase(name):
        t.append(time.perf_counter())
        log(f"[setup] {name} {t[-1] - t[-2]:.2f} s")

    args = serve.parser().parse_args(engine_flags(conf, mix))
    pcfg = program_config(conf, args)
    params = S.model_module(conf, serving=True).serving_params(conf, seed)
    jax.block_until_ready(params)
    phase("weights")
    tracer = Tracer() if traced else None
    eng = serve.build_engine(args, pcfg, params, tracer=tracer)
    phase("engine")
    e = mix["engine"]
    warm = Request(uid=-1, prompt=np.arange(2 * e["prefill_chunk"] + 2,
                                            dtype=np.int32),
                   max_new_tokens=2 * e["decode_chunk"], temperature=0.0)
    eng.submit(warm)
    eng.run_to_completion()
    phase("warm request")
    return eng, tracer


def registry_delta(before: dict, after: dict) -> dict:
    """What the engine's registry counted between two of its snapshots
    (``MetricsRegistry.snapshot()``): name -> a counter's increase, or a
    histogram's ``{"count": n, "total": sum}`` over the observations made
    in between. Gauges, which count nothing, are left out."""
    out = {}
    for name, a in after["metrics"].items():
        b = before["metrics"].get(name, {})
        if a["type"] == "counter":
            out[name] = a["value"] - b.get("value", 0)
        elif a["type"] == "histogram":
            out[name] = {"count": a["count"] - b.get("count", 0),
                         "total": a["sum"] - b.get("sum", 0.0)}
    return out


def _admit_times(tracer, epoch_ns) -> dict:
    """uid -> start of the engine's ``admit`` span (perf_counter seconds)."""
    evs = tracer.chrome_trace()["traceEvents"]
    mark = next(ev for ev in evs if ev.get("name") == "bench.epoch")
    off = epoch_ns - mark["ts"] * 1e3
    return {ev["args"]["uid"]: (ev["ts"] * 1e3 + off) / 1e9
            for ev in evs if ev.get("name") == "admit"}


def decode_scopes_per_step(run) -> list:
    """``[[label, device seconds per decode step], ...]`` of the decode
    program's scopes, longest first (empty where there are none)."""
    scopes = readers.decode_scopes(run)
    if scopes is None:
        return []
    seconds, steps = scopes
    return sorted(([k, v / steps] for k, v in seconds.items()),
                  key=lambda x: -x[1])[:10]


def run_cell(cell, seed: int, seconds: float, *, traced: bool,
             t_process: float, require_chip: bool = True,
             control: bool = False, step_hook=None, log=print):
    """One run of ``cell``. Returns (result line dict, Run, check dict).
    With ``control``, the int4 control takes the program's place in the
    check, so ``correct`` is the control's."""
    import glob

    import jax

    from bench.harness import spec as S

    info = (D.require(cell.chips) if require_chip
            else D.describe(jax.devices()[:cell.chips]))
    devs = jax.devices()[:cell.chips]
    _count_compiles()
    conf, mix = cell.config, cell.traffic

    log(f"[setup] start, imports and device "
        f"{time.perf_counter() - t_process:.2f} s")
    eng, tracer = build(cell, seed, traced=traced, log=log)
    requests = T.make_requests(mix, conf["vocab_size"], seed, seconds)
    t_setup_end = time.perf_counter()
    log(f"[setup] {t_setup_end - t_process:.1f} s to the first timed request")
    scope_maps = None
    if traced:  # after set-up: untraced runs, which give setup_s, skip it
        scope_maps = eng.op_scopes()
        log(f"[setup] op scopes {time.perf_counter() - t_setup_end:.2f} s")

    compiles0, registry0 = _COMPILES[0], eng.metrics.snapshot()
    epoch = time.perf_counter_ns()
    if tracer is not None:
        tracer.complete("bench.epoch", epoch, epoch)
    loop, t0, t_end, tmp, trace_pc = drive(eng, cell, requests, seconds,
                                           traced=traced, step_hook=step_hook)
    compiles = _COMPILES[0] - compiles0
    log(f"[window] {t_end - t0:.3f} s, {len(loop.chunks)} decode chunks; "
        f"compilations in the window: {compiles}")
    tracks = list(loop.tracks.values())
    attempted = [t for t in tracks if t.due <= t_end
                 and (t.done_t is None or t.done_t > t0)]
    failed = sum(1 for t in attempted if t.done_t is not None
                 and len(t.req.output) != t.spec.max_new)
    sample = sample_for_check(attempted, mix["check_requests"], seed)
    run = Run(cell=cell, model=S.model_module(conf),
              peaks=D.PEAKS.get(info["kind"], {}), t_process=t_process,
              t_setup_end=t_setup_end, t0=t0, t_end=t_end, tracks=tracks,
              chunks=loop.chunks,
              counters=registry_delta(registry0, eng.metrics.snapshot()),
              admits=_admit_times(tracer, epoch) if tracer else {},
              trace=None, trace_pc=trace_pc, compiles_in_window=compiles)
    memory = D.memory_peak(devs)
    del eng, loop, tracer
    gc.collect()

    if tmp is not None:
        files = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))
        run.trace = attribution.reduce(TR.load(files[-1]), scope_maps)
        shutil.rmtree(tmp, ignore_errors=True)

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = S.metric_reader(m["name"], cell.root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    positions = mix["check_requests"] * mix["output"]["max"]
    check = (served_gaps(conf, seed, sample, mix["engine"]["max_seq"],
                         positions, control=control) if sample else {})
    limit = cell.check["max_logit_gap"]
    gap = check.get("max_logit_gap")  # None: no request finished to check
    correct = gap is not None and failed == 0 and gap <= limit
    dev = dict(info, memory_peak_bytes=memory)
    result = {"correct": correct, "attempted": len(attempted),
              "failed": failed, "metrics": metrics, "device": dev}
    if run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": [list(x) for x in run.trace["device_ops"]],
            "idle_gaps": [list(x) for x in run.trace["idle_gaps"]],
            "scopes": decode_scopes_per_step(run)}
    result["check"] = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "checked_tokens": {"value": check.get("served_tokens", 0),
                           "limit": 1}}
    return result, run, check
