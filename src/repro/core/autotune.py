"""Measured-time kernel autotuner with a persistent on-disk tuning cache.

The paper's compilation story (§3.2.2, Fig 11/14) searches tile shapes with
*measured* feedback instead of trusting an analytical model. This module is
that search for the Pallas mpGEMM stack:

  * ``candidate_configs`` enumerates (fusion, bm, bn, bg) candidates for one
    mpGEMM shape, using the LMMA memory-size scheduler and the DSE traffic
    model (``core.lmma._score`` / ``core.dse.tile_traffic``) as the *prior*
    — the analytical score orders the space, wall-clock decides.
  * ``tune_mpgemm`` times each candidate on the real kernels (one jit per
    candidate), recording **compile time and steady-state time separately**
    — the two failure modes of a bad dispatch (compile-shape churn vs a
    genuinely bad tile) look identical in end-to-end latency and are only
    distinguishable with both numbers.
  * ``TuningCache`` persists winners to a JSON file keyed by
    (M, N, G, k_group, weight_bits, dtype, table_quant), with the backend
    and jax version recorded at file level. Loads are tolerant: a corrupt /
    truncated / format-version-mismatched file degrades to an empty cache
    with a warning (dispatch falls back to heuristics); a cache written on
    a *different backend* is kept but every entry is re-validated and
    re-clamped at lookup so it can never crash dispatch. Saves are atomic
    (write-to-temp + ``os.replace``) so concurrent writers can interleave
    without ever leaving a torn file.

Dispatch integration: ``fusion="tuned"`` (kernels/ops.py) consults the
module-level *active* cache at trace time — a dict lookup, microseconds —
and falls back to the ``"auto"`` heuristic on a miss. Measurement never
happens inside a trace; populate the cache offline via ``tune_mpgemm`` /
``pretune_params`` (the serving engine and ``benchmarks/bench_autotune.py``
both drive it).
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core import lmma
from repro.core.packing import chunk_groups
from repro.core.lmma import (LMMADescriptor, TileSchedule, fused_tile_bytes,
                             select_fusion)

__all__ = ["TunedConfig", "TuningCache", "shape_key", "candidate_configs",
           "tune_mpgemm", "pretune_params", "configure", "deactivate",
           "get_active", "lookup_tuned", "lookup_fusion_any"]

CACHE_FORMAT_VERSION = 1

# block-shape candidate axes (the scheduler's own lattice; bg in packing
# chunks, see lmma.align_blocks)
_BM_CANDS = (32, 64, 128, 256)
_BN_CANDS = (128, 256, 512, 1024, 2048)
_BG_CHUNKS = (1, 2, 4, 8)


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One dispatch decision for one mpGEMM shape, plus its measurements."""

    fusion: str                 # "fused" | "staged"
    block_m: int
    block_n: int
    block_g: int
    steady_ms: float = 0.0      # median post-compile wall-clock
    compile_ms: float = 0.0     # first-call (trace + compile) wall-clock
    heuristic_ms: float = 0.0   # same-pass steady time of the "auto" pick
    source: str = "heuristic"   # "heuristic" | "measured"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @property
    def blocks(self) -> Tuple[int, int, int]:
        return (self.block_m, self.block_n, self.block_g)


def shape_key(m: int, n: int, g: int, k_group: int, w_bits: int, *,
              dtype: str = "f32",
              table_quant: Optional[str] = "per_row") -> str:
    """Cache key for one mpGEMM problem: shape + dtype + quant layout."""
    return (f"m{m}.n{n}.g{g}.kg{k_group}.w{w_bits}."
            f"{dtype}.tq{table_quant or 'none'}")


def sanitize_config(cfg: TunedConfig, m: int, n: int, g: int, k_group: int,
                    planes: int,
                    vmem_budget: int = lmma.TILE_BUDGET) -> Optional[TunedConfig]:
    """Force a (possibly foreign) cache entry into a valid dispatch decision.

    Returns None when the entry is unusable (bad types / non-positive
    blocks / unknown fusion); otherwise rounds the blocks to the TPU tiling
    rule, clamps them to the padded problem, and demotes ``fused`` to
    ``staged`` when the fused working set cannot fit VMEM — the exact
    constraints ops._clamp_blocks (lmma.align_blocks) / select_fusion
    enforce, so a sanitized config can never crash the wrappers.
    """
    try:
        bm, bn, bg = int(cfg.block_m), int(cfg.block_n), int(cfg.block_g)
        fusion = str(cfg.fusion)
    except (TypeError, ValueError, OverflowError):  # None, "x", inf
        return None
    if fusion not in ("fused", "staged") or bm <= 0 or bn <= 0 or bg <= 0:
        return None
    bm, bn, bg = lmma.align_blocks(m, n, g, k_group, planes, bm, bn, bg)
    desc = LMMADescriptor(m=m, n=n, k=g * k_group, w_bits=planes,
                          k_group=k_group)
    if fusion == "fused" and fused_tile_bytes(bm, bn, bg, desc) > vmem_budget:
        fusion = "staged"
    return dataclasses.replace(cfg, fusion=fusion, block_m=bm, block_n=bn,
                               block_g=bg)


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------

class TuningCache:
    """JSON-backed {shape_key -> TunedConfig} map with durable load/save."""

    def __init__(self, path: Optional[str] = None, *,
                 backend: Optional[str] = None):
        if backend is None:
            import jax
            backend = jax.default_backend()
        import jax
        self.path = path
        self.backend = backend
        self.jax_version = jax.__version__
        self.entries: Dict[str, TunedConfig] = {}
        self.foreign = False      # loaded from a different backend/jax
        self.hits = 0
        self.misses = 0
        self.sanitized = 0        # lookups whose entry needed repair/drop
        if path is not None and os.path.exists(path):
            self._load(path)

    # -- durability -------------------------------------------------------
    def _load(self, path: str):
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            warnings.warn(f"tuning cache {path!r} unreadable ({e}); "
                          "falling back to heuristic dispatch")
            return
        if not isinstance(raw, dict) \
                or raw.get("version") != CACHE_FORMAT_VERSION \
                or not isinstance(raw.get("entries"), dict):
            warnings.warn(
                f"tuning cache {path!r} has unknown format "
                f"(version={raw.get('version') if isinstance(raw, dict) else '?'}, "
                f"want {CACHE_FORMAT_VERSION}); ignoring it")
            return
        if raw.get("backend") != self.backend \
                or raw.get("jax_version") != self.jax_version:
            self.foreign = True
            warnings.warn(
                f"tuning cache {path!r} was tuned on "
                f"backend={raw.get('backend')!r}/jax={raw.get('jax_version')!r} "
                f"(running {self.backend!r}/{self.jax_version}); entries will "
                "be re-validated at lookup")
        fields = {f.name for f in dataclasses.fields(TunedConfig)}
        for key, ent in raw["entries"].items():
            if not isinstance(ent, dict):
                continue
            try:
                cfg = TunedConfig(**{k: v for k, v in ent.items()
                                     if k in fields})
                int(cfg.block_m), int(cfg.block_n), int(cfg.block_g)
            except (TypeError, ValueError):
                continue  # skip malformed entries, keep the rest
            self.entries[key] = cfg

    def save(self, path: Optional[str] = None):
        """Atomic save: temp file in the target dir + os.replace."""
        path = path or self.path
        if path is None:
            raise ValueError("TuningCache has no path to save to")
        payload = {
            "version": CACHE_FORMAT_VERSION,
            "backend": self.backend,
            "jax_version": self.jax_version,
            "entries": {k: v.as_dict() for k, v in sorted(self.entries.items())},
        }
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".tuning_cache.",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1)
            os.replace(tmp, path)  # atomic on POSIX: readers never see a torn file
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        self.path = path

    # -- access -----------------------------------------------------------
    def put(self, key: str, cfg: TunedConfig):
        self.entries[key] = cfg

    def lookup(self, key: str) -> Optional[TunedConfig]:
        cfg = self.entries.get(key)
        if cfg is None:
            self.misses += 1
        else:
            self.hits += 1
        return cfg

    def counters(self) -> dict:
        """Observability snapshot: lookup traffic + durability state.

        ``sanitized`` counts lookups whose entry had to be repaired (blocks
        re-clamped, fused demoted to staged) or dropped entirely — nonzero
        on a healthy same-backend cache means the cache file is stale or
        foreign. Exposed via ``engine.stats()['tuning_cache']`` and
        ``bench_autotune.py``."""
        return {
            "entries": len(self.entries),
            "hits": self.hits,
            "misses": self.misses,
            "sanitized": self.sanitized,
            "foreign": self.foreign,
            "backend": self.backend,
            "path": self.path,
        }

    def __len__(self):
        return len(self.entries)


# ---------------------------------------------------------------------------
# module-level active cache (what fusion="tuned" consults at trace time)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[TuningCache] = None


def configure(path: Optional[str], **kw) -> TuningCache:
    """Load (or create) the active tuning cache used by ``fusion="tuned"``."""
    global _ACTIVE
    _ACTIVE = TuningCache(path, **kw)
    return _ACTIVE


def deactivate():
    global _ACTIVE
    _ACTIVE = None


def get_active() -> Optional[TuningCache]:
    return _ACTIVE


def lookup_tuned(m: int, n: int, g: int, k_group: int, planes: int, *,
                 w_bits: Optional[int] = None, dtype: str = "f32",
                 table_quant: Optional[str] = "per_row"
                 ) -> Optional[TunedConfig]:
    """Trace-time lookup for dispatch: sanitized entry or None (miss)."""
    if _ACTIVE is None:
        return None
    key = shape_key(m, n, g, k_group,
                    planes if w_bits is None else w_bits,
                    dtype=dtype, table_quant=table_quant)
    cfg = _ACTIVE.lookup(key)
    if cfg is None:
        return None
    out = sanitize_config(cfg, m, n, g, k_group, planes)
    if out != cfg:  # repaired (clamped/demoted) or dropped (None)
        _ACTIVE.sanitized += 1
    return out


def lookup_fusion_any(m: int, g: int, k_group: int, w_bits: int) -> Optional[str]:
    """Best-effort fusion vote for table-sharing decisions (layers.make_table
    doesn't know N). Returns the fusion of the largest-N tuned entry whose
    (M, G, k_group, bits) match, or None when nothing matches."""
    if _ACTIVE is None:
        return None
    prefix = f"m{m}."
    want = f".g{g}.kg{k_group}.w{w_bits}."
    best_n, best = -1, None
    for key, cfg in _ACTIVE.entries.items():
        if not key.startswith(prefix) or want not in key:
            continue
        try:
            n = int(key.split(".n")[1].split(".")[0])
        except (IndexError, ValueError):
            continue
        if n > best_n and cfg.fusion in ("fused", "staged"):
            best_n, best = n, cfg.fusion
    return best


# ---------------------------------------------------------------------------
# candidate generation: DSE prior over the scheduler's lattice
# ---------------------------------------------------------------------------

def candidate_configs(m: int, n: int, g: int, k_group: int, planes: int, *,
                      vmem_budget: int = lmma.TILE_BUDGET,
                      max_candidates: int = 6) -> List[TunedConfig]:
    """Analytically-ranked search space for one mpGEMM shape.

    The heuristic pick (ops.pick_blocks + select_fusion — what ``"auto"``
    would do) is always candidate 0, so measured tuning can never select a
    config worse than the heuristic *as measured in the same pass*. The rest
    are the top-scoring tiles under the LMMA MACs-per-byte prior, each in
    its VMEM-feasible fusion mode (plus the opposite mode for the best tile,
    so measurement — not the model — settles fused-vs-staged).
    """
    from repro.kernels.ops import pick_blocks  # lazy: ops imports autotune

    desc = LMMADescriptor(m=m, n=n, k=g * k_group, w_bits=planes,
                          k_group=k_group)
    scored = []
    seen = set()
    chunk = chunk_groups(k_group, planes)
    for bm in _BM_CANDS:
        for bn in _BN_CANDS:
            for f in _BG_CHUNKS:
                bmc, bnc, bg = lmma.align_blocks(m, n, g, k_group, planes,
                                                 bm, bn, f * chunk)
                if (bmc, bnc, bg) in seen:
                    continue
                seen.add((bmc, bnc, bg))
                t, w, a = lmma._tile_bytes(bmc, bnc, bg, desc)
                tot = 2 * (t + w) + a
                if tot > vmem_budget:
                    continue
                ts = TileSchedule(bmc, bnc, bg, t, w, a, tot)
                scored.append((lmma._score(ts, desc, True), ts))
    scored.sort(key=lambda s: -s[0])

    hm, hn, hg = pick_blocks(m, n, g, k_group, planes)
    hfusion = select_fusion(desc, TileSchedule(hm, hn, hg, 0, 0, 0, 0),
                            vmem_budget=vmem_budget)
    out = [TunedConfig(hfusion, hm, hn, hg, source="heuristic")]
    emitted = {(hfusion, hm, hn, hg)}
    for _, ts in scored:
        if len(out) >= max_candidates:
            break
        fusion = ("fused"
                  if fused_tile_bytes(ts.bm, ts.bn, ts.bg, desc) <= vmem_budget
                  else "staged")
        cand = (fusion, ts.bm, ts.bn, ts.bg)
        if cand in emitted:
            continue
        emitted.add(cand)
        out.append(TunedConfig(*cand, source="measured"))
    # let measurement arbitrate fused-vs-staged on the best tile
    if out and len(out) < max_candidates + 1:
        top = out[1] if len(out) > 1 else out[0]
        alt = "staged" if top.fusion == "fused" else "fused"
        if alt == "staged" or fused_tile_bytes(
                top.block_m, top.block_n, top.block_g, desc) <= vmem_budget:
            cand = (alt, top.block_m, top.block_n, top.block_g)
            if cand not in emitted:
                out.append(TunedConfig(*cand, source="measured"))
    return out


# ---------------------------------------------------------------------------
# measured tuning
# ---------------------------------------------------------------------------

def _measure(fn, args, repeats: int) -> Tuple[float, float]:
    """(compile_ms, steady_ms): first call vs median of post-compile calls."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_ms = (time.perf_counter() - t0) * 1e3
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return compile_ms, times[len(times) // 2]


def tune_mpgemm(m: int, qw, *, table_quant: Optional[str] = "per_row",
                cache: Optional[TuningCache] = None, repeats: int = 3,
                max_candidates: int = 6, interpret: Optional[bool] = None,
                seed: int = 0, verbose: bool = False
                ) -> Tuple[TunedConfig, List[TunedConfig]]:
    """Measure candidates for one (M × qw) mpGEMM and record the winner.

    Returns (best, all_measured). Each measured config carries compile_ms
    and steady_ms — together they distinguish compile-shape churn (high
    compile, fine steady) from a genuinely bad tile (fine compile, slow
    steady). Winner selection uses steady_ms only; compile cost is paid
    once per shape and must not bias the steady-state choice.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops  # lazy: ops imports autotune

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    g, planes = qw.g, qw.num_planes
    x = jax.random.normal(jax.random.key(seed), (m, qw.k_total), jnp.float32)
    measured: List[TunedConfig] = []
    for cand in candidate_configs(m, qw.n, g, qw.k_group, planes,
                                  max_candidates=max_candidates):
        fn = jax.jit(functools.partial(
            ops.lut_mpgemm, table_quant=table_quant, fusion=cand.fusion,
            block_m=cand.block_m, block_n=cand.block_n,
            block_g=cand.block_g, interpret=interpret))
        try:
            compile_ms, steady_ms = _measure(fn, (x, qw), repeats)
        except Exception as e:  # candidate invalid on this backend: skip
            warnings.warn(f"autotune candidate {cand.blocks} "
                          f"({cand.fusion}) failed: {e}")
            continue
        measured.append(dataclasses.replace(
            cand, compile_ms=compile_ms, steady_ms=steady_ms))
        if verbose:
            print(f"  cand {cand.fusion:6s} bm={cand.block_m:<4d}"
                  f"bn={cand.block_n:<5d}bg={cand.block_g:<4d}"
                  f"compile {compile_ms:8.1f} ms  steady {steady_ms:8.2f} ms"
                  f"  [{cand.source}]")
    if not measured:
        raise RuntimeError(f"no viable autotune candidate for m={m}, {qw}")
    best = min(measured, key=lambda c: c.steady_ms)
    heur = next((c for c in measured if c.source == "heuristic"), best)
    best = dataclasses.replace(best, source="measured",
                               heuristic_ms=heur.steady_ms)
    if cache is not None:
        cache.put(shape_key(m, qw.n, g, qw.k_group, planes,
                            table_quant=table_quant), best)
    return best, measured


def collect_qw_shapes(params) -> List:
    """Unique QuantizedWeight leaves in a param tree (by shape signature).

    Batched QuantizedWeights (vmapped MoE experts: packed [E, N, bytes])
    are represented by their first slice — every expert shares the shape,
    so one tuned entry covers the whole batched einsum dispatch.
    """
    from repro.core.quantize import QuantizedWeight

    found, seen = [], set()

    def walk(node):
        if isinstance(node, QuantizedWeight):
            if node.packed is None:
                return  # offline-CW store: no packed planes to tile-tune
            if node.packed.ndim > 2:
                # vmap-batched (stacked layers / experts, possibly nested):
                # every slice shares the shape, so tune on the first one
                ix = (0,) * (node.packed.ndim - 2)
                node = QuantizedWeight(
                    node.packed[ix], node.scale[ix],
                    None if node.zero_prime is None else node.zero_prime[ix],
                    node.plane_scales, bits=node.bits, k_group=node.k_group,
                    k_total=node.k_total, n=node.n)
            sig = (node.n, node.k_total, node.k_group, node.num_planes)
            if sig not in seen:
                seen.add(sig)
                found.append(node)
            return
        if isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    return found


def _local_slice(qw, mp: int):
    """The [n/mp, bytes] shard of a packed weight one model-parallel device
    holds — what its mpGEMM actually runs, hence what must be measured."""
    from repro.core.quantize import QuantizedWeight
    if mp <= 1 or qw.n % mp:
        return qw
    nl = qw.n // mp
    return QuantizedWeight(
        qw.packed[:nl], qw.scale[:nl],
        None if qw.zero_prime is None else qw.zero_prime[:nl],
        qw.plane_scales, bits=qw.bits, k_group=qw.k_group,
        k_total=qw.k_total, n=nl)


def pretune_params(params, ms: Sequence[int], *,
                   cache: Optional[TuningCache] = None,
                   table_quant: Optional[str] = "per_row",
                   plan=None, repeats: int = 2, max_candidates: int = 4,
                   skip_cached: bool = True, verbose: bool = False) -> int:
    """Tune every (M, projection-shape) pair a serving config will dispatch.

    ``ms`` is the list of M values the engine emits (decode: max_batch;
    prefill: prefill_chunk). Under an AxisPlan the tuned unit is the
    PER-SHARD tile: each qw is sliced to the [n/mp] rows one model-parallel
    device holds and M is divided over the batch axis, producing cache
    entries keyed by the local shapes ``kernels.ops.resolve_dispatch``
    looks up at trace time inside a ``plan_scope``. Returns the number of
    shapes tuned; entries already in the cache are skipped unless
    ``skip_cached=False``. Call ``cache.save()`` afterwards to persist.
    """
    cache = cache if cache is not None else get_active()
    mp = dp = 1
    if plan is not None:
        mp, dp = plan.axis_size("model"), plan.axis_size("batch")
    tuned = 0
    for qw in collect_qw_shapes(params):
        qw = _local_slice(qw, mp)
        for m in ms:
            if dp > 1 and m % dp == 0:
                m //= dp
            key = shape_key(m, qw.n, qw.g, qw.k_group, qw.num_planes,
                            table_quant=table_quant)
            if skip_cached and cache is not None and key in cache.entries:
                continue
            if verbose:
                print(f"tuning {key} ...")
            tune_mpgemm(m, qw, table_quant=table_quant, cache=cache,
                        repeats=repeats, max_candidates=max_candidates,
                        verbose=verbose)
            tuned += 1
    return tuned
