"""Device-resident continuous-batching decode engine.

The engine owns a fixed pool of ``max_batch`` decode slots backed by one
static-shape KV/SSM cache. Weights are the packed low-bit serving format
(``serve_quantized`` params): batched decode is exactly the mpGEMM regime
the paper targets — memory-bound GEMV-shaped ops where the 4–16x
weight-traffic cut pays off — so the engine loop must not squander the
kernel's win on host round-trips.

All per-token control state lives ON DEVICE in an :class:`EngineState`
pytree (per-slot ``pos``/``budget``/``last_tok``/``active``, per-slot
sampling params, the PRNG key, and the caches). Three jitted programs:

  * ``decode_chunk``: ``jax.lax.scan`` over N decode steps for the whole
    pool — per-slot active masking, on-device budget/max-seq/EOS stopping,
    on-device per-slot sampling — emitting a ``[N, B]`` token buffer. The
    host syncs ONCE per chunk (read tokens + liveness), not once per token.
  * ``prefill_chunk``: ONE fixed-``[1, C]``-shape program that writes a
    prompt chunk into a batch-1 slot-cache view at a dynamic cache offset
    (no per-length recompiles, no B× wasted full-batch forward per admit).
    The LM head of a prefill chunk is dead code (only caches are returned),
    so XLA drops the vocab projection entirely.
  * ``merge``: write the batch-1 slot caches back into the pool at the
    slot's batch index (per-leaf batch axes via ``kvcache.batch_axes``).

Admission leaves the LAST prompt token out of prefill: it becomes the
slot's ``last_tok`` at ``pos = len(prompt) - 1``, so the first generated
token falls out of the decode scan itself — admission costs zero host syncs
and zero sampling dispatches.

Admit/retire stay on host but only run at chunk boundaries, preserving
continuous-batching semantics: finished slots are refilled from the queue
without touching in-flight ones. Per-slot positions mean one program serves
ragged sequence lengths (attention masks by each slot's own valid length;
SSM state is position-free).

Known edges (documented, covered by tests):
  * a prompt longer than ``max_seq`` is truncated to its last
    ``max(1, max_seq - max_new_tokens)`` tokens (room to generate);
  * a prompt that already fills the cache (``len == max_seq``) yields no
    tokens (there is no cache position left to write the first one);
  * ``max_new_tokens <= 0`` completes immediately with no output;
  * slots that finish mid-chunk idle until the next chunk boundary (their
    compute is masked out, their state is reset at the next admit).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import warnings
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ArchConfig
from repro.distributed import sharding as shrules
from repro.distributed.sharding import AxisPlan, plan_scope
from repro.models import api, kvcache
from repro.obs import dispatch as dispatch_obs
from repro.obs import scopes
from repro.obs.metrics import MetricsRegistry, export_stats
from repro.obs.trace import Tracer
from repro.serving import blockpool, decoding
from repro.serving.sampler import mask_logits, sample

# an engine span site with no tracer enters this shared no-op context
_NO_SPAN = contextlib.nullcontext()


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # [len] int32
    max_new_tokens: int = 32
    temperature: float = 0.0           # <= 0 -> greedy
    top_k: int = 0                     # 0 -> disabled
    top_p: float = 1.0                 # >= 1 -> disabled
    decoding: str = "greedy"           # greedy | sample | beam[:W] | spec
    done: bool = False
    output: Optional[List[int]] = None
    beams: Optional[List[Tuple[List[int], float]]] = None  # beam mode: all
    # retired hypotheses as (tokens, length-normalized score), best first
    spec_stats: Optional[Dict[str, int]] = None  # spec mode: verify_steps /
    # accepted_draft_tokens for this request
    # lifecycle on the time.perf_counter_ns() clock: arrival (set by
    # submit() unless the caller gave it), the start of admission, and the
    # decode sync that first returned a token
    arrival_ns: Optional[int] = None
    admit_ns: Optional[int] = None
    first_token_ns: Optional[int] = None


@dataclasses.dataclass
class EngineState:
    """Device-resident engine state (registered pytree; one leaf per field).

    All leaves are arrays: ``[B]`` per-slot control/sampling vectors, the
    PRNG key, and the full cache pytree. The decode scan threads the whole
    state through ``jax.lax.scan``; the host only reads it back at chunk
    boundaries.
    """
    pos: jax.Array          # [B] i32  next cache write position (= valid len)
    budget: jax.Array       # [B] i32  remaining new tokens
    last_tok: jax.Array     # [B] i32  next token to feed
    active: jax.Array       # [B] bool decoding live
    temperature: jax.Array  # [B] f32  per-slot sampling params
    top_k: jax.Array        # [B] i32
    top_p: jax.Array        # [B] f32
    mode: jax.Array         # [B] i32  decoding kind (decoding.NORMAL/BEAM/SPEC)
    beam_group: jax.Array   # [B] i32  beam-group id (leader slot idx); -1 none
    beam_score: jax.Array   # [B] f32  cumulative hypothesis log-prob
    spec_steps: jax.Array   # [B] i32  verify rounds run by this occupant
    spec_accepted: jax.Array  # [B] i32 draft tokens accepted+emitted
    key: jax.Array          # PRNG key
    page_table: jax.Array   # [B, blocks_per_slot] i32 pool block per logical
                            # page (paged mode; [B, 1] zeros when dense)
    caches: Any             # model cache pytree


jax.tree_util.register_dataclass(
    EngineState,
    data_fields=["pos", "budget", "last_tok", "active", "temperature",
                 "top_k", "top_p", "mode", "beam_group", "beam_score",
                 "spec_steps", "spec_accepted", "key", "page_table",
                 "caches"],
    meta_fields=[])


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 8,
                 max_seq: int = 512, seed: int = 0, decode_chunk: int = 8,
                 prefill_chunk: int = 32, eos_id: Optional[int] = None,
                 tuning_cache: Optional[str] = None,
                 cache_block_size: Optional[int] = None,
                 num_cache_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 kv_cache_dtype: Optional[str] = None,
                 plan: Optional[AxisPlan] = None,
                 spec_k: int = 4,
                 spec_draft_planes: Optional[int] = None,
                 beam_length_alpha: float = 0.6,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None):
        self.cfg = cfg
        # ---- telemetry (repro.obs) ---------------------------------------
        # The tracer records live spans around admission, prefill dispatch,
        # the decode dispatch and sync, and token emission; each enters a
        # jax.profiler.TraceAnnotation of the same name, so a profile shows
        # them beside the device's ops. Tracing adds zero device
        # round-trips (host_syncs_per_token is invariant). A None tracer
        # costs one `is not None` check per site. The metrics
        # registry always exists: its bounded-reservoir histograms ARE the
        # engine's latency/occupancy storage (O(reservoir) however long the
        # engine lives, unlike the unbounded lists they replaced).
        self.tracer = tracer
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        if plan is not None:
            # per-host series labels so mesh'd snapshots merge cleanly
            self.metrics.set_common_labels(
                host=str(jax.process_index()),
                mesh="x".join(str(s) for s in plan.mesh.devices.shape))
        self._h_chunk_s = self.metrics.histogram(
            "engine_decode_chunk_seconds",
            help="wall seconds per decode-chunk dispatch (sync to sync)",
            unit="s")
        self._h_occupancy = self.metrics.histogram(
            "engine_slot_occupancy_ratio",
            help="occupied slots / max_batch, sampled once per chunk")
        # Tensor/data-parallel serving: ``plan`` shards the packed weights
        # (named_sharding_tree), the engine state and the cache pool across
        # the plan's mesh, and every jitted program traces inside
        # ``plan_scope`` so the models' logical-axis shard() hooks fire.
        # ``plan=None`` is the single-device default — identical to a 1x1
        # mesh plan, where every constraint resolves to replication.
        self.plan = plan
        if plan is not None:
            params = shrules.pad_row_parallel(params, plan)
            params = jax.device_put(
                params, shrules.named_sharding_tree(params, plan))
        elif (cfg.quant and jax.default_backend() == "cpu"
              and cfg.quant.get("mpgemm_mode", "lut_xla") == "lut_xla"
              and cfg.quant.get("store") is None
              and spec_draft_planes is None):
            # (self-speculation pins the packed store: the draft view is a
            # plane slice of the packed buffer, which the CW expansion
            # destroys — see plane_sliced_params)
            # Single-device CPU serving: the XLA LUT path has no hardware
            # lookup unit, so a packed store forces a packed->CW expansion
            # inside every decode step. Hoist it: convert once to the
            # offline-CW store (bit-exact, same lut_xla epilogue). Pin
            # quant["store"]="packed" to keep packed planes resident.
            from repro.models.quantized import to_cw_params
            params = to_cw_params(params)
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.decode_chunk = max(1, decode_chunk)
        self.prefill_chunk = max(1, min(prefill_chunk, max_seq))
        self.eos_id = eos_id
        self._seed = seed
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_batch

        # persistent kernel-tuning cache: activates fusion="tuned" lookups
        # for every mpGEMM dispatched by this engine's jitted programs
        # (trace-time dict hits; populate via pretune() or bench_autotune)
        self.tuning_cache = None
        if tuning_cache is not None:
            from repro.core import autotune
            self.tuning_cache = autotune.configure(tuning_cache)

        kv_dt = kv_cache_dtype or cfg.kv_cache_dtype
        self._cache_dtype = "int8" if kv_dt == "int8" else jnp.float32

        # per-leaf batch axes of the cache pytree (shape-diff discovery:
        # hybrid stacks carry batch at axis 2, plain stacks at axis 1)
        c1 = jax.eval_shape(
            lambda: api.init_cache(cfg, 1, max_seq, dtype=self._cache_dtype))
        c2 = jax.eval_shape(
            lambda: api.init_cache(cfg, 2, max_seq, dtype=self._cache_dtype))
        self._axes = kvcache.batch_axes(c1, c2)
        # per-leaf sequence axes (same probe trick, varying s_cache): leaves
        # with no sequence axis — SSM conv/ssm state, image/cross KV — are
        # O(1) per slot and stay dense slot-indexed even in paged mode
        s1 = jax.eval_shape(
            lambda: api.init_cache(cfg, 1, 16, dtype=self._cache_dtype))
        s2 = jax.eval_shape(
            lambda: api.init_cache(cfg, 1, 32, dtype=self._cache_dtype))
        self._seq_axes = kvcache.seq_axes(s1, s2)
        # self-speculation rewrites cache POSITIONS (draft writes are
        # overwritten by the verify forward, rejected suffixes by the next
        # round) — only valid when every cache leaf is positional. SSM /
        # conv state is cumulative and cannot rewind a rejected token.
        self._spec_ok = all(sax >= 0
                            for sax in jax.tree.leaves(self._seq_axes))

        # ---- decoding-mode zoo (serving/decoding.py) ----------------------
        self.spec_k = max(1, int(spec_k))
        self.spec_draft_planes = spec_draft_planes
        self.beam_length_alpha = float(beam_length_alpha)
        self.draft_params = None
        self.draft_extra_hbm_bytes = 0
        if spec_draft_planes is not None:
            from repro.models import quantized as qz
            self.draft_params = qz.plane_sliced_params(
                self.params, int(spec_draft_planes))
            # acceptance probe: the draft view must share every buffer with
            # the target by identity (zero extra weight HBM)
            self.draft_extra_hbm_bytes = qz.extra_hbm_bytes(
                self.draft_params, self.params)
        # compiled decode variants keyed by the pool's static mode mix
        # (has_beam, has_spec); (False, False) is the legacy self._decode
        self._decode_variants: Dict[Tuple[bool, bool], Any] = {}
        # zero batch-1 slot caches: the prefill starting point for every
        # admit (a retiring request's state must never leak into its slot's
        # next occupant — SSM states are cumulative)
        self._zero_slot = api.init_cache(cfg, 1, max_seq,
                                         dtype=self._cache_dtype)

        # ---- block-paged cache pool (optional) ----------------------------
        self.paged = cache_block_size is not None
        self.prefix_caching = bool(prefix_cache) and self.paged
        self._alloc: Optional[blockpool.BlockAllocator] = None
        self._prefix: Optional[blockpool.PrefixCache] = None
        if self.paged:
            bs = int(cache_block_size)
            if bs < 1 or max_seq % bs != 0:
                raise ValueError(
                    f"cache_block_size={bs} must be >= 1 and divide "
                    f"max_seq={max_seq}: the gathered paged view must be "
                    f"exactly max_seq long for bit-exact parity with dense")
            self.cache_block_size = bs
            self.blocks_per_slot = max_seq // bs
            if num_cache_blocks is None:
                # dense-equivalent capacity: every slot can hold max_seq,
                # plus the reserved null block
                num_cache_blocks = max_batch * self.blocks_per_slot + 1
            if num_cache_blocks < self.blocks_per_slot + 1:
                raise ValueError(
                    f"num_cache_blocks={num_cache_blocks} cannot hold even "
                    f"one max_seq={max_seq} request at block size {bs} "
                    f"(need >= {self.blocks_per_slot + 1} incl. null block)")
            self.num_cache_blocks = int(num_cache_blocks)

            # pooled leaves must carry (batch, seq) adjacently so that
            # init_cache(cfg, num_blocks, block_size) IS the pool ctor
            def _check(path, bax, sax):
                if sax >= 0 and sax != bax + 1:
                    raise ValueError(
                        f"cannot page cache leaf at "
                        f"{jax.tree_util.keystr(path)!r}: sequence axis "
                        f"{sax} is not adjacent to batch axis {bax}")
                return sax >= 0
            self._pooled = jax.tree_util.tree_map_with_path(
                _check, self._axes, self._seq_axes)
            pooled_leaves = jax.tree.leaves(self._pooled)
            self.has_pooled = any(pooled_leaves)
            self._all_pooled = all(pooled_leaves)
            if self.prefix_caching and not all(jax.tree.leaves(self._pooled)):
                warnings.warn(
                    "prefix caching needs every cache leaf paged; family="
                    f"{cfg.family!r} holds slot-resident state (SSM/cross "
                    "KV) that cannot fan out by block reference — disabled")
                self.prefix_caching = False

            nb_total = self.num_cache_blocks

            def _build_paged():
                # one jitted builder selecting pool vs dense per leaf: XLA
                # DCEs the unused half, so SSM state is never allocated at
                # batch=num_blocks nor attention KV at [B, max_seq] density
                pool = api.init_cache(cfg, nb_total, bs,
                                      dtype=self._cache_dtype)
                dense = api.init_cache(cfg, max_batch, max_seq,
                                       dtype=self._cache_dtype)
                return jax.tree.map(
                    lambda p, d, pooled: p if pooled else d,
                    pool, dense, self._pooled)

            self._build_paged = jax.jit(_build_paged)
            # prefill view: pool leaves ride through whole; unpooled leaves
            # are a batch-1 slot view (donated through the chunk loop)
            self._prefill_paged = jax.jit(self._paged_prefill_impl,
                                          donate_argnums=(1,))
            self._copy_block = jax.jit(self._copy_block_impl,
                                       donate_argnums=(0,))

        # the decode carry (caches dominate it) is donated: without donation
        # every chunk dispatch copies the full [B, S] cache pytree just to
        # write the new state next to it — pure memory traffic that grows
        # with max_batch·max_seq and was a visible slice of per-chunk
        # latency at large decode_chunk settings
        self._decode = jax.jit(self._decode_chunk_impl, donate_argnums=(1,))
        self._prefill = jax.jit(self._prefill_chunk_impl)
        # beam admission fork: copy one slot's unpooled cache rows to another
        self._fork_slot = jax.jit(self._fork_slot_impl, donate_argnums=(0,))
        self._merge = jax.jit(
            lambda caches, slot, i: kvcache.merge_batch(
                caches, slot, self._axes, i))

        self.reset(seed=seed)

    # -- lifecycle ----------------------------------------------------------
    def reset(self, seed: Optional[int] = None):
        """Clear queue/slots/state/counters; keep compiled programs."""
        if seed is None:
            seed = self._seed
        b = self.max_batch
        self.queue = deque()
        self.slots = [None] * b
        if self.paged:
            self._alloc = blockpool.BlockAllocator(self.num_cache_blocks,
                                                   metrics=self.metrics)
            self._prefix = (blockpool.PrefixCache(self._alloc,
                                                  metrics=self.metrics)
                            if self.prefix_caching else None)
            self._pending_keys: set = set()  # divergence entries whose last
            # position is unwritten until the origin's first decode chunk
            self._slot_blocks: List[List[int]] = [[] for _ in range(b)]
            caches = self._build_paged()
            page_table = jnp.zeros((b, self.blocks_per_slot), jnp.int32)
        else:
            caches = api.init_cache(self.cfg, b, self.max_seq,
                                    dtype=self._cache_dtype)
            page_table = jnp.zeros((b, 1), jnp.int32)
        self.state = EngineState(
            pos=jnp.zeros(b, jnp.int32),
            budget=jnp.zeros(b, jnp.int32),
            last_tok=jnp.zeros(b, jnp.int32),
            active=jnp.zeros(b, bool),
            temperature=jnp.zeros(b, jnp.float32),
            top_k=jnp.zeros(b, jnp.int32),
            top_p=jnp.ones(b, jnp.float32),
            mode=jnp.zeros(b, jnp.int32),
            beam_group=jnp.full(b, -1, jnp.int32),
            beam_score=jnp.zeros(b, jnp.float32),
            spec_steps=jnp.zeros(b, jnp.int32),
            spec_accepted=jnp.zeros(b, jnp.int32),
            key=jax.random.key(seed),
            page_table=page_table,
            caches=caches)
        if self.plan is not None:
            self.state = jax.device_put(
                self.state, self._engine_state_shardings(self.state))
        self.decode_syncs = 0       # host round-trips in the decode loop
        self.decode_tokens = 0      # tokens emitted by decode chunks
        self.prefill_dispatches = 0
        # per-chunk latency/occupancy history lives in bounded-reservoir
        # histograms (engine_decode_chunk_seconds etc.), not python lists:
        # memory stays O(reservoir) however long the engine serves
        self._h_chunk_s.reset()
        self._h_occupancy.reset()
        self.prefill_s = 0.0        # wall seconds spent in prefill dispatch
        self.prefill_tokens = 0     # prompt tokens actually prefilled
        self.prefill_tokens_reused = 0  # prompt tokens served from shared
        # blocks (prefix cache hits) instead of being re-prefilled
        self.admit_attempts = 0
        self.admit_blocked = 0      # admissions deferred for lack of blocks
        self.peak_active_slots = 0
        # decoding-mode bookkeeping (host mirrors of per-slot device state)
        self._slot_kind: List[int] = [decoding.NORMAL] * b
        self._beam_hist: List[List[int]] = [[] for _ in range(b)]
        self._beam_groups: Dict[int, Dict[str, Any]] = {}  # leader -> group
        self.spec_verify_steps = 0      # totals over retired spec requests
        self.spec_accepted_tokens = 0

    def _engine_state_shardings(self, state: EngineState) -> EngineState:
        """NamedSharding pytree for the engine state under ``self.plan``.

        Per-slot control vectors and the DENSE cache batch dim shard over
        the plan's batch axes; attention KV heads (dim seq+1) and SSM
        feature dims (dim batch+1) shard over the model axis, matching the
        column-parallel projections that produce them. Paged POOL leaves
        keep their block dim replicated: page tables index the global pool,
        so any slot may reference any block — sharding blocks over data
        would turn every page gather into a cross-shard collective. All of
        this is layout-only (GSPMD), so every fallback is replication, not
        an error."""
        plan = self.plan
        mesh = plan.mesh
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        batch_ax = plan.resolve("batch")
        model_ax = plan.resolve("model")

        def ns(shape, phys):
            return NamedSharding(mesh, P(*shrules.resolve_physical_spec(
                shape, phys, sizes)))

        def vec(x):
            return ns(x.shape, (batch_ax,) + (None,) * (x.ndim - 1))

        pooled = (self._pooled if self.paged
                  else jax.tree.map(lambda _: False, self._axes))

        def cache_leaf(c, bax, sax, is_pooled):
            phys = [None] * c.ndim
            if not is_pooled:
                phys[bax] = batch_ax
            feat = (sax + 1) if sax >= 0 else (bax + 1)
            if feat < c.ndim and phys[feat] is None:
                phys[feat] = model_ax
            return ns(c.shape, tuple(phys))

        caches_sh = jax.tree.map(cache_leaf, state.caches, self._axes,
                                 self._seq_axes, pooled)
        rep = NamedSharding(mesh, P())
        return EngineState(
            pos=vec(state.pos), budget=vec(state.budget),
            last_tok=vec(state.last_tok), active=vec(state.active),
            temperature=vec(state.temperature), top_k=vec(state.top_k),
            top_p=vec(state.top_p), mode=vec(state.mode),
            beam_group=vec(state.beam_group),
            beam_score=vec(state.beam_score),
            spec_steps=vec(state.spec_steps),
            spec_accepted=vec(state.spec_accepted), key=rep,
            page_table=vec(state.page_table), caches=caches_sh)

    # -- jitted programs ----------------------------------------------------
    def _prefill_chunk_impl(self, params, slot_caches, tokens, offset, valid):
        """Write one [1, C] prompt chunk into a batch-1 slot-cache view at
        cache offset ``offset``; ``valid`` <= C real tokens (right-pad)."""
        with plan_scope(self.plan):
            _, new_caches, _ = api.forward(
                params, {"tokens": tokens}, self.cfg, caches=slot_caches,
                cache_pos=offset, token_valid=jnp.reshape(valid, (1,)))
        return new_caches

    def _paged_prefill_impl(self, params, view_caches, tokens, offset, valid,
                            page_row):
        """One [1, C] prompt chunk written straight into the pool: pooled
        leaves scatter through the slot's page-table row ``page_row``
        ([1, blocks_per_slot]); unpooled (SSM/cross) leaves ride along as a
        batch-1 slot view. The whole view is donated through the chunk loop,
        so pool pages are updated in place across chunks."""
        with plan_scope(self.plan):
            _, new_caches, _ = api.forward(
                params, {"tokens": tokens}, self.cfg, caches=view_caches,
                cache_pos=offset, token_valid=jnp.reshape(valid, (1,)),
                page_table=page_row)
        return new_caches

    def _copy_block_impl(self, caches, src, dst):
        """Copy-on-write: clone pool block ``src`` into ``dst`` on every
        pooled leaf (unpooled leaves pass through untouched)."""
        def one(c, bax, sax):
            if sax < 0:
                return c
            blk = jax.lax.dynamic_index_in_dim(c, src, axis=bax,
                                               keepdims=True)
            return jax.lax.dynamic_update_slice_in_dim(c, blk, dst, axis=bax)
        return jax.tree.map(one, caches, self._axes, self._seq_axes)

    def _fork_slot_impl(self, caches, src, dst):
        """Beam admission fork: copy slot ``src``'s cache row to ``dst`` on
        every slot-resident (unpooled) leaf. Pooled leaves pass through —
        the member's page-table row handles those (shared prefix blocks by
        reference, private blocks by ``_copy_block``)."""
        pooled = (self._pooled if self.paged
                  else jax.tree.map(lambda _: False, self._axes))

        def one(c, bax, is_pooled):
            if is_pooled:
                return c
            row = jax.lax.dynamic_index_in_dim(c, src, axis=bax,
                                               keepdims=True)
            return jax.lax.dynamic_update_slice_in_dim(c, row, dst, axis=bax)
        return jax.tree.map(one, caches, self._axes, pooled)

    def _beam_fork_caches(self, caches, parent, page_table, do_copy):
        """In-scan beam reassignment: slot ``b`` adopts ``parent[b]``'s
        hypothesis state. Runs AFTER the step's forward, so the adopted
        content includes the parent's freshly written position.

        Unpooled leaves: batch gather by ``parent`` (identity rows for
        non-forking slots). Pooled leaves: the slot's page-table row is
        immutable inside the scan, so the fork copies block CONTENT from
        the parent's blocks into the slot's own blocks. Duplicate
        destinations are safe by construction: group members share
        identical prefix rows (those writes are value-identical
        self-copies), post-divergence blocks are private per slot, and
        non-forking slots are routed to the never-read null block 0.
        """
        pooled = (self._pooled if self.paged
                  else jax.tree.map(lambda _: False, self._axes))
        bsz = parent.shape[0]

        def one(c, bax, is_pooled):
            cm = jnp.moveaxis(c, bax, 0)
            if is_pooled:
                src_rows = page_table[parent].reshape(-1)      # [B*nbs]
                dst_rows = jnp.where(do_copy[:, None], page_table,
                                     0).reshape(-1)
                cm = cm.at[dst_rows].set(cm[src_rows])
            else:
                cm = cm[parent]
            return jnp.moveaxis(cm, 0, bax)
        del bsz
        return jax.tree.map(one, caches, self._axes, pooled)

    def _get_decode(self, has_beam: bool, has_spec: bool):
        """Compiled decode-chunk program for a pool mode mix. The
        (False, False) mix is the legacy two-arg ``self._decode``; the
        others share ``_decode_general_impl`` with the mode flags baked in
        as trace-time statics (signature: (params, draft_params, state))."""
        key = (has_beam, has_spec)
        fn = self._decode_variants.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(self._decode_general_impl,
                                           has_beam=has_beam,
                                           has_spec=has_spec),
                         donate_argnums=(2,))
            self._decode_variants[key] = fn
        return fn

    def _decode_general_impl(self, params, draft_params, state, *,
                             has_beam: bool, has_spec: bool):
        """Decoding-mode-zoo decode chunk: N scan steps over the pool with
        per-slot NORMAL / BEAM / SPEC behaviour in one jitted program.

        Emissions are ``[N, B, S_e]`` (``S_e = spec_k + 1`` when the pool
        holds spec slots, else 1) plus a ``[N, B]`` parent map for beam
        hypothesis reconstruction on the host.

        Speculative step anatomy (spec slots; every other slot rides along
        emitting at most its position-0 token):
          1. draft K tokens autoregressively with the plane-sliced view,
             writing PROVISIONAL KV at pos..pos+K-1;
          2. ONE s=K+1 target forward over [last_tok, d_0..d_{K-1}]
             re-writes pos..pos+K with target KV (the draft writes are
             fully overwritten — rejected positions hold invisible values
             that the next round re-writes before any read reaches them);
          3. accept the longest agreeing prefix (argmax agreement for
             greedy slots — bit-exact with plain greedy — or Leviathan
             rejection sampling), emit the replacement/bonus token, and
             advance ``pos`` by the emission count.
        """
        paged_kw = ({"page_table": state.page_table} if self.paged else {})
        k_spec = self.spec_k
        s_e = (k_spec + 1) if has_spec else 1
        bsz = self.max_batch
        self_idx = jnp.arange(bsz, dtype=jnp.int32)

        def step(st, _):
            key, k_draft, k_accept, k_sample = jax.random.split(st.key, 4)
            greedy = st.temperature <= 0.0
            is_spec = st.mode == decoding.SPEC
            is_beam = st.mode == decoding.BEAM

            if has_spec:
                # ---- 1. draft rollout (sliced-plane view) ---------------
                caches = st.caches
                last, dpos = st.last_tok, st.pos
                dkeys = jax.random.split(k_draft, k_spec)
                d_toks, d_masked = [], []
                for j in range(k_spec):
                    dl, caches, _ = api.forward(
                        draft_params, {"tokens": last[:, None]}, self.cfg,
                        caches=caches, cache_pos=dpos, **paged_kw)
                    dl = dl[:, -1]
                    ml = mask_logits(dl, temperature=st.temperature,
                                     top_k=st.top_k, top_p=st.top_p)
                    d = jnp.where(
                        greedy,
                        jnp.argmax(dl, axis=-1).astype(jnp.int32),
                        jax.random.categorical(dkeys[j], ml,
                                               axis=-1).astype(jnp.int32))
                    d_toks.append(d)
                    d_masked.append(ml)
                    last, dpos = d, dpos + 1
                d_toks = jnp.stack(d_toks, axis=1)          # [B, K]
                q_logits = jnp.stack(d_masked, axis=1)      # [B, K, V]

                # ---- 2. single verify forward (overwrites draft KV) -----
                verify_in = jnp.concatenate(
                    [st.last_tok[:, None], d_toks], axis=1)  # [B, K+1]
                vlogits, new_caches, _ = api.forward(
                    params, {"tokens": verify_in}, self.cfg,
                    caches=caches, cache_pos=st.pos, **paged_kw)
                logits1 = vlogits[:, 0]  # == the s=1 forward's logits
                tgt_raw_argmax = jnp.argmax(vlogits,
                                            axis=-1).astype(jnp.int32)
                p_logits = jnp.stack(
                    [mask_logits(vlogits[:, j],
                                 temperature=st.temperature,
                                 top_k=st.top_k, top_p=st.top_p)
                     for j in range(k_spec + 1)], axis=1)
                accept, repl, bonus = decoding.speculative_accept(
                    k_accept, d_toks, q_logits, p_logits, tgt_raw_argmax,
                    greedy)
            else:
                logits, new_caches, _ = api.forward(
                    params, {"tokens": st.last_tok[:, None]}, self.cfg,
                    caches=st.caches, cache_pos=st.pos, **paged_kw)
                logits1 = logits[:, -1]

            # ---- position-0 token per mode ------------------------------
            nxt_norm = sample(k_sample, logits1, temperature=st.temperature,
                              top_k=st.top_k, top_p=st.top_p)
            parent = self_idx
            beam_score = st.beam_score
            if has_beam:
                logp = jax.nn.log_softmax(logits1.astype(jnp.float32),
                                          axis=-1)
                live_beam = is_beam & st.active
                parent, btok, beam_score = decoding.beam_select(
                    st.beam_score, logp, live_beam, st.beam_group)
                new_caches = self._beam_fork_caches(
                    new_caches, parent, st.page_table, live_beam)
                tok0_ride = jnp.where(is_beam, btok, nxt_norm)
            else:
                tok0_ride = nxt_norm

            # ---- emission chain -----------------------------------------
            toks_l, valid_l = [], []
            cum = jnp.ones(bsz, bool)
            prior_eos = jnp.zeros(bsz, bool)
            n_emit = jnp.zeros(bsz, jnp.int32)
            acc_emitted = jnp.zeros(bsz, jnp.int32)
            for j in range(s_e):
                if has_spec:
                    if j < k_spec:
                        tok_j = jnp.where(accept[:, j], d_toks[:, j],
                                          repl[:, j])
                    else:
                        tok_j = bonus
                    if j == 0:
                        tok_j = jnp.where(is_spec, tok_j, tok0_ride)
                else:
                    tok_j = tok0_ride
                allow = (cum & st.active & (st.pos + 1 + j < self.max_seq)
                         & (st.budget > j) & ~prior_eos)
                if j > 0:
                    allow &= is_spec
                if self.eos_id is not None:
                    prior_eos = prior_eos | (allow & (tok_j == self.eos_id))
                toks_l.append(tok_j)
                valid_l.append(allow)
                n_emit = n_emit + allow.astype(jnp.int32)
                if has_spec and j < k_spec:
                    acc_emitted = acc_emitted + (
                        allow & accept[:, j] & is_spec).astype(jnp.int32)
                    cum = cum & accept[:, j]
            toks_m = jnp.stack(toks_l, axis=1)    # [B, S_e]
            valid_m = jnp.stack(valid_l, axis=1)  # [B, S_e]

            # ---- slot state update --------------------------------------
            emitted = n_emit > 0
            last_idx = jnp.clip(n_emit - 1, 0, s_e - 1)
            last_emitted = jnp.take_along_axis(
                toks_m, last_idx[:, None], axis=1)[:, 0]
            new_last = jnp.where(emitted, last_emitted, st.last_tok)
            new_pos = st.pos + n_emit
            hit_cap = st.active & (st.pos + 1 >= self.max_seq)
            new_budget = jnp.where(hit_cap, 0, st.budget - n_emit)
            new_active = st.active & emitted & (new_budget > 0) & ~prior_eos

            ran_spec = is_spec & st.active & emitted
            st = dataclasses.replace(
                st,
                pos=new_pos,
                budget=new_budget,
                last_tok=new_last,
                active=new_active,
                beam_score=beam_score,
                spec_steps=st.spec_steps + ran_spec.astype(jnp.int32),
                spec_accepted=st.spec_accepted + jnp.where(ran_spec,
                                                           acc_emitted, 0),
                key=key,
                caches=new_caches)
            return st, (toks_m, valid_m, parent)

        with plan_scope(self.plan):
            state, (toks, valid, parent) = jax.lax.scan(
                step, state, None, length=self.decode_chunk)
        return state, toks, valid, parent  # [N, B, S_e], [N, B]

    def _decode_chunk_impl(self, params, state):
        """N decode steps for the whole pool in one dispatch."""
        # the page table is closed over per chunk, not threaded through the
        # scan carry: no decode step ever remaps pages
        paged_kw = ({"page_table": state.page_table} if self.paged else {})

        def step(st, _):
            key, sub = jax.random.split(st.key)
            logits, new_caches, _ = api.forward(
                params, {"tokens": st.last_tok[:, None]}, self.cfg,
                caches=st.caches, cache_pos=st.pos, **paged_kw)
            nxt = sample(sub, logits[:, -1], temperature=st.temperature,
                         top_k=st.top_k, top_p=st.top_p)
            # emit iff live and the cache has room for this token
            can = st.active & (st.pos + 1 < self.max_seq)
            hit_cap = st.active & ~can
            budget = jnp.where(can, st.budget - 1,
                               jnp.where(hit_cap, 0, st.budget))
            active = can & (budget > 0)
            if self.eos_id is not None:
                active &= nxt != self.eos_id
            st = dataclasses.replace(
                st,
                pos=st.pos + can.astype(jnp.int32),
                budget=budget,
                last_tok=jnp.where(can, nxt, st.last_tok),
                active=active,
                key=key,
                caches=new_caches)
            return st, (nxt, can)

        with plan_scope(self.plan):
            state, (toks, valid) = jax.lax.scan(
                step, state, None, length=self.decode_chunk)
        return state, toks, valid  # toks/valid: [N, B]

    # -- host loop (chunk boundaries only) ----------------------------------
    def submit(self, req: Request):
        # parse eagerly so a bad decoding string / unsupported mode fails at
        # submit time, not mid-batch at admission
        dm = decoding.parse(req.decoding)
        if dm.kind == decoding.SPEC:
            if self.draft_params is None:
                raise ValueError(
                    "spec decoding needs a draft view: construct the engine "
                    "with spec_draft_planes=<planes> (and a packed-store "
                    "quant config)")
            if not self._spec_ok:
                raise ValueError(
                    f"self-speculative decoding unsupported for family "
                    f"{self.cfg.family!r}: its cache holds cumulative "
                    "(SSM/conv) state that cannot rewind rejected drafts")
        if dm.kind == decoding.BEAM and dm.beam_width > self.max_batch:
            raise ValueError(
                f"beam width {dm.beam_width} exceeds max_batch "
                f"{self.max_batch}: the W hypotheses are W pool slots")
        req.output = []
        if req.arrival_ns is None:
            req.arrival_ns = time.perf_counter_ns()
        self.queue.append(req)

    def _truncate(self, req: Request) -> np.ndarray:
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError(f"request {req.uid}: empty prompt")
        if prompt.size > self.max_seq:
            keep = max(1, self.max_seq - req.max_new_tokens)
            prompt = prompt[-keep:]
        return prompt

    def _set_slot(self, i: int, req: Request, prompt, caches, **extra):
        """Common admission epilogue: per-slot control state + caches.

        Decoding-mode state is reset from ``req.decoding`` every admission
        (beam MEMBER slots are stamped separately — this path admits the
        group leader, whose group id is its own slot index and whose
        cumulative score starts at 0 while members start at -inf, so the
        first expansion step fans the leader out into the full width).
        """
        st = self.state
        plen = int(prompt.size)
        live = req.max_new_tokens > 0
        dm = decoding.parse(req.decoding)
        group = i if dm.kind == decoding.BEAM else -1
        self.state = dataclasses.replace(
            st,
            pos=st.pos.at[i].set(plen - 1),
            budget=st.budget.at[i].set(req.max_new_tokens),
            last_tok=st.last_tok.at[i].set(int(prompt[-1])),
            active=st.active.at[i].set(live),
            temperature=st.temperature.at[i].set(float(req.temperature)),
            top_k=st.top_k.at[i].set(int(req.top_k)),
            top_p=st.top_p.at[i].set(float(req.top_p)),
            mode=st.mode.at[i].set(dm.kind),
            beam_group=st.beam_group.at[i].set(group),
            beam_score=st.beam_score.at[i].set(0.0),
            spec_steps=st.spec_steps.at[i].set(0),
            spec_accepted=st.spec_accepted.at[i].set(0),
            caches=caches, **extra)
        self._slot_kind[i] = dm.kind
        self._beam_hist[i] = []
        if live:
            self.slots[i] = req
        else:
            req.done = True  # nothing to generate
        return live

    def _stamp_beam_member(self, m: int, lead: int, req: Request, prompt):
        """Per-slot state for a beam MEMBER: same position/budget/params as
        the leader, score -inf so the first ``beam_select`` replaces it with
        one of the leader's top-W continuations."""
        st = self.state
        plen = int(prompt.size)
        self.state = dataclasses.replace(
            st,
            pos=st.pos.at[m].set(plen - 1),
            budget=st.budget.at[m].set(req.max_new_tokens),
            last_tok=st.last_tok.at[m].set(int(prompt[-1])),
            active=st.active.at[m].set(True),
            temperature=st.temperature.at[m].set(float(req.temperature)),
            top_k=st.top_k.at[m].set(int(req.top_k)),
            top_p=st.top_p.at[m].set(float(req.top_p)),
            mode=st.mode.at[m].set(decoding.BEAM),
            beam_group=st.beam_group.at[m].set(lead),
            beam_score=st.beam_score.at[m].set(decoding._NEG),
            spec_steps=st.spec_steps.at[m].set(0),
            spec_accepted=st.spec_accepted.at[m].set(0))
        self.slots[m] = req
        self._slot_kind[m] = decoding.BEAM
        self._beam_hist[m] = []

    def _evict_slot(self, i: int):
        """Admission rollback / group retirement: release slot ``i``'s
        reservation and deactivate it (request bookkeeping is the caller's
        problem)."""
        if self.paged:
            for bid in self._slot_blocks[i]:
                self._alloc.decref(bid)
            self._slot_blocks[i] = []
            self.state = dataclasses.replace(
                self.state,
                page_table=self.state.page_table.at[i].set(0))
        self.state = dataclasses.replace(
            self.state, active=self.state.active.at[i].set(False))
        self.slots[i] = None
        self._slot_kind[i] = decoding.NORMAL
        self._beam_hist[i] = []

    def _admit_one(self, i: int, req: Request):
        prompt = self._truncate(req)
        plen = int(prompt.size)
        tr = self.tracer
        req.admit_ns = t0 = time.perf_counter_ns()
        with (tr.span("admit", uid=req.uid, slot=i, prompt_len=plen,
                      mode=req.decoding, paged=False)
              if tr is not None else _NO_SPAN):
            # chunked prefill of prompt[:-1] into a zeroed batch-1 slot view;
            # the last token is fed to the first decode step instead
            c = self.prefill_chunk
            slot_caches = self._zero_slot
            for j in range(0, plen - 1, c):
                vl = min(c, plen - 1 - j)
                buf = np.zeros((1, c), np.int32)
                buf[0, :vl] = prompt[j:j + vl]
                with (tr.span("prefill_chunk", cat="prefill", uid=req.uid,
                              slot=i, offset=j, valid=vl)
                      if tr is not None else _NO_SPAN):
                    slot_caches = self._prefill(
                        self.params, slot_caches, jnp.asarray(buf),
                        np.int32(j), np.int32(vl))
                self.prefill_dispatches += 1
                self.prefill_tokens += vl
            self.prefill_s += (time.perf_counter_ns() - t0) / 1e9
            self._set_slot(i, req, prompt,
                           self._merge(self.state.caches, slot_caches,
                                       np.int32(i)))

    def _admit_one_paged(self, i: int, req: Request) -> bool:
        """Paged admission: reserve blocks, reuse shared-prefix blocks,
        prefill only the unshared suffix. Returns False (leaving the
        request queued and the engine untouched) when the pool cannot
        grant the reservation."""
        prompt = self._truncate(req)
        plen = int(prompt.size)
        bs = self.cache_block_size

        # all-or-nothing reservation covering every position this slot can
        # ever touch: prefill writes 0..plen-2, decode writes plen-1 onward,
        # and a finished slot keeps (idempotently) rewriting its frozen
        # position until the next chunk boundary
        n_need = 0
        if self.has_pooled:
            cap = min(plen + max(0, req.max_new_tokens), self.max_seq)
            n_need = max(1, -(-cap // bs))

        # shared-prefix lookup: block j is shared READ-ONLY only if it lies
        # entirely below the first decode write — (j+1)*bs <= plen-1
        shared: List[int] = []
        cow_src = None
        m_share = (plen - 1) // bs
        if self._prefix is not None:
            for j in range(min(m_share, n_need)):
                key = blockpool.chain_key(prompt[:(j + 1) * bs])
                bid = self._prefix.get(key)
                if bid is None or key in self._pending_keys:
                    break
                shared.append(bid)
            if len(shared) == m_share and (m_share + 1) * bs == plen:
                # divergence block ends exactly at plen: its content is a
                # pure function of the prompt, but decode overwrites its
                # last position — reuse is copy-on-write (pending entries
                # are fine here: the copy's tail is rewritten before read)
                cow_src = self._prefix.get(blockpool.chain_key(prompt))
        m0 = len(shared)

        # pin shared blocks BEFORE eviction can run: evict_until() may drop
        # the very entries we just looked up, and an unpinned block could be
        # freed and reissued to this same allocation
        for bid in shared:
            self._alloc.incref(bid)
        if cow_src is not None:
            self._alloc.incref(cow_src)
        n_priv = n_need - m0
        blocks = self._alloc.alloc(n_priv)
        if blocks is None and self._prefix is not None:
            self._prefix.evict_until(n_priv)
            blocks = self._alloc.alloc(n_priv)
        if blocks is None:
            for bid in shared:
                self._alloc.decref(bid)
            if cow_src is not None:
                self._alloc.decref(cow_src)
            return False  # admission blocked: not enough free blocks

        row = shared + blocks
        self._slot_blocks[i] = list(row)
        row_arr = np.zeros(self.blocks_per_slot, np.int32)
        row_arr[:len(row)] = row
        st = self.state
        new_pt = st.page_table.at[i].set(jnp.asarray(row_arr))
        caches = st.caches

        if cow_src is not None:
            caches = self._copy_block(caches, np.int32(cow_src),
                                      np.int32(blocks[0]))
            self._alloc.decref(cow_src)  # private copy taken
            start = (m0 + 1) * bs
        else:
            start = m0 * bs
        self.prefill_tokens_reused += min(start, plen - 1)
        if self.tracer is not None and (m0 > 0 or cow_src is not None):
            self.tracer.instant("prefix_hit", cat="prefill", uid=req.uid,
                                slot=i, shared_blocks=m0,
                                cow=cow_src is not None,
                                tokens_reused=min(start, plen - 1))

        # prefill the unshared suffix straight into the pool (prefix hits
        # skip whole chunks; a full COW hit skips prefill entirely)
        tr = self.tracer
        req.admit_ns = t0 = time.perf_counter_ns()
        with (tr.span("admit", uid=req.uid, slot=i, prompt_len=plen,
                      mode=req.decoding, paged=True, shared_blocks=m0,
                      cow=cow_src is not None)
              if tr is not None else _NO_SPAN):
            if start >= plen - 1 and self._all_pooled:
                # everything came from shared blocks and there is no slot-
                # resident state to reset: the fan-out fast path is pure
                # bookkeeping, zero device work
                new_caches = caches
            else:
                page_row = jnp.asarray(row_arr)[None, :]
                # fresh zero views for the unpooled leaves each admit: the
                # previous admit's views were donated (invalidated) by the
                # prefill jit
                view = jax.tree.map(
                    lambda c, z, pooled: c if pooled
                    else jnp.zeros(z.shape, z.dtype),
                    caches, self._zero_slot, self._pooled)
                c = self.prefill_chunk
                for j in range(start, plen - 1, c):
                    vl = min(c, plen - 1 - j)
                    buf = np.zeros((1, c), np.int32)
                    buf[0, :vl] = prompt[j:j + vl]
                    with (tr.span("prefill_chunk", cat="prefill",
                                  uid=req.uid, slot=i, offset=j, valid=vl)
                          if tr is not None else _NO_SPAN):
                        view = self._prefill_paged(
                            self.params, view, jnp.asarray(buf),
                            np.int32(j), np.int32(vl), page_row)
                    self.prefill_dispatches += 1
                    self.prefill_tokens += vl
                # merge eagerly in python: pooled leaves pass through BY
                # REFERENCE (the pool was updated in place via donation);
                # unpooled leaves are written into slot i of the dense half
                new_caches = jax.tree.map(
                    lambda cc, v, bax, pooled: v if pooled else
                    jax.lax.dynamic_update_slice_in_dim(
                        cc, v.astype(cc.dtype), i, axis=bax),
                    caches, view, self._axes, self._pooled)
            self.prefill_s += (time.perf_counter_ns() - t0) / 1e9
            live = self._set_slot(i, req, prompt, new_caches,
                                  page_table=new_pt)

        # register freshly-written shareable blocks for future prompts
        if self._prefix is not None:
            for j in range(m0, min(m_share, n_need)):
                self._prefix.put(
                    blockpool.chain_key(prompt[:(j + 1) * bs]), row[j])
            if live and (m_share + 1) * bs == plen and m_share < len(row):
                # divergence entry: valid for COW immediately, but its last
                # position is only written by this slot's first decode
                # chunk — mark pending so no one shares it by reference yet
                key = blockpool.chain_key(prompt)
                self._prefix.put(key, row[m_share])
                self._pending_keys.add(key)
        if not live:
            # nothing to generate: the slot never occupies, so retire its
            # reservation now (prefix-registered blocks survive via the
            # cache's own ref)
            for bid in self._slot_blocks[i]:
                self._alloc.decref(bid)
            self._slot_blocks[i] = []
            self.state = dataclasses.replace(
                self.state, page_table=self.state.page_table.at[i].set(0))
        return True

    def _admit_beam(self, slots_w: List[int], req: Request) -> bool:
        """Admit a beam request into ``len(slots_w)`` slots: leader via the
        ordinary admission path (prefill once), members fork the leader —
        shared-prefix blocks by reference plus private-block content copies
        in paged mode (the PR-7 COW fan-out), full cache-row copies for
        unpooled leaves. Returns False (request left queued, engine rolled
        back) if the pool cannot grant every member's reservation."""
        lead = slots_w[0]
        if self.paged:
            if not self._admit_one_paged(lead, req):
                return False
        else:
            self._admit_one(lead, req)
        prompt = self._truncate(req)
        stamped = [lead]
        for m in slots_w[1:]:
            if self.paged:
                lead_row = self._slot_blocks[lead]
                # blocks strictly below the first decode write (plen-1) are
                # immutable for the rest of the group's life: share them by
                # reference. The divergence block and everything after is
                # per-hypothesis mutable -> private content copy.
                m_share = min((int(prompt.size) - 1) // self.cache_block_size,
                              len(lead_row))
                n_priv = len(lead_row) - m_share
                blocks = self._alloc.alloc(n_priv)
                if blocks is None and self._prefix is not None:
                    self._prefix.evict_until(n_priv)
                    blocks = self._alloc.alloc(n_priv)
                if blocks is None:
                    for s in stamped:
                        self._evict_slot(s)
                    return False
                for bid in lead_row[:m_share]:
                    self._alloc.incref(bid)
                caches = self.state.caches
                for src, dst in zip(lead_row[m_share:], blocks):
                    caches = self._copy_block(caches, np.int32(src),
                                              np.int32(dst))
                row = lead_row[:m_share] + blocks
                self._slot_blocks[m] = list(row)
                row_arr = np.zeros(self.blocks_per_slot, np.int32)
                row_arr[:len(row)] = row
                self.state = dataclasses.replace(
                    self.state,
                    page_table=self.state.page_table.at[m].set(
                        jnp.asarray(row_arr)),
                    caches=self._fork_slot(caches, np.int32(lead),
                                           np.int32(m)))
            else:
                self.state = dataclasses.replace(
                    self.state,
                    caches=self._fork_slot(self.state.caches, np.int32(lead),
                                           np.int32(m)))
            self._stamp_beam_member(m, lead, req, prompt)
            stamped.append(m)
        self._beam_groups[lead] = {
            "req": req, "slots": list(slots_w),
            "live": set(slots_w), "finished": []}
        return True

    def _admit(self) -> int:
        n = 0
        while self.queue:
            req = self.queue[0]
            dm = decoding.parse(req.decoding)
            width = (dm.beam_width
                     if dm.kind == decoding.BEAM and req.max_new_tokens > 0
                     else 1)
            free = [i for i, r in enumerate(self.slots) if r is None]
            if len(free) < width:
                break  # FIFO head-of-line: wait for slots to free
            self.admit_attempts += 1
            if dm.kind == decoding.BEAM and req.max_new_tokens > 0:
                if not self._admit_beam(free[:width], req):
                    self.admit_blocked += 1
                    break  # wait for blocks to free
            elif self.paged:
                if not self._admit_one_paged(free[0], req):
                    self.admit_blocked += 1
                    break
            else:
                self._admit_one(free[0], req)
            self.queue.popleft()
            if self.tracer is not None:
                # drawn from arrival, so the queueing before admit shows
                self.tracer.async_begin("request", id=req.uid,
                                        ts_ns=req.arrival_ns,
                                        mode=req.decoding, width=width)
                if req.done:  # max_new_tokens <= 0: retires at admission
                    self.tracer.async_end("request", id=req.uid, tokens=0)
            n += 1
        return n

    def _find_beam_group(self, i: int) -> Optional[Dict[str, Any]]:
        for g in self._beam_groups.values():
            if i in g["slots"]:
                return g
        return None

    def step(self) -> bool:
        """One chunk cycle: admit, decode N tokens/slot, retire."""
        admitted = self._admit()
        occupied = [i for i, r in enumerate(self.slots) if r is not None]
        occ = len(occupied)
        self.peak_active_slots = max(self.peak_active_slots, occ)
        if not occupied:
            if self.paged and self.queue and admitted == 0:
                # no live slot can ever free blocks: the head request's
                # reservation exceeds what the pool can ever grant
                raise RuntimeError(
                    f"request {self.queue[0].uid} needs more cache blocks "
                    f"than the pool can ever free (num_cache_blocks="
                    f"{self.num_cache_blocks}, block={self.cache_block_size})")
            return admitted > 0
        self._h_occupancy.observe(occ / self.max_batch)
        # decode-variant dispatch on the pool's current mode mix: a pure
        # NORMAL pool runs the legacy two-arg program unchanged (same AOT
        # artifact bench_serving compiles); beam/spec pools run the general
        # program with the matching static flags
        has_beam = any(self._slot_kind[i] == decoding.BEAM for i in occupied)
        has_spec = any(self._slot_kind[i] == decoding.SPEC for i in occupied)
        tr = self.tracer
        t0 = time.perf_counter_ns()
        with (tr.span("decode_chunk", cat="decode", steps=self.decode_chunk,
                      active_slots=occ, occupancy=occ / self.max_batch,
                      has_beam=has_beam, has_spec=has_spec)
              if tr is not None else _NO_SPAN):
            with (tr.span("decode_dispatch", cat="decode")
                  if tr is not None else _NO_SPAN):
                if not (has_beam or has_spec):
                    self.state, toks, valid = self._decode(self.params,
                                                           self.state)
                    fetch = (toks, valid, self.state.active)
                else:
                    fn = self._get_decode(has_beam, has_spec)
                    dp = self.draft_params if has_spec else self.params
                    self.state, toks, valid, parent = fn(self.params, dp,
                                                         self.state)
                    fetch = (toks, valid, parent, self.state.active,
                             self.state.beam_score, self.state.spec_steps,
                             self.state.spec_accepted)
            with (tr.span("decode_sync", cat="decode")
                  if tr is not None else _NO_SPAN):
                got = jax.device_get(fetch)  # THE once-per-chunk sync
            t1 = time.perf_counter_ns()  # the timestamp the sync earned
        if not (has_beam or has_spec):
            toks, valid, alive = got
            toks, valid = toks[:, :, None], valid[:, :, None]  # [N, B, 1]
            parent = scores = sst = sacc = None
        else:
            toks, valid, parent, alive, scores, sst, sacc = got
        self.decode_syncs += 1
        self._h_chunk_s.observe((t1 - t0) / 1e9)
        with tr.span("emit", cat="decode") if tr is not None else _NO_SPAN:
            self._emit(occupied, has_beam, t1, toks, valid, parent, alive,
                       scores, sst, sacc)
        return True

    def _emit(self, occupied, has_beam, t_sync, toks, valid, parent, alive,
              scores, sst, sacc):
        """After a decode sync: append the returned tokens to their
        requests, stamp first tokens with the sync's time, retire finished
        slots and beam groups."""
        if self.paged and self._pending_keys:
            # every pending divergence entry's origin slot just ran its
            # first decode chunk, writing the entry's last position: promote
            # to fully shareable
            self._pending_keys.clear()
        for n in range(toks.shape[0]):
            if has_beam:
                # hypothesis histories fork exactly like the device caches:
                # read every parent's history BEFORE committing any
                moved = {}
                for i in occupied:
                    if self._slot_kind[i] == decoding.BEAM and valid[n, i, 0]:
                        moved[i] = list(self._beam_hist[parent[n, i]])
                for i, hist in moved.items():
                    hist.append(int(toks[n, i, 0]))
                    self._beam_hist[i] = hist
                    self.decode_tokens += 1
            for i in occupied:
                if self._slot_kind[i] == decoding.BEAM:
                    continue  # recorded above (hypotheses fork, not append)
                for j in range(valid.shape[2]):
                    if valid[n, i, j]:
                        self.slots[i].output.append(int(toks[n, i, j]))
                        self.decode_tokens += 1
        for i in occupied:
            req = self.slots[i]
            if req.first_token_ns is None and (req.output
                                                or self._beam_hist[i]):
                req.first_token_ns = t_sync
        retired = []
        for i in occupied:
            if alive[i]:
                continue
            kind = self._slot_kind[i]
            if kind == decoding.BEAM:
                # freeze the finished hypothesis; the slot stays reserved
                # (not refillable) until every group member retires, so the
                # group id — the leader's slot index — stays unambiguous
                g = self._find_beam_group(i)
                if g is not None and i in g["live"]:
                    g["live"].discard(i)
                    g["finished"].append(
                        (list(self._beam_hist[i]), float(scores[i])))
                continue
            req = self.slots[i]
            if kind == decoding.SPEC:
                vs, at = int(sst[i]), int(sacc[i])
                req.spec_stats = {"verify_steps": vs,
                                  "accepted_draft_tokens": at}
                self.spec_verify_steps += vs
                self.spec_accepted_tokens += at
            req.done = True
            if self.tracer is not None:
                self.tracer.async_end("request", id=req.uid,
                                      tokens=len(req.output or []))
            self.slots[i] = None  # retire -> refillable next boundary
            retired.append(i)
        # beam groups with no live hypothesis left: rank and retire together
        for lead in list(self._beam_groups):
            g = self._beam_groups[lead]
            if g["live"]:
                continue
            req = g["req"]
            hyps = g["finished"]
            norm = decoding.rank_hypotheses(
                [s for _, s in hyps], [len(t) for t, _ in hyps],
                self.beam_length_alpha)
            order = np.argsort(-np.asarray(norm), kind="stable")
            req.beams = [(list(hyps[k][0]), float(norm[k])) for k in order]
            req.output = list(req.beams[0][0]) if req.beams else []
            req.done = True
            if self.tracer is not None:
                self.tracer.async_end("request", id=req.uid,
                                      tokens=len(req.output),
                                      hypotheses=len(req.beams))
            for m in g["slots"]:
                self.slots[m] = None
                self._slot_kind[m] = decoding.NORMAL
                self._beam_hist[m] = []
                retired.append(m)
            del self._beam_groups[lead]
        if self.paged and retired:
            for i in retired:
                for bid in self._slot_blocks[i]:
                    self._alloc.decref(bid)
                self._slot_blocks[i] = []
            # point retired rows at the null block so their frozen-position
            # writes stop touching (possibly reissued) pool blocks
            self.state = dataclasses.replace(
                self.state,
                page_table=self.state.page_table
                .at[jnp.asarray(retired)].set(0))

    def run_to_completion(self, max_ticks: int = 10000):
        ticks = 0
        while any(s is not None for s in self.slots) or self.queue:
            if not self.step():
                break
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("serving did not converge")
        return ticks

    # -- kernel autotuning --------------------------------------------------
    def pretune(self, *, repeats: int = 2, max_candidates: int = 4,
                verbose: bool = False) -> int:
        """Measure-tune every mpGEMM shape this engine dispatches.

        Decode steps run M = max_batch activations per projection; prefill
        chunks run M = prefill_chunk. Tunes each (M, packed-weight shape)
        pair missing from the tuning cache and persists the cache, so a
        subsequent trace with ``fusion="tuned"`` resolves every dispatch
        from measured data (trace-time dict hit, sub-ms). Only meaningful
        for ``mpgemm_mode="lut_pallas"`` — the other modes have no block
        knobs to tune.
        """
        from repro.core import autotune
        cache = self.tuning_cache or autotune.get_active()
        if cache is None:
            raise ValueError("pretune() needs a tuning cache — construct "
                             "the engine with tuning_cache=<path>")
        q = self.cfg.quant or {}
        if q.get("mpgemm_mode") != "lut_pallas":
            warnings.warn("pretune() is a no-op for mpgemm_mode="
                          f"{q.get('mpgemm_mode')!r} (no kernel knobs)")
            return 0
        from repro.core.mpgemm import resolve_table_quant
        n = autotune.pretune_params(
            self.params, [self.max_batch, self.prefill_chunk], cache=cache,
            table_quant=resolve_table_quant(q.get("table_quant", "per_row")),
            plan=self.plan,
            repeats=repeats, max_candidates=max_candidates, verbose=verbose)
        if cache.path is not None:
            cache.save()
        return n

    # -- observability ------------------------------------------------------
    def stats(self) -> dict:
        # latency/occupancy come from the bounded-reservoir histograms;
        # percentiles interpolate between closest ranks (the old nearest-
        # rank lambda reported p50 of 3 samples as the second LARGEST)
        h = self._h_chunk_s
        toks = max(1, self.decode_tokens)
        decode_s = h.total
        out = {
            "decode_chunk": self.decode_chunk,
            "prefill_chunk": self.prefill_chunk,
            "decode_syncs": self.decode_syncs,
            "decode_tokens": self.decode_tokens,
            "host_syncs_per_token": self.decode_syncs / toks,
            "prefill_dispatches": self.prefill_dispatches,
            "p50_chunk_ms": h.percentile(0.50) * 1e3,
            "p95_chunk_ms": h.percentile(0.95) * 1e3,
            # decode-only throughput: excludes prefill/admit/compile, so it
            # is the number that isolates a decode-chunk latency cliff
            "decode_tok_s": self.decode_tokens / decode_s if decode_s else 0.0,
            # cache-pool observability (meaningful for dense too: the HBM
            # number is what the paged/dense capacity comparison fixes)
            "paged": self.paged,
            "mesh": (None if self.plan is None else dict(zip(
                self.plan.mesh.axis_names, self.plan.mesh.devices.shape))),
            "cache_hbm_bytes": int(sum(
                l.nbytes for l in jax.tree.leaves(self.state.caches))),
            "slot_occupancy": self._h_occupancy.mean,
            "peak_active_slots": self.peak_active_slots,
            "admit_attempts": self.admit_attempts,
            "admit_blocked": self.admit_blocked,
            "admission_blocked_rate": (self.admit_blocked
                                       / max(1, self.admit_attempts)),
            "prefill_s": self.prefill_s,
            "prefill_tokens": self.prefill_tokens,
            "prefill_tokens_reused": self.prefill_tokens_reused,
        }
        if self.paged:
            out["cache_block_size"] = self.cache_block_size
            out["num_cache_blocks"] = self.num_cache_blocks
            out["blocks_in_use"] = self._alloc.num_used
            if self._prefix is not None:
                out["prefix_cache"] = {
                    "entries": len(self._prefix),
                    "hits": self._prefix.hits,
                    "misses": self._prefix.misses,
                    "evictions": self._prefix.evictions,
                }
        if self.draft_params is not None:
            # retired totals plus the still-occupied spec slots' live
            # counters (stats() is a rare observability call, so the extra
            # sync here does not count against the decode loop's one/chunk)
            sst, sacc = jax.device_get(
                (self.state.spec_steps, self.state.spec_accepted))
            vs = self.spec_verify_steps + sum(
                int(sst[i]) for i in range(self.max_batch)
                if self.slots[i] is not None
                and self._slot_kind[i] == decoding.SPEC)
            at = self.spec_accepted_tokens + sum(
                int(sacc[i]) for i in range(self.max_batch)
                if self.slots[i] is not None
                and self._slot_kind[i] == decoding.SPEC)
            out["spec"] = {
                "spec_k": self.spec_k,
                "draft_planes": int(self.spec_draft_planes),
                "draft_extra_hbm_bytes": int(self.draft_extra_hbm_bytes),
                "verify_steps": vs,
                "accepted_draft_tokens": at,
                # +1 for the verify forward's own token (replacement or
                # bonus): tokens emitted per verify round
                "mean_emitted_per_step": ((at + vs) / max(1, vs)),
                "mean_accepted_per_step": at / max(1, vs),
            }
        if self._beam_groups or any(
                k == decoding.BEAM for k in self._slot_kind):
            out["beam"] = {
                "active_groups": len(self._beam_groups),
                "length_alpha": self.beam_length_alpha,
            }
        if self.tuning_cache is not None:
            out["tuning_cache"] = self.tuning_cache.counters()
        rec = dispatch_obs.get_active()
        if rec is not None:
            s = rec.summary()
            out["dispatch"] = {k: s[k] for k in
                               ("decisions", "tuned", "heuristic", "forced",
                                "lut_xla")}
        return out

    def op_scopes(self) -> Dict[str, Dict[str, str]]:
        """{HLO module name: {instruction: scope label}} for the programs a
        pool of greedy slots runs: the decode chunk, the prefill chunk and
        (dense) the cache merge. Each is compiled once more with the
        engine's own arguments and read by ``repro.obs.scopes``, so a
        device trace of this engine can put its ops' time down to the
        ``mpgemm``, ``attention`` and ``lm_head`` scopes."""
        tok = jnp.zeros((1, self.prefill_chunk), jnp.int32)
        at = (np.int32(0), np.int32(1))
        progs = [(self._decode, (self.params, self.state))]
        if self.paged:
            view = jax.tree.map(lambda c, z, pooled: c if pooled else z,
                                self.state.caches, self._zero_slot,
                                self._pooled)
            row = jnp.zeros((1, self.blocks_per_slot), jnp.int32)
            progs.append((self._prefill_paged,
                          (self.params, view, tok, *at, row)))
        else:
            progs += [(self._prefill, (self.params, self._zero_slot, tok,
                                       *at)),
                      (self._merge, (self.state.caches, self._zero_slot,
                                     at[0]))]
        out = {}
        for fn, args in progs:
            text = fn.lower(*args).compile().as_text()
            out[scopes.module_name(text)] = scopes.op_scopes(text)
        return out

    def metrics_snapshot(self) -> dict:
        """JSON-able registry snapshot with ``stats()`` mirrored in as
        ``engine_*`` gauges (counters/gauges/histogram summaries)."""
        export_stats(self.metrics, self.stats(), prefix="engine")
        return self.metrics.snapshot()

    def prometheus_text(self) -> str:
        """Prometheus text exposition of the same snapshot."""
        export_stats(self.metrics, self.stats(), prefix="engine")
        return self.metrics.prometheus_text()
