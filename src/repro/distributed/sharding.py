"""Logical-axis sharding rules → NamedSharding / PartitionSpec.

The model code annotates activations with *logical* axes via :func:`shard`
(no-op outside a mesh context), and parameters are matched by path patterns
to logical specs which an :class:`AxisPlan` maps onto physical mesh axes.

Physical meshes (launch/mesh.py):
  single-pod (16, 16)      axes ("data", "model")
  multi-pod  (2, 16, 16)   axes ("pod", "data", "model")
  serving    (data, model) over however many devices the host exposes

The plan maps logical -> physical:
  batch   -> ("pod", "data")   (pod composes with data for all batch ops)
  model   -> "model"           (TP: attention heads / ffn / vocab)
  expert  -> "model"           (EP shares the TP axis by default)
  fsdp    -> "data"            (ZeRO-3 parameter sharding over data)
  seq     -> "data"            (sequence parallelism for long prefill)
  stage   -> "pp"              (pipeline axis when a 3D (pp,...) mesh is used)

Packed low-bit weights (core/quantize.QuantizedWeight) flatten with named
child paths (".../qw/packed" etc.), and their rules mirror the float ones:
a column-parallel float weight [K, N] sharded ("fsdp", "model") becomes a
packed plane [N, ceil(K·B/8)] sharded ("model", None) — the quantizer packs
output-major — while a row-parallel weight shards the byte dim, which is
only legal on whole packing chunks (see :func:`resolve_physical_spec`);
:func:`pad_row_parallel` pads a packed weight to a chunk count the plan
divides.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import re
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["AxisPlan", "plan_scope", "current_plan", "shard",
           "param_spec_tree", "named_sharding_tree", "constrain_tree",
           "resolve_physical_spec", "packed_group_bytes", "pad_row_parallel",
           "DEFAULT_RULES"]

_state = threading.local()


@dataclasses.dataclass(frozen=True)
class AxisPlan:
    mesh: Mesh
    batch: Tuple[str, ...] = ("data",)
    model: Optional[str] = "model"
    expert: Optional[str] = "model"
    fsdp: Optional[str] = None          # set to "data" for ZeRO-3
    seq: Optional[str] = None           # set to "data" for sequence parallelism
    stage: Optional[str] = None         # set to "pp" for pipeline meshes

    def resolve(self, logical: Optional[str]):
        if logical is None:
            return None
        if logical == "batch":
            return self.batch if len(self.batch) > 1 else self.batch[0]
        return getattr(self, logical)

    def axis_size(self, logical: Optional[str]) -> int:
        """Number of shards the resolved physical axis produces (1 = off)."""
        ax = self.resolve(logical)
        if ax is None:
            return 1
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        if isinstance(ax, str):
            return sizes[ax]
        return int(math.prod(sizes[a] for a in ax))


@contextlib.contextmanager
def plan_scope(plan: Optional[AxisPlan]):
    prev = getattr(_state, "plan", None)
    _state.plan = plan
    try:
        yield plan
    finally:
        _state.plan = prev


def current_plan() -> Optional[AxisPlan]:
    return getattr(_state, "plan", None)


def constrain_tree(params, rules=None):
    """Apply rule-based sharding constraints to a param(-slice) tree.

    Used inside scan-over-layers bodies: without it XLA's SPMD propagation
    frequently loses the sharding of per-layer param slices inside the while
    loop, replicating both the forward all-gather result AND the backward
    grad-accumulation buffers (observed: 243 GiB/device temp on the
    qwen2-72b train step — §Perf iteration T1). The constraint also pins the
    cotangent sharding, which is what shards the scanned gradient stack.
    """
    plan = current_plan()
    if plan is None:
        return params
    sh = named_sharding_tree(params, plan, rules)
    return jax.tree.map(jax.lax.with_sharding_constraint, params, sh)


def shard(x, *logical_axes):
    """Constrain activation sharding by logical axis names (None = replicate
    that dim). No-op when no plan is active (single-device tests)."""
    plan = current_plan()
    if plan is None:
        return x
    spec = P(*[plan.resolve(a) for a in logical_axes])
    return jax.lax.with_sharding_constraint(x, NamedSharding(plan.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules: path-regex -> logical spec per dim.
# Param paths look like "layers/attn/wq/w", "layers/moe/experts/up", etc.
# Stacked layer params have a leading L dim -> logical None prepended
# automatically when the rule has one fewer axis than the array rank.
#
# Quantized leaves: QuantizedWeight flattens to named children, so packed
# serving trees yield paths "layers/attn/wq/qw/packed" / ".../qw/scale" /
# ".../qw/zero_prime" / ".../qw/cw". packed is uint8 [N, Gp·B·k_group/8]
# (groups padded to whole packing chunks, core/packing.py) with
# N = d_out (the quantizer consumes w.T), scale/zero_prime are [N], and cw
# is the offline combined-lookup matrix [G·E, N] (group-major rows, so a
# K-shard is a contiguous row block).
#
# Every parameter leaf MUST match a rule: there is deliberately no ".*"
# catch-all, and an unmatched leaf raises with its key path (same style as
# the kvcache.batch_axes keyed errors) — a silently replicated 72B-scale
# weight is a perf bug that otherwise only shows up as OOM much later.
# ---------------------------------------------------------------------------

DEFAULT_RULES = [
    # embeddings / positional tables / lm head: vocab sharded over model axis
    (r"embed/table$", ("model", "fsdp")),
    (r"pos_embed$", (None, None)),
    (r"lm_head/w$", ("fsdp", "model")),
    (r"lm_head/qw/packed$", ("model", None)),
    (r"lm_head/qw/(scale|zero_prime)$", ("model",)),
    (r"lm_head/qw/cw$", (None, "model")),
    # attention projections: column-parallel qkv, row-parallel o
    (r"(attn|xattn|shared_attn)/wq/w$", ("fsdp", "model")),
    (r"(attn|xattn|shared_attn)/wk/w$", ("fsdp", "model")),
    (r"(attn|xattn|shared_attn)/wv/w$", ("fsdp", "model")),
    (r"(attn|xattn|shared_attn)/w[qkv]/b$", ("model",)),
    (r"(attn|xattn|shared_attn)/wo/w$", ("model", "fsdp")),
    (r"(attn|xattn|shared_attn)/wo/b$", (None,)),
    # mlp: column-parallel gate/up, row-parallel down
    (r"(mlp|shared_mlp)/(gate|up)/w$", ("fsdp", "model")),
    (r"(mlp|shared_mlp)/down/w$", ("model", "fsdp")),
    (r"(mlp|shared_mlp)/(gate|up|down)/b$", (None,)),
    # MoE: experts dim over expert axis, then like mlp
    (r"experts/(gate|up)$", ("expert", "fsdp", None)),
    (r"experts/down$", ("expert", None, "fsdp")),
    (r"experts/(gate|up|down)_qw/packed$", ("expert", None, None)),
    (r"experts/(gate|up|down)_qw/(scale|zero_prime)$", ("expert", None)),
    (r"experts/(gate|up|down)_qw/cw$", ("expert", None, None)),
    (r"router/w$", (None, "expert")),
    # mamba: d_inner sharded over model
    (r"ssm/in_proj/w$", ("fsdp", "model")),
    (r"ssm/out_proj/w$", ("model", "fsdp")),
    (r"ssm/(x_proj|dt_proj)/w$", ("model", None)),
    (r"ssm/dt_proj/b$", (None,)),
    (r"ssm/(conv_w)$", (None, "model")),
    (r"ssm/(conv_b|A_log|D|dt_bias|norm_g)$", ("model",)),
    # quantized linears (serving): packed is [N(out), ceil(K·B/8)].
    # column-parallel (the float weight sharded its OUT dim over model):
    (r"(/|^)(wq|wk|wv|gate|up|in_proj)/qw/packed$", ("model", None)),
    (r"(/|^)(wq|wk|wv|gate|up|in_proj)/qw/(scale|zero_prime)$", ("model",)),
    (r"(/|^)(wq|wk|wv|gate|up|in_proj)/qw/cw$", (None, "model")),
    # row-parallel (the float weight sharded its IN dim over model): shard
    # the byte dim — legal only on bit-group boundaries, enforced by
    # resolve_physical_spec. x_proj/dt_proj read the model-sharded d_inner.
    (r"(/|^)(wo|down|out_proj|x_proj|dt_proj)/qw/packed$", (None, "model")),
    (r"(/|^)(wo|down|out_proj|x_proj|dt_proj)/qw/(scale|zero_prime)$", (None,)),
    (r"(/|^)(wo|down|out_proj|x_proj|dt_proj)/qw/cw$", ("model", None)),
    # norms / gates / small vectors replicated
    (r"norm/(g|b)$", (None,)),
    (r"gate_(attn|mlp)$", (None,)),
    (r"/b$", (None,)),
]


def _key_str(k) -> str:
    # DictKey -> .key, SequenceKey -> .idx, GetAttrKey (QuantizedWeight
    # children) -> .name, FlattenedIndexKey -> .key
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _path_str(path) -> str:
    return "/".join(_key_str(k) for k in path)


def _spec_for(path: str, shape, rules) -> Optional[Tuple[Optional[str], ...]]:
    """Logical spec for a leaf, or None when no rule matches."""
    for pat, spec in rules:
        if re.search(pat, path):
            spec = tuple(spec)
            if len(spec) < len(shape):  # stacked layer/group leading dims
                spec = (None,) * (len(shape) - len(spec)) + spec
            elif len(spec) > len(shape):
                spec = spec[-len(shape):] if len(shape) else ()
            return spec
    return None


def _spec_leaves(params, rules):
    """[(path, leaf, logical_spec)] for every leaf; raises listing every
    unmatched leaf by key path."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out, unmatched = [], []
    for path, leaf in flat:
        pstr = _path_str(path)
        spec = _spec_for(pstr, getattr(leaf, "shape", ()), rules)
        if spec is None:
            unmatched.append(jax.tree_util.keystr(path))
        out.append((path, leaf, spec))
    if unmatched:
        raise ValueError(
            "no sharding rule matched these parameter leaves (add a rule or "
            "an explicit replicate entry): " + ", ".join(unmatched))
    return out, treedef


def param_spec_tree(params, rules=None):
    """Pytree of logical specs (tuples of logical axis names) for params."""
    leaves, treedef = _spec_leaves(params, rules or DEFAULT_RULES)
    return jax.tree_util.tree_unflatten(treedef, [s for _, _, s in leaves])


def packed_group_bytes(qw) -> int:
    """Bytes one packing chunk (``packing.chunk_groups`` groups, all stored
    planes) occupies in a packed row — the granularity below which the byte
    dim of ``packed`` must never be split."""
    from repro.core.packing import chunk_groups
    if qw.packed is None:
        return 1
    planes = qw.stored_planes
    return chunk_groups(qw.k_group, planes) * planes * qw.k_group // 8


def resolve_physical_spec(shape, phys_axes, axis_sizes,
                          *, last_dim_align: int = 1):
    """Pure resolver: per-dim physical axis names -> a legal PartitionSpec
    tuple for ``shape``.

    A dim is replicated (None) when its mesh axis does not evenly divide
    it.  ``last_dim_align`` additionally requires the per-shard extent of
    the FINAL dim to be a multiple of the given alignment — used for packed
    low-bit planes, where a byte-dim shard boundary inside a bit-group
    would split a group code across devices (the never-mid-byte /
    never-mid-group rule).  GSPMD shardings are layout-only, so falling
    back to replication is always semantics-preserving.
    """
    out = []
    ndim = len(shape)
    for i, (dim, ax) in enumerate(zip(shape, phys_axes)):
        if ax is None:
            out.append(None)
            continue
        if isinstance(ax, str):
            size = axis_sizes[ax]
        else:
            size = int(math.prod(axis_sizes[a] for a in ax))
        if size <= 0 or dim % size != 0:
            out.append(None)
            continue
        if i == ndim - 1 and last_dim_align > 1 and \
                (dim // size) % last_dim_align != 0:
            out.append(None)
            continue
        out.append(ax)
    return tuple(out)


def _packed_align_map(params):
    """path-prefix (of the qw node) -> group-byte alignment, from a pre-walk
    over QuantizedWeight nodes (their static metadata is invisible once the
    tree is flattened to array leaves)."""
    from repro.core.quantize import QuantizedWeight
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QuantizedWeight))
    return {_path_str(path): packed_group_bytes(leaf)
            for path, leaf in flat if isinstance(leaf, QuantizedWeight)}


def pad_row_parallel(params, plan: AxisPlan, rules=None):
    """Append zero packing chunks to every packed weight whose byte dim the
    plan shards over a count that does not divide its chunks, so its K
    splits instead of replicating (qwen2-72b ``mlp/down``: K=29568 is 58
    chunks, 60 at TP=4). Nothing reads the padding: unpacking and the
    kernels stop at the true group count."""
    from repro.core.quantize import QuantizedWeight
    rules = rules or DEFAULT_RULES

    def pad(path, node):
        if not isinstance(node, QuantizedWeight) or node.packed is None:
            return node
        packed = node.packed
        spec = _spec_for(_path_str(path) + "/packed", packed.shape, rules)
        shards = plan.axis_size(spec[-1]) if spec else 1
        chunk = packed_group_bytes(node)
        extra = -(packed.shape[-1] // chunk) % shards
        if not extra:
            return node
        widths = [(0, 0)] * (packed.ndim - 1) + [(0, extra * chunk)]
        return QuantizedWeight(
            jnp.pad(packed, widths), node.scale, node.zero_prime,
            node.plane_scales, bits=node.bits, k_group=node.k_group,
            k_total=node.k_total, n=node.n, cw=node.cw,
            plane_start=node.plane_start, stored_planes=node.stored_planes)

    return jax.tree_util.tree_map_with_path(
        pad, params, is_leaf=lambda x: isinstance(x, QuantizedWeight))


def named_sharding_tree(params, plan: AxisPlan, rules=None):
    """Pytree of NamedSharding for params under the plan.

    Divisibility-safe: any dim that does not divide by its mesh axis size is
    replicated, and the byte dim of a packed plane is only sharded when
    every shard covers whole bit-groups (see :func:`resolve_physical_spec`).
    """
    mesh = plan.mesh
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    align = _packed_align_map(params)

    leaves, treedef = _spec_leaves(params, rules or DEFAULT_RULES)
    out = []
    for path, leaf, logical in leaves:
        pstr = _path_str(path)
        last_align = 1
        if pstr.endswith("/packed"):
            last_align = align.get(pstr[:-len("/packed")], 1)
        phys = resolve_physical_spec(
            getattr(leaf, "shape", ()),
            [plan.resolve(l) for l in logical],
            axis_sizes, last_dim_align=last_align)
        out.append(NamedSharding(mesh, P(*phys)))
    return jax.tree_util.tree_unflatten(treedef, out)
