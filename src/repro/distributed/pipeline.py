"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For a mesh with a leading "pp" axis: the layer stack [L, ...] is split into
``n_stages`` contiguous stages, each resident on one pp-shard. The schedule
is the classic GPipe loop over ``n_micro + n_stages - 1`` ticks: at every
tick each stage runs its microbatch (bubble ticks compute-but-discard) and
activations hop stage→stage+1 with jax.lax.ppermute.

This composes with the data/model axes: inside shard_map over "pp" only, the
per-stage body is still a pjit-style program over ("data", "model").
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["pipelined_forward", "split_stages"]


def split_stages(stacked_params, n_stages: int):
    """[L, ...] stacked params -> [n_stages, L/n_stages, ...]."""
    def resh(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(resh, stacked_params)


def pipelined_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    staged_params,          # pytree with leading [n_stages, ...] dims
    x_micro: jax.Array,     # [n_micro, mb, ...] microbatched input
    *,
    mesh,
    n_stages: int,
    pp_axis: str = "pp",
) -> jax.Array:
    """Returns [n_micro, mb, ...] outputs of the full L-layer stack."""
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def per_stage(params_local, x_local):
        # params_local: [1, L/S, ...]; x_local: [n_micro, mb, ...]
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(pp_axis)
        mb_shape = x_local.shape[1:]
        buf = jnp.zeros((n_micro,) + mb_shape, x_local.dtype)
        carry_in = jnp.zeros(mb_shape, x_local.dtype)

        def tick(state, t):
            buf_, inflow = state
            # stage 0 feeds from the microbatch queue; others from inflow
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(stage == 0,
                             x_local[mb_idx], inflow)
            y = stage_fn(params_local, x_in)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            outflow = jax.lax.ppermute(y, pp_axis, perm)
            # last stage banks its result for microbatch (t - (S-1))
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid = (t >= n_stages - 1) & (stage == n_stages - 1)
            buf_ = jax.lax.cond(
                valid,
                lambda b: jax.lax.dynamic_update_index_in_dim(b, y, out_idx, 0),
                lambda b: b, buf_)
            return (buf_, outflow), None

        (buf, _), _ = jax.lax.scan(tick, (buf, carry_in), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them so every
        # pp shard returns the same value (ppermute needs unique dests, so
        # use an all_gather + select).
        buf = jax.lax.all_gather(buf, pp_axis)[n_stages - 1]
        return buf

    spec_p = jax.tree.map(lambda _: P(pp_axis), staged_params)
    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_p, P()), out_specs=P(),
        check_vma=False)
    return fn(staged_params, x_micro)
