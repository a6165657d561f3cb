"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Layers are split into P contiguous stages along a `pipe` mesh axis; M
microbatches stream through the stages with the canonical (P + M - 1)-step
schedule. Each step, every device applies its stage to its current
microbatch and the activations rotate one stage forward via ppermute —
the static, compile-time-known communication pattern of the paper's
management core, expressed on the ICI.

Bubble fraction = (P - 1) / (M + P - 1); amortize with M >> P.
Used as an optional parallelism mode (train over `pipe` axis) and as a
§Perf hillclimb candidate; validated in tests/test_distribution.py against
the sequential reference on forced host devices.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(mesh, layer_fn: Callable, stage_params, x_micro,
                   axis: str = "pipe"):
    """Run microbatches through pipeline stages.

    layer_fn(params_one_layer, x) -> x        (applied over a stage's
                                               layers with lax.scan)
    stage_params: pytree with leading dim (P, layers_per_stage, ...)
                  sharded so each pipe rank holds its (1, Lp, ...) slice.
    x_micro: (M, mb, ...) microbatched input, replicated across `pipe`.
    Returns (M, mb, ...) outputs (as produced by the last stage).
    """
    Pn = mesh.shape[axis]
    M = x_micro.shape[0]
    steps = Pn + M - 1

    def stage_apply(params, x):
        def body(h, pl_):
            return layer_fn(pl_, h), None
        out, _ = jax.lax.scan(body, x, params)
        return out

    def per_device(params, xs):
        # params: (1, Lp, ...) this rank's stage;  xs: (M, mb, ...)
        params = jax.tree.map(lambda a: a[0], params)
        rank = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)          # current activation
        outs = jnp.zeros_like(xs)                    # stage-P outputs

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            take = jnp.clip(t, 0, M - 1)
            fresh = jax.lax.dynamic_index_in_dim(xs, take, 0, False)
            h = jnp.where(rank == 0, fresh, buf)
            active = (t - rank >= 0) & (t - rank < M)
            y = jnp.where(active, stage_apply(params, h), h)
            # last stage emits microbatch (t - P + 1)
            emit_idx = jnp.clip(t - Pn + 1, 0, M - 1)
            emit = (rank == Pn - 1) & (t - Pn + 1 >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, emit_idx, 0),
                lambda o: o, outs)
            # rotate activations one stage forward
            perm = [(i, (i + 1) % Pn) for i in range(Pn)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(step, (buf, outs), jnp.arange(steps))
        # every rank holds zeros except the last; share results
        outs = jax.lax.psum(outs, axis)
        return outs

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params),
                P())                                  # xs replicated
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    return fn(stage_params, x_micro)


def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (P, L/P, ...)."""
    def r(a):
        L = a.shape[0]
        assert L % n_stages == 0, f"{L} layers not divisible by {n_stages}"
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return jax.tree.map(r, stacked_params)
