"""GPipe-style pipeline parallelism with micro-batching (paper §4.2, Fig 6).

The paper's inference schedule overlaps `n` micro-batches over `p` pipeline
stages so per-token latency is max(l_mb, n * l_s).  This module implements
that schedule as a real jax program: a ``shard_map`` over a ``stage`` mesh
axis, with ``lax.ppermute`` moving activations stage->stage each tick.

The layer stack is stacked as (n_stages, layers_per_stage, ...) and each
stage device owns one slice — the same weight-stationary placement the
analytic engine assumes.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, microbatches: jnp.ndarray,
                   mesh, axis: str = "stage") -> jnp.ndarray:
    """Run microbatches (n_mb, mb, ...) through p pipeline stages.

    stage_fn(params_for_stage, x) -> x, applied by every stage.
    stage_params has leading dim n_stages (sharded over `axis`).
    Returns outputs with the same shape as `microbatches`.
    """
    n_stages = mesh.shape[axis]
    n_mb = microbatches.shape[0]

    def body(params, mbs):
        # params: (1, ...) local slice; mbs: (n_mb, mb, ...) replicated.
        stage = jax.lax.axis_index(axis)
        local = jax.tree.map(lambda x: x[0], params)
        mb_shape = mbs.shape[1:]
        # The carry becomes device-varying after ppermute; mark the initial
        # values as varying over the stage axis to satisfy shard_map typing.
        carry, out = jax.lax.pcast(
            (jnp.zeros(mb_shape, mbs.dtype), jnp.zeros_like(mbs)), (axis,),
            to="varying")

        def tick(t, state):
            carry, out = state
            # Stage 0 injects microbatch t (while available); other stages
            # consume what arrived from the previous stage.
            inject = jnp.where(t < n_mb, t, n_mb - 1)
            x = jnp.where(stage == 0, mbs[inject], carry)
            y = stage_fn(local, x)
            # Last stage commits its result for microbatch (t - p + 1).
            commit = t - (n_stages - 1)
            commit_c = jnp.clip(commit, 0, n_mb - 1)
            do_commit = (stage == n_stages - 1) & (commit >= 0) & (commit < n_mb)
            starts = (commit_c,) + (0,) * y.ndim
            cur = jax.lax.dynamic_slice(out, starts, (1,) + y.shape)
            new = jnp.where(do_commit, y[None], cur)
            out = jax.lax.dynamic_update_slice(out, new, starts)
            # Shift activations to the next stage.
            carry = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return carry, out

        ticks = n_mb + n_stages - 1
        _, out = jax.lax.fori_loop(0, ticks, tick, (carry, out))
        # Every stage holds zeros except the last: reduce to broadcast.
        return jax.lax.psum(out, axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P())
    return fn(stage_params, microbatches)


def split_microbatches(batch: jnp.ndarray, n: int) -> jnp.ndarray:
    """(B, ...) -> (n, B/n, ...)."""
    B = batch.shape[0]
    assert B % n == 0
    return batch.reshape((n, B // n) + batch.shape[1:])
