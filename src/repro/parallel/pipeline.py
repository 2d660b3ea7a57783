"""Pipeline parallelism (PP): GPipe-style microbatch schedule over a mesh
axis via shard_map + lax.ppermute.

The paper analogue is I2's *streaming FLITs*: instead of moving a whole
activation tensor and waiting, microbatches stream through a chain of stages
with each hop overlapping the next stage's compute — the die-to-die
streaming discipline at pod scale. Used as an optional plan for the 'pod'
axis (stage = pod) and validated against the sequential reference in
tests/test_pipeline.py.

Schedule: classic GPipe fill-compute-drain over n_micro ≥ n_stage
microbatches; bubbles = (n_stage-1)/(n_micro + n_stage - 1).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def pipeline_forward(stage_fn: Callable, x_micro: jnp.ndarray,
                     stage_params, axis_name: str):
    """Run inside shard_map: each device holds ONE stage's params.

    stage_fn(params, x) → x (same shape). x_micro: (n_micro, mb, ...) —
    identical on every stage (only stage 0's values are consumed).
    Returns (n_micro, mb, ...) outputs valid on the LAST stage.
    """
    n_stage = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stage - 1
    perm = [(i, i + 1) for i in range(n_stage - 1)]   # chain, not a ring

    buf = jnp.zeros_like(x_micro)                      # collected outputs
    carry = jnp.zeros_like(x_micro[0])                 # inter-stage activation

    def tick(state, t):
        carry, buf = state
        # stage 0 ingests microbatch t (when in range)
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        x_in = jax.lax.dynamic_index_in_dim(x_micro, mb_idx, 0, False)
        x = jnp.where(stage == 0, x_in, carry)
        y = stage_fn(stage_params, x)
        # last stage stores microbatch (t - n_stage + 1) when valid
        out_idx = t - (n_stage - 1)
        valid = jnp.logical_and(stage == n_stage - 1, out_idx >= 0)
        store = jnp.clip(out_idx, 0, n_micro - 1)
        buf = jax.lax.cond(
            valid,
            lambda b: jax.lax.dynamic_update_index_in_dim(b, y, store, 0),
            lambda b: b, buf)
        # stream the activation down the chain (FLIT hop)
        carry = jax.lax.ppermute(y, axis_name, perm)
        return (carry, buf), None

    (carry, buf), _ = jax.lax.scan(tick, (carry, buf),
                                   jnp.arange(n_ticks))
    return buf


def run_pipeline(mesh, stage_fn: Callable, params_stacked, x: jnp.ndarray,
                 n_micro: int, axis_name: str = "stage"):
    """Host-side wrapper: params_stacked (n_stage, ...), x (batch, ...).

    Splits the batch into microbatches, shard_maps the schedule, and returns
    outputs gathered from the last stage (broadcast to all for convenience).
    """
    from jax.sharding import PartitionSpec as P
    n_stage = mesh.shape[axis_name]
    assert x.shape[0] % n_micro == 0
    xm = x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:])

    def fn(params, xm):
        local = jax.tree.map(lambda t: t[0], params)   # drop the stage dim
        out = pipeline_forward(stage_fn, xm, local, axis_name)
        # broadcast the last stage's result to every stage (masked psum)
        stage = jax.lax.axis_index(axis_name)
        masked = jnp.where(stage == n_stage - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(masked, axis_name)

    spec_p = jax.tree.map(lambda _: P(axis_name), params_stacked)
    out = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(spec_p, P()), out_specs=P(),
        check_vma=False))(params_stacked, xm)
    return out.reshape(x.shape[0], *out.shape[2:])


def bubble_fraction(n_stage: int, n_micro: int) -> float:
    return (n_stage - 1) / (n_micro + n_stage - 1)
