"""Pipeline parallelism: GPipe microbatch schedule over the mesh
`pipeline` axis.

Reference parity: PP is absent upstream (SURVEY.md §2 census — rebuild
obligation). Design:

- Stage weights carry a leading [P] dim sharded over `pipeline`; inside
  `shard_map` each device holds exactly its stage's slice.
- The schedule is the classic GPipe wavefront: T = n_micro + P - 1 ticks;
  every tick each stage computes one microbatch and `ppermute`s its
  activation to the next stage (nearest-neighbor ICI). Stage 0 feeds fresh
  microbatches, the last stage collects outputs.
- All control flow is a static Python loop over T with stage-id `where`
  selects — no dynamic shapes, and autodiff through ppermute yields the
  reverse schedule (backward wavefront) for free.
- Activations must keep one shape through the stage fn (true for
  transformer blocks).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from .mesh import BATCH_AXES


def _gpipe_body(
    params, x, stage_fn: Callable, axis: str, n_stages: int, n_micro: int
):
    """Runs inside shard_map. params: leading dim 1 (this stage's slice);
    x: [B_local, ...]."""
    params = jax.tree.map(lambda a: jnp.squeeze(a, 0), params)
    stage = jax.lax.axis_index(axis)
    B = x.shape[0]
    if B < n_micro or B % n_micro:
        raise ValueError(
            f"per-device batch {B} must be a multiple of "
            f"pipeline_microbatches {n_micro}"
        )
    mb = B // n_micro
    xs = x.reshape(n_micro, mb, *x.shape[1:])
    zeros = jnp.zeros_like(xs[0])
    carry = zeros  # activation arriving from the previous stage
    out = jnp.zeros_like(xs)
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
    T = n_micro + n_stages - 1
    for t in range(T):
        feed = xs[t] if t < n_micro else zeros
        inp = jnp.where(stage == 0, feed, carry)
        y = stage_fn(params, inp)
        if t >= n_stages - 1:  # last stage emits microbatch t-(P-1)
            out = jnp.where(
                stage == n_stages - 1, out.at[t - n_stages + 1].set(y), out
            )
        if t != T - 1:
            carry = jax.lax.ppermute(y, axis, perm)
    # emit with a leading stage dim; only the last stage's slot is real
    return out.reshape(B, *x.shape[1:])[None]


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x,
    *,
    mesh: Mesh,
    axis: str = "pipeline",
    n_micro: int,
):
    """Apply `stage_fn(params_slice, x_mb) -> y_mb` as a P-stage pipeline.

    stage_params: pytree with leading dim P (stage-stacked weights).
    x: [B, ...] activations; returns [B, ...] (shape-preserving stages).
    """
    n_stages = int(mesh.shape.get(axis, 1))
    if n_stages <= 1:
        raise ValueError("pipeline_apply requires a pipeline axis of size > 1")
    if stage_params and jax.tree.leaves(stage_params):
        lead = jax.tree.leaves(stage_params)[0].shape[0]
        if lead != n_stages:
            raise ValueError(
                f"stage_params leading dim {lead} != pipeline axis size {n_stages}"
            )
    batch = tuple(ax for ax in BATCH_AXES if mesh.shape.get(ax, 1) > 1) or None
    x_spec = P(batch, *([None] * (x.ndim - 1)))
    p_spec = jax.tree.map(lambda a: P(axis, *([None] * (a.ndim - 1))), stage_params)
    out_spec = P(axis, batch, *([None] * (x.ndim - 1)))
    body = partial(
        _gpipe_body,
        stage_fn=stage_fn,
        axis=axis,
        n_stages=n_stages,
        n_micro=n_micro,
    )
    from .sharding import suspend_constraints

    with suspend_constraints():  # body code must not re-constrain locally
        stacked = shard_map(
            body,
            mesh=mesh,
            in_specs=(p_spec, x_spec),
            out_specs=out_spec,
        )(stage_params, x)
    return stacked[-1]  # the last stage's output (XLA inserts the transfer)
