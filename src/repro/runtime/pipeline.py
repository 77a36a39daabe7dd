"""Pipeline parallelism over the ``pod`` axis (GPipe-style microbatch flow).

At 1000+ chips the cross-pod (DCN/optical) links are the scarcest resource;
pipelining the layer stack across pods replaces per-layer cross-pod
collectives with one boundary activation transfer per microbatch — the same
observation that drives the paper's tree loader (on-chip links ≫ host link)
applied to inter-POD links.

Schedule: classic GPipe forward pipeline via ``shard_map`` over the stage
axis.  With S stages and M microbatches the loop runs M + S - 1 ticks; at
each tick every stage applies its layer block to its current microbatch and
``ppermute``s the boundary activation to the next stage.  Bubble fraction =
(S-1)/(M+S-1), reported by :func:`bubble_fraction`.

This module provides the *forward* pipeline (serving / prefill; also the
building block for 1F1B training which interleaves a mirrored backward
flow).  Stage-sharded parameters are expressed with the existing logical
rules: a leading ``stages`` axis mapped to ``pod``.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(stage_fn: Callable, stage_params, x_micro: jax.Array,
                     mesh: Mesh, axis: str = "pod") -> jax.Array:
    """Run ``stage_fn`` as an S-stage pipeline over microbatches.

    stage_fn(params_slice, x) -> y : one stage's layer block (same activation
    shape in/out — a transformer stage).
    stage_params: pytree with leading axis S, sharded P(axis) on dim 0.
    x_micro: (M, B_m, ...) microbatched input, replicated over ``axis``.

    Returns (M, B_m, ...) outputs of the LAST stage, replicated.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]

    param_specs = jax.tree.map(
        lambda _: P(*([axis] + [None] * 0)), stage_params)

    def body(params, xs):
        # inside shard_map: params leading dim == 1 (this stage's slice)
        my_params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)

        def tick(carry, t):
            boundary, outputs = carry
            # stage 0 ingests microbatch t (or junk after the last one)
            m_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(xs, m_idx, axis=0,
                                                 keepdims=False)
            x_in = jnp.where(stage == 0, fresh, boundary)
            y = stage_fn(my_params, x_in)
            # last stage commits its result for microbatch t-(S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            commit = (t >= n_stages - 1) & (stage == n_stages - 1)
            outputs = jnp.where(
                commit,
                jax.lax.dynamic_update_index_in_dim(
                    outputs, y, out_idx, axis=0),
                outputs)
            # boundary activations flow one stage forward
            boundary = jax.lax.ppermute(y, axis, perm_fwd)
            return (boundary, outputs), None

        boundary0 = jnp.zeros_like(xs[0])
        outputs0 = jnp.zeros_like(xs)
        (boundary, outputs), _ = jax.lax.scan(
            tick, (boundary0, outputs0), jnp.arange(n_ticks))
        # broadcast the last stage's outputs to every stage for a replicated
        # result (one extra fan-out; cheap vs the M transfers above)
        src = n_stages - 1
        outputs = jax.lax.psum(
            jnp.where(stage == src, outputs, jnp.zeros_like(outputs)), axis)
        return outputs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_micro)
