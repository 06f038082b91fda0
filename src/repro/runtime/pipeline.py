"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Optional PP stage for very deep archs (DESIGN.md §5): the layer stack is
split into S contiguous stages along the mesh 'stage' axis; microbatches
stream through with the standard (S + M - 1)-slot schedule. Activations
move stage-to-stage with ``jax.lax.ppermute`` — the JAX-native rendering of
the paper's producer/consumer stream decoupling, one level up the stack
(GALS islands -> pipeline stages, async FIFOs -> permute buffers).

The implementation processes the classic skewed schedule: at slot t, stage
s computes microbatch (t - s). We run S + M - 1 slots of compute on every
stage (idle slots compute on zeros — the pipeline bubble, visible in the
roofline as the (S-1)/(M+S-1) utilisation factor).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(
    layer_stack_fn: Callable,
    stage_params,
    x_microbatches: jnp.ndarray,
    *,
    mesh,
    axis: str = "stage",
):
    """Run microbatches through pipeline stages.

    layer_stack_fn(stage_params_slice, x) -> x : one stage's compute.
    stage_params: pytree with leading axis = n_stages (sharded over axis).
    x_microbatches: (M, mb, ...) microbatched input, replicated.
    Returns (M, mb, ...) outputs from the last stage.
    """
    n_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]
    n_slots = m + n_stages - 1

    def stage_prog(params_slice, xs):
        stage = jax.lax.axis_index(axis)
        params_local = jax.tree.map(lambda v: v[0], params_slice)
        buf = jnp.zeros_like(xs[0])  # incoming activation register
        outs = jnp.zeros_like(xs)

        def slot(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t; others take the permuted input
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(stage == 0, xs[mb_idx], buf)
            y = layer_stack_fn(params_local, x_in)
            # forward the result to the next stage
            buf_next = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)]
            )
            # last stage records its finished microbatch (t - (S-1))
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            valid = (t >= n_stages - 1) & (stage == n_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0
                ),
                lambda o: o,
                outs,
            )
            return (buf_next, outs), None

        (buf, outs), _ = jax.lax.scan(
            slot, (buf, outs), jnp.arange(n_slots)
        )
        # broadcast the last stage's outputs to every stage replica
        # (ppermute is a partial permutation; broadcast = masked psum)
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    fn = jax.shard_map(
        stage_prog,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_microbatches)
