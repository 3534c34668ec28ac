"""GPipe-style pipeline parallelism over a mesh axis, via shard_map.

Each device along the ``stage`` axis holds one contiguous slice of the layer
stack; microbatches stream through with ``collective_permute`` moving
activations stage→stage. The schedule is the classic GPipe fill/steady/drain
with ``n_micro + n_stages - 1`` ticks.

The production dry-run meshes use DP×TP (the assigned topology); this module
provides the PP primitive for deployments that want depth-wise scaling —
tested on small meshes in tests/test_pipeline.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline(block_fn: Callable, n_stages: int, n_micro: int,
             axis: str = "stage"):
    """Build a pipelined forward: f(stage_params, x_micro) -> y_micro.

    block_fn(params_slice, x) -> y applies this stage's layers.
    stage_params: pytree with leading dim = n_stages (sharded over `axis`).
    x_micro: (n_micro, mb, ...) microbatched input (replicated).
    Returns (n_micro, mb, ...) output of the LAST stage.
    """

    def staged(params_local, x_micro):
        # params_local: (1, ...) this stage's slice; x_micro replicated
        stage = jax.lax.axis_index(axis)
        params_me = jax.tree_util.tree_map(lambda t: t[0], params_local)
        mb_shape = x_micro.shape[1:]
        state = jnp.zeros(mb_shape, x_micro.dtype)     # current activation
        outputs = jnp.zeros_like(x_micro)              # collected at last stage

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (when in range)
            inject = jnp.clip(t, 0, n_micro - 1)
            x_in = x_micro[inject]
            state = jnp.where(stage == 0,
                              jnp.where(t < n_micro, x_in, state), state)
            # every stage processes its current activation
            y = block_fn(params_me, state)
            # last stage's result for microbatch (t - n_stages + 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            take = (t >= n_stages - 1) & (stage == n_stages - 1)
            outputs = jnp.where(
                take,
                jax.lax.dynamic_update_index_in_dim(
                    outputs, y, out_idx, 0),
                outputs)
            # shift activations stage i -> i+1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(n_micro + n_stages - 1))
        # the last stage holds the real outputs; broadcast to all stages
        outputs = jax.lax.ppermute(
            outputs, axis,
            [( (n_stages - 1 + i) % n_stages, i) for i in range(n_stages)])
        return outputs

    return staged


def run_pipeline(mesh: Mesh, block_fn: Callable, stage_params, x,
                 n_micro: int, axis: str = "stage"):
    """Convenience wrapper: shard params over `axis`, microbatch x, run."""
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    x_micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])

    staged = pipeline(block_fn, n_stages, n_micro, axis)
    fn = jax.shard_map(
        staged, mesh=mesh,
        in_specs=(P(axis), P()),            # params sharded, x replicated
        out_specs=P(), check_vma=False)
    y_micro = fn(stage_params, x_micro)
    return y_micro.reshape(b, *x.shape[1:])
