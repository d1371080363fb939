"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

The last of the parallelism families (dp/tp/sp/ep/pp). Each device along
the 'pipe' mesh axis owns ONE stage's parameters (a pytree with a leading
``[n_stages, ...]`` dim, sharded over the axis); microbatches stream
through the stages, activations hopping stage-to-stage with
``jax.lax.ppermute`` — one ICI hop per tick, the TPU ring's sweet spot.
The schedule is the standard pipeline trapezoid: ``n_micro + n_stages - 1``
ticks, with bubble fraction ``(S-1)/(M+S-1)``; everything is a static
``lax.scan`` over ticks (compiler-friendly control flow, no per-tick
dispatch).

Differentiable end to end: the whole schedule is traced jax code, so
``jax.grad`` backpropagates through the ppermute hops (reverse hops become
the backward pipeline automatically).

Role parity: the pipeline-parallel engines of GPU training stacks
(1F1B/GPipe schedulers in CUDA frameworks) — rebuilt as a pure XLA program.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


def _stage_index(axis_name):
    return jax.lax.axis_index(axis_name)


def pipeline_apply(stage_fn, stage_params, x, mesh, pipe_axis='pipe',
                   microbatches=None):
    """Run ``x`` through ``n_stages`` sequential stages, pipelined.

    :param stage_fn: ``(params_slice, activation) -> activation`` — one
        stage's computation. Activation shape must be stage-invariant.
    :param stage_params: pytree whose leaves have a leading ``[n_stages]``
        dim (stage i's params at index i). Shard leaves over ``pipe_axis``
        (e.g. with :func:`pipeline_param_spec`).
    :param x: ``[batch, ...]`` global input; ``batch`` must divide into
        ``microbatches`` equal microbatches.
    :param microbatches: number of microbatches (default: n_stages).
    :returns: ``[batch, ...]`` output of the final stage.
    """
    n_stages = mesh.shape[pipe_axis]
    if microbatches is None:
        microbatches = n_stages
    batch = x.shape[0]
    if batch % microbatches:
        raise ValueError('batch {} not divisible into {} microbatches'
                         .format(batch, microbatches))
    micro = batch // microbatches

    # [M, micro, ...] stream of microbatches, replicated across the pipe
    # axis (each stage picks out the tick it needs).
    xs = x.reshape((microbatches, micro) + x.shape[1:])

    # Per-leaf placement via pipeline_param_spec: stage-stacked leaves shard
    # over the pipe axis; anything it declines (rank-0 scalars, leading dims
    # the pipe size doesn't divide) replicates to every stage instead of
    # crashing or silently mis-slicing.
    params_spec = jax.tree_util.tree_map(
        lambda p: pipeline_param_spec((), p, mesh), stage_params)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(params_spec, PartitionSpec()),
             out_specs=PartitionSpec(pipe_axis),
             check_vma=False)
    def run(local_params, xs):
        # Sharded leaves arrive as [1, ...] (this device's stage slice);
        # replicated leaves arrive whole.
        leaves, treedef = jax.tree_util.tree_flatten(local_params)
        specs = jax.tree_util.tree_leaves(
            params_spec, is_leaf=lambda s: isinstance(s, PartitionSpec))
        my_params = jax.tree_util.tree_unflatten(
            treedef, [p[0] if spec else p for p, spec in zip(leaves, specs)])
        stage = _stage_index(pipe_axis)
        n_ticks = microbatches + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            acc, buf = carry
            # Stage 0 injects microbatch t (or garbage past the end, which
            # never reaches the output accumulator); others take the
            # ppermuted activation from the previous stage.
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, microbatches - 1), keepdims=False)
            state_in = jnp.where(stage == 0, inject, buf)
            out = stage_fn(my_params, state_in)
            # The final stage's result for microbatch m pops out at tick
            # m + n_stages - 1; collect it into the accumulator.
            m = t - (n_stages - 1)
            take = (stage == n_stages - 1) & (m >= 0)
            acc = jax.lax.cond(
                take,
                lambda a: jax.lax.dynamic_update_index_in_dim(
                    a, out, jnp.maximum(m, 0), axis=0),
                lambda a: a, acc)
            buf = jax.lax.ppermute(out, pipe_axis, fwd_perm)
            return (acc, buf), None

        acc0 = jnp.zeros_like(xs)
        buf0 = jnp.zeros_like(xs[0])
        (acc, _), _ = jax.lax.scan(tick, (acc0, buf0),
                                   jnp.arange(n_ticks))
        # Only the last stage holds real outputs. Each stage returns its
        # accumulator under a leading [1] pipe-sharded dim — no collective;
        # the caller slices the final stage's shard.
        return acc[None]

    out = run(stage_params, xs)[-1]                  # last stage's shard
    return out.reshape((batch,) + out.shape[2:])


def pipeline_param_spec(path, value, mesh):
    """Sharding rule for stage-stacked parameter pytrees: leading dim over
    'pipe'; composes with create_train_state(param_spec_fn=...)."""
    del path
    if mesh is None or 'pipe' not in mesh.axis_names:
        return PartitionSpec()
    if value.ndim >= 1 and value.shape[0] % mesh.shape['pipe'] == 0:
        return PartitionSpec('pipe')
    return PartitionSpec()
