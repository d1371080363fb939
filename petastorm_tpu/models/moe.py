"""Mixture-of-experts feed-forward layers: :class:`SwitchMoE` (top-1 with a
capacity, dense dispatch, the expert axis sharded over a mesh) and
:class:`RoutedMoE` (dropless top-k over the experts *held here* of a wider
published router, with a shared expert; below).

**Switch-style Mixture-of-Experts MLP with expert parallelism.**

The GShard/Switch formulation — the original TPU MoE design: top-1 routing
becomes dense one-hot dispatch/combine einsums (no gather/scatter, every op
a static-shaped matmul the MXU likes), and expert parallelism is nothing
but sharding the expert dimension of the dispatched activations and expert
weights over a mesh axis — XLA turns the dispatch einsums into all-to-alls
across that axis. Routing is computed **per group** (one group per batch
row), so with the batch sharded over 'data' every routing tensor shards
with it — no cross-data-shard cumsum (GShard's groups exist for exactly
this). Capacity is static (``capacity_factor``): overflow tokens drop
(their combine weight is zero; the surrounding residual carries them).

The standard Switch load-balance auxiliary loss is sown under
``intermediates/aux_loss`` — add it to the training loss (scaled ~1e-2) or
top-1 routing collapses onto few experts::

    logits, mods = model.apply(vars, x, mutable=['intermediates'])
    aux = sum(jax.tree_util.tree_leaves(mods['intermediates']))

Role: completes the parallelism families (dp/tp/sp/ep) for the model
stand-ins; ``expert_param_spec`` composes with
``models.train.create_train_state``.
"""

import collections
import functools
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from petastorm_tpu.ops.grouped_matmul import (TILE_M, aligned_layout,
                                              grouped_matmul, tile_groups)
from petastorm_tpu.trace import get_global_tracer


class SwitchMoE(nn.Module):
    """Top-1 routed expert MLP: ``[B, T, d] -> [B, T, d]``.

    :param num_experts: E. Shard over the mesh 'expert' axis via
        :func:`expert_param_spec` for expert parallelism.
    :param capacity_factor: per-expert slots per group =
        ``ceil(T/E * factor)``; overflow tokens pass through with a zero
        expert contribution (standard Switch behavior).
    :param expert_axis: optional mesh axis name to constrain the dispatched
        activations over (pure annotation — XLA places the all-to-alls).
    """

    num_experts: int
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    mesh: Any = None
    expert_axis: Optional[str] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        g, s, d = x.shape            # groups (batch rows) x tokens x features
        e = self.num_experts
        capacity = max(1, int(-(-s * self.capacity_factor // e)))

        # --- router (float32 for numerics, standard practice) -------------
        logits = nn.Dense(e, dtype=jnp.float32, name='router')(
            x.astype(jnp.float32))                          # [G, S, E]
        probs = jax.nn.softmax(logits, axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)             # [G, S]
        expert_prob = jnp.max(probs, axis=-1)
        expert_mask = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)

        # Switch load-balance loss: E * mean_e(frac_tokens_e * mean_prob_e),
        # minimized at uniform routing. Consumers pull it from
        # intermediates and add ~1e-2 * aux to the training loss.
        frac = expert_mask.mean(axis=(0, 1))                # [E]
        mean_prob = probs.mean(axis=(0, 1))                 # [E]
        self.sow('intermediates', 'aux_loss', e * jnp.sum(frac * mean_prob))

        # Slot within each (group, expert) capacity buffer — cumsum runs
        # over the group-local token axis only, so routing math shards with
        # the batch.
        position_in_expert = (jnp.cumsum(expert_mask, axis=1) - 1.0) * expert_mask
        in_capacity = position_in_expert < capacity
        expert_mask = expert_mask * in_capacity
        gate = expert_prob[..., None] * expert_mask         # [G, S, E]

        pos = jnp.sum(position_in_expert, axis=-1).astype(jnp.int32)  # [G, S]
        slot_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
        dispatch = expert_mask[..., None] * slot_onehot[:, :, None, :]  # [G,S,E,C]
        combine = gate[..., None] * slot_onehot[:, :, None, :]

        expert_in = jnp.einsum('gsec,gsd->egcd', dispatch,
                               x.astype(jnp.float32)).astype(self.dtype)
        if self.mesh is not None and self.expert_axis is not None:
            expert_in = jax.lax.with_sharding_constraint(
                expert_in,
                jax.sharding.NamedSharding(
                    self.mesh,
                    PartitionSpec(self.expert_axis, None, None, None)))

        # --- experts: one fused [E, ...] weight pair -----------------------
        # batch_axis=0: the expert dim is a batch of independent matrices,
        # NOT receptive field — plain lecun_normal on [E, d, h] would scale
        # by fan_in = E*d and under-initialize every expert by sqrt(E).
        expert_init = nn.initializers.variance_scaling(
            1.0, 'fan_in', 'truncated_normal', in_axis=-2, out_axis=-1,
            batch_axis=(0,))
        hidden = self.mlp_ratio * d
        w_up = self.param('w_up', expert_init,
                          (e, d, hidden), jnp.float32).astype(self.dtype)
        w_down = self.param('w_down', expert_init,
                            (e, hidden, d), jnp.float32).astype(self.dtype)
        h = jnp.einsum('egcd,edh->egch', expert_in, w_up)
        h = nn.gelu(h)
        expert_out = jnp.einsum('egch,ehd->egcd', h, w_down)

        out = jnp.einsum('gsec,egcd->gsd', combine,
                         expert_out.astype(jnp.float32))
        return out.astype(self.dtype)


def expert_param_spec(path, value, mesh):
    """Sharding rule: expert-stacked weights shard over 'expert'; composes
    with ``transformer_param_spec`` by falling back to it for non-MoE
    params."""
    from petastorm_tpu.models.train import transformer_param_spec
    if mesh is None or 'expert' not in mesh.axis_names:
        return transformer_param_spec(path, value, mesh)
    names = [str(getattr(p, 'key', getattr(p, 'name', ''))) for p in path]
    if names and names[-1] in ('w_up', 'w_down') \
            and value.shape[0] % mesh.shape['expert'] == 0:
        return PartitionSpec('expert', None, None)
    return transformer_param_spec(path, value, mesh)


# --------------------------------------------------------------------------
# dropless top-k routing over the experts held here
# --------------------------------------------------------------------------

def top_k_routing(scores, top_k, scale=1.0, normalise=True, n_group=1,
                  topk_group=1):
    """``scores [..., E]`` float32 (one a published expert) -> ``(experts
    [..., k] int32, weights [..., k] float32)``: the ``top_k`` scores, each
    weighted by its own score over the picked scores' sum (``normalise``)
    times ``scale``. With ``n_group`` > 1 the selection is limited by groups
    (DeepSeek-V3's ``noaux_tc``): the experts lie in ``n_group`` equal groups
    in their order, a group's score is the sum of its two best, and the top
    ``top_k`` are taken among the experts of the best ``topk_group`` groups."""
    allowed = scores
    if n_group > 1:
        grouped = scores.reshape(scores.shape[:-1] + (n_group, -1))
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, groups = jax.lax.top_k(group_score, topk_group)
        kept = jnp.any(groups[..., None] == jnp.arange(n_group), axis=-2)
        allowed = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
            scores.shape)
    _, experts = jax.lax.top_k(allowed, top_k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    if normalise:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return experts, picked * scale


Dispatch = collections.namedtuple('Dispatch', [
    'group_sizes',  # [G] rows of each held expert's group, whole tiles
    'counts',       # [G] pairs routed to each held expert
    'dest',         # [N, k] the row of each pair (0 where its expert is absent)
    'is_held',      # [N, k] whether the pair's expert is held here
    'row_token',    # [rows] the token each row holds (0 for a padding row)
    'row_pair',     # [rows] the pair each row holds, as n * k + slot
    'row_valid'])   # [rows] whether the row holds a pair at all


def dispatch_plan(experts, held, experts_published, tile_m):
    """Where every (token, expert) pair goes: pairs sorted by the expert held
    (in ``held``'s order, a token's pairs in its own order), each group
    starting on a tile of ``tile_m`` rows and at least one tile long
    (:func:`petastorm_tpu.ops.grouped_matmul.aligned_layout`); the pairs of
    absent experts have no row. ``experts [N, k]`` int32 ids over the
    published experts. Static shapes: ``rows = N * k + len(held) * tile_m``
    holds every pair there could be, so nothing is dropped; the group sizes
    are data."""
    n, k = experts.shape
    g = len(held)
    local = np.full((experts_published,), g, np.int32)
    local[np.asarray(held)] = np.arange(g, dtype=np.int32)
    key = jnp.asarray(local)[experts].reshape(-1)                   # [P]
    pairs = n * k
    onehot = key[:, None] == jnp.arange(g, dtype=jnp.int32)[None]   # [P, G]
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    rank = jnp.sum(jnp.where(onehot, jnp.cumsum(onehot, axis=0,
                                                dtype=jnp.int32) - 1, 0),
                   axis=-1)
    sizes, starts = aligned_layout(counts, tile_m)
    is_held = key < g
    dest = jnp.where(is_held, starts[jnp.minimum(key, g - 1)] + rank, 0)
    # The other way: which pair a row holds. Sorted by group, a group's
    # pairs lie from ``first[g]`` on in the order ``rank`` counts them.
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    first = jnp.cumsum(counts) - counts
    rows = pairs + g * tile_m
    row = jnp.arange(rows, dtype=jnp.int32)
    group, used = tile_groups(sizes, tile_m, rows // tile_m)
    mine = group[row // tile_m]
    within = row - starts[mine]
    valid = (within < counts[mine]) & (row // tile_m < used[0])
    pair = order[jnp.clip(first[mine] + within, 0, pairs - 1)]
    return Dispatch(sizes, counts, dest.reshape(n, k), is_held.reshape(n, k),
                    jnp.where(valid, pair // k, 0), pair, valid)


# Both directions of dispatch and combine are gathers: a token's pairs know
# their rows and a row knows its token, so neither transpose is a scatter.

@jax.custom_vjp
def _to_rows(x, row_token, dest, is_held):
    """``x [N, d]`` -> ``[rows, d]``: each row its token's vector."""
    return x[row_token]


def _to_rows_fwd(x, row_token, dest, is_held):
    return x[row_token], (dest, is_held)


def _to_rows_bwd(residuals, g):
    dest, is_held = residuals
    picked = jnp.where(is_held[..., None], g[dest].astype(jnp.float32), 0.0)
    return jnp.sum(picked, axis=1).astype(g.dtype), None, None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


def _weighted(y, weights, dest, is_held):
    picked = y[dest].astype(jnp.float32)                            # [N, k, d]
    return picked, jnp.where(is_held, weights, 0.0)


@jax.custom_vjp
def _from_rows(y, weights, dest, is_held, row_token, row_weight):
    """``y [rows, d]`` -> ``[N, d]``: each token the weighted sum of its
    held pairs' rows."""
    picked, w = _weighted(y, weights, dest, is_held)
    return jnp.sum(picked * w[..., None], axis=1).astype(y.dtype)


def _from_rows_fwd(y, weights, dest, is_held, row_token, row_weight):
    return (_from_rows(y, weights, dest, is_held, row_token, row_weight),
            (y, weights, dest, is_held, row_token, row_weight))


def _from_rows_bwd(residuals, g):
    y, weights, dest, is_held, row_token, row_weight = residuals
    dy = (g[row_token].astype(jnp.float32)
          * row_weight[:, None]).astype(y.dtype)
    picked, _ = _weighted(y, weights, dest, is_held)
    dw = jnp.sum(picked * g.astype(jnp.float32)[:, None, :], axis=-1)
    return (dy, jnp.where(is_held, dw, 0.0).astype(weights.dtype), None,
            None, None, None)


_from_rows.defvjp(_from_rows_fwd, _from_rows_bwd)


def routed_experts(x, experts, weights, w_gate_up, w_down, held,
                   experts_published, tile_m=TILE_M, impl='pallas'):
    """The held experts' part of a routed layer. ``x [N, d]``, ``experts,
    weights [N, k]`` (:func:`top_k_routing`), ``w_gate_up [G, d, 2 f]`` (an
    expert's gate columns, then its up columns), ``w_down [G, f, d]``.
    Returns ``([N, d], counts [G])``: ``sum over a token's held pairs of
    weight * expert(x)``, every expert ``down(silu(gate x) * up x)``, and the
    pairs each held expert was sent. Nothing is dropped whatever the routing
    (:func:`dispatch_plan`)."""
    plan = dispatch_plan(experts, held, experts_published, tile_m)
    f = w_down.shape[1]
    rows = _to_rows(x, plan.row_token, plan.dest, plan.is_held)
    hidden = grouped_matmul(rows, w_gate_up, plan.group_sizes, tile_m, impl)
    hidden = nn.silu(hidden[:, :f]) * hidden[:, f:]
    y = grouped_matmul(hidden, w_down, plan.group_sizes, tile_m, impl)
    row_weight = jnp.where(plan.row_valid,
                           weights.reshape(-1)[plan.row_pair], 0.0)
    out = _from_rows(y, weights, plan.dest, plan.is_held, plan.row_token,
                     row_weight)
    return out, plan.counts


class RoutedMoE(nn.Module):
    """Dropless top-k routed experts with a shared expert, told which
    experts it holds: ``[B, T, d] -> ([B, T, d], expert_load [G])``.

    The router is ``experts_published`` wide whatever is held: ``s =
    sigmoid(W_r x)`` in float32, the ``top_k`` experts of each token (among
    the best ``topk_group`` of ``n_group`` groups where there are groups:
    :func:`top_k_routing`), their scores normalised over the picked and times
    ``scale``. ``held`` lists the published experts that live here (a chip
    of an expert-parallel group);
    the layer computes ``shared(x) + sum over a token's picked experts that
    are held of weight * expert(x)`` and nothing for the absent ones: the
    partial result of the chip before the group's exchange, with the shared
    expert, which every chip computes alike, counted here. **No capacity and
    no drop**: the held experts' products run over row groups of
    data-dependent size (:mod:`petastorm_tpu.ops.grouped_matmul`; ``impl``
    ``'pallas'``, ``'pallas:interpret'`` or ``'ragged_dot'``), in an array
    that holds every pair there could be. ``expert_load`` is how many pairs
    each held expert was sent.

    A device trace names the layer's Pallas calls by this module's name, so
    name it ``moe``. With ``mesh`` the routed part is mapped over the
    batch's shards (a Pallas call is opaque to the SPMD partitioner); every
    shard routes its own rows.
    """

    experts_published: int
    held: Sequence[int]
    top_k: int = 4
    scale: float = 1.0
    d_ff: int = 1024                    # an expert's width
    shared_d_ff: int = 0                # the shared expert's; 0: none
    normalise: bool = True
    n_group: int = 1                    # groups the selection is limited by
    topk_group: int = 1
    impl: str = 'pallas'
    tile_m: int = TILE_M
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        from petastorm_tpu.models.hybrid import SwiGLU
        from petastorm_tpu.models.transformer import usable_axis

        b, t, d = x.shape
        g = len(self.held)
        x = x.astype(self.dtype)
        scores = nn.sigmoid(nn.Dense(
            self.experts_published, use_bias=False, dtype=jnp.float32,
            precision=jax.lax.Precision.HIGHEST, name='router')(
                x.astype(jnp.float32)))
        experts, weights = top_k_routing(scores, self.top_k, self.scale,
                                         self.normalise, self.n_group,
                                         self.topk_group)
        init = nn.initializers.normal(0.02)
        w_gate_up = self.param('experts_gate_up', init,
                               (g, d, 2 * self.d_ff)).astype(self.dtype)
        w_down = self.param('experts_down', init,
                            (g, self.d_ff, d)).astype(self.dtype)

        def routed(x, experts, weights, w_gate_up, w_down, axis=None):
            if axis is not None and self.impl == 'pallas':
                # Where shard_map checks how values vary (not under the
                # interpreter), the kernels' custom_vjp hands back a
                # gradient as varying as the rows it was made from: weights
                # that vary like them get it summed over the shards by the
                # cast's transpose.
                w_gate_up, w_down = (jax.lax.pcast(w, (axis,), to='varying')
                                     for w in (w_gate_up, w_down))
            rows = x.shape[0] * x.shape[1]
            y, counts = routed_experts(
                x.reshape(rows, d), experts.reshape(rows, self.top_k),
                weights.reshape(rows, self.top_k), w_gate_up, w_down,
                tuple(self.held), self.experts_published, self.tile_m,
                self.impl)
            return y.reshape(x.shape), counts[None]

        if self.mesh is not None and self.impl.startswith('pallas'):
            axis = usable_axis(self.mesh, self.batch_axis, b)
            rows, whole = PartitionSpec(axis, None, None), PartitionSpec()
            routed = jax.shard_map(
                functools.partial(routed, axis=axis), mesh=self.mesh,
                in_specs=(rows, rows, rows, whole, whole),
                out_specs=(rows, PartitionSpec(axis, None)),
                check_vma=self.impl == 'pallas')
        y, counts = routed(x, experts, weights, w_gate_up, w_down)
        if self.shared_d_ff:
            y = y + SwiGLU(self.shared_d_ff, dtype=self.dtype,
                           name='shared')(x)
        return y, jnp.sum(counts, axis=0)


# A loop that dispatches step k and then awaits step k - 1 (one step kept in
# flight, as the loader keeps ``inflight`` 2 transfers) has step k - 2 behind
# it when step k's metrics are handed over.
LOAD_LAG = 2


class ExpertLoadCounter(object):
    """Running totals of a step's ``expert_load`` on the global tracer's
    ring, as counters ``moe.expert_load.e<slot>`` (one a held expert): what a
    reader takes the difference of at a window's two ends. ``add`` is handed
    every step's ``metrics`` and reads the load of the step ``LOAD_LAG`` calls
    back, so it never waits for the device."""

    def __init__(self):
        self._pending, self._total = collections.deque(), None

    def add(self, metrics):
        self._pending.append(metrics['expert_load'])
        if len(self._pending) <= LOAD_LAG:
            return
        load = np.asarray(self._pending.popleft()).astype(np.int64)
        self._total = load if self._total is None else self._total + load
        tracer = get_global_tracer()
        for slot, value in enumerate(self._total.tolist()):
            tracer.counter('moe.expert_load.e{}'.format(slot), value,
                           cat='step')
