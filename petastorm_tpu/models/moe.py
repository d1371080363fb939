"""Mixture-of-experts feed-forward layers: :class:`SwitchMoE` (top-1 with a
capacity, dense dispatch, the expert axis sharded over a mesh) and
:class:`RoutedMoE` (dropless top-k over the experts *held here* of a wider
published router, with a shared expert; below: its rows are laid out for
four times the pairs the held share expects, and a step that is sent more,
which its count says, applies the held experts to every token instead).

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
                                              grouped_matmul,
                                              grouped_matmul_grads,
                                              tile_groups, token_sums)
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
    with jax.named_scope('routing'):
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
    'row_token',    # [rows] the token each row holds (0 for a padding row)
    'row_pair',     # [rows] the pair each row holds, as n * k + slot
    'row_valid'])   # [rows] whether the row holds a pair at all


#: The rows are laid out for this many times the pairs the held experts get
#: of an even routing. Over whole benchmark runs (PERF.md section 6, PR 36)
#: an eighth of Xing4.0's experts never passed it in any layer, and a
#: sixty-fourth of Ling-3.0's passed it in one layer of some steps, until its
#: routers collapse onto the held experts and every layer passes it for good:
#: what passes four times the share is the collapse or next to it, and
#: :func:`held_experts_on_every_token` takes that at the matrix unit's rate.
CAPACITY_OVER_SHARE = 4


def pairs_capacity(pairs, held, experts_published, tile_m):
    """The held pairs the layout has rows for: ``CAPACITY_OVER_SHARE`` times
    the ``held`` experts' share of ``pairs``, in whole tiles, and never more
    than ``pairs`` (a chip that holds a quarter of the experts or more lays
    out every pair there could be)."""
    share = -(-CAPACITY_OVER_SHARE * pairs * held // experts_published)
    return min(pairs, -(-share // tile_m) * tile_m)


def sorted_pairs(experts, held):
    """``(counts [G], order [N k])``: the pairs each held expert was sent, and
    the pairs sorted by the expert held (in ``held``'s order, a token's pairs
    in their own order, the pairs of absent experts behind every held one)."""
    g, pairs = len(held), experts.size
    # [G, P], the pairs along the lanes. An absent expert's pair matches no
    # row of it and takes the key g.
    match = jnp.asarray(held, jnp.int32)[:, None] == experts.reshape(1, pairs)
    key = jnp.min(jnp.where(match, jnp.arange(g, dtype=jnp.int32)[:, None], g),
                  axis=0)
    _, order = jax.lax.sort((key, jnp.arange(pairs, dtype=jnp.int32)),
                            num_keys=1, is_stable=True)
    return jnp.sum(match, axis=1, dtype=jnp.int32), order


def layout(counts, order, k, tile_m, capacity):
    """Where every (token, expert) pair goes, from :func:`sorted_pairs`: the
    rows of ``capacity`` pairs and a tile a group, each group starting on a
    tile of ``tile_m`` rows and at least one tile long
    (:func:`petastorm_tpu.ops.grouped_matmul.aligned_layout`); the pairs of
    absent experts have no row. Static shapes: every table is ``rows =
    capacity + len(counts) * tile_m`` long, which holds any held pairs that
    number ``capacity`` or fewer. More of them (``sum(counts)`` says so) and
    the tables place only the first ``capacity``: the caller has to compute
    those steps another way. The group sizes are data."""
    g = counts.shape[0]
    sizes, starts = aligned_layout(counts, tile_m)
    first = jnp.cumsum(counts) - counts
    # A tile lies in one group, so its rows count on from where the group's
    # pairs begin among the sorted ones.
    tiles = capacity // tile_m + g
    group, used = tile_groups(sizes, tile_m, tiles)
    tile = jnp.arange(tiles, dtype=jnp.int32)
    within = ((tile * tile_m - starts[group])[:, None]
              + jnp.arange(tile_m, dtype=jnp.int32)[None])
    valid = ((within < counts[group][:, None])
             & (tile < used[0])[:, None]).reshape(-1)
    at = (first[group][:, None] + within).reshape(-1)
    pair = order[:capacity][jnp.clip(at, 0, capacity - 1)]
    return Dispatch(sizes, counts, jnp.where(valid, pair // k, 0), pair, valid)


# --------------------------------------------------------------------------
# the routed part: over the rows of a layout, forward and backward by hand,
# and over every token where the held pairs pass the rows
# --------------------------------------------------------------------------

def _swiglu(hidden):
    f = hidden.shape[-1] // 2
    return nn.silu(hidden[..., :f]) * hidden[..., f:]


def _relu2(hidden):
    return jnp.square(nn.relu(hidden))


#: An expert's activation by name, the one place the rows, their backward
#: pass and the dense form look it up: ``'swiglu'``, ``silu(gate) * up`` over
#: a ``[.., 2 f]`` hidden (gate columns first); ``'relu2'``, ``relu(h)^2``
#: over a ``[.., f]`` one, no gate.
ACTIVATIONS = {'swiglu': _swiglu, 'relu2': _relu2}


def expert_activation(name):
    if name not in ACTIVATIONS:
        raise ValueError('unknown expert activation {!r}: one of {}'.format(
            name, sorted(ACTIVATIONS)))
    return ACTIVATIONS[name]


def _row_weight(weights, plan):
    return jnp.where(plan.row_valid, weights.reshape(-1)[plan.row_pair], 0.0)


def _sum_rows(values, weights, plan, n, tile_m, impl):
    """``values [rows, d]`` -> ``[N, d]``: a token the sum of the rows that
    hold a pair of it, each under its weight. Dispatch is a gather of the
    tokens by the rows' ``row_token``; this is its transpose, and nothing
    ``N * k`` long with a trailing ``d`` exists on either side."""
    return token_sums(values, weights,
                      jnp.where(plan.row_valid, plan.row_token, n), n, tile_m,
                      impl)


def _rows_forward(x, weights, w_gate_up, w_down, plan, tile_m, impl,
                  activation='swiglu'):
    """``(out [N, d], kept)``: every row's expert applied to its token, and a
    token the sum of its rows under their weights, float32 inside. ``kept``
    is what :func:`_rows_backward` reads again."""
    with jax.named_scope('gather'):
        rows = x[plan.row_token]
    hidden = grouped_matmul(rows, w_gate_up, plan.group_sizes, tile_m, impl)
    y = grouped_matmul(expert_activation(activation)(hidden), w_down,
                       plan.group_sizes, tile_m, impl)
    out = _sum_rows(y, _row_weight(weights, plan), plan, x.shape[0], tile_m,
                    impl)
    return out, (rows, hidden, y)


def _rows_backward(g, weights, w_gate_up, w_down, plan, kept, tile_m, impl,
                   activation='swiglu'):
    """The cotangents of ``x, weights, w_gate_up, w_down`` from ``g [N, d]``:
    :func:`_rows_forward` gone back through piece by piece."""
    rows, hidden, y = kept
    with jax.named_scope('gather'):
        g_rows = g[plan.row_token].astype(jnp.float32)
    dy = (g_rows * _row_weight(weights, plan)[:, None]).astype(y.dtype)
    # <y[r], g[token of r]> a row, then a scatter of ``rows`` scalars.
    d_weights = jnp.zeros((weights.size,), jnp.float32).at[
        jnp.where(plan.row_valid, plan.row_pair, weights.size)].set(
            jnp.sum(y.astype(jnp.float32) * g_rows, axis=-1),
            mode='drop').reshape(weights.shape)
    activated, activation_back = jax.vjp(expert_activation(activation),
                                         hidden)
    d_activated, d_w_down = grouped_matmul_grads(
        activated, w_down, plan.group_sizes, dy, tile_m, impl)
    d_rows, d_w_gate_up = grouped_matmul_grads(
        rows, w_gate_up, plan.group_sizes, activation_back(d_activated)[0],
        tile_m, impl)
    dx = _sum_rows(d_rows, plan.row_valid.astype(jnp.float32), plan,
                   g.shape[0], tile_m, impl)
    return dx, d_weights.astype(weights.dtype), d_w_gate_up, d_w_down


def held_experts_on_every_token(x, experts, weights, w_gate_up, w_down, held,
                                activation='swiglu'):
    """The same ``[N, d]`` with no rows at all: every held expert applied to
    every token, a token's sum taken under the weight of its pair that picked
    the expert and under 0 where none did. Two dense products (``[N, d]`` by
    ``[d, G 2f]``, ``[N, G f]`` by ``[G f, d]``, the second summing a token's
    pairs in float32 as it goes; a ``[G f]`` hidden where the activation has no
    gate), which cost what ``N G`` pairs cost however
    many are held: what :func:`routed_experts` runs where the held pairs pass
    its rows, at most ``experts_published / (4 k)`` times the arithmetic the
    pairs asked for, on nothing but the matrix unit."""
    with jax.named_scope('every_token'):
        picked = experts[:, :, None] == jnp.asarray(held, jnp.int32)
        weight = jnp.sum(jnp.where(picked, weights[:, :, None], 0.0), axis=1)
        hidden = jnp.einsum('nd,gdf->ngf', x, w_gate_up.astype(x.dtype))
        weighted = expert_activation(activation)(hidden).astype(
            jnp.float32) * weight[:, :, None]
        return jnp.einsum('ngf,gfd->nd', weighted.astype(x.dtype),
                          w_down.astype(x.dtype),
                          preferred_element_type=jnp.float32).astype(x.dtype)


def _varying_like(value, like):
    """``value`` varying over the mesh axes ``like`` varies over: inside
    ``jax.shard_map`` the branches of a ``cond`` have to agree on that."""
    axes = tuple(jax.typeof(like).vma)
    return jax.lax.pcast(value, axes, to='varying') if axes else value


# The choice between the rows and every token sits outside differentiation: a
# ``cond`` that ``jax.grad`` goes through hands back both branches' residuals,
# the untaken branch's as zeros, written in every forward pass. Here the
# forward's and the backward's ``cond`` each choose by the same flag and the
# residuals have the rows' shapes in both branches: the dense branch keeps
# nothing and forms its first product again going back. A branch is a scope
# of its own, and a device trace names a Pallas call by the innermost scope:
# ``name`` is entered again inside. Both directions are ``jax.jit(inline=True)``
# (PERF.md section 6, PR 27): a model's layers call them with the same shapes,
# so they are traced once a shape, and the step's program holds no call that
# is not its own (jitted apart, ``ling3.tokens8k``'s step never came back from
# the persistent cache: PERF.md section 6, PR 36).

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _routed(x, experts, weights, w_gate_up, w_down, static):
    return _routed_fwd(x, experts, weights, w_gate_up, w_down, static)[0]


@functools.partial(jax.jit, static_argnums=(5,), inline=True)
def _routed_fwd(x, experts, weights, w_gate_up, w_down, static):
    held, capacity, tile_m, impl, name, activation = static
    with jax.named_scope('dispatch'):
        counts, order = sorted_pairs(experts, held)
        plan = layout(counts, order, experts.shape[1], tile_m, capacity)
    operands = (x, weights, w_gate_up, w_down)

    def rows(*operands):
        with jax.named_scope(name):
            return _rows_forward(*operands, plan, tile_m, impl, activation)

    def every_token(x, weights, w_gate_up, w_down):
        out = held_experts_on_every_token(x, experts, weights, w_gate_up,
                                          w_down, held, activation)
        return out, tuple(
            _varying_like(jnp.zeros((plan.row_token.shape[0], width), x.dtype),
                          x)
            for width in (x.shape[1], w_gate_up.shape[2], x.shape[1]))

    fits = jnp.sum(counts) <= capacity
    if capacity == experts.size:        # rows for every pair: no choice
        out, kept = rows(*operands)
    else:
        out, kept = jax.lax.cond(fits, rows, every_token, *operands)
    return ((out, counts, 1 - fits.astype(jnp.int32)),
            (experts, fits, operands, plan, kept))


def _routed_bwd(static, residuals, cotangents):
    return _routed_back(static, residuals, cotangents[0])   # the ints' is none


@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _routed_back(static, residuals, g):
    held, capacity, tile_m, impl, name, activation = static
    experts, fits, (x, weights, w_gate_up, w_down), plan, kept = residuals

    def rows(g, x, weights, w_gate_up, w_down, kept):
        with jax.named_scope(name):
            return _rows_backward(g, weights, w_gate_up, w_down, plan, kept,
                                  tile_m, impl, activation)

    def every_token(g, x, weights, w_gate_up, w_down, kept):
        _, back = jax.vjp(
            lambda x, weights, w_gate_up, w_down: held_experts_on_every_token(
                x, experts, weights, w_gate_up, w_down, held, activation),
            x, weights, w_gate_up, w_down)
        return back(g)

    operands = (g, x, weights, w_gate_up, w_down, kept)
    if capacity == weights.size:
        dx, d_weights, d_w_gate_up, d_w_down = rows(*operands)
    else:
        dx, d_weights, d_w_gate_up, d_w_down = jax.lax.cond(
            fits, rows, every_token, *operands)
    return dx, None, d_weights, d_w_gate_up, d_w_down


_routed.defvjp(_routed_fwd, _routed_bwd)


def _routed_static(pairs, held, experts_published, tile_m, impl, name,
                   activation):
    return (tuple(held), pairs_capacity(pairs, len(held), experts_published,
                                        tile_m), tile_m, impl, name,
            activation)


def routed_experts(x, experts, weights, w_gate_up, w_down, held,
                   experts_published, tile_m=TILE_M, impl='pallas',
                   name='moe', activation='swiglu'):
    """The held experts' part of a routed layer. ``x [N, d]``, ``experts,
    weights [N, k]`` (:func:`top_k_routing`), ``w_gate_up [G, d, 2 f]`` (an
    expert's gate columns, then its up columns), ``w_down [G, f, d]``.
    Returns ``([N, d], counts [G])``: ``sum over a token's held pairs of
    weight * expert(x)``, every expert ``down(silu(gate x) * up x)`` (with
    ``activation`` ``'relu2'``, ``w_gate_up [G, d, f]`` and ``down(relu(up
    x)^2)``), and the pairs each held expert was sent. The rows are laid out for
    :func:`pairs_capacity` held pairs (:func:`layout`) and the experts'
    products run over them group by group; a step whose routing sends more
    (``sum(counts)`` says so) applies the held experts to every token instead
    (:func:`held_experts_on_every_token`), chosen by ``jax.lax.cond`` on that
    count, so nothing is dropped whatever the routing and both ways compute
    the same function. Differentiable in ``x``, ``weights`` and both expert
    leaves, each direction holding its own ``cond``. ``name`` is the scope a
    device trace names the Pallas calls by: the module's."""
    return _routed(x, experts, weights, w_gate_up, w_down, _routed_static(
        experts.size, held, experts_published, tile_m, impl, name,
        activation))[:2]


class RoutedMoE(nn.Module):
    """Dropless top-k routed experts with a shared expert, told which
    experts it holds: ``[B, T, d] -> ([B, T, d], load)``, ``load`` a dict of
    ``expert_load [G]`` and ``layout_fallbacks []``, both int32.

    The router is ``experts_published`` wide whatever is held: ``s =
    sigmoid(W_r x)`` in float32, the ``top_k`` experts of each token (among
    the best ``topk_group`` of ``n_group`` groups where there are groups:
    :func:`top_k_routing`), their scores normalised over the picked and times
    ``scale``. ``activation`` names every expert's (:data:`ACTIVATIONS`):
    ``'swiglu'``'s leaves are ``experts_gate_up [G, d, 2 f]`` and the shared
    expert a SwiGLU, ``'relu2'``'s ``experts_up [G, d, f]`` and a
    :class:`ReluSquaredMLP`. With ``latent`` the routed experts work in a
    space of that width: ``latent_down`` takes a token there before its rows
    are gathered, ``latent_up`` brings the sum of its rows back, and the
    router and the shared expert stay on the hidden state. ``held`` lists
    the published experts that live here (a chip of an expert-parallel
    group);
    the layer computes ``shared(x) + sum over a token's picked experts that
    are held of weight * expert(x)`` and nothing for the absent ones: the
    partial result of the chip before the group's exchange, with the shared
    expert, which every chip computes alike, counted here. **A capacity that
    never drops**: the held experts' products run over row groups of
    data-dependent size (:mod:`petastorm_tpu.ops.grouped_matmul`; ``impl``
    ``'pallas'``, ``'pallas:interpret'`` or ``'ragged_dot'``), in an array
    laid out for :data:`CAPACITY_OVER_SHARE` times the pairs the held experts
    get of an even routing (:func:`pairs_capacity`: the held share and the
    tokens say it, nothing sets it); a step that sends them more applies the
    held experts to every token, two dense products and no rows
    (:func:`routed_experts`). ``expert_load`` is how many pairs each held
    expert was sent, ``layout_fallbacks`` how many shards of the batch went
    that second way (0 or 1 without a mesh).

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
    activation: str = 'swiglu'          # 'swiglu' | 'relu2' (ACTIVATIONS)
    latent: int = 0                     # the experts' width in and out; 0: d
    impl: str = 'pallas'
    tile_m: int = TILE_M
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        from petastorm_tpu.models.hybrid import SwiGLU, _projection
        from petastorm_tpu.models.transformer import usable_axis

        b, t, d_model = x.shape
        g = len(self.held)
        x = x.astype(self.dtype)
        scores = nn.sigmoid(nn.Dense(
            self.experts_published, use_bias=False, dtype=jnp.float32,
            precision=jax.lax.Precision.HIGHEST, name='router')(
                x.astype(jnp.float32)))
        experts, weights = top_k_routing(scores, self.top_k, self.scale,
                                         self.normalise, self.n_group,
                                         self.topk_group)
        # The router reads the hidden state; the routed experts live in the
        # latent space, entered before the rows are gathered and left after
        # a token's rows are summed.
        hidden = x
        if self.latent:
            hidden = _projection(x, self.latent, 'latent_down', self.dtype)
        d = hidden.shape[-1]
        init = nn.initializers.normal(0.02)
        gated = self.activation == 'swiglu'
        w_gate_up = self.param('experts_gate_up' if gated else 'experts_up',
                               init, (g, d, (2 if gated else 1) * self.d_ff)
                               ).astype(self.dtype)
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
            # :func:`routed_experts`, and the flag its ``cond`` chose by.
            y, counts, fell_back = _routed(
                x.reshape(rows, d), experts.reshape(rows, self.top_k),
                weights.reshape(rows, self.top_k), w_gate_up, w_down,
                _routed_static(rows * self.top_k, self.held,
                               self.experts_published, self.tile_m,
                               self.impl, self.name or 'moe',
                               self.activation))
            return y.reshape(x.shape), counts[None], fell_back[None]

        if self.mesh is not None and self.impl.startswith('pallas'):
            axis = usable_axis(self.mesh, self.batch_axis, b)
            rows, whole = PartitionSpec(axis, None, None), PartitionSpec()
            routed = jax.shard_map(
                functools.partial(routed, axis=axis), mesh=self.mesh,
                in_specs=(rows, rows, rows, whole, whole),
                out_specs=(rows, PartitionSpec(axis, None),
                           PartitionSpec(axis)),
                check_vma=self.impl == 'pallas')
        y, counts, fell_back = routed(hidden, experts, weights, w_gate_up,
                                      w_down)
        if self.latent:
            y = _projection(y, d_model, 'latent_up', self.dtype)
        if self.shared_d_ff:
            shared = SwiGLU if gated else ReluSquaredMLP
            y = y + shared(self.shared_d_ff, dtype=self.dtype,
                           name='shared')(x)
        return y, {'expert_load': jnp.sum(counts, axis=0),
                   'layout_fallbacks': jnp.sum(fell_back)}


class ReluSquaredMLP(nn.Module):
    """``down(relu(up x)^2)``, no gate: a shared expert of the ``'relu2'``
    kind."""
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        from petastorm_tpu.models.hybrid import _projection
        up = _projection(x, self.d_ff, 'up', self.dtype)
        return _projection(_relu2(up), x.shape[-1], 'down', self.dtype)


def total_load(held, loads):
    """A step's ``metrics`` of its expert layers: the ``load`` of every
    :class:`RoutedMoE` summed (``None``: a layer that routes nothing)."""
    total = {'expert_load': jnp.zeros((len(held),), jnp.int32),
             'layout_fallbacks': jnp.zeros((), jnp.int32)}
    with jax.named_scope('routing'):
        for load in loads:
            if load is not None:
                total = jax.tree.map(jnp.add, total, load)
    return total


# A loop that dispatches step k and then awaits step k - 1 (one step kept in
# flight, as the loader keeps ``inflight`` 2 transfers) has step k - 2 behind
# it when step k's metrics are handed over.
LOAD_LAG = 2


class ExpertLoadCounter(object):
    """Running totals of a step's ``expert_load`` on the global tracer's
    ring, as counters ``moe.expert_load.e<slot>`` (one a held expert), of
    its ``layout_fallbacks`` as ``moe.layout_fallbacks`` (expert layers whose
    held pairs passed their rows), and of a Kimi-delta model's
    ``decay_below_bound`` as ``kda.decay_below_bound.<j>`` (its ``j``-th
    Kimi-delta layer's decay entries under the bounded kernels' floor, where
    the model has them): what a reader takes the difference of at a window's
    two ends. ``add`` is handed every step's ``metrics`` and reads those of
    the step ``LOAD_LAG`` calls back, so it never waits for the device."""

    def __init__(self):
        self._pending, self._total = collections.deque(), None

    def add(self, metrics):
        self._pending.append((metrics['expert_load'],
                              metrics.get('layout_fallbacks', 0),
                              metrics.get('decay_below_bound', ())))
        if len(self._pending) <= LOAD_LAG:
            return
        step = np.concatenate([np.asarray(a, np.int64).reshape(-1)
                               for a in self._pending.popleft()])
        self._total = step if self._total is None else self._total + step
        tracer = get_global_tracer()
        total = self._total.tolist()
        held = len(metrics['expert_load'])
        for slot, value in enumerate(total[:held]):
            tracer.counter('moe.expert_load.e{}'.format(slot), value,
                           cat='step')
        tracer.counter('moe.layout_fallbacks', total[held], cat='step')
        for layer, value in enumerate(total[held + 1:]):
            tracer.counter('kda.decay_below_bound.{}'.format(layer), value,
                           cat='step')
