"""Hybrid state-space / attention mixture-of-experts LM (Nemotron-H's layout):
blocks of one sub-layer each, their kinds named by a pattern string, told
which share of each layer it holds.

``[B, T] int32 tokens -> {'logits': [B, T, vocab rows held] float32,
'metrics': {'expert_load': [experts held], 'layout_fallbacks': []}}``. Block
``i`` is ``x + f_i(rmsnorm(x))`` (pre-norm, one residual stream, RMSNorm of
``eps`` throughout, no bias but the convolution's), ``f_i`` by letter ``i`` of
``pattern``:

* ``M``, **Mamba-2** (:class:`MambaMixer`): ``z``, ``x``, ``B``, ``C`` and
  ``dt`` are column blocks of the in-projection; ``x``, ``B`` and ``C`` pass a
  causal depthwise convolution with a bias and SiLU
  (:func:`petastorm_tpu.ops.causal_conv.causal_conv_silu`); ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state-space rule is
  :func:`petastorm_tpu.ops.ssd.ssd_rule` (``ssm`` picks its implementation:
  ``'pallas'``, ``'pallas:interpret'``, ``'xla'``), heads 64 wide reading
  their group's ``B`` and ``C``; then ``rmsnorm(y * silu(z))`` by groups of
  a group's heads (the gate before the norm) and the out-projection.
* ``*``, **grouped-query attention** (:class:`GroupedQueryAttention`): causal,
  no rotary positions, a KV head shared by ``heads / kv_heads`` query heads
  (repeated to them before the flash kernel).
* ``E``, **latent mixture of experts**
  (:class:`petastorm_tpu.models.moe.RoutedMoE` with ``activation='relu2'``
  and ``latent``): sigmoid routing over the published experts on the hidden
  state, the routed experts ``relu(u W_up)^2 W_down`` in the latent space, a
  relu² shared expert on the hidden state.

**The share.** ``mamba_heads_held`` heads in ``mamba_groups_held`` groups,
``heads_held`` query heads with ``kv_heads_held`` KV heads, and the experts
``experts_held`` of ``experts_published`` live here; the partial output of
what is held is what goes on (nothing stands in for the absent chips).
``vocab_size`` is the rows of the vocabulary held. Every width is the
published one. ``remat=True`` recomputes each block in the backward pass. One
``model.layer_plan`` instant on the global tracer says what a process built.
"""

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from petastorm_tpu.models.hybrid import RMSNorm, _projection, causal_conv_silu
from petastorm_tpu.models.moe import RoutedMoE, total_load
from petastorm_tpu.models.transformer import (FlatDenseGeneral, self_attention,
                                              usable_axis)
from petastorm_tpu.ops.grouped_matmul import TILE_M
from petastorm_tpu.ops.ssd import ssd_rule
from petastorm_tpu.trace import get_global_tracer

#: A block's sub-layer by its letter in ``pattern``.
BLOCK_KINDS = {'M': 'mamba', '*': 'attention', 'E': 'moe'}
EPS = 1e-5


class SSDRule(nn.Module):
    """The rule itself, in a module of its own so that a device trace names
    its Pallas calls by the module's name (``ssd``). With a mesh the kernels
    are mapped over the batch's shards, ``A`` and ``D`` whole on each."""
    groups: int
    chunk: int = 128
    impl: str = 'pallas'
    mesh: Any = None
    batch_axis: Optional[str] = 'data'

    @nn.compact
    def __call__(self, x, b, c, dt, a, d):
        def rule(x, b, c, dt, a, d):
            return ssd_rule(x, b, c, dt, a, d, self.groups, chunk=self.chunk,
                            impl=self.impl)

        if self.mesh is None or not self.impl.startswith('pallas'):
            return rule(x, b, c, dt, a, d)
        axis = usable_axis(self.mesh, self.batch_axis, x.shape[0])

        def mapped(x, b, c, dt, a, d):
            if axis is not None and self.impl == 'pallas':
                # The kernels' gradients vary like the rows they come from
                # (see RoutedMoE): the cast's transpose sums them over shards.
                a, d = (jax.lax.pcast(v, (axis,), to='varying')
                        for v in (a, d))
            return rule(x, b, c, dt, a, d)

        rows, whole = PartitionSpec(axis, None, None), PartitionSpec()
        return jax.shard_map(
            mapped, mesh=self.mesh, in_specs=(rows,) * 4 + (whole, whole),
            out_specs=rows, check_vma=self.impl == 'pallas')(x, b, c, dt, a, d)


class GatedGroupNorm(nn.Module):
    """``rmsnorm(y * silu(z))`` over ``groups`` equal groups of channels, one
    scale a channel (Mamba-2's gated norm, the gate before the norm)."""
    groups: int
    eps: float = EPS
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, y, z):
        scale = self.param('scale', nn.initializers.ones, (y.shape[-1],))
        f32 = jnp.float32
        g = y.astype(f32) * nn.silu(z.astype(f32))
        grouped = g.reshape(g.shape[:-1] + (self.groups, -1))
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + self.eps)
        return (grouped.reshape(g.shape) * scale).astype(self.dtype)


class MambaMixer(nn.Module):
    """Every width of this layer is a head's or a group's own, so the heads
    and groups held are all it needs to know; heads ``i`` read group ``i //
    (heads_held / groups_held)``."""
    heads_held: int
    groups_held: int
    head_dim: int = 64
    state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    eps: float = EPS
    impl: str = 'pallas'
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model, h, g = x.shape[-1], self.heads_held, self.groups_held
        inner, width = h * self.head_dim, g * self.state
        x = x.astype(self.dtype)

        def proj(name, features):
            return FlatDenseGeneral(features, use_bias=False, dtype=self.dtype,
                                    name=name + '_proj')(x)

        def conv(name, features):
            kernel = self.param('conv_' + name, nn.initializers.normal(0.02),
                                (self.conv_kernel, features))
            bias = self.param('conv_' + name + '_bias', nn.initializers.zeros,
                              (features,))
            return causal_conv_silu(proj(name, features), kernel, bias)

        z = proj('z', inner)
        xs, b, c = conv('x', inner), conv('b', width), conv('c', width)
        dt_bias = self.param('dt_bias', nn.initializers.zeros, (h,))
        a_log = self.param('A_log', nn.initializers.zeros, (h,))
        d = self.param('D', nn.initializers.ones, (h,))
        dt = nn.softplus(proj('dt', h).astype(jnp.float32) + dt_bias)
        y = SSDRule(groups=g, chunk=self.chunk, impl=self.impl,
                    mesh=self.mesh, batch_axis=self.batch_axis,
                    name='ssd')(xs, b, c, dt, -jnp.exp(a_log), d)
        y = GatedGroupNorm(groups=g, eps=self.eps, dtype=self.dtype,
                           name='norm')(y, z)
        return FlatDenseGeneral(d_model, use_bias=False, dtype=self.dtype,
                                name='out_proj')(y)


class GroupedQueryAttention(nn.Module):
    """Causal attention of ``heads_held`` query heads over ``kv_heads_held``
    KV heads, ``head_dim`` wide, scores times ``head_dim ** -0.5``, no
    rotary positions: each KV head is repeated to its query heads before
    :func:`petastorm_tpu.models.transformer.self_attention` (``flash``: the
    Pallas kernels), and the gradients of the copies are summed back.
    ``gate``: the heads' output times ``sigmoid(x W_gate)``, one gate a
    channel, before the out-projection (scope ``gqa_gate``)."""
    heads_held: int
    kv_heads_held: int
    head_dim: int = 128
    attention: str = 'flash'
    gate: bool = False
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h, kv = self.heads_held, self.kv_heads_held
        if h % kv:
            raise ValueError('{} query heads over {} KV heads'.format(h, kv))
        x = x.astype(self.dtype)

        def proj(name, heads):
            return FlatDenseGeneral((heads, self.head_dim), use_bias=False,
                                    dtype=self.dtype, name=name + '_proj')(x)

        q, k, v = proj('q', h), proj('k', kv), proj('v', kv)
        k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
        out = self_attention(q, k, v, attention=self.attention, causal=True,
                             mesh=self.mesh, batch_axis=self.batch_axis,
                             head_axis=None)
        if self.gate:
            with jax.named_scope('gqa_gate'):
                out = out.astype(jnp.float32) * nn.sigmoid(
                    proj('gate', h).astype(jnp.float32))
        return FlatDenseGeneral(x.shape[-1], contract=2, use_bias=False,
                                dtype=self.dtype, name='o_proj')(
                                    out.astype(self.dtype))


class NemotronHBlock(nn.Module):
    """``x [B, T, d] -> (x, load)``: :class:`RoutedMoE`'s ``load``, None
    from a block of another kind."""
    kind: str                           # a letter of BLOCK_KINDS
    mamba_args: Any
    attention_args: Any
    moe_args: Any
    eps: float = EPS
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        if self.kind == 'M':
            scope, layer = 'mixer', MambaMixer(dtype=self.dtype, name='mixer',
                                               **self.mamba_args)
        elif self.kind == '*':
            # Named as TransformerLM names its attention: a device trace
            # names the flash kernels ``attn*`` in every model.
            scope, layer = 'mixer', GroupedQueryAttention(
                dtype=self.dtype, name='attn', **self.attention_args)
        elif self.kind == 'E':
            scope, layer = 'moe', RoutedMoE(dtype=self.dtype, name='moe',
                                            **self.moe_args)
        else:
            raise ValueError('unknown block kind {!r}: one of {}'.format(
                self.kind, sorted(BLOCK_KINDS)))
        # The norm and the residual sum under the sub-layer's name
        # (``Tracer.op_scopes``); the Pallas calls stay innermost in ``ssd``,
        # ``attn`` and ``moe``.
        with jax.named_scope(scope):
            out = layer(RMSNorm(dtype=self.dtype, eps=self.eps,
                                name='norm')(x))
            out, load = out if self.kind == 'E' else (out, None)
            return x + out, load


_plans_reported = set()


class NemotronHLM(nn.Module):
    vocab_size: int                     # rows of the vocabulary held here
    d_model: int
    pattern: str                        # a letter of BLOCK_KINDS a block
    mamba_heads_held: int = 128
    mamba_heads_published: Optional[int] = None     # None: every one held
    mamba_groups_held: int = 8
    mamba_groups_published: Optional[int] = None
    mamba_head_dim: int = 64
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    heads_held: int = 32                # grouped-query attention
    heads_published: Optional[int] = None
    kv_heads_held: int = 2
    kv_heads_published: Optional[int] = None
    head_dim: int = 128
    experts_published: int = 512
    experts_held: Sequence[int] = tuple(range(512))
    top_k: int = 22
    routed_scale: float = 5.0
    expert_d_ff: int = 2688
    shared_d_ff: int = 5376
    latent: int = 1024
    normalise_top_k: bool = True
    eps: float = EPS
    attention: str = 'flash'            # dense | flash[:interpret]
    ssm: str = 'pallas'                 # xla | pallas[:interpret]
    experts: str = 'pallas'             # ragged_dot | pallas[:interpret]
    expert_tile: int = TILE_M
    remat: bool = False                 # recompute each block going back
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    def layer_plan(self):
        return {'pattern': self.pattern,
                'layer_kinds': [BLOCK_KINDS[k] for k in self.pattern],
                'mamba_heads_held': self.mamba_heads_held,
                'mamba_heads_published': (self.mamba_heads_published
                                          or self.mamba_heads_held),
                'mamba_groups_held': self.mamba_groups_held,
                'mamba_groups_published': (self.mamba_groups_published
                                           or self.mamba_groups_held),
                'heads_held': self.heads_held,
                'heads_published': self.heads_published or self.heads_held,
                'kv_heads_held': self.kv_heads_held,
                'kv_heads_published': (self.kv_heads_published
                                       or self.kv_heads_held),
                'experts_held': list(self.experts_held),
                'experts_published': self.experts_published,
                'top_k': self.top_k, 'latent': self.latent,
                'expert_activation': 'relu2',
                'vocab_rows_held': self.vocab_size,
                'next_token_depth': 0,
                'recompute': bool(self.remat),
                'attention': self.attention, 'ssm': self.ssm,
                'experts': self.experts}

    @nn.compact
    def __call__(self, tokens, train=True):
        plan = self.layer_plan()
        key = repr(sorted(plan.items()))
        if key not in _plans_reported:      # once a process, not once a trace
            _plans_reported.add(key)
            get_global_tracer().instant('model.layer_plan', cat='model',
                                        args=plan)
        shared = dict(mesh=self.mesh, batch_axis=self.batch_axis)
        mamba_args = dict(heads_held=self.mamba_heads_held,
                          groups_held=self.mamba_groups_held,
                          head_dim=self.mamba_head_dim, state=self.ssm_state,
                          conv_kernel=self.conv_kernel, chunk=self.chunk,
                          eps=self.eps, impl=self.ssm, **shared)
        attention_args = dict(heads_held=self.heads_held,
                              kv_heads_held=self.kv_heads_held,
                              head_dim=self.head_dim,
                              attention=self.attention, **shared)
        moe_args = dict(experts_published=self.experts_published,
                        held=tuple(self.experts_held), top_k=self.top_k,
                        scale=self.routed_scale, d_ff=self.expert_d_ff,
                        shared_d_ff=self.shared_d_ff,
                        normalise=self.normalise_top_k, activation='relu2',
                        latent=self.latent, impl=self.experts,
                        tile_m=self.expert_tile, **shared)
        block = nn.remat(NemotronHBlock) if self.remat else NemotronHBlock
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name='embed')(tokens)
        loads = []
        for i, kind in enumerate(self.pattern):
            x, load = block(kind, mamba_args, attention_args, moe_args,
                            eps=self.eps, dtype=self.dtype,
                            name='block_{}'.format(i))(x)
            loads.append(load)
        x = RMSNorm(dtype=self.dtype, eps=self.eps, name='final_norm')(x)
        logits = _projection(x, self.vocab_size, 'head', self.dtype)
        return {'logits': logits.astype(jnp.float32),
                'metrics': total_load(self.experts_held, loads)}
