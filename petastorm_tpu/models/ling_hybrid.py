"""Hybrid linear- and latent-attention mixture-of-experts LM (Ling-3.0-flash's
layout): Kimi delta attention in all but the last layer of every period,
latent attention there, routed experts with a shared one after a leading
dense layer, told which share of each layer it holds.

``[B, T] int32 tokens -> {'logits': [B, T, vocab rows held] float32,
'metrics': {'expert_load': [experts held], 'layout_fallbacks': []}}``. A layer
is ``x + mixer(norm(x))`` then ``x + ffn(norm(x))`` (pre-norm, one residual
stream), RMSNorm throughout, no bias anywhere. Layer ``i`` of a period of
``layer_group_size``:

* **Kimi delta attention** (:class:`KimiDeltaMixer`; arXiv:2510.26692) where
  ``(i + 1) % layer_group_size != 0``: q, k, v pass a causal depthwise
  convolution and SiLU
  (:func:`petastorm_tpu.ops.causal_conv.causal_conv_silu`); q and k are
  L2-normalised a head; the decay is a vector a head and token,
  ``g = gate_lower_bound * sigmoid(exp(A_log) * (x W_f + dt_bias))`` in
  ``(gate_lower_bound, 0)`` (flash-linear-attention's bounded gate, what the
  rule's sub-blocks are safe for); ``beta = sigmoid(x W_b)``; the rule is
  :func:`petastorm_tpu.ops.kimi_delta.kda_rule` (``linear_attention`` picks
  its implementation: ``'pallas'``, ``'pallas:interpret'``, ``'chunked'``);
  each head's output is RMS-normed and gated by one ``sigmoid(x W_g)`` a
  head.
* **Latent attention** (:class:`petastorm_tpu.models.latent_moe.LatentAttention`
  with no query latent, plain rotary positions) in the period's last layer.

The first ``dense_layers`` layers have a SwiGLU of ``d_ff``; the others
:class:`petastorm_tpu.models.moe.RoutedMoE` (dropless ``top_k`` over the
published experts, limited to the best ``topk_group`` of ``n_group`` groups,
plus a shared expert).

**The share.** ``heads_held`` of ``heads_published`` heads in both kinds of
mixer and the experts ``experts_held`` of ``experts_published`` live here;
the partial output of the held heads and experts is what goes on (nothing
stands in for the absent chips). ``vocab_size`` is the rows of the vocabulary
held. Every width is the published one. ``remat=True`` recomputes each block
in the backward pass. One ``model.layer_plan`` instant on the global tracer
says what a process built.
"""

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from petastorm_tpu.models.hybrid import (EPS, RMSNorm, SwiGLU, _projection,
                                         causal_conv_silu, over_row_shards)
from petastorm_tpu.models.latent_moe import (LatentAttention,
                                             yarn_frequencies,
                                             yarn_softmax_scale)
from petastorm_tpu.models.moe import RoutedMoE, total_load
from petastorm_tpu.models.transformer import FlatDenseGeneral
from petastorm_tpu.ops.grouped_matmul import TILE_M
from petastorm_tpu.ops.kimi_delta import GATE_LOWER_BOUND, kda_rule
from petastorm_tpu.trace import get_global_tracer

LAYER_KINDS = ('kda', 'latent')


def layer_kinds(num_layers, layer_group_size):
    """Layer ``i`` is latent attention where ``(i + 1) % layer_group_size ==
    0``, else Kimi delta attention."""
    return ['latent' if (i + 1) % layer_group_size == 0 else 'kda'
            for i in range(num_layers)]


class KimiDeltaRule(nn.Module):
    """The rule itself, in a module of its own so that a device trace names
    its Pallas calls by the module's name (``kda``)."""
    chunk: int = 64
    sub_block: int = 16
    impl: str = 'pallas'
    mesh: Any = None
    batch_axis: Optional[str] = 'data'

    @nn.compact
    def __call__(self, q, k, v, g, beta):
        def rule(q, k, v, g, beta):
            return kda_rule(q, k, v, g, beta, chunk=self.chunk,
                            sub_block=self.sub_block, impl=self.impl)

        return over_row_shards(rule, self.mesh, self.batch_axis, self.impl,
                               q, k, v, g, beta)


class KimiDeltaMixer(nn.Module):
    """Every width of this layer is a head's own (``key_dim``,
    ``value_dim``), so the heads held are all it needs to know."""
    heads_held: int
    key_dim: int = 128
    value_dim: int = 128
    conv_kernel: int = 4
    gate_lower_bound: float = GATE_LOWER_BOUND
    chunk: int = 64
    sub_block: int = 16
    impl: str = 'pallas'
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model, h = x.shape[-1], self.heads_held
        x = x.astype(self.dtype)

        def heads(name, width):
            # One 2-D product whose result the kernels read as it is written.
            return FlatDenseGeneral((h, width), use_bias=False,
                                    dtype=self.dtype, name=name + '_proj')(x)

        def conv(name, width):
            kernel = self.param('conv_' + name, nn.initializers.normal(0.02),
                                (self.conv_kernel, h, width))
            return causal_conv_silu(heads(name, width), kernel,
                                    mesh=self.mesh, batch_axis=self.batch_axis)

        def unit(a):
            a32 = a.astype(jnp.float32)
            return a32 * jax.lax.rsqrt(
                jnp.sum(jnp.square(a32), axis=-1, keepdims=True) + EPS)

        q, k, v = (conv('q', self.key_dim), conv('k', self.key_dim),
                   conv('v', self.value_dim))
        q = (unit(q) * self.key_dim ** -0.5).astype(self.dtype)
        k = unit(k).astype(self.dtype)
        a_log = self.param('A_log', nn.initializers.zeros, (h,))
        dt_bias = self.param('dt_bias', nn.initializers.zeros,
                             (h, self.key_dim))
        f = heads('f', self.key_dim).astype(jnp.float32)
        g = self.gate_lower_bound * nn.sigmoid(
            jnp.exp(a_log)[:, None] * (f + dt_bias))   # log of the decay
        beta = nn.sigmoid(_projection(x, h, 'b_proj', self.dtype).astype(
            jnp.float32))
        o = KimiDeltaRule(chunk=self.chunk, sub_block=self.sub_block,
                          impl=self.impl, mesh=self.mesh,
                          batch_axis=self.batch_axis,
                          name='kda')(q, k, v, g, beta)
        o = RMSNorm(dtype=self.dtype, name='o_norm')(o)    # over a head's values
        gate = nn.sigmoid(_projection(x, h, 'g_proj', self.dtype))
        return FlatDenseGeneral(d_model, contract=2, use_bias=False,
                                dtype=self.dtype, name='o_proj')(
                                    o * gate[..., None])


class LingHybridBlock(nn.Module):
    """``x [B, T, d] -> (x, load)``: :class:`RoutedMoE`'s ``load``, None
    from a dense layer."""
    kind: str                           # the mixer: 'kda' | 'latent'
    dense: bool                         # a SwiGLU in the experts' place
    kda_args: Any
    latent_args: Any
    moe_args: Any
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        if self.kind == 'kda':
            mixer = KimiDeltaMixer(dtype=self.dtype, name='mixer',
                                   **self.kda_args)
        elif self.kind == 'latent':
            # Named as TransformerLM names its attention: a device trace
            # names the flash kernels ``attn*`` in every model.
            mixer = LatentAttention(dtype=self.dtype, name='attn',
                                    **self.latent_args)
        else:
            raise ValueError('unknown layer kind {!r}: one of {}'.format(
                self.kind, LAYER_KINDS))
        # A sub-layer's norm and its residual sum under the sub-layer's
        # name (``Tracer.op_scopes``); the Pallas calls stay innermost in
        # ``kda``, ``attn`` and ``moe``.
        with jax.named_scope('mixer'):
            x = x + mixer(RMSNorm(dtype=self.dtype, name='mixer_norm')(x))
        with jax.named_scope('mlp' if self.dense else 'moe'):
            inner = RMSNorm(dtype=self.dtype, name='ffn_norm')(x)
            if self.dense:
                return x + SwiGLU(self.d_ff, dtype=self.dtype,
                                  name='mlp')(inner), None
            y, load = RoutedMoE(dtype=self.dtype, name='moe',
                                **self.moe_args)(inner)
            return x + y, load


_plans_reported = set()


class LingHybridLM(nn.Module):
    vocab_size: int                     # rows of the vocabulary held here
    d_model: int
    d_ff: int                           # the dense layers' SwiGLU
    num_layers: int
    layer_group_size: int = 6           # a period: its last layer is latent attention
    dense_layers: int = 1               # leading layers with a dense SwiGLU
    heads_held: int = 32
    heads_published: Optional[int] = None   # None: every head is held
    key_dim: int = 128                  # the delta rule's head
    value_dim: int = 128
    conv_kernel: int = 4
    gate_lower_bound: float = GATE_LOWER_BOUND
    chunk: int = 64
    sub_block: int = 16
    kv_rank: int = 512                  # latent attention
    nope: int = 128
    rope: int = 64
    v_dim: int = 128
    rope_theta: float = 6000000.0
    experts_published: int = 512
    experts_held: Sequence[int] = tuple(range(512))
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scale: float = 2.5
    expert_d_ff: int = 768
    shared_d_ff: int = 768
    normalise_top_k: bool = True
    attention: str = 'flash'            # dense | flash[:interpret]
    linear_attention: str = 'pallas'    # chunked | pallas[:interpret]
    experts: str = 'pallas'             # ragged_dot | pallas[:interpret]
    expert_tile: int = TILE_M
    remat: bool = False                 # recompute each block in the backward pass
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    def layer_plan(self):
        return {'layer_kinds': layer_kinds(self.num_layers,
                                           self.layer_group_size),
                'dense_layers': self.dense_layers,
                'heads_held': self.heads_held,
                'heads_published': self.heads_published or self.heads_held,
                'experts_held': list(self.experts_held),
                'experts_published': self.experts_published,
                'n_group': self.n_group, 'topk_group': self.topk_group,
                'top_k': self.top_k,
                'vocab_rows_held': self.vocab_size,
                'next_token_depth': 0,
                'recompute': bool(self.remat),
                'attention': self.attention,
                'linear_attention': self.linear_attention,
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
        kda_args = dict(heads_held=self.heads_held, key_dim=self.key_dim,
                        value_dim=self.value_dim,
                        conv_kernel=self.conv_kernel,
                        gate_lower_bound=self.gate_lower_bound,
                        chunk=self.chunk, sub_block=self.sub_block,
                        impl=self.linear_attention, **shared)
        latent_args = dict(
            heads_held=self.heads_held, q_rank=None, kv_rank=self.kv_rank,
            nope=self.nope, rope=self.rope, v_dim=self.v_dim,
            attention=self.attention,
            frequencies=yarn_frequencies(self.rope, self.rope_theta),
            softmax_scale=yarn_softmax_scale(self.nope + self.rope), **shared)
        moe_args = dict(experts_published=self.experts_published,
                        held=tuple(self.experts_held), top_k=self.top_k,
                        scale=self.routed_scale, d_ff=self.expert_d_ff,
                        shared_d_ff=self.shared_d_ff,
                        normalise=self.normalise_top_k, n_group=self.n_group,
                        topk_group=self.topk_group, impl=self.experts,
                        tile_m=self.expert_tile, **shared)
        block = nn.remat(LingHybridBlock) if self.remat else LingHybridBlock
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name='embed')(tokens)
        loads = []
        for i, kind in enumerate(plan['layer_kinds']):
            x, load = block(kind, i < self.dense_layers, kda_args,
                            latent_args, moe_args, self.d_ff,
                            dtype=self.dtype, name='block_{}'.format(i))(x)
            loads.append(load)
        x = RMSNorm(dtype=self.dtype, name='final_norm')(x)
        logits = _projection(x, self.vocab_size, 'head', self.dtype)
        return {'logits': logits.astype(jnp.float32),
                'metrics': total_load(self.experts_held, loads)}
