"""Hybrid linear- and full-attention mixture-of-experts LM (Ling-3.0-flash's
layout, and Solar-Open2's with ``layer_pattern``): Kimi delta attention in
most layers, latent or grouped-query attention in the others, routed experts
with a shared one after any leading dense layers, told which share of each
layer it holds.

``[B, T] int32 tokens -> {'logits': [B, T, vocab rows held] float32,
'metrics': {'expert_load': [experts held], 'layout_fallbacks': []}}`` (and
``'decay_below_bound'``, int32 a Kimi-delta layer: how many entries of its
decay ``g`` lay under ``GATE_LOWER_BOUND`` in the step, where the decay has
no bound). A layer is ``x + mixer(norm(x))`` then ``x + ffn(norm(x))``
(pre-norm, one residual stream), RMSNorm of ``eps`` throughout, no bias
anywhere. Layer ``i`` is ``layer_pattern[i]``, or else, of a period of
``layer_group_size``:

* **Kimi delta attention** (:class:`KimiDeltaMixer`; arXiv:2510.26692) where
  ``(i + 1) % layer_group_size != 0``: q, k, v pass a causal depthwise
  convolution and SiLU
  (:func:`petastorm_tpu.ops.causal_conv.causal_conv_silu`); q and k are
  L2-normalised a head; the decay is a vector a head and token: with
  ``decay='bounded'`` ``g = gate_lower_bound * sigmoid(exp(A_log) * (x W_f +
  dt_bias))`` in ``(gate_lower_bound, 0)`` (flash-linear-attention's bounded
  gate, what the rule's sub-blocks are safe for), with ``'softplus'``
  ``g = -exp(A_log) * softplus(x W_f + dt_bias)`` (Kimi Linear's, no lower
  bound: the rule's exact path); ``W_f`` full or of rank ``low_rank``
  (``x W_fa W_fb``); ``beta = beta_scale * sigmoid(x W_b)`` (2: negative
  eigenvalues); the rule is :func:`petastorm_tpu.ops.kimi_delta.kda_rule`
  (``linear_attention`` picks its implementation: ``'pallas'``,
  ``'pallas:interpret'``, ``'chunked'``); each head's output is RMS-normed
  and gated by ``sigmoid(x W_g)``, one gate a head (``gate='head'``) or a
  channel (``'channel'``, ``W_g`` of rank ``low_rank`` where it is set).
* **Latent attention** (:class:`petastorm_tpu.models.latent_moe.LatentAttention`
  with no query latent, plain rotary positions) in the period's last layer.
* **Gated grouped-query attention**
  (:class:`petastorm_tpu.models.nemotron_h.GroupedQueryAttention`, no
  rotary positions, its output gated a channel) where ``layer_pattern``
  says ``'gqa'``.

The first ``dense_layers`` layers have a SwiGLU of ``d_ff``; the others
:class:`petastorm_tpu.models.moe.RoutedMoE` (dropless ``top_k`` over the
published experts, limited to the best ``topk_group`` of ``n_group`` groups,
plus a shared expert).

**The share.** ``heads_held`` of ``heads_published`` heads in every kind of
mixer (``kv_heads_held`` KV heads in grouped-query attention) and the experts
``experts_held`` of ``experts_published`` live here;
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
from petastorm_tpu.models.nemotron_h import GroupedQueryAttention
from petastorm_tpu.models.transformer import FlatDenseGeneral
from petastorm_tpu.ops.grouped_matmul import TILE_M
from petastorm_tpu.ops.kimi_delta import GATE_LOWER_BOUND, kda_rule
from petastorm_tpu.trace import get_global_tracer

LAYER_KINDS = ('kda', 'latent', 'gqa')
DECAYS = ('bounded', 'softplus')


def layer_kinds(num_layers, layer_group_size):
    """Layer ``i`` is latent attention where ``(i + 1) % layer_group_size ==
    0``, else Kimi delta attention."""
    return ['latent' if (i + 1) % layer_group_size == 0 else 'kda'
            for i in range(num_layers)]


class KimiDeltaRule(nn.Module):
    """The rule itself, in a module of its own so that a device trace names
    its Pallas calls by the module's name (``kda``; ``kda_exact`` for the
    exact path)."""
    chunk: int = 64
    sub_block: int = 16
    impl: str = 'pallas'
    exact: bool = False
    mesh: Any = None
    batch_axis: Optional[str] = 'data'

    @nn.compact
    def __call__(self, q, k, v, g, beta):
        def rule(q, k, v, g, beta):
            return kda_rule(q, k, v, g, beta, chunk=self.chunk,
                            sub_block=self.sub_block, impl=self.impl,
                            exact=self.exact)

        return over_row_shards(rule, self.mesh, self.batch_axis, self.impl,
                               q, k, v, g, beta)


class KimiDeltaMixer(nn.Module):
    """Every width of this layer is a head's own (``key_dim``,
    ``value_dim``) or the low rank's, held whole, so the heads held are all
    it needs to know. ``x -> y``; with the unbounded decay ``(y, below)``,
    ``below`` the count of its entries under ``GATE_LOWER_BOUND`` (int32):
    what the bounded rule could not have taken."""
    heads_held: int
    key_dim: int = 128
    value_dim: int = 128
    conv_kernel: int = 4
    gate_lower_bound: float = GATE_LOWER_BOUND
    decay: str = 'bounded'              # DECAYS
    low_rank: Optional[int] = None      # W_f, and W_g a channel, as W_a W_b
    gate: str = 'head'                  # 'head' | 'channel'
    beta_scale: float = 1.0
    eps: float = EPS                    # the output's norm
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
        def low_rank(name, width):
            # W_a held whole, W_b by heads
            return FlatDenseGeneral(
                (h, width), use_bias=False, dtype=self.dtype,
                name=name + '_b_proj')(FlatDenseGeneral(
                    self.low_rank, use_bias=False, dtype=self.dtype,
                    name=name + '_a_proj')(x))

        def gate_input(name, width):
            return low_rank(name, width) if self.low_rank else \
                heads(name, width)

        if self.decay not in DECAYS or self.gate not in ('head', 'channel'):
            raise ValueError('decay one of {}, gate head or channel: {!r}, '
                             '{!r}'.format(DECAYS, self.decay, self.gate))
        q = (unit(q) * self.key_dim ** -0.5).astype(self.dtype)
        k = unit(k).astype(self.dtype)
        a_log = self.param('A_log', nn.initializers.zeros, (h,))
        dt_bias = self.param('dt_bias', nn.initializers.zeros,
                             (h, self.key_dim))
        f = gate_input('f', self.key_dim).astype(jnp.float32)
        exact = self.decay == 'softplus'
        if exact:                                       # log of the decay
            g = -jnp.exp(a_log)[:, None] * nn.softplus(f + dt_bias)
        else:
            g = self.gate_lower_bound * nn.sigmoid(
                jnp.exp(a_log)[:, None] * (f + dt_bias))
        beta = nn.sigmoid(_projection(x, h, 'b_proj', self.dtype).astype(
            jnp.float32))
        if self.beta_scale != 1.0:
            beta = self.beta_scale * beta
        o = KimiDeltaRule(chunk=self.chunk, sub_block=self.sub_block,
                          impl=self.impl, exact=exact, mesh=self.mesh,
                          batch_axis=self.batch_axis,
                          name='kda_exact' if exact else 'kda')(
                              q, k, v, g, beta)
        # over a head's values
        o = RMSNorm(dtype=self.dtype, eps=self.eps, name='o_norm')(o)
        if self.gate == 'head':
            gate = nn.sigmoid(_projection(x, h, 'g_proj', self.dtype))[
                ..., None]
        else:
            gate = nn.sigmoid(gate_input('g', self.value_dim))
        y = FlatDenseGeneral(d_model, contract=2, use_bias=False,
                             dtype=self.dtype, name='o_proj')(o * gate)
        if exact:
            return y, jnp.sum(g < GATE_LOWER_BOUND, dtype=jnp.int32)
        return y


class LingHybridBlock(nn.Module):
    """``x [B, T, d] -> (x, load, below)``: :class:`RoutedMoE`'s ``load``,
    None from a dense layer; :class:`KimiDeltaMixer`'s ``below``, None from
    another mixer."""
    kind: str                           # the mixer: one of LAYER_KINDS
    dense: bool                         # a SwiGLU in the experts' place
    kda_args: Any
    latent_args: Any
    moe_args: Any
    d_ff: int
    gqa_args: Any = None
    eps: float = EPS
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
        elif self.kind == 'gqa':
            mixer = GroupedQueryAttention(dtype=self.dtype, name='attn',
                                          **self.gqa_args)
        else:
            raise ValueError('unknown layer kind {!r}: one of {}'.format(
                self.kind, LAYER_KINDS))
        # A sub-layer's norm and its residual sum under the sub-layer's
        # name (``Tracer.op_scopes``); the Pallas calls stay innermost in
        # ``kda``, ``kda_exact``, ``attn`` and ``moe``.
        with jax.named_scope('mixer'):
            out = mixer(RMSNorm(dtype=self.dtype, eps=self.eps,
                                name='mixer_norm')(x))
            out, below = out if isinstance(out, tuple) else (out, None)
            x = x + out
        with jax.named_scope('mlp' if self.dense else 'moe'):
            inner = RMSNorm(dtype=self.dtype, eps=self.eps,
                            name='ffn_norm')(x)
            if self.dense:
                return x + SwiGLU(self.d_ff, dtype=self.dtype,
                                  name='mlp')(inner), None, below
            y, load = RoutedMoE(dtype=self.dtype, name='moe',
                                **self.moe_args)(inner)
            return x + y, load, below


_plans_reported = set()


class LingHybridLM(nn.Module):
    vocab_size: int                     # rows of the vocabulary held here
    d_model: int
    d_ff: int                           # the dense layers' SwiGLU
    num_layers: int
    layer_group_size: int = 6           # a period: its last layer is latent attention
    layer_pattern: Optional[Sequence[str]] = None   # a kind a layer; None: by the period
    dense_layers: int = 1               # leading layers with a dense SwiGLU
    heads_held: int = 32
    heads_published: Optional[int] = None   # None: every head is held
    kv_heads_held: int = 1              # grouped-query attention
    kv_heads_published: Optional[int] = None
    key_dim: int = 128                  # the delta rule's head
    value_dim: int = 128
    conv_kernel: int = 4
    gate_lower_bound: float = GATE_LOWER_BOUND
    decay: str = 'bounded'              # DECAYS
    low_rank: Optional[int] = None      # of W_f and W_g; None: full rank
    gate: str = 'head'                  # the output gate: 'head' | 'channel'
    beta_scale: float = 1.0             # 2: write strengths in (0, 2)
    eps: float = EPS                    # every RMSNorm's
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

    def kinds(self):
        if self.layer_pattern is None:
            return layer_kinds(self.num_layers, self.layer_group_size)
        if len(self.layer_pattern) != self.num_layers or set(
                self.layer_pattern) - set(LAYER_KINDS):
            raise ValueError('layer_pattern: {} kinds of {}, got {}'.format(
                self.num_layers, LAYER_KINDS, self.layer_pattern))
        return list(self.layer_pattern)

    def layer_plan(self):
        kinds = self.kinds()
        plan = {'layer_kinds': kinds,
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
        if 'gqa' in kinds:
            plan.update(kv_heads_held=self.kv_heads_held,
                        kv_heads_published=(self.kv_heads_published
                                            or self.kv_heads_held))
        if self.decay != 'bounded' or self.beta_scale != 1.0:
            plan.update(decay=self.decay, beta_scale=self.beta_scale,
                        low_rank=self.low_rank, output_gate=self.gate)
        return plan

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
                        decay=self.decay, low_rank=self.low_rank,
                        gate=self.gate, beta_scale=self.beta_scale,
                        eps=self.eps, chunk=self.chunk,
                        sub_block=self.sub_block,
                        impl=self.linear_attention, **shared)
        latent_args = dict(
            heads_held=self.heads_held, q_rank=None, kv_rank=self.kv_rank,
            nope=self.nope, rope=self.rope, v_dim=self.v_dim,
            attention=self.attention,
            frequencies=yarn_frequencies(self.rope, self.rope_theta),
            softmax_scale=yarn_softmax_scale(self.nope + self.rope), **shared)
        gqa_args = dict(heads_held=self.heads_held,
                        kv_heads_held=self.kv_heads_held,
                        head_dim=self.key_dim, attention=self.attention,
                        gate=True, **shared)
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
        loads, below = [], []
        for i, kind in enumerate(plan['layer_kinds']):
            x, load, count = block(kind, i < self.dense_layers, kda_args,
                                   latent_args, moe_args, self.d_ff,
                                   gqa_args, eps=self.eps, dtype=self.dtype,
                                   name='block_{}'.format(i))(x)
            loads.append(load)
            if count is not None:
                below.append(count)
        x = RMSNorm(dtype=self.dtype, eps=self.eps, name='final_norm')(x)
        logits = _projection(x, self.vocab_size, 'head', self.dtype)
        out = {'logits': logits.astype(jnp.float32),
               'metrics': total_load(self.experts_held, loads)}
        if below:
            out['metrics']['decay_below_bound'] = jnp.stack(below)
        return out
