"""Latent-attention mixture-of-experts LM with several residual streams (the
DeepSeek-V3 family's decoder with manifold-constrained hyper-connections:
Xing4.0's layout), told which share of each layer it holds.

``[B, T + nextn] int32 tokens -> {'logits': (next token, second next, ...),
'metrics': {'expert_load': [experts held], 'layout_fallbacks': []}}``, every
logits ``[B, T, vocab rows held]`` float32. RMSNorm throughout, SwiGLU
feed-forward, no bias.

**Streams** (:class:`StreamSubLayer`; arXiv:2512.24880 after
arXiv:2409.19606). The residual is ``n`` streams side by side in the lanes of
``X [B, T, n d]`` (stream ``j`` the lanes ``j d .. (j + 1) d``), the
embedding copied ``n`` times at the start and the streams summed before the
final norm. Each sub-layer ``F`` has three maps of its own, made per token
from ``x~ = rmsnorm(X_t)`` over all ``n d`` values: ``H_pre = sigmoid(a x~
Phi_pre + b)`` weighs the streams into the sub-layer's one input, ``H_post =
2 sigmoid(...)`` spreads its output over them, and ``H_res =
sinkhorn(exp(clip(a mat(x~ Phi_res) + b)))``, a doubly stochastic ``n x n``
matrix, mixes the streams that pass it by: ``X <- H_res X + H_post^T
F(rmsnorm(H_pre X))``. The product ``x~ Phi`` takes ``dtype`` operands and
accumulates in float32; the maps' sigmoids, Sinkhorn's iterations and the
mixings are float32; the streams, and so their gradients, are kept in
``dtype`` between sub-layers. Where a stream is whole vregs wide (``d % 128
== 0``) a sub-layer's maps, Sinkhorn and mixings run as the Pallas kernels of
:mod:`petastorm_tpu.ops.hyper_connections` (two a pass; interpreted off a
TPU), which read the streams from HBM once each and keep nothing ``n`` wide
there; at other widths as ``jax.numpy`` (:class:`StreamMaps`,
:func:`mix_streams`: XLA fusions, and what the kernels are tested against).
``model.layer_plan``'s ``stream_mixing`` says which.

**Latent attention** (:class:`LatentAttention`): queries through a
``q_rank`` latent (``None``: straight from ``x``) and keys and values through
a ``kv_rank`` latent, an RMSNorm on each; a head's key is ``nope`` content
lanes from the latent and ``rope`` rotary lanes that all heads share (rotated
by yarn-scaled frequencies), its value ``v_dim`` wide. The flash kernel takes keys and
values of two widths (``ops.flash_attention``); the scale is ``(nope +
rope) ** -0.5 * mscale ** 2``.

**Experts** (:class:`petastorm_tpu.models.moe.RoutedMoE`): dropless top-k
over the published experts with a shared expert; the first ``dense_layers``
layers have a plain SwiGLU of ``d_ff`` instead.

**Next-token modules** (:class:`NextTokenModule`, DeepSeek-V3's): depth ``k``
takes the summed streams of depth ``k - 1`` and the embedding of the token
one further on, ``h' = W_eh [rmsnorm(h) ; rmsnorm(Emb(t_{i+k}))]``, runs one
more expert block with its own streams, and shares embedding, final norm and
head; its logits at position ``i`` are for token ``i + k + 1``. So a row
holds ``T + nextn`` input tokens.

**The share.** ``heads_held`` of ``heads_published`` heads and the experts
``experts_held`` of ``experts_published`` live here; the partial output of
the held heads and experts is what goes on (a chip of a tensor- and
expert-parallel group before its exchange; nothing stands in for the absent
chips). ``vocab_size`` is the rows of the vocabulary held. Every width is the
published one. ``remat=True`` recomputes each block in the backward pass.
One ``model.layer_plan`` instant on the global tracer says what a process
built.
"""

import functools
import math
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from petastorm_tpu.models.hybrid import (RMSNorm, SwiGLU, _projection,
                                         rms_normalise)
from petastorm_tpu.models.moe import RoutedMoE, total_load
from petastorm_tpu.models.transformer import self_attention
from petastorm_tpu.ops import hyper_connections
from petastorm_tpu.ops.grouped_matmul import TILE_M
from petastorm_tpu.trace import get_global_tracer


def yarn_frequencies(rope_dim, theta, factor=1.0, original_length=4096,
                     beta_fast=32, beta_slow=1):
    """``rope_dim / 2`` rotary frequencies, yarn-scaled as DeepSeek-V3 scales
    them: a pair that turns more than ``beta_fast`` times over the original
    length keeps ``theta ** (-2 i / rope_dim)``, one that turns fewer than
    ``beta_slow`` times takes it over ``factor``, a linear ramp between."""
    i = np.arange(rope_dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / rope_dim)

    def pair_of(turns):
        return rope_dim * math.log(original_length / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), rope_dim - 1)
    scaled = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return tuple((plain * (1.0 - scaled) + plain / factor * scaled).tolist())


def yarn_softmax_scale(qk_dim, factor=1.0, mscale_all_dim=0.0):
    """``qk_dim ** -0.5 * m ** 2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``."""
    m = 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return qk_dim ** -0.5 * m * m


@functools.lru_cache(maxsize=None)
def _rotary_tables(t, frequencies):
    """``(cos, sin) [t, rope / 2]`` float32 of ``position * frequency``, made
    once a length and set of frequencies, not once a layer's trace."""
    angles = np.arange(t)[:, None] * np.asarray(frequencies)[None, :]
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def rotate(x, frequencies):
    """Rotary positions on the last axis of ``x [B, T, ..., rope]``, whose
    lanes are interleaved pairs ``(2 i, 2 i + 1)``. The result has the pairs'
    first members in its lower half and their second members in its upper
    half: a permutation of the lanes that queries and keys share, so their
    products are those of the interleaved form."""
    t = x.shape[1]
    shape = (1, t) + (1,) * (x.ndim - 3) + (len(frequencies),)
    with jax.named_scope('rotary'):
        cos, sin = (jnp.asarray(table.reshape(shape))
                    for table in _rotary_tables(t, tuple(frequencies)))
        x32 = x.astype(jnp.float32)
        even, odd = x32[..., 0::2], x32[..., 1::2]
        return jnp.concatenate(
            [even * cos - odd * sin, even * sin + odd * cos],
            axis=-1).astype(x.dtype)


def sinkhorn(logits, iterations, eps):
    """``exp(logits) [..., n, n]`` made doubly stochastic: each row over its
    sum, then each column over its sum, ``iterations`` times (``eps`` in the
    denominators)."""
    m = jnp.exp(logits)
    for _ in range(iterations):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


class _StreamLeaves(nn.Module):
    """What a sub-layer's maps are made from, under the names the benchmark's
    weights have: ``norm/scale``, ``phi_pre``, ``phi_post``, ``phi_res``,
    ``alpha_*``, ``b_*``."""
    sinkhorn_iterations: int = 20
    eps: float = 1e-6
    clamp: Sequence[float] = (-30.0, 30.0)
    alpha_init: float = 0.01
    res_diagonal_init: float = 0.0
    dtype: Any = jnp.bfloat16

    def leaves(self, n, d):
        init = nn.initializers.normal(0.02)
        out = {'scale': _Scale(name='norm')(n * d)}
        for name, width in (('pre', n), ('post', n), ('res', n * n)):
            out['phi_' + name] = self.param('phi_' + name, init,
                                            (n * d, width))
            out['alpha_' + name] = self.param(
                'alpha_' + name, nn.initializers.constant(self.alpha_init), ())
        out['b_pre'] = self.param('b_pre', nn.initializers.zeros, (n,))
        out['b_post'] = self.param('b_post', nn.initializers.zeros, (n,))
        out['b_res'] = self.param(
            'b_res', lambda key, shape: self.res_diagonal_init * jnp.eye(n),
            (n, n))
        return out

    def maps(self, x, leaves):
        """The ``jax.numpy`` formulation: ``x [B, T, n, d] -> (H_pre [B, T,
        n], H_post [B, T, n], H_res [B, T, n, n])``, float32."""
        b, t, n, d = x.shape
        flat = rms_normalise(x.reshape(b, t, n * d).astype(self.dtype),
                             leaves['scale'])
        phi = jnp.concatenate([leaves['phi_' + name] for name in
                               ('pre', 'post', 'res')], axis=-1)
        maps = jnp.einsum('btk,km->btm', flat, phi.astype(self.dtype),
                          preferred_element_type=jnp.float32)
        pre = nn.sigmoid(leaves['alpha_pre'] * maps[..., :n] + leaves['b_pre'])
        post = 2.0 * nn.sigmoid(leaves['alpha_post'] * maps[..., n:2 * n]
                                + leaves['b_post'])
        res = leaves['alpha_res'] * maps[..., 2 * n:].reshape(b, t, n, n) \
            + leaves['b_res']
        res = sinkhorn(jnp.clip(res, *self.clamp), self.sinkhorn_iterations,
                       self.eps)
        return pre, post, res


class _Scale(nn.Module):
    """An rmsnorm's ``scale`` leaf where the norm itself runs elsewhere."""

    @nn.compact
    def __call__(self, width):
        return self.param('scale', nn.initializers.ones, (width,))


class StreamMaps(_StreamLeaves):
    """The three maps of one sub-layer from the streams ``X [B, T, n, d]``:
    ``(H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n])``, float32
    (``x~ Phi`` from ``dtype`` operands, accumulated in float32), in plain
    ``jax.numpy``: what the kernels are held against."""

    @nn.compact
    def __call__(self, x):
        return self.maps(x, self.leaves(*x.shape[2:]))


def mix_streams(x, pre, post, res, fn):
    """``X <- H_res X + H_post^T fn(H_pre X)``: the streams ``x [B, T, n,
    d]`` through one sub-layer. The mixings are sums over the ``n`` streams
    written out (elementwise on the VPU; a contraction of 4 is nothing for an
    MXU), float32 inside, ``x``'s dtype out. ``fn`` may hand back a pair
    whose second member is passed on beside the streams."""
    n = x.shape[2]
    x32 = x.astype(jnp.float32)
    inner = sum(pre[..., j, None] * x32[:, :, j] for j in range(n))
    y = fn(inner.astype(x.dtype))
    y, extra = y if isinstance(y, tuple) else (y, None)
    mixed = sum(res[..., j, None] * x32[:, :, j, None, :] for j in range(n))
    out = mixed + post[..., None] * y.astype(jnp.float32)[:, :, None, :]
    return out.astype(x.dtype), extra


class StreamSubLayer(_StreamLeaves):
    """``X [B, T, n d] -> (X', extra)``: the streams, side by side in the
    lanes, through the sub-layer ``fn`` between its maps. Streams of whole
    vregs (``d % 128 == 0``) go through the kernels of
    ``ops.hyper_connections``, others through :meth:`maps` and
    :func:`mix_streams`: one algorithm, chosen by the width it is handed."""
    streams: int = 4
    mesh: Any = None
    batch_axis: Optional[str] = 'data'

    @nn.compact
    def __call__(self, x, fn):
        b, t, width = x.shape
        n, d = self.streams, width // self.streams
        leaves = self.leaves(n, d)
        if hyper_connections.implementation(n, d) == 'xla':
            x = x.reshape(b, t, n, d)
            x, extra = mix_streams(x, *self.maps(x, leaves), fn)
            return x.reshape(b, t, width), extra
        return hyper_connections.hyper_connection(
            x.astype(self.dtype), fn, leaves, n, self.sinkhorn_iterations,
            self.eps, tuple(self.clamp), mesh=self.mesh,
            batch_axis=self.batch_axis)


class LatentAttention(nn.Module):
    heads_held: int
    q_rank: Optional[int] = 768         # None: no query latent, one x W_q
    kv_rank: int = 512
    nope: int = 128
    rope: int = 64
    v_dim: int = 128
    frequencies: Sequence[float] = ()   # rope / 2 of them
    softmax_scale: Optional[float] = None
    attention: str = 'flash'
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model, h = x.shape[-1], self.heads_held
        x = x.astype(self.dtype)
        if self.q_rank is None:
            q = _projection(x, (h, self.nope + self.rope), 'q_proj',
                            self.dtype)
        else:
            c_q = RMSNorm(dtype=self.dtype, name='q_norm')(
                _projection(x, self.q_rank, 'q_down', self.dtype))
            q = _projection(c_q, (h, self.nope + self.rope), 'q_up',
                            self.dtype)
        kv = _projection(x, self.kv_rank + self.rope, 'kv_down', self.dtype)
        c_kv = RMSNorm(dtype=self.dtype, name='kv_norm')(
            kv[..., :self.kv_rank])
        up = _projection(c_kv, (h, self.nope + self.v_dim), 'kv_up',
                         self.dtype)
        # One rotary key a token, shared by the heads.
        k_rot = rotate(kv[..., self.kv_rank:], self.frequencies)
        q = jnp.concatenate([q[..., :self.nope],
                             rotate(q[..., self.nope:], self.frequencies)],
                            axis=-1)
        k = jnp.concatenate(
            [up[..., :self.nope], jnp.broadcast_to(
                k_rot[:, :, None, :], up.shape[:3] + (self.rope,))], axis=-1)
        out = self_attention(q, k, up[..., self.nope:],
                             attention=self.attention, causal=True,
                             mesh=self.mesh, batch_axis=self.batch_axis,
                             head_axis=None, scale=self.softmax_scale)
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, name='out')(
                                   out.astype(self.dtype))


class LatentMoEBlock(nn.Module):
    """``X [B, T, n d] -> (X, load)``: an attention sub-layer and a
    feed-forward (``kind='dense'``, ``load`` None) or expert (``'moe'``,
    :class:`RoutedMoE`'s ``load``) sub-layer, each between its own stream
    maps."""
    kind: str
    attention_args: Any
    maps_args: Any
    moe_args: Any
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        if self.kind not in ('dense', 'moe'):
            raise ValueError('unknown layer kind {!r}'.format(self.kind))
        # Made here and called inside the sub-layers' wrappers: a module
        # belongs to the one it is made in. Named as TransformerLM names its
        # attention: a device trace names the flash kernels ``attn*`` in
        # every model.
        attn_norm = RMSNorm(dtype=self.dtype, name='attn_norm')
        attn = LatentAttention(dtype=self.dtype, name='attn',
                               **self.attention_args)
        ffn_norm = RMSNorm(dtype=self.dtype, name='ffn_norm')
        if self.kind == 'dense':
            ffn = SwiGLU(self.d_ff, dtype=self.dtype, name='mlp')
        else:
            ffn = RoutedMoE(dtype=self.dtype, name='moe', **self.moe_args)

        def attend(inner):
            return attn(attn_norm(inner))

        def feed_forward(inner):
            # The norm under the name of the sub-layer it feeds: the rules
            # of ``Tracer.op_scopes`` cannot tell a dense layer's
            # ``ffn_norm`` from an expert layer's.
            with jax.named_scope(ffn.name):
                inner = ffn_norm(inner)
            return ffn(inner)

        x, _ = StreamSubLayer(dtype=self.dtype, name='attn_hc',
                              **self.maps_args)(x, attend)
        x, load = StreamSubLayer(dtype=self.dtype, name='ffn_hc',
                                 **self.maps_args)(x, feed_forward)
        return x, load


class NextTokenModule(nn.Module):
    """``(h [B, T, d], e [B, T, d]) -> (h' [B, T, d], load)``: the
    summed streams of the depth before and the embedding of the token one
    further on, through ``W_eh`` and one expert block with its own streams."""
    block: Any
    streams: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h, e):
        both = jnp.concatenate([RMSNorm(dtype=self.dtype, name='h_norm')(h),
                                RMSNorm(dtype=self.dtype, name='e_norm')(e)],
                               axis=-1)
        x = _projection(both, h.shape[-1], 'eh_proj', self.dtype)
        x, load = self.block(name='block')(_copies(x, self.streams))
        return _summed(x, self.streams).astype(self.dtype), load


def _copies(h, n):
    """``h [B, T, d]`` as ``n`` equal streams ``[B, T, n d]`` (joined, not
    tiled: XLA makes a tile a 4-D broadcast and then copies it flat)."""
    with jax.named_scope('streams'):
        return jnp.concatenate([h] * n, axis=-1)


def _summed(x, n):
    """The ``n`` streams of ``x [B, T, n d]`` added up, float32."""
    d = x.shape[-1] // n
    with jax.named_scope('streams'):
        return sum(x[..., j * d:(j + 1) * d].astype(jnp.float32)
                   for j in range(n))


_plans_reported = set()


class LatentMoELM(nn.Module):
    vocab_size: int                     # rows of the vocabulary held here
    d_model: int
    d_ff: int                           # the dense layers' SwiGLU
    num_layers: int
    dense_layers: int                   # leading layers with a dense SwiGLU
    heads_held: int
    heads_published: Optional[int] = None   # None: every head is held
    q_rank: int = 768
    kv_rank: int = 512
    nope: int = 128
    rope: int = 64
    v_dim: int = 128
    rope_theta: float = 10000.0
    rope_factor: float = 1.0            # yarn; 1: plain rotary positions
    rope_original_length: int = 4096
    rope_beta_fast: float = 32
    rope_beta_slow: float = 1
    rope_mscale_all_dim: float = 0.0
    experts_published: int = 64
    experts_held: Sequence[int] = tuple(range(64))
    top_k: int = 4
    routed_scale: float = 1.0
    expert_d_ff: int = 1024
    shared_experts: int = 1
    normalise_top_k: bool = True
    streams: int = 4
    sinkhorn_iterations: int = 20
    stream_eps: float = 1e-6
    stream_clamp: Sequence[float] = (-30.0, 30.0)
    stream_alpha_init: float = 0.01
    stream_res_diagonal_init: float = 0.0
    nextn: int = 0                      # next-token modules beyond the first
    attention: str = 'flash'            # dense | flash[:interpret]
    experts: str = 'pallas'             # ragged_dot | pallas[:interpret]
    expert_tile: int = TILE_M
    remat: bool = False                 # recompute each block in the backward pass
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    def layer_plan(self):
        kinds = ['dense'] * self.dense_layers \
            + ['moe'] * (self.num_layers - self.dense_layers)
        return {'layer_kinds': kinds,
                'heads_held': self.heads_held,
                'heads_published': self.heads_published or self.heads_held,
                'experts_held': list(self.experts_held),
                'experts_published': self.experts_published,
                'top_k': self.top_k,
                'vocab_rows_held': self.vocab_size,
                'streams': self.streams,
                'next_token_depth': self.nextn,
                'recompute': bool(self.remat),
                'attention': self.attention, 'experts': self.experts,
                'stream_mixing': hyper_connections.implementation(
                    self.streams, self.d_model)}

    @nn.compact
    def __call__(self, tokens, train=True):
        plan = self.layer_plan()
        key = repr(sorted(plan.items()))
        if key not in _plans_reported:      # once a process, not once a trace
            _plans_reported.add(key)
            get_global_tracer().instant('model.layer_plan', cat='model',
                                        args=plan)
        shared = dict(mesh=self.mesh, batch_axis=self.batch_axis)
        attention_args = dict(
            heads_held=self.heads_held, q_rank=self.q_rank,
            kv_rank=self.kv_rank, nope=self.nope, rope=self.rope,
            v_dim=self.v_dim, attention=self.attention,
            frequencies=yarn_frequencies(
                self.rope, self.rope_theta, self.rope_factor,
                self.rope_original_length, self.rope_beta_fast,
                self.rope_beta_slow),
            softmax_scale=yarn_softmax_scale(
                self.nope + self.rope, self.rope_factor,
                self.rope_mscale_all_dim), **shared)
        maps_args = dict(streams=self.streams,
                         sinkhorn_iterations=self.sinkhorn_iterations,
                         eps=self.stream_eps, clamp=tuple(self.stream_clamp),
                         alpha_init=self.stream_alpha_init,
                         res_diagonal_init=self.stream_res_diagonal_init,
                         **shared)
        moe_args = dict(experts_published=self.experts_published,
                        held=tuple(self.experts_held), top_k=self.top_k,
                        scale=self.routed_scale, d_ff=self.expert_d_ff,
                        shared_d_ff=self.shared_experts * self.expert_d_ff,
                        normalise=self.normalise_top_k, impl=self.experts,
                        tile_m=self.expert_tile, **shared)
        block_class = nn.remat(LatentMoEBlock) if self.remat else LatentMoEBlock

        def block(kind, name):
            return block_class(kind, attention_args, maps_args, moe_args,
                               self.d_ff, dtype=self.dtype, name=name)

        embed = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         name='embed')
        final_norm = RMSNorm(dtype=self.dtype, name='final_norm')
        head = nn.DenseGeneral(self.vocab_size, axis=-1, use_bias=False,
                               dtype=self.dtype, name='head')
        t = tokens.shape[1] - self.nextn
        x = _copies(embed(tokens[:, :t]), self.streams)
        loads = []
        for i, kind in enumerate(plan['layer_kinds']):
            x, load = block(kind, 'block_{}'.format(i))(x)
            loads.append(load)
        h = _summed(x, self.streams).astype(self.dtype)
        logits = [head(final_norm(h)).astype(jnp.float32)]
        for depth in range(self.nextn):
            h, load = NextTokenModule(
                lambda name: block('moe', name), self.streams,
                dtype=self.dtype, name='mtp_{}'.format(depth))(
                    h, embed(tokens[:, depth + 1:depth + 1 + t]))
            loads.append(load)
            logits.append(head(final_norm(h)).astype(jnp.float32))
        return {'logits': tuple(logits),
                'metrics': total_load(self.experts_held, loads)}
