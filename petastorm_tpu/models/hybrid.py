"""Hybrid linear-attention LM: gated delta-rule layers with a full-attention
layer among every few (the Olmo-Hybrid layout), told which share of each
layer it holds.

``[B, T] int32 tokens -> [B, T, vocab rows held] float32 logits``. A layer is
``x + norm(mixer(x))`` then ``x + norm(mlp(x))`` (norms on the sub-layers'
outputs, as OLMo 2 places them), RMSNorm throughout, SwiGLU feed-forward, no
bias anywhere. ``layer_types`` names each layer's mixer:

* ``'linear_attention'`` (:class:`GatedDeltaMixer`): q, k, v pass a causal
  depthwise convolution and SiLU; q and k are L2-normalised a head; a decay
  and a write strength a head and token drive the gated delta rule
  (:mod:`petastorm_tpu.ops.gated_delta`); each head's output is RMS-normed
  and gated by ``SiLU(W_g x)``. ``linear_attention`` picks the rule's
  implementation as ``attention`` picks flash attention's: ``'pallas'``
  (compiled, a TPU), ``'pallas:interpret'``, ``'chunked'`` (``jax.numpy``).
* ``'full_attention'`` (:class:`FullAttentionMixer`): causal softmax
  attention through :func:`petastorm_tpu.models.transformer.self_attention`
  (``attention='flash'`` the Pallas kernel), RMSNorm on queries and keys, no
  rotation: the linear layers carry position.

**The share.** ``heads_held`` of ``heads_published`` heads live here: the
projections, convolution, recurrence, per-head norms and the output
projection run over the held heads only, and that partial output is what
goes on (the chip of a tensor-parallel deployment before its all-reduce;
nothing stands in for the absent chips). The widths are the published
ones: a full-attention head is ``d_model // heads_published`` wide whatever
is held. ``vocab_size`` is the rows of the vocabulary held: ids, logits and
loss are over that slice.

``remat=True`` recomputes each layer in the backward pass
(``jax.checkpoint``): only the layers' inputs are kept. One
``model.layer_plan`` instant on the global tracer says what a process built.
"""

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from petastorm_tpu.models.transformer import self_attention, usable_axis
from petastorm_tpu.ops.causal_conv import causal_conv_silu
from petastorm_tpu.ops.swiglu import swiglu
from petastorm_tpu.trace import get_global_tracer

LAYER_TYPES = ('linear_attention', 'full_attention')
EPS = 1e-6


def rms_normalise(x, scale, mean_square=None, eps=EPS):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, in float32.
    ``mean_square`` hands the statistic in where it spans more columns than
    ``x`` holds."""
    x32 = x.astype(jnp.float32)
    if mean_square is None:
        mean_square = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(mean_square + eps) * scale).astype(x.dtype)


class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16
    eps: float = EPS

    @nn.compact
    def __call__(self, x, mean_square=None):
        scale = self.param('scale', nn.initializers.ones, (x.shape[-1],))
        return rms_normalise(x.astype(self.dtype), scale, mean_square,
                             self.eps)


def _projection(x, features, name, dtype):
    return nn.DenseGeneral(features, axis=-1, use_bias=False, dtype=dtype,
                           name=name)(x)


def over_row_shards(rule, mesh, batch_axis, impl, *operands):
    """``rule(*operands)``, mapped over the batch's shards where a mesh is
    given and the rule runs Pallas kernels: as for flash attention, a Pallas
    call is opaque to the SPMD partitioner, and rows are independent. Every
    operand and the result lead with the batch."""
    if mesh is None or not impl.startswith('pallas'):
        return rule(*operands)
    axis = usable_axis(mesh, batch_axis, operands[0].shape[0])
    specs = tuple(PartitionSpec(axis, *(None,) * (a.ndim - 1))
                  for a in operands)
    return jax.shard_map(rule, mesh=mesh, in_specs=specs, out_specs=specs[0],
                         check_vma=impl == 'pallas')(*operands)


class GatedDeltaRule(nn.Module):
    """The rule itself, in a module of its own so that a device trace names
    its Pallas calls by the module's name (``gdn``)."""
    chunk: int = 64
    impl: str = 'pallas'
    mesh: Any = None
    batch_axis: Optional[str] = 'data'

    @nn.compact
    def __call__(self, q, k, v, g, beta):
        from petastorm_tpu.ops.gated_delta import gated_delta_rule

        def rule(q, k, v, g, beta):
            return gated_delta_rule(q, k, v, g, beta, chunk=self.chunk,
                                    impl=self.impl)

        return over_row_shards(rule, self.mesh, self.batch_axis, self.impl,
                               q, k, v, g, beta)


class GatedDeltaMixer(nn.Module):
    """Every width of this layer is a head's own (``key_dim``,
    ``value_dim``), so the heads held are all it needs to know."""
    heads_held: int
    key_dim: int = 96
    value_dim: int = 192
    conv_kernel: int = 4
    chunk: int = 64
    impl: str = 'pallas'
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model, h = x.shape[-1], self.heads_held
        x = x.astype(self.dtype)

        def conv(name, width):
            kernel = self.param('conv_' + name, nn.initializers.normal(0.02),
                                (self.conv_kernel, h, width))
            y = _projection(x, (h, width), name + '_proj', self.dtype)
            return causal_conv_silu(y, kernel, mesh=self.mesh,
                                    batch_axis=self.batch_axis)

        def unit(a):
            a32 = a.astype(jnp.float32)
            return a32 * jax.lax.rsqrt(
                jnp.sum(jnp.square(a32), axis=-1, keepdims=True) + EPS)

        q, k, v = (conv('q', self.key_dim), conv('k', self.key_dim),
                   conv('v', self.value_dim))
        q = (unit(q) * self.key_dim ** -0.5).astype(self.dtype)
        k = unit(k).astype(self.dtype)
        a_log = self.param('A_log', nn.initializers.zeros, (h,))
        dt_bias = self.param('dt_bias', nn.initializers.zeros, (h,))
        a = _projection(x, h, 'a_proj', self.dtype).astype(jnp.float32)
        b = _projection(x, h, 'b_proj', self.dtype).astype(jnp.float32)
        g = -jnp.exp(a_log) * nn.softplus(a + dt_bias)     # log of the decay
        beta = 2.0 * nn.sigmoid(b)      # to 2: negative eigenvalues allowed
        o = GatedDeltaRule(chunk=self.chunk, impl=self.impl, mesh=self.mesh,
                           batch_axis=self.batch_axis,
                           name='gdn')(q, k, v, g, beta)
        o = RMSNorm(dtype=self.dtype, name='o_norm')(o)    # over a head's values
        o = o * nn.silu(_projection(x, (h, self.value_dim), 'g_proj',
                                    self.dtype))
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, name='o_proj')(o)


class FullAttentionMixer(nn.Module):
    heads_held: int
    heads_published: Optional[int] = None
    attention: str = 'flash'
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, qk_mean_square=None):
        """``qk_mean_square``: the q/k norms' statistics over all published
        columns, ``([B, T, 1], [B, T, 1])``; left out, each is taken over
        the columns held here (the one-chip program's way)."""
        d_model, h = x.shape[-1], self.heads_held
        head_dim = d_model // (self.heads_published or h)
        x = x.astype(self.dtype)
        b, t = x.shape[:2]

        def proj(name):
            return _projection(x, (h, head_dim), name, self.dtype)

        def normed(a, name, mean_square):
            a = RMSNorm(dtype=self.dtype, name=name)(
                a.reshape(b, t, h * head_dim), mean_square)
            return a.reshape(b, t, h, head_dim)

        q_ms, k_ms = qk_mean_square or (None, None)
        q = normed(proj('query'), 'q_norm', q_ms)
        k = normed(proj('key'), 'k_norm', k_ms)
        out = self_attention(q, k, proj('value'), attention=self.attention,
                             causal=True, mesh=self.mesh,
                             batch_axis=self.batch_axis, head_axis=None)
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, name='out')(
                                   out.astype(self.dtype))


class _Kernel(nn.Module):
    """A bias-free dense layer's kernel alone, under the path, shape and
    initialiser ``nn.DenseGeneral`` gives it, for an op that does the
    product itself."""
    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self):
        return self.param('kernel', nn.initializers.lecun_normal(),
                          self.shape)


class SwiGLU(nn.Module):
    """``down(silu(gate x) · up x)`` through :func:`petastorm_tpu.ops.swiglu.
    swiglu`; the kernels are ``gate``, ``up`` and ``down`` as three dense
    layers would hold them."""
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]

        def kernel(name, shape):
            return _Kernel(shape, name=name)().astype(self.dtype)

        return swiglu(x.astype(self.dtype),
                      kernel('gate', (d_model, self.d_ff)),
                      kernel('up', (d_model, self.d_ff)),
                      kernel('down', (self.d_ff, d_model)))


class HybridBlock(nn.Module):
    layer_type: str
    d_ff: int
    heads_held: int
    heads_published: Optional[int] = None
    key_dim: int = 96
    value_dim: int = 192
    conv_kernel: int = 4
    chunk: int = 64
    attention: str = 'flash'
    linear_attention: str = 'pallas'
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        shared = dict(heads_held=self.heads_held, mesh=self.mesh,
                      batch_axis=self.batch_axis, dtype=self.dtype)
        if self.layer_type == 'linear_attention':
            mixer = GatedDeltaMixer(key_dim=self.key_dim,
                                    value_dim=self.value_dim,
                                    conv_kernel=self.conv_kernel,
                                    chunk=self.chunk,
                                    impl=self.linear_attention,
                                    name='mixer', **shared)
        elif self.layer_type == 'full_attention':
            # Named as TransformerLM names its attention: a device trace
            # names the flash kernels ``attn*`` in both models.
            mixer = FullAttentionMixer(heads_published=self.heads_published,
                                       attention=self.attention, name='attn',
                                       **shared)
        else:
            raise ValueError('unknown layer type {!r}: one of {}'.format(
                self.layer_type, LAYER_TYPES))
        # A sub-layer's norm and its residual sum under the sub-layer's
        # name (``Tracer.op_scopes``); the Pallas calls stay innermost in
        # ``gdn`` and ``attn``.
        with jax.named_scope('mixer'):
            x = x + RMSNorm(dtype=self.dtype, name='mixer_norm')(mixer(x))
        with jax.named_scope('mlp'):
            y = SwiGLU(self.d_ff, dtype=self.dtype, name='mlp')(x)
            return x + RMSNorm(dtype=self.dtype, name='mlp_norm')(y)


_plans_reported = set()


class HybridLM(nn.Module):
    vocab_size: int                     # rows of the vocabulary held here
    d_model: int
    d_ff: int
    layer_types: Sequence[str]
    heads_held: int
    heads_published: Optional[int] = None   # None: every head is held
    key_dim: int = 96
    value_dim: int = 192
    conv_kernel: int = 4
    chunk: int = 64
    attention: str = 'flash'            # as TransformerLM: dense | flash[:interpret]
    linear_attention: str = 'pallas'    # chunked | pallas[:interpret]
    remat: bool = False                 # recompute each layer in the backward pass
    mesh: Any = None
    batch_axis: Optional[str] = 'data'
    dtype: Any = jnp.bfloat16

    def layer_plan(self):
        return {'layer_types': list(self.layer_types),
                'heads_held': self.heads_held,
                'heads_published': self.heads_published or self.heads_held,
                'vocab_rows_held': self.vocab_size,
                'recompute': bool(self.remat),
                'attention': self.attention,
                'linear_attention': self.linear_attention}

    @nn.compact
    def __call__(self, tokens, train=True):
        plan = self.layer_plan()
        key = repr(sorted(plan.items()))
        if key not in _plans_reported:      # once a process, not once a trace
            _plans_reported.add(key)
            get_global_tracer().instant('model.layer_plan', cat='model',
                                        args=plan)
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name='embed')(tokens)
        block = nn.remat(HybridBlock) if self.remat else HybridBlock
        for i, layer_type in enumerate(self.layer_types):
            x = block(layer_type, self.d_ff, self.heads_held,
                      heads_published=self.heads_published,
                      key_dim=self.key_dim, value_dim=self.value_dim,
                      conv_kernel=self.conv_kernel, chunk=self.chunk,
                      attention=self.attention,
                      linear_attention=self.linear_attention, mesh=self.mesh,
                      batch_axis=self.batch_axis, dtype=self.dtype,
                      name='block_{}'.format(i))(x)
        x = RMSNorm(dtype=self.dtype, name='final_norm')(x)
        return _projection(x, self.vocab_size, 'head', self.dtype).astype(
            jnp.float32)
