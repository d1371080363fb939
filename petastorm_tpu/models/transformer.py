"""Decoder-only Transformer LM with pluggable attention backends.

The long-context flagship: the same flax module runs with

* ``attention='dense'`` — reference XLA attention (small inputs, tests),
* ``attention='flash'`` — the Pallas blocked kernels
  (:mod:`petastorm_tpu.ops.flash_attention`), no ``[T, T]`` materialization;
  they read and write the projections' own ``[B, T, H*D]`` arrays, heads by
  128-lane blocks (:class:`FlatDenseGeneral` writes them so); compiled, so
  it needs a TPU,
* ``attention='ring'`` — sequence parallelism: q/k/v sharded over a mesh
  axis, kv blocks rotating over ICI
  (:mod:`petastorm_tpu.models.attention`), for contexts longer than one
  device's HBM,
* ``attention='a2a'`` — Ulysses-style sequence parallelism: two
  ``all_to_all``s re-shard sequence<->heads around full-sequence local
  attention (fewest collectives when heads are plentiful; needs
  ``heads % mesh[seq_axis] == 0``); the local attention is the flash kernel.

``'flash:interpret'`` / ``'a2a:interpret'`` run the same kernels in the
Pallas interpreter — what CPU tests and dry runs name when they mean it.

TPU-first choices: bfloat16 activations with float32 params, pre-LN
residual blocks, static shapes throughout, and the sequence axis is the
only thing that changes between single-chip and pod runs — the module code
is identical (mesh + shardings, XLA inserts the collectives).
"""

import math
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


def usable_axis(mesh, axis, dim):
    """A configured mesh axis carries a dim only when the mesh has it AND it
    evenly divides the (static) dim, so e.g. an init trace with batch 1
    falls back to replication for that trace alone."""
    return (axis if axis in mesh.axis_names and dim % mesh.shape[axis] == 0
            else None)


class FlatDenseGeneral(nn.Module):
    """``nn.DenseGeneral(features, axis=<the last dimensions the kernel
    covers>)`` with the same parameters (names, shapes, initial values) and
    the same values out, computed as one product of 2-D shape with the bias
    added before the result takes its head dimensions: ``[..., K] x [K, N]
    + [N]``, then reshaped. What differs is the program. XLA gives a dot or
    a bias sum whose result is ``[B, T, H, 64]`` a layout with T minor and
    copies every such array on its way to and from a kernel that reads
    ``[B, T, H * 64]`` rows, eight 25 MB copies an attention layer at
    GPT-2's shape; a ``[B, T, H * 64]`` result is written as the flash
    kernels read it, and their reshape is a bitcast."""
    features: Any                       # int or tuple: the output's last dims
    contract: int = 1                   # how many last dims of x are contracted
    use_bias: bool = True
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        features = (tuple(self.features) if isinstance(self.features, tuple)
                    else (self.features,))
        free, contracted = (x.shape[:x.ndim - self.contract],
                            x.shape[x.ndim - self.contract:])
        flat = (math.prod(contracted), math.prod(features))

        def kernel_init(rng, shape, dtype=jnp.float32):
            return nn.linear.default_kernel_init(rng, flat, dtype).reshape(shape)

        kernel = self.param('kernel', kernel_init, contracted + features)
        bias = (self.param('bias', nn.initializers.zeros_init(), features)
                if self.use_bias else None)
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        out = x.reshape(free + flat[:1]) @ kernel.reshape(flat)
        if bias is not None:
            out += bias.reshape(flat[1:])
        return out.reshape(free + features)


def self_attention(q, k, v, attention='dense', causal=True, mesh=None,
                   seq_axis=None, batch_axis='data', head_axis='model',
                   scale=None):
    """``[B, T, H, Dh]`` projections -> ``[B, T, H, Dh]`` through the backend
    ``attention`` names (see the module docstring): what lies between a
    layer's projections and its output projection, shared by
    :class:`MultiHeadAttention`, ``models.hybrid``'s full-attention layer and
    ``models.latent_moe``'s latent attention (whose values are narrower than
    its keys and whose scores take a ``scale`` of their own; ``dense`` and
    ``flash`` only)."""
    num_heads = q.shape[2]
    backend, _, mode = attention.partition(':')
    interpret = mode == 'interpret'
    if mode and not (interpret and backend in ('flash', 'a2a')):
        raise ValueError('unknown attention {!r}'.format(attention))

    if backend in ('ring', 'a2a'):
        if scale is not None:
            raise ValueError('attention={!r} takes no scale'.format(attention))
        if mesh is None or seq_axis is None:
            raise ValueError("attention={!r} needs mesh= and seq_axis="
                             .format(attention))
        from petastorm_tpu.models.attention import (a2a_self_attention,
                                                    ring_self_attention)
        # Keep batch/head shards local inside the shard_map.
        batch_axis = usable_axis(mesh, batch_axis, q.shape[0])
        head_axis = usable_axis(mesh, head_axis, num_heads)
        if backend == 'ring':
            sp_attention = ring_self_attention
        else:
            sp_attention = partial(a2a_self_attention, interpret=interpret)
        return sp_attention(q, k, v, mesh, seq_axis, causal=causal,
                            batch_axis=batch_axis, head_axis=head_axis)
    if backend == 'flash':
        from petastorm_tpu.ops.flash_attention import flash_attention
        attend = partial(flash_attention, causal=causal, interpret=interpret,
                         scale=scale)
        if mesh is not None:
            # A Pallas call is opaque to the SPMD partitioner, which
            # would gather the whole batch onto every device to run it.
            # Attention is elementwise over batch and heads: map the
            # kernel over their shards instead.
            spec = PartitionSpec(usable_axis(mesh, batch_axis, q.shape[0]),
                                 None,
                                 usable_axis(mesh, head_axis, num_heads),
                                 None)
            # check_vma: see models.attention.a2a_self_attention.
            attend = jax.shard_map(attend, mesh=mesh,
                                   in_specs=(spec, spec, spec),
                                   out_specs=spec, check_vma=not interpret)
        return attend(q, k, v)
    if backend == 'dense':
        from petastorm_tpu.models.attention import dense_attention
        return dense_attention(q, k, v, causal=causal, scale=scale)
    raise ValueError('unknown attention {!r}'.format(attention))


class MultiHeadAttention(nn.Module):
    num_heads: int
    attention: str = 'dense'            # dense | flash | ring | a2a
    causal: bool = True                 # (flash/a2a take ':interpret')
    mesh: Any = None                    # required for 'ring' / 'a2a'
    seq_axis: Optional[str] = None      # mesh axis name for 'ring' / 'a2a'
    batch_axis: Optional[str] = 'data'  # mesh axis carrying the batch (sp)
    head_axis: Optional[str] = 'model'  # mesh axis carrying the heads (sp)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        if d_model % self.num_heads:
            raise ValueError('d_model {} not divisible by num_heads {}'.format(
                d_model, self.num_heads))
        head_dim = d_model // self.num_heads

        def proj(name):
            return FlatDenseGeneral((self.num_heads, head_dim),
                                    dtype=self.dtype, name=name)(x)

        q, k, v = proj('query'), proj('key'), proj('value')   # [B, T, H, Dh]

        out = self_attention(q, k, v, attention=self.attention,
                             causal=self.causal, mesh=self.mesh,
                             seq_axis=self.seq_axis,
                             batch_axis=self.batch_axis,
                             head_axis=self.head_axis)

        out = out.astype(self.dtype)
        return FlatDenseGeneral(d_model, contract=2, dtype=self.dtype,
                                name='out')(out)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    attention: str = 'dense'
    causal: bool = True                 # False: bidirectional (e.g. ViT)
    mesh: Any = None
    seq_axis: Optional[str] = None
    batch_axis: Optional[str] = 'data'
    head_axis: Optional[str] = 'model'
    moe_experts: int = 0                # >0: SwitchMoE replaces the MLP
    expert_axis: Optional[str] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        # A sub-layer's norm, its activation and its residual sum run in no
        # module of their own: the two scopes say which sub-layer they are
        # (``Tracer.op_scopes``). The Pallas calls stay innermost in ``attn``.
        with jax.named_scope('mixer'):
            y = nn.LayerNorm(dtype=self.dtype)(x)
            y = MultiHeadAttention(self.num_heads, attention=self.attention,
                                   causal=self.causal,
                                   mesh=self.mesh, seq_axis=self.seq_axis,
                                   batch_axis=self.batch_axis,
                                   head_axis=self.head_axis,
                                   dtype=self.dtype, name='attn')(y)
            x = x + y
        with jax.named_scope('moe' if self.moe_experts > 0 else 'mlp'):
            y = nn.LayerNorm(dtype=self.dtype)(x)
            if self.moe_experts > 0:
                from petastorm_tpu.models.moe import SwitchMoE
                y = SwitchMoE(num_experts=self.moe_experts,
                              mlp_ratio=self.mlp_ratio, mesh=self.mesh,
                              expert_axis=self.expert_axis, dtype=self.dtype,
                              name='moe')(y)
            else:
                y = nn.Dense(d_model * self.mlp_ratio, dtype=self.dtype)(y)
                y = nn.gelu(y)
                y = nn.Dense(d_model, dtype=self.dtype)(y)
            return x + y


class TransformerLM(nn.Module):
    """``[B, T] int32 tokens -> [B, T, vocab] float32 logits`` (causal).

    The head's product is in ``dtype`` and the cast to float32 comes last:
    under ``jax.jit`` with a consumer in the same program (the loss of
    ``models.train``) XLA fuses the cast into that consumer's reads, and no
    float32 ``[B, T, vocab]`` is written."""

    vocab_size: int
    d_model: int = 256
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 2048
    attention: str = 'dense'
    mesh: Any = None
    seq_axis: Optional[str] = None
    batch_axis: Optional[str] = 'data'  # mesh axes carrying batch / heads
    head_axis: Optional[str] = 'model'  # (ring attention shard locality)
    moe_experts: int = 0                # >0: Switch MoE MLPs (expert parallel
    expert_axis: Optional[str] = None   # over this mesh axis)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, train=True):
        b, t = tokens.shape
        if t > self.max_len:
            # XLA's gather would silently clamp out-of-range positions to the
            # last positional embedding — fail loudly instead (t is static).
            raise ValueError('sequence length {} exceeds max_len {}'.format(
                t, self.max_len))
        with jax.named_scope('embed'):
            x = nn.Embed(self.vocab_size, self.d_model,
                         dtype=self.dtype)(tokens)
            pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                           name='pos_embed')(jnp.arange(t)[None, :])
            x = x + pos
        for i in range(self.num_layers):
            x = Block(self.num_heads, attention=self.attention, mesh=self.mesh,
                      seq_axis=self.seq_axis, batch_axis=self.batch_axis,
                      head_axis=self.head_axis, moe_experts=self.moe_experts,
                      expert_axis=self.expert_axis, dtype=self.dtype,
                      name='block_{}'.format(i))(x)
        with jax.named_scope('head'):
            x = nn.LayerNorm(dtype=self.dtype)(x)
            logits = nn.Dense(self.vocab_size, dtype=self.dtype,
                              name='head')(x)
            return logits.astype(jnp.float32)
