"""Ring attention: exact attention over sequences sharded across a mesh axis.

Long-context training shards the sequence dimension across devices; each
device holds a ``[B, T/n, H, D]`` slice. Dense attention would need the full
``[T, T]`` score matrix — instead key/value blocks rotate around the ring via
``jax.lax.ppermute`` (one ICI hop per step, n-1 steps) while a numerically
stable online softmax (flash-attention-style running max / normalizer)
accumulates the output blockwise. Memory per device stays O(T/n · T/n) and
the rotation overlaps compute, which is exactly the TPU ICI topology's sweet
spot (SURVEY §7 / scaling-book recipe: mesh + collectives, no hand-rolled
NCCL — role parity with the reference's distributed attention path).

Two sequence-parallel schemes are provided, both exact:

* ``ring_self_attention`` — kv blocks rotate around the ring (n-1 ppermute
  hops), O(T/n) activations, no constraint on head count;
* ``a2a_self_attention`` — Ulysses-style: two ``all_to_all``s re-shard
  sequence<->heads so each device runs full-sequence attention on ``H/n``
  heads (cheapest in collective count when heads are plentiful).

Everything here is functional and shard_map-based: the ``*_self_attention``
functions are the public entries; ``_*_local`` are the per-device programs.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


def _ring_attention_local(q, k, v, axis_name, causal, varying_axes):
    """Per-device ring attention body.

    q, k, v: ``[B, T_local, H, D]`` — this device's sequence slice.
    Returns ``[B, T_local, H, D]``.
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])

    q_pos = my_index * t_local + jnp.arange(t_local)          # global positions

    def step(carry, _):
        k_blk, v_blk, blk_index, out, running_max, denom = carry
        # scores for this kv block: [B, H, Tq, Tk]
        scores = jnp.einsum('bqhd,bkhd->bhqk', q, k_blk) * scale
        if causal:
            k_pos = blk_index * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]           # [Tq, Tk]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        # running stats live as [B, Tq, H] (out's layout sans D)
        blk_max = jnp.moveaxis(jnp.max(scores, axis=-1), 1, 2)
        new_max = jnp.maximum(running_max, blk_max)
        # exp(-inf - -inf) guards: a row with nothing unmasked yet keeps
        # new_max = -inf; where() keeps the rescale finite (0).
        correction = jnp.exp(jnp.where(jnp.isneginf(running_max),
                                       -jnp.inf, running_max - new_max))
        probs = jnp.exp(scores - jnp.moveaxis(new_max, 1, 2)[..., None])
        probs = jnp.where(jnp.isneginf(scores), 0.0, probs)   # [B, H, Tq, Tk]
        denom = denom * correction + jnp.moveaxis(probs.sum(axis=-1), 1, 2)
        out = (out * correction[..., None]
               + jnp.einsum('bhqk,bkhd->bqhd', probs, v_blk))
        # rotate the kv block (and its global index) one hop around the ring
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        blk_index = jax.lax.ppermute(blk_index, axis_name, perm)
        return (k_blk, v_blk, blk_index, out, new_max, denom), None

    out0 = jnp.zeros(q.shape, dtype=jnp.float32)
    max0 = jnp.full((q.shape[0], q.shape[1], q.shape[2]), -jnp.inf)  # [B,Tq,H]
    denom0 = jnp.zeros_like(max0)
    # The scan carry must be device-varying from step 0: the accumulators are
    # built from constants, but each step mixes in the (varying) kv blocks,
    # so shard_map's vma check requires the initial carry be cast varying
    # over every mesh axis the inputs are mapped over (seq + any batch/head
    # axes), not just the ring axis.
    out0, max0, denom0 = (jax.lax.pcast(x, varying_axes, to='varying')
                          for x in (out0, max0, denom0))
    carry = (k, v, my_index, out0, max0, denom0)
    (_, _, _, out, _, denom), _ = jax.lax.scan(step, carry, None,
                                               length=axis_size)
    denom = jnp.where(denom == 0.0, 1.0, denom)              # fully masked rows
    return (out / denom[..., None]).astype(q.dtype)


def ring_self_attention(q, k, v, mesh, seq_axis, causal=False,
                        batch_axis=None, head_axis=None):
    """Exact multi-head attention with q/k/v sequence-sharded over
    ``mesh[seq_axis]``.

    :param q, k, v: ``[B, T, H, D]`` arrays (globally); the sequence dim must
        be sharded (or shardable) over ``seq_axis``.
    :param causal: apply a causal mask using *global* positions, so the
        result matches dense causal attention on the unsharded arrays.
    :param batch_axis, head_axis: optional mesh axes carrying the batch /
        head dims. Attention is elementwise over both, so naming them keeps
        each shard local — leaving them ``None`` on a multi-axis mesh makes
        shard_map replicate (all-gather) those dims onto every device,
        re-introducing the full-batch score memory dp/tp exist to divide.
    """
    spec = PartitionSpec(batch_axis, seq_axis, head_axis, None)
    varying = tuple(a for a in (batch_axis, seq_axis, head_axis)
                    if a is not None)
    fn = jax.shard_map(partial(_ring_attention_local, axis_name=seq_axis,
                               causal=causal, varying_axes=varying),
                       mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _a2a_attention_local(q, k, v, axis_name, causal, interpret):
    """Per-device Ulysses body: trade sequence shards for head shards.

    In: ``[B, T/n, H_local, D]`` (sequence-sharded). Two ``all_to_all``s
    bracket an ordinary exact attention over the FULL sequence on a subset
    of heads — attention is elementwise over heads, so the math is identical
    to the unsharded computation.
    """
    n = jax.lax.psum(1, axis_name)
    if q.shape[2] % n:
        raise ValueError('a2a sequence parallelism needs heads ({}) divisible '
                         'by the mesh axis size ({})'.format(q.shape[2], n))

    # One collective each way: q/k/v stacked -> [3, B, T/n, H, D], heads
    # split / sequence concatenated -> [3, B, T, H/n, D].
    qkv = jax.lax.all_to_all(jnp.stack((q, k, v)), axis_name,
                             split_axis=3, concat_axis=2, tiled=True)
    q, k, v = qkv[0], qkv[1], qkv[2]
    # Full sequence locally: the Pallas flash kernel gives O(T) memory; the
    # causal mask needs no global-position bookkeeping because T is whole
    # here.
    from petastorm_tpu.ops.flash_attention import flash_attention
    out = flash_attention(q, k, v, causal=causal, interpret=interpret)
    # [B, T, H/n, D] -> [B, T/n, H, D]
    return jax.lax.all_to_all(out.astype(q.dtype), axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


def a2a_self_attention(q, k, v, mesh, seq_axis, causal=False,
                       batch_axis=None, head_axis=None, interpret=False):
    """Ulysses-style sequence parallelism: all-to-all over ``mesh[seq_axis]``
    re-shards sequence<->heads so each device runs exact attention on the
    full sequence for ``H/n`` heads, then shards the sequence back.

    Complements :func:`ring_self_attention`: two all-to-alls total (vs n-1
    ppermute hops) — cheaper in collective count when heads are plentiful,
    while ring has no ``heads % n`` constraint and keeps peak activation at
    ``O(T/n)``. Same signature; the module layer exposes both as
    ``attention='a2a' | 'ring'``.

    :param q, k, v: ``[B, T, H, D]`` global arrays, sequence-shardable over
        ``seq_axis``. Heads (per ``head_axis`` shard, if tensor parallelism
        is also active) must divide by ``mesh.shape[seq_axis]``.
    :param interpret: passed to :func:`~petastorm_tpu.ops.flash_attention.
        flash_attention`, the per-device block compute: the compiled kernel
        needs a TPU; ``True`` runs it in the Pallas interpreter (CPU tests).
    """
    spec = PartitionSpec(batch_axis, seq_axis, head_axis, None)
    # The Pallas interpreter evaluates the kernel body op by op and its
    # scratch buffers carry no varying-axes type, which the static vma check
    # rejects (jax 0.9.0 says so itself); the compiled call is one opaque
    # primitive and keeps the check.
    fn = jax.shard_map(partial(_a2a_attention_local, axis_name=seq_axis,
                               causal=causal, interpret=interpret),
                       mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=not interpret)
    return fn(q, k, v)


def dense_attention(q, k, v, causal=False, scale=None):
    """Reference dense attention (for tests/small inputs): [B, T, H, D]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', probs, v)
