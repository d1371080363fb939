"""Blocked (flash) attention as Pallas TPU kernels (forward + backward).

Single-device exact attention without materializing the ``[T, T]`` score
matrix: a 3-D grid ``(batch*heads, q_blocks, kv_blocks)`` streams one
``[block_q, d]`` query tile and one ``[block_k, d]`` kv tile into VMEM per
step — VMEM use is O(block) regardless of sequence length, so context is
bounded by HBM, not VMEM. The online softmax (running max / normalizer)
lives in VMEM scratch that persists across the kv-block axis (TPU grids
execute sequentially, innermost axis fastest), and every matmul runs on the
MXU. The backward is two more Pallas passes (dq over kv blocks; dk+dv over
q blocks) that reconstruct ``P = exp(S - lse)`` tile by tile from the
logsumexp rows the training forward saves — O(block) memory in both
directions. Role parity: the attention compute the reference's training
stacks get from fused CUDA kernels — rebuilt the TPU way.

Composes with :mod:`petastorm_tpu.models.attention`: ring attention shards
the sequence across a mesh axis and rotates kv blocks over ICI; within a
device, this kernel is the block compute. ``flash_attention`` always runs
the Pallas kernels: compiled by Mosaic on a TPU, or — only when the caller
passes ``interpret=True`` — in the Pallas interpreter (how the CPU tests
validate the numerics). Asking for the compiled kernel on any other backend
raises; nothing substitutes the dense reference silently.
"""

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-finite: -inf breaks the running-max rescale at init

_LANES = 128     # VPU lane width: in-kernel scratch vectors are lane-broadcast


def _mosaic_params(interpret):
    """Compiler hints for the compiled path: all three kernels carry their
    online-softmax / accumulator state only along the LAST grid axis, so the
    first two axes (batch*heads, outer block) are declared parallel —
    Mosaic may then reorder/pipeline them freely. Interpret mode takes no
    TPU compiler params."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel', 'parallel', 'arbitrary'))}


def _out_struct(shape, dtype, like):
    """``ShapeDtypeStruct`` for a kernel output that varies over the same
    mesh axes as the input ``like``: inside ``jax.shard_map`` (the a2a
    sequence-parallel path) ``pallas_call`` refuses an ``out_shape`` that
    does not say so; outside one the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _block_mask(qi, ki, block_q, block_k, seq_len, causal):
    """[block_q, block_k] validity mask: kv tail padding + causal triangle."""
    k_pos = ki * block_k + jax.lax.iota(jnp.int32, block_k)
    mask = k_pos[None, :] < seq_len
    if causal:
        q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
        mask = mask & (q_pos[:, None] >= k_pos[None, :])
    return mask


def _recompute_p(q, k_blk, lse_vec, qi, ki, block_q, block_k, seq_len,
                 causal, scale):
    """Rebuild this tile's probabilities ``P = exp(S - lse)`` (backward).

    Operands stay in their input dtype (bf16 matmuls run the MXU at twice
    the f32 rate); the product accumulates in f32 and the scalar scale is
    applied to the f32 product — scale*(QK) == (scale*Q)K up to rounding,
    and post-scaling in f32 keeps more bits than pre-scaling bf16 Q.
    """
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _block_mask(qi, ki, block_q, block_k, seq_len, causal)
    return jnp.where(mask, jnp.exp(s - lse_vec[:, None]), 0.0)


def _to_bhtd(x, t_pad):
    """[B, T, H, D] -> padded [B*H, T_pad, D]."""
    b, t, h, d = x.shape
    x = jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    return x


def _pad_plan(t, block_q, block_k):
    """(block_q, block_k, t_pad): blocks clamped to ``t`` and rounded down
    to powers of two (min 8), ``t`` padded to a multiple of both.

    The power-of-two rounding is load-bearing: clamping alone can hand back
    a block that shares no factors with the other one, and padding to their
    raw lcm then explodes — e.g. ``block_q=512`` against a T=1000 clamp of
    ``block_k=1000`` gives lcm 64,000, a 64x memory/compute cliff for the
    'arbitrary per-device slice lengths' ring attention feeds us. With
    power-of-two blocks the lcm IS the larger block, so padding overhead is
    bounded by ``max_block - 1``. The floor of 8 keeps the sublane dimension
    Mosaic-legal for tiny sequences (the kernel masks the pad via
    ``seq_len``)."""
    def _pow2_floor(b):
        return 1 << (b.bit_length() - 1)

    block_q = max(8, _pow2_floor(min(block_q, t)))
    block_k = max(8, _pow2_floor(min(block_k, t)))
    lcm = block_q * block_k // math.gcd(block_q, block_k)
    return block_q, block_k, -(-t // lcm) * lcm


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, block_q, block_k,
                  seq_len, causal, scale, emit_lse):
    """One grid step: one (block_q, d) query tile x one (block_k, d) kv tile.

    acc/m/l scratch persists across the kv axis (axis 2, innermost): init at
    ki == 0, accumulate every step, normalize + store at the last ki. m/l
    are lane-broadcast ``[block_q, _LANES]`` to respect TPU vector tiling.
    """
    import jax.experimental.pallas as pl

    if emit_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        lse_ref, (acc_ref, m_ref, l_ref) = None, rest

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: kv blocks wholly above the diagonal contribute nothing — skip
    # their matmuls entirely (the diagonal block still needs the mask).
    needed = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(needed)
    def _step():
        q = q_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        # Native-dtype operands, f32 accumulation: bf16 matmuls run the
        # MXU at twice the f32 rate; scale applies to the f32 product.
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _block_mask(qi, ki, block_q, block_k, seq_len, causal)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_new = l_ref[:, 0] * correction + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * correction[:, None]
                        + jax.lax.dot_general(
                            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)                   # fully masked rows
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        if emit_lse:
            # logsumexp rows: the backward kernels reconstruct P without
            # re-running the online softmax.
            lse_ref[...] = m_ref[...] + jnp.log(l[:, None])


def _flash_bhtd(q, k, v, seq_len, causal, block_q, block_k, interpret,
                emit_lse):
    """Padded ``[BH, T_pad, D]`` -> ``out`` (+ ``lse [BH, T_pad, _LANES]`` when
    ``emit_lse`` — the training forward; inference skips the write)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t_pad, d = q.shape
    scale = 1.0 / math.sqrt(d)
    grid = (bh, t_pad // block_q, t_pad // block_k)
    kernel = functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                               seq_len=seq_len, causal=causal, scale=scale,
                               emit_lse=emit_lse)
    # o/lse blocks ignore ki: revisited across the kv axis, written at the
    # last ki only.
    out_specs = [pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0))]
    out_shape = [_out_struct((bh, t_pad, d), q.dtype, q)]
    if emit_lse:
        # Lane-broadcast [BH, T_pad, _LANES] (all lanes carry the same
        # value) — the layout the official TPU flash kernels use for l/m
        # residuals. A (block_q,) rank-1 or (1, block_q) block violates
        # Mosaic's (8,128)-or-full rule on real chips (found on first
        # hardware contact); the 128x HBM redundancy is the price of a
        # layout every Mosaic version tiles natively.
        out_specs.append(pl.BlockSpec((None, block_q, _LANES),
                                      lambda b, qi, ki: (b, qi, 0)))
        out_shape.append(_out_struct((bh, t_pad, _LANES), jnp.float32, q))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),       # acc
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
        ],
        interpret=interpret,
        **_mosaic_params(interpret),
    )(q, k, v)
    return (out[0], out[1]) if emit_lse else (out[0], None)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref,
                     acc_ref, *, block_q, block_k, seq_len, causal, scale):
    """dQ pass: grid (bh, q_blocks, kv_blocks); dq accumulates across ki.

    dS = P * (dO V^T - D);  dQ = scale * dS K, with D = rowsum(dO * O).
    """
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(needed)
    def _step():
        q = q_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        do = do_ref[...]
        p = _recompute_p(q, k_blk, lse_ref[:, 0], qi, ki, block_q, block_k,
                         seq_len, causal, scale)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd_ref[:, 0:1])
        acc_ref[...] += scale * jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                      dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                      block_q, block_k, seq_len, causal, scale):
    """dK/dV pass: grid (bh, kv_blocks, q_blocks); accumulates across qi.

    dV = P^T dO;  dK = dS^T (scale * Q).
    """
    import jax.experimental.pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    needed = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(needed)
    def _step():
        q = q_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        do = do_ref[...]
        p = _recompute_p(q, k_blk, lse_ref[:, 0], qi, ki, block_q, block_k,
                         seq_len, causal, scale)
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd_ref[:, 0:1])
        # dK = dS^T (scale*Q): scale folds onto the f32 accumulator so Q
        # stays a native-dtype operand.
        dk_acc_ref[...] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_bhtd(q, k, v, do, lse, dd, seq_len, causal, block_q, block_k,
                    interpret):
    """Backward over padded ``[BH, T_pad, D]`` tensors -> (dq, dk, dv)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t_pad, d = q.shape
    scale = 1.0 / math.sqrt(d)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, block_q=block_q, block_k=block_k,
                          seq_len=seq_len, causal=causal, scale=scale),
        grid=(bh, t_pad // block_q, t_pad // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=_out_struct((bh, t_pad, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        **_mosaic_params(interpret),
    )(q, k, v, do, lse, dd)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, block_q=block_q, block_k=block_k,
                          seq_len=seq_len, causal=causal, scale=scale),
        grid=(bh, t_pad // block_k, t_pad // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, ki, qi: (b, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            _out_struct((bh, t_pad, d), k.dtype, k),
            _out_struct((bh, t_pad, d), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        **_mosaic_params(interpret),
    )(q, k, v, do, lse, dd)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public entry + custom vjp
# --------------------------------------------------------------------------

def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    interpret=False):
    """Exact multi-head attention, ``[B, T, H, D]`` -> ``[B, T, H, D]``.

    Runs the Pallas blocked kernels compiled for the TPU; ``interpret=True``
    runs them in the Pallas interpreter instead (any backend — the CPU
    tests). The compiled kernel on a backend that is not a TPU raises.
    ``block_q``/``block_k`` default per dtype on TPU —
    ``(512, 1024)`` for bf16, ``(256, 512)`` for f32 (hardware sweep on a
    v5e, T=8192 causal fwd+bwd: (512,1024) sustains ~40 TF/s vs ~11 at
    (128,128); f32 doubles VMEM so its blocks halve to stay inside the
    16MB scoped budget) — and ``(128, 128)`` under the interpreter. Blocks
    are clamped to the sequence length and rounded down to powers of two
    (keeping pad overhead bounded by one block — see ``_pad_plan``);
    sequences are zero-padded up to a block multiple and the pad is
    masked/stripped (padding tolerance is what lets ring attention hand
    this kernel arbitrary per-device slice lengths).

    Differentiable end to end in O(block) memory: the training forward saves
    the logsumexp rows and the backward runs two more Pallas passes (a dq
    pass over kv blocks and a dk/dv pass over q blocks) that reconstruct
    ``P = exp(S - lse)`` tile by tile — no ``[T, T]`` materialization in
    either direction. The inference (non-differentiated) path skips the lse
    write entirely.
    """
    if not interpret and jax.devices()[0].platform != 'tpu':
        raise RuntimeError(
            'flash_attention compiles Pallas TPU kernels but the default jax '
            'backend is {!r}; pass interpret=True to run them in the Pallas '
            'interpreter, or use dense_attention'.format(
                jax.devices()[0].platform))
    if block_q is None or block_k is None:
        if interpret:
            dq, dk = 128, 128
        elif q.dtype == jnp.bfloat16:
            dq, dk = 512, 1024
        else:
            dq, dk = 256, 512
        block_q = dq if block_q is None else block_q
        block_k = dk if block_k is None else block_k
    return _flash_diff(q, k, v, causal, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _flash_pallas(q, k, v, causal, block_q, block_k, interpret,
                           emit_lse=False)
    return out


def _flash_diff_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_pallas(q, k, v, causal, block_q, block_k, interpret,
                             emit_lse=True)
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    b, t, h, d = q.shape
    block_q, block_k, t_pad = _pad_plan(t, block_q, block_k)

    # D = rowsum(dO * O): cheap elementwise+reduce, left to XLA.
    dd = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dd = jnp.moveaxis(dd, 2, 1).reshape(b * h, t)   # [BH, T]
    if t_pad != t:
        # lse is already padded (saved at the forward's padded length).
        dd = jnp.pad(dd, ((0, 0), (0, t_pad - t)))
    # Lane-broadcast like lse: [BH, T_pad, _LANES] (see _flash_bhtd).
    dd = jnp.broadcast_to(dd[:, :, None], (b * h, t_pad, _LANES))

    dq, dk, dv = _flash_bwd_bhtd(
        _to_bhtd(q, t_pad), _to_bhtd(k, t_pad), _to_bhtd(v, t_pad),
        _to_bhtd(g, t_pad), lse, dd, t, causal, block_q, block_k, interpret)

    def from_bhtd(x):
        return jnp.moveaxis(x[:, :t].reshape(b, h, t, d), 1, 2)

    return from_bhtd(dq), from_bhtd(dk), from_bhtd(dv)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def _flash_pallas(q, k, v, causal, block_q, block_k, interpret, emit_lse):
    """Returns ``(out [B,T,H,D], lse [BH, T_pad, _LANES] | None)``."""
    b, t, h, d = q.shape
    block_q, block_k, t_pad = _pad_plan(t, block_q, block_k)
    out, lse = _flash_bhtd(_to_bhtd(q, t_pad), _to_bhtd(k, t_pad),
                           _to_bhtd(v, t_pad), t, causal, block_q, block_k,
                           interpret, emit_lse)
    out = out[:, :t]
    return jnp.moveaxis(out.reshape(b, h, t, d), 1, 2), lse
