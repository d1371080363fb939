"""The gated delta rule (linear attention with a decayed, corrected state),
chunked, with the pass over chunks as Pallas TPU kernels (forward + backward).

Per head, with keys of width ``dk`` and values of width ``dv``, a state
``S`` in ``R^{dv x dk}`` that starts at zero::

    S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T        o_t = S_t q_t

``a_t = exp(g_t)`` (``g_t <= 0``) forgets, ``b_t`` in ``[0, 2]`` writes: the
old value stored under ``k_t`` is taken out before the new one goes in.
:func:`gated_delta_scan` is that recurrence as written, token by token.

**The chunked form** (:func:`gated_delta_rule`, Yang et al., arXiv:2412.06464,
section 3). Tokens go in chunks of ``C``; ``c_i`` is the sum of ``g`` from the
chunk's start to token ``i``, ``h`` the transposed state (``dk x dv``) the
chunk starts from. Two stages:

1. *The transform*, every chunk at once, plain batched ``jax.numpy`` that
   ``jax`` differentiates: ``L`` = strictly-lower ``(b_i k_i . k_j)
   exp(c_i - c_j)``; ``T = (I + L)^-1`` as five squarings, ``L`` being
   nilpotent: ``(I - L)(I + L^2)(I + L^4)...``; ``w = T (b k e^c)``,
   ``u = T (b v)``, ``p`` = lower ``(q_i . k_j) exp(c_i - c_j)``,
   ``qg = q e^c``, ``kg = k e^(c_C - c)``, ``ec = e^(c_C)``.
2. *The pass over chunks*, sequential, a ``jax.custom_vjp``
   (:func:`_chunk_pass`)::

       v_new = u - w h      o = qg h + p v_new      h' = ec h + kg^T v_new

   and in reverse, carrying the gradient ``G`` of the state a chunk ends in,
   from the saved ``h`` and ``v_new`` of each chunk::

       H^ = qg^T do            V^ = p^T do            dqg = do h^T
       dp = do v_new^T         dV = V^ + kg G         dkg = v_new G^T
       du = dV                 dw = -dV h^T           dec = sum(G * h)
       G' = H^ + ec G - w^T dV

   Two implementations of the same two passes: ``lax.scan`` over chunks in
   ``jax.numpy`` (``impl='chunked'``: the CPU's form and the kernels'
   reference), and Pallas calls on a grid ``(heads, chunks)`` whose chunk
   axis runs in order with the state in VMEM scratch (``impl='pallas'``,
   ``'pallas:interpret'`` for the Pallas interpreter): four products a chunk
   forward, eight in reverse, nothing of the state in HBM but the copy each
   chunk starts from, which the reverse pass reads.

Every exponent is a difference ``c_i - c_j`` with ``i >= j`` or ``c_i``
itself, so no exponential overflows however strong the decay.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from petastorm_tpu.ops.flash_attention import _once_a_shape, _out_struct
from petastorm_tpu.trace import get_global_tracer

HIGHEST = lax.Precision.HIGHEST
IMPLS = ('chunked', 'pallas', 'pallas:interpret')
#: Heads a grid step of the Pallas passes holds: a chunk's products are small
#: (64 x 96 x 192), so several heads' independent chains fill a step.
HEADS_PER_STEP = 5


# --------------------------------------------------------------------------
# the recurrence as written
# --------------------------------------------------------------------------

def gated_delta_scan(q, k, v, g, beta):
    """Token by token, float32: ``q, k [B, T, H, dk]``, ``v [B, T, H, dv]``,
    ``g, beta [B, T, H]`` -> ``o [B, T, H, dv]``. The definition the chunked
    forms are tested against; differentiable by ``jax`` as it stands."""
    f32 = jnp.float32
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs                  # [B, H, .]
        s = s * jnp.exp(g_t)[..., None, None]
        old = jnp.einsum('bhvk,bhk->bhv', s, k_t, precision=HIGHEST)
        s = s + jnp.einsum('bhv,bhk->bhvk', b_t[..., None] * (v_t - old), k_t,
                           precision=HIGHEST)
        return s, jnp.einsum('bhvk,bhk->bhv', s, q_t, precision=HIGHEST)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, dv, dk), f32), xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


# --------------------------------------------------------------------------
# the plan: what a call will run, reported once
# --------------------------------------------------------------------------

_plans_reported = set()


def chunk_plan(t, heads_held, dk, dv, chunk, impl, dtype):
    """What a call on ``T`` tokens runs: the account ``kernel.gdn_plan``
    carries. ``heads_held`` is the heads of the call; how many the model
    publishes is the model's to say (``model.layer_plan``)."""
    chunks = -(-t // chunk)
    return {'t': t, 'chunk': chunk, 'chunks_per_row': chunks,
            't_pad': chunks * chunk, 'heads_held': heads_held,
            'key_width': dk, 'value_width': dv, 'state_bytes_per_head': 4 * dk * dv,
            'impl': impl, 'dtype': dtype}


def report_plan(name, plan):
    """One ``name`` instant on the global tracer the first time a process
    traces a rule with ``plan`` (a model's layers share one plan, so one
    record, not one a layer or a pass)."""
    key = (name,) + tuple(sorted(plan.items()))
    if key not in _plans_reported:
        _plans_reported.add(key)
        get_global_tracer().instant(name, cat='kernel', args=plan)
    return plan


def _plan_for(q, v, chunk, impl):
    _, t, h, dk = q.shape
    return report_plan('kernel.gdn_plan', chunk_plan(
        t, h, dk, v.shape[-1], chunk, impl, jnp.dtype(q.dtype).name))


# --------------------------------------------------------------------------
# stage 1: the transform, batched over chunks
# --------------------------------------------------------------------------

def _inverse_of_unit_lower(low):
    """``(I + L)^-1`` for strictly lower ``L [..., C, C]``: ``L^C = 0``, so
    the Neumann series is the finite product ``(I - L)(I + L^2)(I + L^4)...``;
    matrix products only, float32 at full precision (they are 64 wide)."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=low.dtype)
    inv, power, reach = eye - low, low, 2
    while reach < c:
        power = jnp.matmul(power, power, precision=HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=HIGHEST)
        reach *= 2
    return inv


def _transform(q, k, v, g, beta):
    """``[BH, N, C, .]`` operands (``g``, ``beta`` ``[BH, N, C]``) ->
    ``qg, p, kg, w, u, ec`` of the module docstring, in ``q``'s dtype but
    ``ec`` (float32 ``[BH, N, 1, 1]``)."""
    f32 = jnp.float32
    dtype = q.dtype
    c = jnp.cumsum(g.astype(f32), axis=-1)                       # [.., C]
    diff = c[..., :, None] - c[..., None, :]
    n = c.shape[-1]
    lower = jnp.tril(jnp.ones((n, n), bool))
    # Masked before the exponential: above the diagonal the difference is
    # positive without bound, and 0 * inf is what a gradient would make of it.
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    kf, bf = k.astype(f32), beta.astype(f32)[..., None]
    kb = kf * bf
    kk = jnp.einsum('...ik,...jk->...ij', kb, kf, precision=HIGHEST)
    inv = _inverse_of_unit_lower(jnp.tril(kk * decay, -1))
    e_c = jnp.exp(c)[..., None]
    w = jnp.matmul(inv, kb * e_c, precision=HIGHEST)
    u = jnp.matmul(inv, v.astype(f32) * bf, precision=HIGHEST)
    qk = jnp.einsum('...ik,...jk->...ij', q, k,
                    preferred_element_type=f32)
    p = qk * decay
    last = c[..., -1:]
    kg = kf * jnp.exp(last - c)[..., None]
    qg = q.astype(f32) * e_c
    ec = jnp.exp(last)[..., None]
    return (qg.astype(dtype), p.astype(dtype), kg.astype(dtype),
            w.astype(dtype), u.astype(dtype), ec)


# --------------------------------------------------------------------------
# stage 2: the pass over chunks, jax.numpy
# --------------------------------------------------------------------------

def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NN = ((1,), (0,))      # a b
_TN = ((0,), (0,))      # a^T b
_NT = ((1,), (1,))      # a b^T


def _chunk_forward(h, qg, p, kg, w, u, ec):
    """One chunk of one head: the state ``h`` (float32 ``[dk, dv]``) it
    starts from -> ``(h', o, v_new)``. The kernels and the ``lax.scan`` form
    run this same body."""
    dtype = w.dtype
    hb = h.astype(dtype)
    v_new = u.astype(jnp.float32) - _dot(w, hb, _NN)
    vb = v_new.astype(dtype)
    o = _dot(qg, hb, _NN) + _dot(p, vb, _NN)
    return ec * h + _dot(kg, vb, _TN), o, vb


def _chunk_backward(grad, do, qg, p, kg, w, ec, h, v_new):
    """One chunk of one head in reverse: ``grad`` (float32 ``[dk, dv]``), the
    gradient of the state the chunk ends in -> ``(grad', dqg, dp, dkg, dw,
    du, dec)``; ``dec`` summed over ``dk`` only, a ``[1, dv]`` row."""
    dtype = w.dtype
    gb = grad.astype(dtype)
    d_v = _dot(p, do, _TN) + _dot(kg, gb, _NN)
    d_vb = d_v.astype(dtype)
    dqg = _dot(do, h, _NT)
    dp = _dot(do, v_new, _NT)
    dkg = _dot(v_new, gb, _NT)
    dw = -_dot(d_vb, h, _NT)
    dec = jnp.sum(grad * h.astype(jnp.float32), axis=0, keepdims=True)
    grad = _dot(qg, do, _TN) + ec * grad - _dot(w, d_vb, _TN)
    return grad, dqg, dp, dkg, dw, d_v, dec


def _chunks_first(a):
    """``[BH, N, ...]`` -> ``[N, BH, ...]``."""
    return jnp.moveaxis(a, 1, 0)


def _pass_forward_jnp(qg, p, kg, w, u, ec):
    """``[BH, N, C, .]`` -> ``o [BH, N, C, dv]``, and what the reverse pass
    reads: each chunk's starting state and its ``v_new``."""
    bh, _, _, dk = w.shape
    dv = u.shape[-1]
    body = jax.vmap(_chunk_forward)

    def step(h, xs):
        h_next, o, v_new = body(h, *xs)
        return h_next, (o, h.astype(w.dtype), v_new)

    xs = tuple(_chunks_first(a) for a in (qg, p, kg, w, u, ec))
    _, (o, h, v_new) = lax.scan(step, jnp.zeros((bh, dk, dv), jnp.float32), xs)
    o, h, v_new = (_chunks_first(a) for a in (o, h, v_new))
    return o.astype(w.dtype), h, v_new


def _pass_backward_jnp(do, qg, p, kg, w, ec, h, v_new):
    bh, _, _, dk = w.shape
    dv = do.shape[-1]
    body = jax.vmap(_chunk_backward)

    def step(grad, xs):
        out = body(grad, *xs)
        return out[0], out[1:]

    xs = tuple(_chunks_first(a) for a in (do, qg, p, kg, w, ec, h, v_new))
    _, grads = lax.scan(step, jnp.zeros((bh, dk, dv), jnp.float32), xs,
                        reverse=True)
    *grads, dec = (_chunks_first(a) for a in grads)
    return tuple(a.astype(w.dtype) for a in grads) + (dec,)


# --------------------------------------------------------------------------
# stage 2: the pass over chunks, Pallas
# --------------------------------------------------------------------------

def _mosaic_params(interpret, independent_axes=1):
    """Heads are independent, chunks follow one another: the state lives in
    scratch along the last grid axis only."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel',) * independent_axes + ('arbitrary',))}


def _heads_per_step(bh):
    g = min(HEADS_PER_STEP, bh)
    while bh % g:
        g -= 1
    return g


def _forward_kernel(qg_ref, p_ref, kg_ref, w_ref, u_ref, ec_ref,
                    o_ref, h_ref, v_ref, state_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    for i in range(state_ref.shape[0]):
        h = state_ref[i]
        h_ref[i] = h.astype(h_ref.dtype)
        state_ref[i], o, v_new = _chunk_forward(
            h, qg_ref[i], p_ref[i], kg_ref[i], w_ref[i], u_ref[i], ec_ref[i])
        o_ref[i] = o.astype(o_ref.dtype)
        v_ref[i] = v_new


def _backward_kernel(do_ref, qg_ref, p_ref, kg_ref, w_ref, ec_ref, h_ref,
                     v_ref, dqg_ref, dp_ref, dkg_ref, dw_ref, du_ref, dec_ref,
                     grad_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        grad_ref[...] = jnp.zeros_like(grad_ref)

    for i in range(grad_ref.shape[0]):
        grad_ref[i], dqg, dp, dkg, dw, du, dec = _chunk_backward(
            grad_ref[i], do_ref[i], qg_ref[i], p_ref[i], kg_ref[i], w_ref[i],
            ec_ref[i], h_ref[i], v_ref[i])
        dqg_ref[i] = dqg.astype(dqg_ref.dtype)
        dp_ref[i] = dp.astype(dp_ref.dtype)
        dkg_ref[i] = dkg.astype(dkg_ref.dtype)
        dw_ref[i] = dw.astype(dw_ref.dtype)
        du_ref[i] = du.astype(du_ref.dtype)
        dec_ref[i] = dec


def _specs(arrays, heads, index_map):
    """One chunk of ``heads`` heads of each ``[BH, N, r, c]`` array."""
    import jax.experimental.pallas as pl
    return [pl.BlockSpec((heads, None) + a.shape[2:], index_map)
            for a in arrays]


def _pass_forward_pallas(qg, p, kg, w, u, ec, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, n, c, dk = w.shape
    dv = u.shape[-1]
    heads = _heads_per_step(bh)
    ec = jnp.broadcast_to(ec, (bh, n, 1, dv))       # a row the state scales by
    operands = (qg, p, kg, w, u, ec)
    outs = [_out_struct((bh, n, c, dv), w.dtype, w),         # o
            _out_struct((bh, n, dk, dv), w.dtype, w),        # h at chunk start
            _out_struct((bh, n, c, dv), w.dtype, w)]         # v_new

    def at(b, i):
        return (b, i, 0, 0)

    return pl.pallas_call(
        _forward_kernel, grid=(bh // heads, n),
        in_specs=_specs(operands, heads, at),
        out_specs=_specs(outs, heads, at), out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
        interpret=interpret, **_mosaic_params(interpret))(*operands)


def _pass_backward_pallas(do, qg, p, kg, w, ec, h, v_new, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, n, c, dk = w.shape
    dv = do.shape[-1]
    heads = _heads_per_step(bh)
    ec = jnp.broadcast_to(ec, (bh, n, 1, dv))
    operands = (do, qg, p, kg, w, ec, h, v_new)
    outs = [_out_struct(qg.shape, w.dtype, w), _out_struct(p.shape, w.dtype, w),
            _out_struct(kg.shape, w.dtype, w), _out_struct(w.shape, w.dtype, w),
            _out_struct((bh, n, c, dv), w.dtype, w),
            _out_struct((bh, n, 1, dv), jnp.float32, w)]

    def at(b, i):
        return (b, n - 1 - i, 0, 0)                 # the last chunk first

    return pl.pallas_call(
        _backward_kernel, grid=(bh // heads, n),
        in_specs=_specs(operands, heads, at),
        out_specs=_specs(outs, heads, at), out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
        interpret=interpret, **_mosaic_params(interpret))(*operands)


# --------------------------------------------------------------------------
# stage 2 as one differentiable function
# --------------------------------------------------------------------------

# ``_once_a_shape`` (flash_attention): one trace a shape, not one a layer, and
# the kernels keep the name of the scope they were called in.

@functools.partial(_once_a_shape, static_argnums=(6,))
def _pass_forward(qg, p, kg, w, u, ec, impl):
    if impl == 'chunked':
        return _pass_forward_jnp(qg, p, kg, w, u, ec)
    return _pass_forward_pallas(qg, p, kg, w, u, ec,
                                interpret=impl == 'pallas:interpret')


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chunk_pass(qg, p, kg, w, u, ec, impl):
    return _pass_forward(qg, p, kg, w, u, ec, impl)[0]


def _chunk_pass_fwd(qg, p, kg, w, u, ec, impl):
    o, h, v_new = _pass_forward(qg, p, kg, w, u, ec, impl)
    return o, (qg, p, kg, w, ec, h, v_new)


@functools.partial(_once_a_shape, static_argnums=(0,))
def _chunk_pass_bwd(impl, residuals, do):
    if impl == 'chunked':
        *grads, dec = _pass_backward_jnp(do, *residuals)
    else:
        *grads, dec = _pass_backward_pallas(
            do, *residuals, interpret=impl == 'pallas:interpret')
    return (*grads, jnp.sum(dec, axis=-1, keepdims=True))


_chunk_pass.defvjp(_chunk_pass_fwd, _chunk_pass_bwd)


# --------------------------------------------------------------------------
# the public function
# --------------------------------------------------------------------------

def gated_delta_rule(q, k, v, g, beta, chunk=64, impl='chunked'):
    """``q, k [B, T, H, dk]`` (normalised and scaled by the caller),
    ``v [B, T, H, dv]``, ``g`` (log of the decay, ``<= 0``) and ``beta``
    ``[B, T, H]`` -> ``o [B, T, H, dv]`` in ``v``'s dtype.

    ``impl``: ``'chunked'`` the chunked rule in ``jax.numpy`` (any
    backend); ``'pallas'`` the same with
    the pass over chunks as compiled Pallas TPU kernels, which a backend
    that is not a TPU refuses; ``'pallas:interpret'`` those kernels in the
    Pallas interpreter. ``T`` is padded to a multiple of ``chunk`` with
    tokens that write nothing (``beta`` 0) and forget nothing (``g`` 0)."""
    if impl not in IMPLS:
        raise ValueError('unknown impl {!r}: one of {}'.format(impl, IMPLS))
    if impl == 'pallas' and jax.devices()[0].platform != 'tpu':
        raise RuntimeError(
            "gated_delta_rule(impl='pallas') compiles Pallas TPU kernels but "
            'the default jax backend is {!r}; use impl=\'pallas:interpret\' '
            "or 'chunked'".format(jax.devices()[0].platform))
    plan = _plan_for(q, v, chunk, impl)
    b, t, h, _ = q.shape
    pad, n = plan['t_pad'] - t, plan['chunks_per_row']

    def chunked(a):
        """``[B, T, H, ...]`` -> ``[B * H, N, C, ...]``, padded with zeros."""
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape((b * h, n, chunk) + a.shape[3:])

    v = v.astype(q.dtype)
    # Stage 1 under a scope of its own (``Tracer.op_scopes`` tells it from
    # the pass); the pass's Pallas calls stay innermost in the caller's.
    with jax.named_scope('transform'):
        operands = _transform(chunked(q), chunked(k), chunked(v), chunked(g),
                              chunked(beta))
    o = _chunk_pass(*operands, impl)                        # [BH, N, C, dv]
    o = o.reshape(b, h, n * chunk, -1)[:, :, :t]
    return jnp.moveaxis(o, 1, 2)
