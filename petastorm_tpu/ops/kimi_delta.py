"""Kimi delta attention's rule: the gated delta rule with a decay that is a
vector a head and token (Kimi Linear, arXiv:2510.26692, section 3), chunked,
the whole rule as Pallas TPU kernels (forward + backward).

Per head, keys ``dk`` and values ``dv`` wide, a state ``S`` in ``R^{dk x dv}``
that starts at zero::

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T    o_t = S_t^T q_t

``g_t`` in ``R^dk`` (``<= 0``) forgets each key channel at its own rate.
:func:`kda_scan` is that recurrence as written, token by token.

**The chunked form** (:func:`kda_rule`). Tokens go in chunks of ``C``; ``G`` is
the running sum of ``g`` inside the chunk, ``S`` the state it starts from::

    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)          P = lower(sum_c q_ic k_jc exp(G_ic - G_jc))
    L = strictly lower(b_i A_ij)                     T = (I + L)^-1
    W = T (b K e^G)           U = T (b V)
    V_new = U - W S           O = (Q e^G) S + P V_new
    S' = Diag(e^G_C) S + (K e^(G_C - G))^T V_new

With a scalar decay (:mod:`petastorm_tpu.ops.gated_delta`) ``exp(G_i - G_j)``
is a mask laid over ``k k^T``. Here it sits inside the contraction, and its
two factors ``exp(G_i)`` and ``exp(-G_j)`` cannot be formed over a chunk: with
``g`` bounded at ``gate_lower_bound`` = -5 a chunk of 64 reaches -320 and
float32 ends at ``e^88``. So the decayed products go by **sub-blocks** of
``sub_block`` = 16 tokens, each with a reference row ``r_a`` of ``G`` (the
sub-block's first): the rows of sub-block ``a`` take ``k_i exp(G_i - r_a)``
(exponents in ``[-75, 0]``), the columns up to its end ``k_j exp(r_a - G_j)``
(at most 75 inside the sub-block, not positive before it), the columns past it
are not needed and masked before the exponential. No exponent passes ``16 x
5 = 80`` and none is positive without bound.

**The exact path** (``exact=True``) is for a decay with no lower bound (Kimi
Linear's ``g = -exp(A_log) softplus(.)``: a sub-block of 16 tokens at ``-11``
a token spans ``e^176``). The columns of sub-block ``a``'s products stop
before the sub-block (exponents ``r_a - G_j <= 0``), and the pairs inside it
are formed one by one, ``sum_c k_ic k_jc exp(G_ic - G_jc)`` with ``i >= j``
(:func:`_diagonal_pairs`), on the vector unit in float32: every exponent of
the rule is ``<= 0`` for any ``g <= 0``, nothing is clamped or left out.

One chunk of one head is :func:`_chunk_forward_kda` / :func:`_chunk_backward_kda`
on 2-D arrays: the chunk-local quantities (:func:`_local`), then the products
with the state, which are ``ops.gated_delta``'s own (``_chunk_forward``,
``_chunk_backward``: the state's decay a column here where it is a scalar
there). The backward pass is written out (no ``jax`` differentiation inside a
kernel): from the gradients of ``qg, p, kg, w, u`` back through ``T``
(``dL = -lower(dWb W^T + dUb U^T)``), the sub-block products and the
exponentials to ``q, k, v, g, b``. Two implementations run those two bodies:
``lax.scan`` over chunks in ``jax.numpy`` (``impl='chunked'``) and Pallas calls
on a grid ``(rows, heads / s, chunks)`` that read and write the model's own
``[B, T, H d]`` arrays a band of ``s`` 128-lane heads at a time, the chunk
axis in order with the ``s`` states in VMEM scratch (``'pallas'``,
``'pallas:interpret'``). A grid step runs the one-head body batched over
its ``s`` heads (``jax.vmap``: every product and every vector operation
takes the ``s`` heads at once; straight-line code a head at a time gained
a fifth as much, PERF.md section 6, PR 38), so the heads' independent
chains of small products fill each other's waits and the step's fixed cost
is paid once for all ``s`` (:func:`kda_plan`'s ``heads_per_step``). What the
reverse pass reads beside the operands: the state each chunk starts from and
its ``T``.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from petastorm_tpu.ops.flash_attention import _once_a_shape, _out_struct
from petastorm_tpu.ops.gated_delta import (_NN, _NT, _TN, HIGHEST, IMPLS,
                                           _chunk_backward, _chunk_forward,
                                           _dot, _inverse_of_unit_lower,
                                           _mosaic_params, report_plan)

GATE_LOWER_BOUND = -5.0
#: Heads a grid step of the Pallas calls holds at most (a scratch timing at
#: ``[1, 8192, 32, 128]``, PERF.md section 6, PR 38: 1, 2, 4 and 8 heads a
#: step ran a head and chunk forward in 2.16, 1.49, 1.14 and 1.07 us; 16 ask
#: Mosaic for more VMEM than its scoped limit).
HEADS_PER_STEP = 8
#: The exact path's cap: its reverse kernel forms a sub-block's pairs one
#: column after another (:func:`_diagonal_pairs_back`), and at 8 heads a step
#: Mosaic asks 23 MiB of scoped VMEM for it where a v5e allows 16 (4 take it,
#: compiled for a described v5e).
EXACT_HEADS_PER_STEP = 4
#: VMEM the blocks of a grid step may plan for (:func:`kda_plan`'s
#: ``vmem_bytes``), half of Mosaic's default scoped limit on a v5e: the body's
#: own intermediates take the rest.
STEP_VMEM_BUDGET = 8 * 2 ** 20


# --------------------------------------------------------------------------
# the recurrence as written
# --------------------------------------------------------------------------

def kda_scan(q, k, v, g, beta):
    """Token by token, float32: ``q, k, g [B, T, H, dk]``, ``v [B, T, H,
    dv]``, ``beta [B, T, H]`` -> ``o [B, T, H, dv]``. The definition the
    chunked forms are tested against; differentiable by ``jax`` as it
    stands."""
    f32 = jnp.float32
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs                  # [B, H, .]
        s = s * jnp.exp(g_t)[..., None]
        old = jnp.einsum('bhkv,bhk->bhv', s, k_t, precision=HIGHEST)
        s = s + jnp.einsum('bhk,bhv->bhkv', k_t, b_t[..., None] * (v_t - old),
                           precision=HIGHEST)
        return s, jnp.einsum('bhkv,bhk->bhv', s, q_t, precision=HIGHEST)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


# --------------------------------------------------------------------------
# the plan: what a call will run, reported once
# --------------------------------------------------------------------------

def kda_plan(t, heads_held, dk, dv, chunk, sub_block, impl, dtype,
             exact=False):
    """What a call on ``T`` tokens runs: the account ``kernel.kda_plan``
    carries. ``path``: ``'bounded'`` (the gate at or over
    :data:`GATE_LOWER_BOUND`, exponents up to ``largest_exponent``) or
    ``'exact'`` (any gate, no exponent over 0). ``heads_per_step``: the
    heads a grid step of the kernels holds, the largest divisor of
    ``heads_held`` not over :data:`HEADS_PER_STEP` (the exact path's
    :data:`EXACT_HEADS_PER_STEP`) whose blocks fit
    :data:`STEP_VMEM_BUDGET`, 1 at the least. ``vmem_bytes``: what a grid
    step of the reverse kernel, the largest, holds at once: its blocks twice
    (the pipeline's two buffers) and the states' gradients."""
    chunks = -(-t // chunk)
    size = jnp.dtype(dtype).itemsize
    wide = chunk * (2 * dk + dv)
    blocks = (2 * wide * size + chunk * dv * size       # q k v dq dk dv, do
              + 2 * chunk * dk * 4                      # g dg
              + chunks * chunk * 4                      # dbeta, a whole row
              + dk * dv * size + chunk * chunk * size)  # saved state, T
    per_head = 2 * blocks + 4 * dk * dv
    shared = 2 * chunk * heads_held * 4                 # beta, every head
    heads = max(1, min(EXACT_HEADS_PER_STEP if exact else HEADS_PER_STEP,
                       heads_held,
                       (STEP_VMEM_BUDGET - shared) // per_head))
    while heads_held % heads:
        heads -= 1
    return {'t': t, 'chunk': chunk, 'sub_block': sub_block,
            'chunks_per_row': chunks, 't_pad': chunks * chunk,
            'heads_held': heads_held, 'heads_per_step': heads,
            'key_width': dk, 'value_width': dv,
            'path': 'exact' if exact else 'bounded',
            'gate_lower_bound': None if exact else GATE_LOWER_BOUND,
            'largest_exponent': 0.0 if exact else
            -GATE_LOWER_BOUND * (sub_block - 1),
            'state_bytes_per_head': 4 * dk * dv,
            'vmem_bytes': heads * per_head + shared,
            'impl': impl, 'dtype': dtype}


def _plan(q, v, chunk, sub, impl, exact):
    _, t, h, dk = q.shape
    return kda_plan(t, h, dk, v.shape[-1], chunk, sub, impl,
                    jnp.dtype(q.dtype).name, exact)


# --------------------------------------------------------------------------
# one chunk of one head
# --------------------------------------------------------------------------

def _dot32(a, b, dims):
    """A float32 product that has to stay one: sums of ``g`` (to 320) and the
    gradients that come back through them."""
    return lax.dot_general(a, b, (dims, ((), ())), precision=HIGHEST,
                           preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(row):
    """``[1, n] -> [n, 1]`` without a transpose: the row over the diagonal
    of ``[n, n]``, summed along the lanes."""
    n = row.shape[1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _row(column):
    """``[n, 1] -> [1, n]``: :func:`_column` the other way."""
    n = column.shape[0]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(column, (n, n)), 0.0),
                   axis=0, keepdims=True)


def _pick_row(a, index):
    """Row ``index`` of ``a [c, n]`` as ``[1, n]`` (a masked sum: a slice of
    one sublane is no tile)."""
    return jnp.sum(jnp.where(_iota(a.shape, 0) == index, a, 0.0), axis=0,
                   keepdims=True)


def _decayed_key(big_g, k, j):
    """``k_j exp(G_i - G_j)`` for the rows ``i >= j`` of a sub-block's ``G``
    and ``k [sub, dk]`` (0 before ``j``, masked before the exponential), and
    the exponentials."""
    e = jnp.exp(jnp.where(_iota(big_g.shape, 0) >= j,
                          big_g - _pick_row(big_g, j), -jnp.inf))
    return _pick_row(k, j) * e, e


def _sub_blocks(c, sub):
    return [slice(a * sub, (a + 1) * sub) for a in range(c // sub)]


def _diagonal_pairs(big_g, kf, qf, sub):
    """The decayed ``k k^T`` and ``q k^T`` inside each sub-block, pair by
    pair: ``sum_c k_ic k_jc exp(G_ic - G_jc)`` for ``i >= j`` of one
    sub-block, float32, ``[c, c]`` with 0 outside the diagonal sub-blocks.
    Every exponent is a difference ``G_i - G_j <= 0``."""
    c = kf.shape[0]
    col = _iota((sub, c), 1)
    kk, qk = [], []
    for a, rows in enumerate(_sub_blocks(c, sub)):
        g_a, k_a, q_a = big_g[rows], kf[rows], qf[rows]
        kk_a = qk_a = jnp.zeros((sub, c), jnp.float32)
        for j in range(sub):
            y, _ = _decayed_key(g_a, k_a, j)
            mine = col == a * sub + j
            kk_a = jnp.where(mine, jnp.sum(k_a * y, axis=1, keepdims=True),
                             kk_a)
            qk_a = jnp.where(mine, jnp.sum(q_a * y, axis=1, keepdims=True),
                             qk_a)
        kk.append(kk_a)
        qk.append(qk_a)
    return jnp.concatenate(kk, axis=0), jnp.concatenate(qk, axis=0)


def _diagonal_pairs_back(big_g, kf, qf, da, db, sub):
    """:func:`_diagonal_pairs` in reverse: ``da``, ``db [c, c]`` float32, the
    gradients of its two products -> ``(dk, dq, dG) [c, dk]``."""
    c = kf.shape[0]
    col = _iota((sub, c), 1)
    local = _iota((sub, kf.shape[1]), 0)
    out = [], [], []
    for a, rows in enumerate(_sub_blocks(c, sub)):
        g_a, k_a, q_a, da_a, db_a = (x[rows] for x in (big_g, kf, qf, da, db))
        dk_a = dq_a = dg_a = jnp.zeros(k_a.shape, jnp.float32)
        for j in range(sub):
            y, e = _decayed_key(g_a, k_a, j)
            mine = col == a * sub + j
            da_j = jnp.sum(jnp.where(mine, da_a, 0.0), axis=1, keepdims=True)
            db_j = jnp.sum(jnp.where(mine, db_a, 0.0), axis=1, keepdims=True)
            dk_a = dk_a + da_j * y
            dq_a = dq_a + db_j * y
            dy = da_j * k_a + db_j * q_a            # of y's rows
            d_exp = dy * y                          # of G_i - G_j
            at_j = local == j
            dk_a = dk_a + jnp.where(
                at_j, jnp.sum(dy * e, axis=0, keepdims=True), 0.0)
            dg_a = dg_a + d_exp - jnp.where(
                at_j, jnp.sum(d_exp, axis=0, keepdims=True), 0.0)
        for kept, x in zip(out, (dk_a, dq_a, dg_a)):
            kept.append(x)
    return tuple(jnp.concatenate(x, axis=0) for x in out)


def _inverse_by_substitution(low, sub):
    """``(I + L)^-1`` for strictly lower ``L [c, c]`` by forward
    substitution, float32 products at full precision: the sub-blocks' own
    inverses a row of every sub-block at a time (``sub - 1`` products), then
    a block row at a time from the rows already formed (two products each).
    No power of ``L`` is formed: the finite Neumann product of
    ``ops.gated_delta`` forms ``L^32``, which where keys align, the decay is
    weak and ``beta`` nears 2 reaches 1e20, and its sums cancel in float32
    to nothing (or to inf - inf)."""
    c = low.shape[0]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    same = row // sub == col // sub
    own, across = jnp.where(same, low, 0.0), jnp.where(same, 0.0, low)
    inv = (row == col).astype(jnp.float32)
    for s in range(1, sub):
        rows = row % sub == s
        inv = jnp.where(rows, inv - _dot32(jnp.where(rows, own, 0.0), inv,
                                           _NN), inv)
    own_inverse = inv
    for a in range(1, c // sub):
        rows = row // sub == a
        inv = jnp.where(rows, inv - _dot32(own_inverse, _dot32(
            jnp.where(rows, across, 0.0), inv, _NN), _NN), inv)
    return inv


def _local(q, k, v, g, beta, sub, inverse=None, exact=False):
    """The chunk's own quantities, from ``q, k [c, dk]``, ``v [c, dv]``, ``g
    [c, dk]`` float32 and ``beta [c, 1]`` float32; everything float32 but
    the operands of the products, which take ``q``'s dtype. ``inverse``: ``T``
    as a forward pass saved it, where the caller has it. ``exact``: the
    sub-blocks' products stop before the sub-block and its own pairs are
    :func:`_diagonal_pairs`."""
    f32 = jnp.float32
    dtype = q.dtype
    c = q.shape[0]
    blocks = c // sub
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    token = _iota(g.shape, 0)
    g, beta = g.astype(f32), beta.astype(f32)
    qf, kf = q.astype(f32), k.astype(f32)
    tri = (row >= col).astype(f32)
    # G by a product with ones, and the exponent inside a sub-block (G less
    # its reference row) from g itself: a sum of at most ``sub`` terms.
    intra = ((row >= col) & (col > row // sub * sub)).astype(f32)
    big_g = _dot32(tri, g, _NN)
    e_in = jnp.exp(_dot32(intra, g, _NN))
    xk, xq = kf * e_in, qf * e_in
    xkb, xqb = xk.astype(dtype), xq.astype(dtype)
    a_kk = jnp.zeros((c, c), f32)
    a_qk = jnp.zeros((c, c), f32)
    blocks_run = []
    for a in range(blocks):
        # Columns past the sub-block are not needed; masked before the
        # exponential, where the difference is positive without bound. The
        # exact path stops before the sub-block, where it turns positive.
        end = a * sub if exact else (a + 1) * sub
        if not end:
            continue
        e = jnp.exp(jnp.where(token < end,
                              _pick_row(big_g, a * sub) - big_g, -jnp.inf))
        y = kf * e
        yb = y.astype(dtype)
        mine = row // sub == a
        a_kk = a_kk + jnp.where(mine, _dot(xkb, yb, _NT), 0.0)
        a_qk = a_qk + jnp.where(mine, _dot(xqb, yb, _NT), 0.0)
        blocks_run.append((a, e, y))
    if exact:
        pairs_kk, pairs_qk = _diagonal_pairs(big_g, kf, qf, sub)
        a_kk, a_qk = a_kk + pairs_kk, a_qk + pairs_qk
    a_kk = jnp.where(row > col, a_kk, 0.0)
    p = jnp.where(row >= col, a_qk, 0.0)
    if inverse is None:
        inverse = (_inverse_by_substitution(beta * a_kk, sub) if exact else
                   _inverse_of_unit_lower(beta * a_kk))
    decay = jnp.exp(big_g)
    last = _pick_row(big_g, c - 1)
    e_last = jnp.exp(last - big_g)
    kg = kf * decay
    tb = inverse.astype(dtype)
    w = _dot(tb, (beta * kg).astype(dtype), _NN)
    u = _dot(tb, (beta * v.astype(f32)).astype(dtype), _NN)
    return dict(tri=tri, intra=intra, row=row, col=col, token=token,
                e_in=e_in, xk=xk, xq=xq, blocks_run=blocks_run, a_kk=a_kk,
                p=p, inverse=inverse, decay=decay, e_last=e_last, kg=kg,
                qg=qf * decay, kd=kf * e_last, ec_row=jnp.exp(last), w=w, u=u,
                big_g=big_g, kf=kf, qf=qf)


def _chunk_forward_kda(h, q, k, v, g, beta, sub, exact=False):
    """One chunk of one head: the state ``h`` (float32 ``[dk, dv]``) it
    starts from -> ``(h', o, T)``."""
    dtype = q.dtype
    m = _local(q, k, v, g, beta, sub, exact=exact)
    h_next, o, _ = _chunk_forward(
        h, m['qg'].astype(dtype), m['p'].astype(dtype),
        m['kd'].astype(dtype), m['w'].astype(dtype), m['u'],
        _column(m['ec_row']))
    return h_next, o, m['inverse']


def _chunk_backward_kda(grad, do, h, inverse, q, k, v, g, beta, sub,
                        exact=False):
    """One chunk of one head in reverse: ``grad`` (float32 ``[dk, dv]``), the
    gradient of the state the chunk ends in, ``h`` the state it started from
    and ``inverse`` its ``T`` as the forward pass saved them -> ``(grad', dq,
    dk, dv, dg, dbeta [c, 1])``, float32."""
    f32 = jnp.float32
    dtype = q.dtype
    c = q.shape[0]
    m = _local(q, k, v, g, beta, sub, inverse=inverse, exact=exact)
    beta = beta.astype(f32)
    row, col, token = m['row'], m['col'], m['token']
    qg, p, kd, w = (m[name].astype(dtype) for name in ('qg', 'p', 'kd', 'w'))
    hb = h.astype(dtype)
    v_new = (m['u'] - _dot(w, hb, _NN)).astype(dtype)
    d_ec = jnp.sum(grad * hb.astype(f32), axis=1, keepdims=True)   # [dk, 1]
    grad, dqg, dp, dkd, dw, du, _ = _chunk_backward(
        grad, do.astype(dtype), qg, p, kd, w, _column(m['ec_row']), hb, v_new)
    # back through w = T wb, u = T ub and T = (I + L)^-1
    tb = m['inverse'].astype(dtype)
    dwb = _dot(tb, dw.astype(dtype), _TN)
    dub = _dot(tb, du.astype(dtype), _TN)
    d_low = -(_dot(dwb.astype(dtype), w, _NT)
              + _dot(dub.astype(dtype), m['u'].astype(dtype), _NT))
    d_low = jnp.where(row > col, d_low, 0.0)
    vf = v.astype(f32)
    dbeta = jnp.sum(dwb * m['kg'], axis=1, keepdims=True) \
        + jnp.sum(dub * vf, axis=1, keepdims=True) \
        + jnp.sum(d_low * m['a_kk'], axis=1, keepdims=True)
    dkg, dv = beta * dwb, beta * dub
    da = (beta * d_low).astype(dtype)
    db = jnp.where(row >= col, dp, 0.0).astype(dtype)
    # back through the sub-block products
    xkb, xqb = m['xk'].astype(dtype), m['xq'].astype(dtype)
    dxk = jnp.zeros(m['xk'].shape, f32)
    dxq = jnp.zeros(m['xq'].shape, f32)
    dk = jnp.zeros(m['xk'].shape, f32)
    d_big = dkg * m['kg'] + dqg * m['qg']
    zero = jnp.zeros((), dtype)
    for a, e, y in m['blocks_run']:
        mine = row // sub == a
        da_a, db_a = jnp.where(mine, da, zero), jnp.where(mine, db, zero)
        yb = y.astype(dtype)
        dxk = dxk + _dot(da_a, yb, _NN)
        dxq = dxq + _dot(db_a, yb, _NN)
        dy = _dot(da_a, xkb, _TN) + _dot(db_a, xqb, _TN)
        dk = dk + dy * e
        d_exp = dy * y
        d_big = d_big - d_exp + jnp.where(
            token == a * sub, jnp.sum(d_exp, axis=0, keepdims=True), 0.0)
    d_in = dxk * m['xk'] + dxq * m['xq']
    d_last = dkd * m['kd']
    d_big = d_big - d_last + jnp.where(
        token == c - 1, jnp.sum(d_last, axis=0, keepdims=True)
        + _row(d_ec) * m['ec_row'], 0.0)
    dq = dxq * m['e_in'] + dqg * m['decay']
    dk = dk + dxk * m['e_in'] + dkg * m['decay'] + dkd * m['e_last']
    if exact:
        dk_pairs, dq_pairs, dg_pairs = _diagonal_pairs_back(
            m['big_g'], m['kf'], m['qf'], beta * d_low,
            jnp.where(row >= col, dp, 0.0), sub)
        dk, dq, d_big = dk + dk_pairs, dq + dq_pairs, d_big + dg_pairs
    dg = _dot32(m['intra'], d_in, _TN) + _dot32(m['tri'], d_big, _TN)
    return grad, dq, dk, dv, dg, dbeta


# --------------------------------------------------------------------------
# the pass over chunks, jax.numpy: [B, H, N, C, .] operands
# --------------------------------------------------------------------------

def _chunks_first(a):
    """``[B, H, N, ...]`` -> ``[N, B, H, ...]``."""
    return jnp.moveaxis(a, 2, 0)


def _chunks_third(a):
    return jnp.moveaxis(a, 0, 2)


def _over_heads(fn):
    return jax.vmap(jax.vmap(fn))


def _forward_jnp(q, k, v, g, beta, sub, exact):
    b, h, _, _, dk = q.shape
    dv = v.shape[-1]
    body = _over_heads(functools.partial(_chunk_forward_kda, sub=sub,
                                         exact=exact))

    def step(state, xs):
        state_next, o, inverse = body(state, *xs)
        return state_next, (o, state.astype(q.dtype),
                            inverse.astype(q.dtype))

    xs = tuple(_chunks_first(a) for a in (q, k, v, g, beta))
    _, out = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    o, states, inverses = (_chunks_third(a) for a in out)
    return o.astype(q.dtype), states, inverses


def _backward_jnp(do, states, inverses, q, k, v, g, beta, sub, exact):
    b, h, _, _, dk = q.shape
    dv = v.shape[-1]
    body = _over_heads(functools.partial(_chunk_backward_kda, sub=sub,
                                         exact=exact))

    def step(grad, xs):
        out = body(grad, *xs)
        return out[0], out[1:]

    xs = tuple(_chunks_first(a)
               for a in (do, states, inverses, q, k, v, g, beta))
    _, grads = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs,
                        reverse=True)
    return tuple(_chunks_third(a) for a in grads)


def _to_chunks(a, n, chunk):
    """``[B, T_pad, H, ...]`` -> ``[B, H, N, C, ...]``."""
    a = jnp.moveaxis(a, 2, 1)
    return a.reshape(a.shape[:2] + (n, chunk) + a.shape[3:])


def _from_chunks(a):
    """``[B, H, N, C, ...]`` -> ``[B, T_pad, H, ...]``."""
    a = a.reshape(a.shape[:2] + (-1,) + a.shape[4:])
    return jnp.moveaxis(a, 1, 2)


# --------------------------------------------------------------------------
# the pass over chunks, Pallas: the model's own [B, T, H d] arrays
# --------------------------------------------------------------------------

def _beta_columns(beta_ref, heads):
    """The step's heads' columns of the chunk's ``[C, H]`` block, ``[s, C,
    1]``."""
    import jax.experimental.pallas as pl
    block = beta_ref[...].astype(jnp.float32)
    lane = _iota(block.shape, 1)
    first = pl.program_id(1) * heads
    return jnp.stack([
        jnp.sum(jnp.where(lane == first + a, block, 0.0), axis=1,
                keepdims=True) for a in range(heads)])


def _heads(ref, heads):
    """A ``[C, s d]`` block as ``[s, C, d]``: the step's heads on a leading
    axis, from static 128-lane slices."""
    width = ref.shape[1] // heads
    return jnp.stack([ref[:, a * width:(a + 1) * width]
                      for a in range(heads)])


def _put(ref, x):
    """``x [s, C, d]`` into the ``[C, s d]`` block ``ref``, a head's lanes
    at a time."""
    heads, _, width = x.shape
    for a in range(heads):
        ref[:, a * width:(a + 1) * width] = x[a].astype(ref.dtype)


def _step_body(body, sub, exact, heads):
    """The one-head ``body`` over ``[s, ...]`` operands: ``jax.vmap``, so
    that every product and vector operation takes the step's heads at once.
    A lone head runs the body as it stands: batched over one it is slower
    than today's kernel (PERF.md section 6, PR 38)."""
    body = functools.partial(body, sub=sub, exact=exact)
    if heads > 1:
        return jax.vmap(body)
    return lambda *a: tuple(x[None] for x in body(*(x[0] for x in a)))


def _forward_kernel(sub, exact, save, q_ref, k_ref, v_ref, g_ref, beta_ref,
                    o_ref, *rest):
    import jax.experimental.pallas as pl
    state_ref = rest[-1]
    heads = state_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    h = state_ref[...]
    state_ref[...], o, inverse = _step_body(
        _chunk_forward_kda, sub, exact, heads)(
        h, *(_heads(ref, heads) for ref in (q_ref, k_ref, v_ref, g_ref)),
        _beta_columns(beta_ref, heads))
    _put(o_ref, o)
    if save:
        h_ref, t_ref = rest[:2]
        h_ref[...] = h.astype(h_ref.dtype)
        t_ref[...] = inverse.astype(t_ref.dtype)


def _backward_kernel(sub, exact, do_ref, h_ref, t_ref, q_ref, k_ref, v_ref,
                     g_ref, beta_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                     dbeta_ref, grad_ref):
    import jax.experimental.pallas as pl
    i = pl.program_id(2)
    heads = grad_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        grad_ref[...] = jnp.zeros_like(grad_ref)

    grad_ref[...], dq, dk, dv, dg, dbeta = _step_body(
        _chunk_backward_kda, sub, exact, heads)(
            grad_ref[...], _heads(do_ref, heads), h_ref[...], t_ref[...],
            *(_heads(ref, heads) for ref in (q_ref, k_ref, v_ref, g_ref)),
            _beta_columns(beta_ref, heads))
    for ref, x in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv), (dg_ref, dg)):
        _put(ref, x)
    # The heads' write strengths share a lane block, so a head's gradient
    # goes out as a row of its own [chunks, C] block, the last chunk first.
    for a in range(heads):
        dbeta_ref[a, pl.ds(pl.num_programs(2) - 1 - i, 1), :] = \
            _row(dbeta[a])


def _lanes(a):
    """``[B, T, H, d] -> [B, T, H d]``: what the projections write."""
    return a.reshape(a.shape[:2] + (-1,))


def _call(kernel, chunk_of, operands, h, heads, chunk, outs, scratch,
          interpret):
    """``operands``: name -> array; ``outs``: name -> struct; ``h`` heads of
    the call, ``heads`` of a grid step. Blocks by the array's kind: the band
    of ``heads`` 128-lane heads of a chunk of ``[B, T, H d]``, the chunk's
    ``[C, H]`` write strengths, ``heads`` heads' chunk of a ``[B, H, N, .,
    .]`` residual, their whole ``[N, C]`` of ``dbeta``."""
    import jax.experimental.pallas as pl
    b, t = operands['q'].shape[:2]

    def spec(name, a):
        if name == 'beta':
            return pl.BlockSpec((None, chunk, h),
                                lambda b, j, i: (b, chunk_of(i), 0))
        if name == 'dbeta':
            return pl.BlockSpec((None, heads) + a.shape[2:],
                                lambda b, j, i: (b, j, 0, 0))
        if a.ndim == 5:
            return pl.BlockSpec((None, heads, None) + a.shape[3:],
                                lambda b, j, i: (b, j, chunk_of(i), 0, 0))
        return pl.BlockSpec((None, chunk, heads * a.shape[2] // h),
                            lambda b, j, i: (b, chunk_of(i), j))

    return pl.pallas_call(
        kernel, grid=(b, h // heads, t // chunk),
        in_specs=[spec(*item) for item in operands.items()],
        out_specs=[spec(*item) for item in outs.items()],
        out_shape=list(outs.values()), scratch_shapes=scratch,
        interpret=interpret,
        **_mosaic_params(interpret, independent_axes=2))(*operands.values())


def _forward_pallas(q, k, v, g, beta, chunk, sub, heads, save, interpret,
                    exact=False):
    from jax.experimental.pallas import tpu as pltpu
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk
    operands = {'q': _lanes(q), 'k': _lanes(k), 'v': _lanes(v),
                'g': _lanes(g), 'beta': beta}
    outs = {'o': _out_struct((b, t, h * dv), q.dtype, q)}
    if save:
        outs['h'] = _out_struct((b, h, n, dk, dv), q.dtype, q)
        outs['inverse'] = _out_struct((b, h, n, chunk, chunk), q.dtype, q)
    out = _call(functools.partial(_forward_kernel, sub, exact, save),
                lambda i: i,
                operands, h, heads, chunk, outs,
                [pltpu.VMEM((heads, dk, dv), jnp.float32)], interpret)
    return (out[0].reshape(b, t, h, dv),) + tuple(out[1:])


def _backward_pallas(do, states, inverses, q, k, v, g, beta, chunk, sub,
                     heads, interpret, exact=False):
    from jax.experimental.pallas import tpu as pltpu
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk
    operands = {'do': _lanes(do), 'h': states, 'inverse': inverses,
                'q': _lanes(q), 'k': _lanes(k), 'v': _lanes(v),
                'g': _lanes(g), 'beta': beta}
    f32 = jnp.float32
    outs = {'dq': _out_struct((b, t, h * dk), q.dtype, q),
            'dk': _out_struct((b, t, h * dk), q.dtype, q),
            'dv': _out_struct((b, t, h * dv), q.dtype, q),
            'dg': _out_struct((b, t, h * dk), f32, q),
            'dbeta': _out_struct((b, h, n, chunk), f32, q)}
    dq, dk_, dv_, dg, dbeta = _call(
        functools.partial(_backward_kernel, sub, exact), lambda i: n - 1 - i,
        operands, h, heads, chunk, outs,
        [pltpu.VMEM((heads, dk, dv), f32)], interpret)
    wide = (b, t, h, dk)
    return (dq.reshape(wide), dk_.reshape(wide), dv_.reshape(b, t, h, dv),
            dg.reshape(wide),
            jnp.moveaxis(dbeta.reshape(b, h, t), 1, 2))


# --------------------------------------------------------------------------
# the rule as one differentiable function
# --------------------------------------------------------------------------

# ``_once_a_shape`` (flash_attention): one trace a shape, not one a layer, and
# the kernels keep the name of the scope they were called in.

@functools.partial(_once_a_shape, static_argnums=(5, 6, 7, 8, 9))
def _forward(q, k, v, g, beta, chunk, sub, impl, exact, save):
    """``[B, T_pad, H, .]`` operands -> ``(o, states, inverses)``, the last
    two ``None`` where ``save`` is false and the kernels run."""
    if impl == 'chunked':
        n = q.shape[1] // chunk
        o, states, inverses = _forward_jnp(
            *(_to_chunks(a, n, chunk) for a in (q, k, v, g, beta[..., None])),
            sub, exact)
        return _from_chunks(o), states, inverses
    out = _forward_pallas(
        q, k, v, g, beta, chunk, sub,
        _plan(q, v, chunk, sub, impl, exact)['heads_per_step'], save,
        impl == 'pallas:interpret', exact)
    return out if save else out + (None, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rule(q, k, v, g, beta, chunk, sub, impl, exact):
    return _forward(q, k, v, g, beta, chunk, sub, impl, exact, False)[0]


def _rule_fwd(q, k, v, g, beta, chunk, sub, impl, exact):
    o, states, inverses = _forward(q, k, v, g, beta, chunk, sub, impl, exact,
                                   True)
    return o, (states, inverses, q, k, v, g, beta)


@functools.partial(_once_a_shape, static_argnums=(0, 1, 2, 3))
def _rule_bwd(chunk, sub, impl, exact, residuals, do):
    states, inverses, q, k, v, g, beta = residuals
    if impl == 'chunked':
        n = q.shape[1] // chunk
        grads = _backward_jnp(
            _to_chunks(do, n, chunk), states, inverses,
            *(_to_chunks(a, n, chunk) for a in (q, k, v, g, beta[..., None])),
            sub, exact)
        grads = tuple(_from_chunks(a) for a in grads)
        grads = grads[:4] + (grads[4][..., 0],)
    else:
        grads = _backward_pallas(
            do, states, inverses, q, k, v, g, beta, chunk, sub,
            _plan(q, v, chunk, sub, impl, exact)['heads_per_step'],
            impl == 'pallas:interpret', exact)
    return tuple(a.astype(like.dtype) for a, like in
                 zip(grads, (q, k, v, g, beta)))


_rule.defvjp(_rule_fwd, _rule_bwd)


# --------------------------------------------------------------------------
# the public function
# --------------------------------------------------------------------------

def kda_rule(q, k, v, g, beta, chunk=64, sub_block=16, impl='chunked',
             exact=False):
    """``q, k [B, T, H, dk]`` (normalised and scaled by the caller), ``v [B,
    T, H, dv]``, ``g [B, T, H, dk]`` float32 (log of the decay a key channel,
    in ``[GATE_LOWER_BOUND, 0]``: the sub-blocks keep every exponent under
    ``-GATE_LOWER_BOUND * sub_block`` only for such a gate; with ``exact``
    any ``g <= 0``, the sub-blocks' own pairs formed one by one) and ``beta
    [B, T, H]`` -> ``o [B, T, H, dv]`` in ``q``'s dtype.

    ``impl`` as :func:`petastorm_tpu.ops.gated_delta.gated_delta_rule`'s;
    the compiled kernels read a head as a band of whole 128-lane blocks, so
    ``dk`` and ``dv`` are multiples of 128 there. ``T`` is padded to a
    multiple of ``chunk`` with tokens that write nothing (``beta`` 0) and
    forget nothing (``g`` 0)."""
    if impl not in IMPLS:
        raise ValueError('unknown impl {!r}: one of {}'.format(impl, IMPLS))
    if chunk % sub_block:
        raise ValueError('chunk {} is not whole sub-blocks of {}'.format(
            chunk, sub_block))
    _, t, _, dk = q.shape
    dv = v.shape[-1]
    if impl == 'pallas':
        if jax.devices()[0].platform != 'tpu':
            raise RuntimeError(
                "kda_rule(impl='pallas') compiles Pallas TPU kernels but the "
                'default jax backend is {!r}; use impl=\'pallas:interpret\' '
                "or 'chunked'".format(jax.devices()[0].platform))
        if dk % 128 or dv % 128:
            raise ValueError('the compiled kernels read a head as whole '
                             '128-lane blocks: widths {} and {}'.format(dk, dv))
    plan = report_plan('kernel.kda_plan',
                       _plan(q, v, chunk, sub_block, impl, exact))
    pad = plan['t_pad'] - t

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    o = _rule(padded(q), padded(k), padded(v.astype(q.dtype)),
              padded(g.astype(jnp.float32)), padded(beta.astype(jnp.float32)),
              chunk, sub_block, impl, exact)
    return o[:, :t]
