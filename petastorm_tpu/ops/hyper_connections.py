"""A sub-layer between its hyper-connection maps, over ``n`` residual streams
held side by side in the lanes of one array, as Pallas TPU kernels (forward
and backward): manifold-constrained hyper-connections (arXiv:2512.24880
after arXiv:2409.19606) as :mod:`petastorm_tpu.models.latent_moe` states
them.

``hyper_connection(x [..., n d], fn, leaves, n) -> (x' [..., n d], extra)``,
stream ``j`` the lanes ``j d .. (j + 1) d`` of a token's row. Per token, from
``x~ = rmsnorm(x) * scale`` over all ``n d`` values and ``L = x~ Phi`` (``n
(n + 2)`` logits, operands in ``x``'s dtype, accumulated in float32)::

    H_pre  = sigmoid(a_pre L_pre + b_pre)                        [n]
    H_post = 2 sigmoid(a_post L_post + b_post)                   [n]
    H_res  = sinkhorn(exp(clip(a_res L_res + b_res)))            [n, n]
    inner  = sum_j H_pre[j] x_j                  -> fn -> y      [d]
    x'_i   = sum_j H_res[i, j] x_j + H_post[i] y

everything after the product in float32, ``inner`` and ``x'`` rounded to
``x``'s dtype once. Sinkhorn is rows over their sum, then columns over
theirs, ``iterations`` times, ``eps`` in the denominators.

**Four kernels over blocks of tokens**, so that the streams cross HBM as often
as the arithmetic needs and nothing ``n`` wide is an HBM array:

``pre``   reads ``x``; writes ``inner`` and the token's *maps row*: one
          ``[tokens, 128]`` float32 array whose lanes hold ``H`` (``0 .. n (n
          + 2)``), the raw logits ``L`` (from lane 64) and the row's inverse
          rms (lane 127). The product runs on the MXU against ``Phi`` held
          resident, its columns twice (lanes 0 and 64: an MXU pass is 128
          wide whatever it holds); sigmoids and Sinkhorn run on the
          transposed block
          (``[128, tokens]``: an entry of the maps for a block of tokens is a
          row of lanes, not a ``[.., n, n]`` tile).
``post``  reads ``x``, ``y``, the maps row; writes ``x'``.
``dpost`` reads ``dx'``, ``x``, ``y``, the maps row; writes ``dy`` and the
          maps row's gradient (``dH_post``, ``dH_res``: row sums over ``d``).
``dpre``  reads ``dx'``, ``x``, ``d inner``, both rows; recomputes Sinkhorn's
          iterates in VMEM and goes back through them, the sigmoids, the
          product (``dPhi`` accumulated over the blocks in float32, ``dx~``
          formed on the MXU a block at a time, the logits' gradient an
          operand in two parts so that none of its float32 digits is lost
          where the operands are bfloat16) and the rmsnorm, and writes the
          one ``dx``: ``H_res^T dx' + H_pre d inner`` and the norm's part
          summed in float32, rounded once.

``pre`` hands ``x`` on to ``post`` and ``post``'s backward hands ``dx'`` back
through that edge as it is, so that ``dpre`` forms all of ``dx`` from one read
of each operand: the two ``custom_vjp`` functions are one pair and private;
:func:`hyper_connection` is the entry. ``interpret=None`` compiles the kernels
where the default backend is a TPU and runs them in the Pallas interpreter
elsewhere. A device trace names the calls ``hc*``.
"""

import functools
import operator

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from petastorm_tpu.ops.flash_attention import _out_struct
from petastorm_tpu.trace import get_global_tracer

_LANES = 128
#: Lanes of the maps row: ``H`` from 0, the raw logits from ``_LOGITS``, the
#: row's inverse rms at ``_INV``.
_LOGITS, _INV = 64, 127
#: Tokens a block: the transposes between a block's ``[tokens, 128]`` maps
#: and its ``[128, tokens]`` form want whole 128 x 128 tiles, and at the
#: widths of PERF.md's cell (4 x 3584) a block of ``x`` is 3.7 MB in bf16.
BLOCK_TOKENS = 128
_VMEM_LIMIT = 100 * 1024 * 1024
_NORM_EPS = 1e-6        # models.hybrid.rms_normalise's


def implementation(n, d, interpret=None):
    """Which path a sub-layer over ``n`` streams of width ``d`` takes, from
    what the call can see: ``'pallas'`` (``'pallas:interpret'`` off a TPU)
    where every stream is whole vregs wide and the maps row holds the maps
    and their logits, ``'xla'`` otherwise."""
    if d % _LANES or n * (n + 2) > _INV - _LOGITS:
        return 'xla'
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    return 'pallas:interpret' if interpret else 'pallas'


def hc_plan(tokens, n, d, dtype, block, impl):
    """The account a ``kernel.hc_plan`` instant carries: what one sub-layer
    over ``tokens`` tokens runs. ``vmem_bytes`` is what ``dpre``, the largest
    of the four kernels, asks for: its three stream blocks twice (the
    pipeline double-buffers), ``Phi`` and ``dPhi``, and the float32 scratch
    of a block. ``hbm_bytes`` is the streams', ``inner``'s, ``y``'s and the
    maps rows' traffic of a forward and of a backward pass."""
    item = jnp.dtype(dtype).itemsize
    row, maps = n * d * item, _LANES * 4
    forward = tokens * (3 * row + 2 * d * item + 2 * maps)
    backward = tokens * (5 * row + 3 * d * item + 4 * maps)
    vmem = 2 * 3 * block * row + 2 * block * d * item \
        + 2 * n * d * _LANES * (item + 4) + n * block * d * 4
    return {'tokens': tokens, 'streams': n, 'width': d,
            'dtype': jnp.dtype(dtype).name, 'block_tokens': block,
            'blocks': -(-tokens // block), 'vmem_bytes': vmem,
            'hbm_bytes_forward': forward, 'hbm_bytes_backward': backward,
            'impl': impl}


_plans_reported = set()


def _report_plan(*key):
    if key not in _plans_reported:      # once a plan a process
        _plans_reported.add(key)
        get_global_tracer().instant('kernel.hc_plan', cat='kernel',
                                    args=hc_plan(*key))


def _params(interpret, semantics):
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=(semantics,), vmem_limit_bytes=_VMEM_LIMIT)}


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _stream(ref, j, d):
    return ref[:, j * d:(j + 1) * d].astype(jnp.float32)


# -- Sinkhorn over rows of lanes -----------------------------------------------
# The n x n entries of a block of tokens are n * n arrays ``[1, tokens]``,
# entry (i, j) at index i * n + j.

def _group(k, n, columns):
    """The entries of row ``k`` (``columns``: of column ``k``)."""
    return [k + i * n for i in range(n)] if columns \
        else [k * n + j for j in range(n)]


def _normalise(m, n, columns, eps):
    """One half-step: every row (``columns``: every column) of the entries
    ``m`` over its sum + ``eps``. Hands back the new entries and the ``n``
    reciprocals."""
    out, recips = list(m), []
    for k in range(n):
        group = _group(k, n, columns)
        r = 1.0 / (functools.reduce(operator.add, (m[e] for e in group))
                   + eps)
        recips.append(r)
        for e in group:
            out[e] = m[e] * r
    return out, recips


def _normalise_back(dm, m, recips, n, columns):
    """The half-step's transpose: from the gradient ``dm`` of its output
    ``m`` (``m = m_in r``, ``r = 1 / (sum m_in + eps)``), the gradient of its
    input: ``r (dm - sum over the group of dm m)``."""
    out = list(dm)
    for k in range(n):
        group = _group(k, n, columns)
        dot = functools.reduce(operator.add, (dm[e] * m[e] for e in group))
        for e in group:
            out[e] = recips[k] * (dm[e] - dot)
    return out


def _rows(block, count):
    return [block[e:e + 1, :] for e in range(count)]


def _res_start(logits_t, coef_t, n, clamp):
    """``(c, exp(c))`` ``[n n, tokens]`` of the transposed raw logits'
    ``H_res`` rows: ``c = clip(alpha L + b)``."""
    lo, hi = 2 * n, n * (n + 2)
    c = jnp.clip(logits_t[lo:hi] * coef_t[lo:hi, 0:1] + coef_t[lo:hi, 1:2],
                 clamp[0], clamp[1])
    return c, jnp.exp(c)


# -- pre: the maps row and the sub-layer's input -------------------------------

def _pre_kernel(x_ref, scale_ref, phi_ref, coef_ref, inner_ref, maps_ref,
                t_ref, *, n, d, iterations, eps, clamp):
    tm = x_ref.shape[0]
    square = jnp.zeros((tm, 1), jnp.float32)
    for j in range(n):
        xj = _stream(x_ref, j, d)
        square = square + jnp.sum(xj * xj, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(square / (n * d) + _NORM_EPS)
    logits = jnp.zeros((tm, _LANES), jnp.float32)
    for j in range(n):
        x_tilde = (_stream(x_ref, j, d) * inv
                   * scale_ref[:, j * d:(j + 1) * d]).astype(x_ref.dtype)
        logits = logits + jnp.dot(x_tilde, phi_ref[j * d:(j + 1) * d, :],
                                  preferred_element_type=jnp.float32)
    # Tokens to the lanes: a map's entry for the block is then a row.
    logits_t = jnp.where(_lane(logits.shape) == _INV, inv, logits).T
    coef = coef_ref[...]
    m = n * (n + 2)
    t_ref[...] = logits_t       # Phi's second copy put L at _LOGITS already
    gates = jax.nn.sigmoid(logits_t[:2 * n] * coef[:2 * n, 0:1]
                           + coef[:2 * n, 1:2])
    post = jax.lax.broadcasted_iota(jnp.int32, gates.shape, 0) >= n
    t_ref[:2 * n] = jnp.where(post, 2.0 * gates, gates)
    _, start = _res_start(logits_t, coef, n, clamp)

    def iterate(_, entries):
        entries, _ = _normalise(list(entries), n, False, eps)
        entries, _ = _normalise(entries, n, True, eps)
        return tuple(entries)

    entries = jax.lax.fori_loop(0, iterations, iterate,
                                tuple(_rows(start, n * n)))
    for e, entry in enumerate(entries):
        t_ref[2 * n + e:2 * n + e + 1, :] = entry
    maps = t_ref[...].T
    maps_ref[...] = maps
    inner = jnp.zeros((tm, d), jnp.float32)
    for j in range(n):
        inner = inner + maps[:, j:j + 1] * _stream(x_ref, j, d)
    inner_ref[...] = inner.astype(inner_ref.dtype)


def _row_blocks(block, width):
    import jax.experimental.pallas as pl
    return pl.BlockSpec((block, width), lambda i: (i, 0))


def _whole(shape):
    import jax.experimental.pallas as pl
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _scratch(*shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


# One trace a shape, not one a sub-layer, and no trace of the jit in the
# caller's program (ops/flash_attention.py says why).
_once_a_shape = functools.partial(jax.jit, inline=True, static_argnums=(0,))


@_once_a_shape
def _pre_call(static, x, scale, phi, coef):
    import jax.experimental.pallas as pl
    n, iterations, eps, clamp, block, interpret = static
    rows, width = x.shape
    d = width // n
    with jax.named_scope('hc'):
        return pl.pallas_call(
            functools.partial(_pre_kernel, n=n, d=d, iterations=iterations,
                              eps=eps, clamp=clamp),
            grid=(rows // block,),
            in_specs=[_row_blocks(block, width), _whole(scale.shape),
                      _whole(phi.shape), _whole(coef.shape)],
            out_specs=[_row_blocks(block, d), _row_blocks(block, _LANES)],
            out_shape=[_out_struct((rows, d), x.dtype, x),
                       _out_struct((rows, _LANES), jnp.float32, x)],
            scratch_shapes=[_scratch(_LANES, block)],
            interpret=interpret, **_params(interpret, 'parallel'),
        )(x, scale, phi, coef)


# -- post: the three mixings' last two -----------------------------------------

def _post_kernel(x_ref, y_ref, maps_ref, o_ref, *, n, d):
    maps = maps_ref[...]
    y = y_ref[...].astype(jnp.float32)
    for i in range(n):
        out = maps[:, n + i:n + i + 1] * y
        for j in range(n):
            e = 2 * n + i * n + j
            out = out + maps[:, e:e + 1] * _stream(x_ref, j, d)
        o_ref[:, i * d:(i + 1) * d] = out.astype(o_ref.dtype)


@_once_a_shape
def _post_call(static, x, y, maps):
    import jax.experimental.pallas as pl
    n, block, interpret = static
    rows, width = x.shape
    d = width // n
    with jax.named_scope('hc'):
        return pl.pallas_call(
            functools.partial(_post_kernel, n=n, d=d),
            grid=(rows // block,),
            in_specs=[_row_blocks(block, width), _row_blocks(block, d),
                      _row_blocks(block, _LANES)],
            out_specs=_row_blocks(block, width),
            out_shape=_out_struct(x.shape, x.dtype, x),
            interpret=interpret, **_params(interpret, 'parallel'),
        )(x, y, maps)


# -- dpost: dy and the maps row's gradient -------------------------------------

def _dpost_kernel(g_ref, x_ref, y_ref, maps_ref, dy_ref, dmaps_ref, *, n, d):
    maps = maps_ref[...]
    y = y_ref[...].astype(jnp.float32)
    lane = _lane(maps.shape)
    dmaps = jnp.zeros(maps.shape, jnp.float32)
    dy = jnp.zeros(y.shape, jnp.float32)
    for i in range(n):
        gi = _stream(g_ref, i, d)
        dy = dy + maps[:, n + i:n + i + 1] * gi
        dmaps = jnp.where(lane == n + i,
                          jnp.sum(gi * y, axis=-1, keepdims=True), dmaps)
        for j in range(n):
            dmaps = jnp.where(
                lane == 2 * n + i * n + j,
                jnp.sum(gi * _stream(x_ref, j, d), axis=-1, keepdims=True),
                dmaps)
    dy_ref[...] = dy.astype(dy_ref.dtype)
    dmaps_ref[...] = dmaps


@_once_a_shape
def _dpost_call(static, g, x, y, maps):
    import jax.experimental.pallas as pl
    n, block, interpret = static
    rows, width = x.shape
    d = width // n
    with jax.named_scope('hc'):
        return pl.pallas_call(
            functools.partial(_dpost_kernel, n=n, d=d),
            grid=(rows // block,),
            in_specs=[_row_blocks(block, width), _row_blocks(block, width),
                      _row_blocks(block, d), _row_blocks(block, _LANES)],
            out_specs=[_row_blocks(block, d), _row_blocks(block, _LANES)],
            out_shape=[_out_struct(y.shape, y.dtype, y),
                       _out_struct(maps.shape, jnp.float32, maps)],
            interpret=interpret, **_params(interpret, 'parallel'),
        )(g, x, y, maps)


# -- dpre: back through the maps, the product and the norm; the one dx ---------

def _dpre_kernel(g_ref, x_ref, di_ref, maps_ref, dmaps_ref, scale_ref, phi_ref,
                 coef_ref, dx_ref, dphi_ref, dscale_ref, db_ref, da_ref,
                 u_ref, iter_ref, t_ref, *, n, d, iterations, eps, clamp):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        for ref in (dphi_ref, dscale_ref, db_ref, da_ref):
            ref[...] = jnp.zeros_like(ref)

    tm, m = x_ref.shape[0], n * (n + 2)
    maps = maps_ref[...]
    inv = maps[:, _INV:_INV + 1]
    di = di_ref[...].astype(jnp.float32)
    dmaps, lane = dmaps_ref[...], _lane(maps.shape)
    for j in range(n):
        dmaps = jnp.where(
            lane == j,
            jnp.sum(di * _stream(x_ref, j, d), axis=-1, keepdims=True), dmaps)
    maps_t, dmaps_t, coef = maps.T, dmaps.T, coef_ref[...]
    logits_t = maps_t[_LOGITS:_LOGITS + m]
    # the sigmoids: H_pre = s, H_post = 2 s
    gates = maps_t[:2 * n]
    post = jax.lax.broadcasted_iota(jnp.int32, gates.shape, 0) >= n
    t_ref[...] = jnp.zeros_like(t_ref)
    t_ref[:2 * n] = dmaps_t[:2 * n] * gates * (
        1.0 - jnp.where(post, 0.5, 1.0) * gates)
    # Sinkhorn again, every half-step's entries and reciprocals kept, and back
    c, start = _res_start(logits_t, coef, n, clamp)

    def keep(h, entries, recips):
        for e, entry in enumerate(entries):
            iter_ref[h, e:e + 1, :] = entry
        for k, r in enumerate(recips):
            iter_ref[h, n * n + k:n * n + k + 1, :] = r

    def iterate(k, entries):
        entries, recips = _normalise(list(entries), n, False, eps)
        keep(2 * k, entries, recips)
        entries, recips = _normalise(entries, n, True, eps)
        keep(2 * k + 1, entries, recips)
        return tuple(entries)

    jax.lax.fori_loop(0, iterations, iterate, tuple(_rows(start, n * n)))

    def kept(h):
        block = iter_ref[h]
        return _rows(block, n * n), _rows(block[n * n:], n)

    def back(k, grads):
        k = iterations - 1 - k
        grads = _normalise_back(list(grads), *kept(2 * k + 1), n, True)
        grads = _normalise_back(grads, *kept(2 * k), n, False)
        return tuple(grads)

    grads = jax.lax.fori_loop(
        0, iterations, back, tuple(_rows(dmaps_t[2 * n:m], n * n)))
    # d exp(clip(z)) / dz: exp(c) inside the clamp, nothing at it
    slope = jnp.where((c > clamp[0]) & (c < clamp[1]), start, 0.0)
    for e, grad in enumerate(grads):
        t_ref[2 * n + e:2 * n + e + 1, :] = grad * slope[e:e + 1]
    dz = t_ref[:m]
    db_ref[...] += dz
    da_ref[...] += dz * logits_t
    # dL in two parts where the operands are narrower than float32: what
    # their dtype holds of it, and the rest, against Phi's two copies; a sum
    # of the product that all but cancels (block 0's phi_pre) keeps its digits
    dlogits = dz * coef[:m, 0:1]
    high = dlogits.astype(x_ref.dtype).astype(jnp.float32)
    t_ref[:m] = high
    t_ref[_LOGITS:_LOGITS + m] = dlogits - high
    dlogits = t_ref[...].T.astype(x_ref.dtype)
    # the product and the norm: u = dx~ scale, kept for the second pass
    dot_ux = jnp.zeros((tm, 1), jnp.float32)
    for j in range(n):
        lanes = slice(j * d, (j + 1) * d)
        xj = _stream(x_ref, j, d)
        normed = xj * inv
        x_tilde = (normed * scale_ref[:, lanes]).astype(x_ref.dtype)
        dphi_ref[lanes, :] += jax.lax.dot_general(
            x_tilde, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dx_tilde = jax.lax.dot_general(
            dlogits, phi_ref[lanes, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dscale_ref[:, lanes] += jnp.sum(
            (dx_tilde * normed).reshape(tm // 8, 8, d), axis=0)
        u = dx_tilde * scale_ref[:, lanes]
        u_ref[j] = u
        dot_ux = dot_ux + jnp.sum(u * xj, axis=-1, keepdims=True)
    back_x = inv * inv * inv * dot_ux / (n * d)
    for j in range(n):
        dx = inv * u_ref[j] - back_x * _stream(x_ref, j, d) \
            + maps[:, j:j + 1] * di
        for i in range(n):
            e = 2 * n + i * n + j
            dx = dx + maps[:, e:e + 1] * _stream(g_ref, i, d)
        dx_ref[:, j * d:(j + 1) * d] = dx.astype(dx_ref.dtype)


@_once_a_shape
def _dpre_call(static, g, x, di, maps, dmaps, scale, phi, coef):
    import jax.experimental.pallas as pl
    n, iterations, eps, clamp, block, interpret = static
    rows, width = x.shape
    d, m = width // n, n * (n + 2)
    kept = -(-(n * n + n) // 8) * 8
    with jax.named_scope('hc'):
        return pl.pallas_call(
            functools.partial(_dpre_kernel, n=n, d=d, iterations=iterations,
                              eps=eps, clamp=clamp),
            grid=(rows // block,),
            in_specs=[_row_blocks(block, width), _row_blocks(block, width),
                      _row_blocks(block, d), _row_blocks(block, _LANES),
                      _row_blocks(block, _LANES), _whole(scale.shape),
                      _whole(phi.shape), _whole(coef.shape)],
            out_specs=[_row_blocks(block, width), _whole(phi.shape),
                       _whole((8, width)), _whole((m, block)),
                       _whole((m, block))],
            out_shape=[_out_struct(x.shape, x.dtype, x),
                       _out_struct(phi.shape, jnp.float32, x),
                       _out_struct((8, width), jnp.float32, x),
                       _out_struct((m, block), jnp.float32, x),
                       _out_struct((m, block), jnp.float32, x)],
            scratch_shapes=[_scratch(n, block, d),
                            _scratch(2 * iterations, kept, block),
                            _scratch(_LANES, block)],
            interpret=interpret, **_params(interpret, 'arbitrary'),
        )(g, x, di, maps, dmaps, scale, phi, coef)


# -- the pair of custom_vjp functions ------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pre(static, x, scale, phi, coef):
    inner, maps = _pre_call(static, x, scale, phi.astype(x.dtype), coef)
    return inner, maps, x


def _pre_fwd(static, x, scale, phi, coef):
    phi = phi.astype(x.dtype)       # an operand of the product, both passes
    inner, maps = _pre_call(static, x, scale, phi, coef)
    return (inner, maps, x), (x, scale, phi, coef, maps)


def _pre_bwd(static, residuals, cotangents):
    x, scale, phi, coef, maps = residuals
    d_inner, d_maps, g = cotangents     # g: dx', handed back by _post_bwd
    dx, dphi, dscale, db, da = _dpre_call(static, g, x, d_inner, maps, d_maps,
                                          scale, phi, coef)
    dcoef = jnp.stack([da.sum(-1), db.sum(-1)], axis=-1)
    dcoef = jnp.pad(dcoef, ((0, coef.shape[0] - dcoef.shape[0]), (0, 0)))
    return dx, dscale.sum(0, keepdims=True), dphi, dcoef


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _post(static, x, y, maps):
    return _post_call(static, x, y, maps)


def _post_fwd(static, x, y, maps):
    return _post_call(static, x, y, maps), (x, y, maps)


def _post_bwd(static, residuals, g):
    dy, dmaps = _dpost_call(static, g, *residuals)
    # Not x's cotangent but dx' itself: _pre_bwd, which alone receives it,
    # mixes it back through H_res^T with the rest of dx (module docstring).
    return g, dy, dmaps


_post.defvjp(_post_fwd, _post_bwd)


# -- public entry --------------------------------------------------------------

def _operands(leaves, n):
    """The leaves as the kernels take them: ``scale [1, n d]``, ``phi [n d,
    128]`` and ``coef [128, 2]`` (a logit's ``alpha`` and ``b`` a row), all
    float32."""
    width, m = leaves['scale'].shape[0], n * (n + 2)
    phi = jnp.concatenate(
        [leaves['phi_pre'], leaves['phi_post'], leaves['phi_res']], axis=-1)
    # Phi twice, at lane 0 and at lane _LOGITS: one product hands the kernels
    # the logits where the sigmoids read them and where the maps row keeps
    # them, and takes the logits' gradient in two parts
    gap = jnp.zeros((width, _LOGITS - m), jnp.float32)
    phi = phi.astype(jnp.float32)
    phi = jnp.concatenate([phi, gap, phi, gap[:, :_LANES - _LOGITS - m]],
                          axis=-1)
    coef = jnp.stack([
        jnp.concatenate([jnp.broadcast_to(leaves['alpha_' + name], (size,))
                         for name, size in (('pre', n), ('post', n),
                                            ('res', n * n))]),
        jnp.concatenate([leaves['b_pre'], leaves['b_post'],
                         leaves['b_res'].reshape(-1)])], axis=-1)
    coef = jnp.pad(coef.astype(jnp.float32), ((0, _LANES - m), (0, 0)))
    return leaves['scale'].astype(jnp.float32)[None], phi, coef


def _pad_rows(a, rows):
    """Zero rows up to whole blocks: a row of zeros has maps like any other
    and no gradient comes back from it."""
    return a if a.shape[0] == rows else jnp.pad(
        a, ((0, rows - a.shape[0]), (0, 0)))


def hyper_connection(x, fn, leaves, n, iterations=20, eps=1e-6,
                     clamp=(-30.0, 30.0), interpret=None, mesh=None,
                     batch_axis=None):
    """``x [B, T, n d] -> (x' [B, T, n d], extra)``: the ``n`` streams through
    the sub-layer ``fn [B, T, d] -> [B, T, d]`` (or a pair whose second member
    is handed on as ``extra``) between the maps made from ``leaves``:
    ``scale [n d]``, ``phi_pre [n d, n]``, ``phi_post [n d, n]``, ``phi_res [n
    d, n n]``, ``alpha_pre``, ``alpha_post``, ``alpha_res`` (scalars),
    ``b_pre [n]``, ``b_post [n]``, ``b_res [n, n]``. Differentiable in ``x``,
    the leaves and whatever ``fn`` closes over. Needs
    ``implementation(n, d) != 'xla'``. With ``mesh`` the kernels are mapped
    over the batch's shards of ``batch_axis`` (a Pallas call is opaque to the
    SPMD partitioner)."""
    b, t, width = x.shape
    d = width // n
    impl = implementation(n, d, interpret)
    if impl == 'xla' or width != n * d:
        raise ValueError('{} streams in {} lanes: the kernels want streams '
                         'of whole vregs'.format(n, width))
    interpret = impl == 'pallas:interpret'
    scale, phi, coef = _operands(leaves, n)
    clamp = (float(clamp[0]), float(clamp[1]))

    def flat(a):
        return a.reshape(-1, a.shape[-1])

    def padded(rows):
        return -(-rows // BLOCK_TOKENS) * BLOCK_TOKENS

    def pre(x, scale, phi, coef, axis=None):
        if axis is not None and not interpret:
            # As models.moe.RoutedMoE: leaves that vary like the rows get
            # their gradient summed over the shards by the cast's transpose.
            scale, phi, coef = (jax.lax.pcast(a, (axis,), to='varying')
                                for a in (scale, phi, coef))
        rows = x.shape[0] * x.shape[1]
        _report_plan(rows, n, d, x.dtype, BLOCK_TOKENS, impl)
        x2 = _pad_rows(flat(x), padded(rows))
        inner, maps, x2 = _pre(
            (n, iterations, float(eps), clamp, BLOCK_TOKENS, interpret),
            x2, scale, phi, coef)
        return inner[:rows].reshape(x.shape[:2] + (d,)), maps, x2

    def post(x2, y, maps):
        rows = y.shape[0] * y.shape[1]
        y2 = _pad_rows(flat(y), x2.shape[0]).astype(x2.dtype)
        out = _post((n, BLOCK_TOKENS, interpret), x2, y2, maps)
        return out[:rows].reshape(y.shape[:2] + (width,))

    if mesh is not None:
        from petastorm_tpu.models.transformer import usable_axis
        axis = usable_axis(mesh, batch_axis, b)
        rows3, rows2, whole = (PartitionSpec(axis, None, None),
                               PartitionSpec(axis, None), PartitionSpec())
        pre = jax.shard_map(
            functools.partial(pre, axis=axis), mesh=mesh,
            in_specs=(rows3, whole, whole, whole),
            out_specs=(rows3, rows2, rows2), check_vma=not interpret)
        post = jax.shard_map(post, mesh=mesh, in_specs=(rows2, rows3, rows2),
                             out_specs=rows3, check_vma=not interpret)
    inner, maps, x2 = pre(x, scale, phi, coef)
    y = fn(inner)
    y, extra = y if isinstance(y, tuple) else (y, None)
    return post(x2, y, maps), extra
