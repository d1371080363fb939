"""Mamba-2's state-space rule (SSD, Dao and Gu, arXiv:2405.21060), chunked,
the whole rule as Pallas TPU kernels (forward + backward).

Per head, ``P`` wide, a state ``S`` in ``R^{P x N}`` that starts at zero; the
heads of a group read the group's ``B`` and ``C``, ``N`` wide::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

``dt_t >= 0`` (after the softplus) and ``A < 0`` are scalars of the head.
:func:`ssd_scan` is that recurrence as written, token by token.

**The chunked form.** Tokens go in chunks of ``L``; ``c_i`` is the running sum
of ``dt A`` inside the chunk, ``h`` the state the chunk starts from (held as
``S^T``, ``N x P``)::

    E_ij = exp(c_i - c_j) for i >= j, else 0        CB = C B^T   (once a group)
    y = (CB * E) (dt x) + e^c (C h) + D x
    h' = e^(c_L) h + B^T (x dt e^(c_L - c))

Every exponent is a difference ``c_i - c_j`` with ``i >= j``, ``c_i`` or ``c_L
- c_j``, so none is positive: the masked entries are set to ``-inf`` before the
exponential and nothing needs a reference row.

Two implementations. ``impl='xla'``: that form in ``jax.numpy`` at float32,
differentiated by ``jax`` (the CPU's form and the kernels' comparison).
``'pallas'`` (``'pallas:interpret'``): two Pallas calls on a grid ``(rows,
chunks, groups, bands)`` that read and write the model's own flat arrays,
``x [B, T, H P]``, ``B, C [B, T, G N]``, ``dt [B, T, H]``. A band is
:func:`ssd_plan`'s ``heads_per_step`` heads of one group side by side in 128
lanes (two of 64); a head is told apart from its neighbour by a lane mask, so
that the band's state is one ``[N, 128]`` array in VMEM scratch and every
per-head scalar a vector over the lanes. ``C B^T`` is formed once a group and
chunk (by the group's first band) and kept in scratch; in reverse its
gradient is summed there over the group's heads and taken back through the
product once, by the group's last band, which also holds the ``dB`` and
``dC`` blocks its bands add to. The forward pass saves the state each chunk
starts from (bfloat16) for the reverse pass; the reverse pass writes ``dx``,
``dB``, ``dC``, ``d(dt)``, ``dA`` and ``dD``. Every product takes ``x``'s
dtype for its operands and accumulates in float32; decays and states are
float32.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from petastorm_tpu.ops.flash_attention import _once_a_shape, _out_struct
from petastorm_tpu.ops.gated_delta import (_NN, _NT, _TN, HIGHEST, _dot,
                                           report_plan)
from petastorm_tpu.ops.kimi_delta import _column, _iota, _row

IMPLS = ('xla', 'pallas', 'pallas:interpret')
#: Lanes a grid step's band of heads fills.
BAND_LANES = 128


# --------------------------------------------------------------------------
# the recurrence as written
# --------------------------------------------------------------------------

def _split(x, b, c, dt, groups):
    """Flat operands -> ``x [B, T, H, P]``, ``b, c [B, T, H, N]`` (a head
    its group's), float32."""
    bsz, t, h = dt.shape
    f32 = jnp.float32
    x = x.reshape(bsz, t, h, -1).astype(f32)

    def per_head(a):
        a = a.reshape(bsz, t, groups, -1).astype(f32)
        return jnp.repeat(a, h // groups, axis=2)

    return x, per_head(b), per_head(c)


def ssd_scan(x, b, c, dt, a, d, groups):
    """Token by token, float32, the flat operands of :func:`ssd_rule` -> ``y
    [B, T, H P]``. The definition the chunked forms are tested against;
    differentiable by ``jax`` as it stands."""
    f32 = jnp.float32
    xs, bs, cs = _split(x, b, c, dt, groups)
    bsz, t, h, p = xs.shape

    def step(s, inputs):
        x_t, b_t, c_t, dt_t = inputs                  # [B, H, .]
        s = s * jnp.exp(dt_t * a)[..., None, None] + jnp.einsum(
            'bhp,bhn->bhpn', dt_t[..., None] * x_t, b_t, precision=HIGHEST)
        return s, jnp.einsum('bhpn,bhn->bhp', s, c_t, precision=HIGHEST)

    inputs = tuple(jnp.moveaxis(v, 1, 0) for v in (xs, bs, cs,
                                                   dt.astype(f32)))
    _, y = lax.scan(step, jnp.zeros((bsz, h, p, bs.shape[-1]), f32), inputs)
    y = jnp.moveaxis(y, 0, 1) + d[:, None] * xs
    return y.reshape(bsz, t, h * p).astype(x.dtype)


# --------------------------------------------------------------------------
# the plan: what a call will run, reported once
# --------------------------------------------------------------------------

def ssd_plan(t, heads, groups, head_width, state_width, chunk, impl, dtype):
    """What a call on ``T`` tokens runs: the account ``kernel.ssd_plan``
    carries. ``heads_per_step``: the heads of a band, the most of a group
    whose lanes fit :data:`BAND_LANES` (one where a head is wider).
    ``vmem_bytes``: what a grid step of the reverse kernel, the largest,
    holds at once: its blocks twice (the pipeline's two buffers), the bands'
    state gradients and the group's ``C B^T`` and its gradient."""
    chunks = -(-t // chunk)
    per_group = heads // groups
    s = max(1, min(per_group, BAND_LANES // head_width))
    while per_group % s:
        s -= 1
    lanes = s * head_width
    size = jnp.dtype(dtype).itemsize
    blocks = (3 * chunk * lanes * size                  # dy, x, dx
              + state_width * lanes * size              # the saved state
              + 2 * chunk * state_width * size          # B, C
              + 2 * chunk * state_width * 4             # dB, dC
              + 2 * chunk * heads * 4                   # dt, d(dt)
              + 6 * heads * 4)                          # A, D, dA, dD
    scratch = (heads // s) * state_width * lanes * 4 + 2 * chunk * chunk * 4
    return {'t': t, 'chunk': chunk, 'chunks_per_row': chunks,
            't_pad': chunks * chunk, 'heads': heads, 'groups': groups,
            'head_width': head_width, 'state_width': state_width,
            'heads_per_step': s, 'vmem_bytes': 2 * blocks + scratch,
            'impl': impl, 'dtype': dtype}


def _plan(x, b, dt, groups, chunk, impl):
    _, t, h = dt.shape
    return ssd_plan(t, h, groups, x.shape[-1] // h, b.shape[-1] // groups,
                    chunk, impl, jnp.dtype(x.dtype).name)


# --------------------------------------------------------------------------
# impl='xla': the chunked form in jax.numpy
# --------------------------------------------------------------------------

def _ssd_xla(x, b, c, dt, a, d, groups, chunk):
    f32 = jnp.float32
    xs, bs, cs = _split(x, b, c, dt, groups)
    bsz, t, h, p = xs.shape
    n = t // chunk

    def chunks(v):
        return v.reshape((bsz, n, chunk) + v.shape[2:])

    xs, bs, cs = chunks(xs), chunks(bs), chunks(cs)
    dts = chunks(dt.astype(f32))                              # [B, n, L, H]
    cum = jnp.cumsum(dts * a, axis=2)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # [B, n, L, L, H]
    e = jnp.exp(jnp.where(tri[None, None, :, :, None], seg, -jnp.inf))
    cb = jnp.einsum('bnihs,bnjhs->bnijh', cs, bs, precision=HIGHEST)
    y = jnp.einsum('bnijh,bnjh,bnjhp->bnihp', cb * e, dts, xs,
                   precision=HIGHEST)
    last = cum[:, :, -1:, :]
    written = jnp.einsum('bnjhs,bnjh,bnjhp->bnhsp', bs,
                         jnp.exp(last - cum) * dts, xs, precision=HIGHEST)

    def carry(state, inputs):
        write, decay = inputs
        return state * decay[..., None, None] + write, state

    _, starts = lax.scan(carry, jnp.zeros((bsz, h) + written.shape[3:], f32),
                         (jnp.moveaxis(written, 1, 0),
                          jnp.moveaxis(jnp.exp(last[:, :, 0]), 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                     # [B, n, H, N, P]
    y = y + jnp.einsum('bnihs,bnhsp,bnih->bnihp', cs, starts, jnp.exp(cum),
                       precision=HIGHEST)
    y = y + d[:, None] * xs
    return y.reshape(bsz, t, h * p).astype(x.dtype)


# --------------------------------------------------------------------------
# one band of heads and one chunk, as the kernels hold them
# --------------------------------------------------------------------------

def _lane_sums(values, width, s, first=0):
    """``[R, lanes]`` -> ``s`` arrays ``[R, 1]``: the lanes of heads ``first
    .. first + s`` summed a head, ``width`` lanes each (a masked sum: the
    heads share a lane block)."""
    head = _iota(values.shape, 1) // width
    return [jnp.sum(jnp.where(head == first + i, values, 0.0), axis=1,
                    keepdims=True) for i in range(s)]


def _spread(values, width, lanes, first=0):
    """:func:`_lane_sums` the other way: ``s`` arrays ``[R, 1]`` ->
    ``[R, lanes]``, head ``first + i``'s value in its ``width`` lanes, zeros
    elsewhere."""
    head = _iota((1, lanes), 1) // width
    out = jnp.zeros((values[0].shape[0], lanes), jnp.float32)
    for i, v in enumerate(values):
        out = jnp.where(head == first + i, v, out)
    return out


def _decay(dt, a):
    """``dt [L, 1]`` and ``a [1, 1]`` of a head -> ``(c [L, 1], c_L [1, 1],
    E [L, L])``: the running sum of ``dt a``, its last value, and
    ``exp(c_i - c_j)`` under the diagonal (zero above it, masked before the
    exponential)."""
    n = dt.shape[0]
    row, col = _iota((n, n), 0), _iota((n, n), 1)
    c = jnp.sum(jnp.where(col <= row, _row(dt * a), 0.0), axis=1,
                keepdims=True)
    c_last = jnp.sum(jnp.where(_iota(c.shape, 0) == n - 1, c, 0.0), axis=0,
                     keepdims=True)
    e = jnp.exp(jnp.where(row >= col, c - _row(c), -jnp.inf))
    return c, c_last, e


class _Band(object):
    """What a band of ``s`` heads, ``width`` lanes each, derives from a
    chunk's ``dt`` block and the heads' ``A`` and ``D``, for both passes."""

    def __init__(self, dt_block, a_row, d_row, first, s, width):
        self.s, self.width, self.lanes = s, width, s * width
        self.dt = _lane_sums(dt_block, 1, s, first)
        self.a = _lane_sums(a_row, 1, s, first)
        self.d = _lane_sums(d_row, 1, s, first)
        self.decays = [_decay(dt, a) for dt, a in zip(self.dt, self.a)]
        self.ec = [jnp.exp(c) for c, _, _ in self.decays]
        self.el = [jnp.exp(c_last) for _, c_last, _ in self.decays]
        self.tail = [jnp.exp(c_last - c) for c, c_last, _ in self.decays]
        self.w = [t * dt for t, dt in zip(self.tail, self.dt)]
        self.head = _iota((1, self.lanes), 1) // width

    def lanes_of(self, values):
        return _spread(values, self.width, self.lanes)

    def only(self, i, a):
        return jnp.where(self.head == i, a, jnp.zeros((), a.dtype))


def _band_forward(h, cb, x, bm, cm, band):
    """One chunk of a band: the state ``h`` (float32 ``[N, lanes]``) it
    starts from and the group's ``C B^T`` (float32) -> ``(h', y)``."""
    f32, dtype = jnp.float32, x.dtype
    xf = x.astype(f32)
    xdt = (xf * band.lanes_of(band.dt)).astype(dtype)
    y = jnp.zeros(x.shape, f32)
    for i, (_, _, e) in enumerate(band.decays):
        y = y + _dot((cb * e).astype(dtype), band.only(i, xdt), _NN)
    y = y + band.lanes_of(band.ec) * _dot(cm, h.astype(dtype), _NN) \
        + band.lanes_of(band.d) * xf
    h_next = band.lanes_of(band.el) * h + _dot(
        bm, (xf * band.lanes_of(band.w)).astype(dtype), _TN)
    return h_next, y


def _band_backward(grad, dy, h, cb, x, bm, cm, band):
    """One chunk of a band in reverse: ``grad`` (float32 ``[N, lanes]``), the
    gradient of the state the chunk ends in, ``h`` the state it started
    from -> ``(grad', dx, dB, dC, d(C B^T), [d(dt)], [dA], [dD])``, the last
    three a ``[L, 1]`` or ``[1, 1]`` array a head; ``dB`` and ``dC`` leave out
    what comes back through ``C B^T``."""
    f32, dtype = jnp.float32, x.dtype
    s, width, n = band.s, band.width, x.shape[0]
    row, col = _iota((n, n), 0), _iota((n, n), 1)
    xf, dyf = x.astype(f32), dy.astype(f32)
    hb, gb = h.astype(dtype), grad.astype(dtype)
    # the state read: y += e^c (C h)
    q = (band.lanes_of(band.ec) * dyf).astype(dtype)
    dc = _dot(q, hb, _NT)
    d_ec = _lane_sums(dyf * _dot(cm, hb, _NN), width, s)
    # the state written: h' = e^(c_L) h + B^T (x w)
    bg = _dot(bm, gb, _NN)
    dx = band.lanes_of(band.w) * bg + band.lanes_of(band.d) * dyf
    dw = _lane_sums(xf * bg, width, s)
    db = _dot((xf * band.lanes_of(band.w)).astype(dtype), gb, _NT)
    d_el = _lane_sums(jnp.sum(grad * h.astype(f32), axis=0, keepdims=True),
                      width, s)
    grad = band.lanes_of(band.el) * grad + _dot(cm, q, _TN)
    dd = _lane_sums(jnp.sum(dyf * xf, axis=0, keepdims=True), width, s)
    # inside the chunk: y += (C B^T * E) (dt x)
    xdt = (xf * band.lanes_of(band.dt)).astype(dtype)
    dxdt = jnp.zeros(x.shape, f32)
    dcb = jnp.zeros((n, n), f32)
    dc_cols = []
    for i, (c, _, e) in enumerate(band.decays):
        p = cb * e
        dy_i = band.only(i, dy)
        dxdt = dxdt + _dot(p.astype(dtype), dy_i, _TN)
        dp = _dot(dy_i, band.only(i, xdt), _NT)
        dcb = dcb + dp * e
        dseg = dp * p                   # zero above the diagonal
        last = jnp.sum(dw[i] * band.w[i], axis=0, keepdims=True) \
            + d_el[i] * band.el[i]
        dc_cols.append(
            jnp.sum(dseg, axis=1, keepdims=True)
            - _column(jnp.sum(dseg, axis=0, keepdims=True))
            + d_ec[i] * band.ec[i] - dw[i] * band.w[i]
            + jnp.where(_iota(c.shape, 0) == n - 1, last, 0.0))
    dx = dx + dxdt * band.lanes_of(band.dt)
    d_dt_x = _lane_sums(dxdt * xf, width, s)
    ddt, da = [], []
    for i, dc_col in enumerate(dc_cols):
        # back through the running sum: d(dt a)_j = sum over i >= j of dc_i
        d_step = _column(jnp.sum(jnp.where(row >= col, dc_col, 0.0), axis=0,
                                 keepdims=True))
        ddt.append(d_step * band.a[i] + dw[i] * band.tail[i] + d_dt_x[i])
        da.append(jnp.sum(d_step * band.dt[i], axis=0, keepdims=True))
    return grad, dx, db, dc, dcb, ddt, da, dd


# --------------------------------------------------------------------------
# the Pallas calls
# --------------------------------------------------------------------------

def _compiler_params(interpret):
    """Rows are independent; chunks, groups and bands carry the states, the
    group's ``C B^T`` and the blocks they add to."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel', 'arbitrary', 'arbitrary',
                             'arbitrary'))}


def _forward_kernel(dims, x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref,
                    *rest, save):
    import jax.experimental.pallas as pl
    s, width, bands = dims
    state_ref, cb_ref = rest[-2:]
    i, g, k = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    at = g * bands + k

    @pl.when(k == 0)
    def _group():
        cb_ref[...] = _dot(c_ref[...], b_ref[...], _NT)

    @pl.when(i == 0)
    def _init():
        state_ref[at] = jnp.zeros(state_ref.shape[1:], state_ref.dtype)

    h = state_ref[at]
    if save:
        rest[0][...] = h.astype(rest[0].dtype)
    band = _Band(dt_ref[...], a_ref[...], d_ref[...], at * s, s, width)
    state_ref[at], y = _band_forward(h, cb_ref[...], x_ref[...], b_ref[...],
                                     c_ref[...], band)
    y_ref[...] = y.astype(y_ref.dtype)


def _backward_kernel(dims, dy_ref, h_ref, x_ref, b_ref, c_ref, dt_ref, a_ref,
                     d_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref,
                     grad_ref, cb_ref, dcb_ref):
    import jax.experimental.pallas as pl
    s, width, bands = dims
    i, g, k = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    at = g * bands + k
    heads = dt_ref.shape[1]

    @pl.when(k == 0)
    def _group():
        cb_ref[...] = _dot(c_ref[...], b_ref[...], _NT)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)

    @pl.when(i == 0)
    def _init():
        grad_ref[at] = jnp.zeros(grad_ref.shape[1:], grad_ref.dtype)

    @pl.when((i == 0) & (g == 0) & (k == 0))
    def _row_sums():
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    @pl.when((g == 0) & (k == 0))
    def _chunk():
        ddt_ref[...] = jnp.zeros_like(ddt_ref)

    band = _Band(dt_ref[...], a_ref[...], d_ref[...], at * s, s, width)
    grad, dx, db, dc, dcb, ddt, da, dd = _band_backward(
        grad_ref[at], dy_ref[...], h_ref[...], cb_ref[...], x_ref[...],
        b_ref[...], c_ref[...], band)
    grad_ref[at] = grad
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dcb_ref[...] += dcb
    ddt_ref[...] += _spread(ddt, 1, heads, at * s)
    da_ref[...] += _spread(da, 1, heads, at * s)
    dd_ref[...] += _spread(dd, 1, heads, at * s)

    @pl.when(k == 0)
    def _first():
        db_ref[...] = db
        dc_ref[...] = dc

    @pl.when(k > 0)
    def _more():
        db_ref[...] += db
        dc_ref[...] += dc

    @pl.when(k == bands - 1)
    def _through_cb():
        total = dcb_ref[...].astype(x_ref.dtype)
        dc_ref[...] += _dot(total, b_ref[...], _NN)
        db_ref[...] += _dot(total, c_ref[...], _TN)


def _call(kernel, chunk_of, operands, outs, scratch, groups, s, chunk,
          interpret):
    """``operands``: name -> array; ``outs``: name -> struct. Blocks by the
    array's kind: a band's lanes of a chunk of ``x``-like arrays, a group's
    of ``B``-like ones, every head's of ``dt``-like ones, a band's saved
    state of a chunk, the heads' whole ``[1, H]`` of ``A``, ``D`` and of a
    row's ``dA``, ``dD``."""
    import jax.experimental.pallas as pl
    bsz, t, h = operands['dt'].shape
    width = operands['x'].shape[2] // h
    bands = h // groups // s
    lanes = s * width
    state = operands['b'].shape[2] // groups

    def spec(name, a):
        if name in ('x', 'dy', 'y', 'dx'):
            return pl.BlockSpec((None, chunk, lanes),
                                lambda r, i, g, k: (r, chunk_of(i),
                                                    g * bands + k))
        if name in ('b', 'c', 'db', 'dc'):
            return pl.BlockSpec((None, chunk, state),
                                lambda r, i, g, k: (r, chunk_of(i), g))
        if name in ('dt', 'ddt'):
            return pl.BlockSpec((None, chunk, h),
                                lambda r, i, g, k: (r, chunk_of(i), 0))
        if name == 'h':
            return pl.BlockSpec((None, None, None, state, lanes),
                                lambda r, i, g, k: (r, g * bands + k,
                                                    chunk_of(i), 0, 0))
        if name in ('a', 'd'):
            return pl.BlockSpec((1, h), lambda r, i, g, k: (0, 0))
        return pl.BlockSpec((None, 1, h), lambda r, i, g, k: (r, 0, 0))

    return pl.pallas_call(
        functools.partial(kernel, (s, width, bands)),
        grid=(bsz, t // chunk, groups, bands),
        in_specs=[spec(*item) for item in operands.items()],
        out_specs=[spec(*item) for item in outs.items()],
        out_shape=list(outs.values()), scratch_shapes=scratch,
        interpret=interpret, **_compiler_params(interpret))(
            *operands.values())


def _scratch(h, s, state, lanes, chunk, cb_copies):
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    return ([pltpu.VMEM((h // s, state, lanes), f32)]
            + [pltpu.VMEM((chunk, chunk), f32)] * cb_copies)


@functools.partial(_once_a_shape, static_argnums=(6, 7, 8, 9))
def _forward(x, b, c, dt, a, d, groups, chunk, impl, save):
    """Padded operands -> ``(y, states)``; ``states`` ``[B, H / s, chunks,
    N, s P]`` (bfloat16), ``None`` where ``save`` is false."""
    plan = _plan(x, b, dt, groups, chunk, impl)
    s = plan['heads_per_step']
    bsz, t, h = dt.shape
    lanes, state, n = s * plan['head_width'], plan['state_width'], t // chunk
    operands = {'x': x, 'b': b, 'c': c, 'dt': dt, 'a': a[None], 'd': d[None]}
    outs = {'y': _out_struct(x.shape, x.dtype, x)}
    if save:
        outs['h'] = _out_struct((bsz, h // s, n, state, lanes), x.dtype, x)
    out = _call(functools.partial(_forward_kernel, save=save), lambda i: i,
                operands, outs, _scratch(h, s, state, lanes, chunk, 1),
                groups, s, chunk, impl == 'pallas:interpret')
    return out[0], (out[1] if save else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _rule(x, b, c, dt, a, d, groups, chunk, impl):
    return _forward(x, b, c, dt, a, d, groups, chunk, impl, False)[0]


def _rule_fwd(x, b, c, dt, a, d, groups, chunk, impl):
    y, states = _forward(x, b, c, dt, a, d, groups, chunk, impl, True)
    return y, (states, x, b, c, dt, a, d)


@functools.partial(_once_a_shape, static_argnums=(0, 1, 2))
def _rule_bwd(groups, chunk, impl, residuals, dy):
    states, x, b, c, dt, a, d = residuals
    plan = _plan(x, b, dt, groups, chunk, impl)
    s = plan['heads_per_step']
    bsz, t, h = dt.shape
    lanes, state = s * plan['head_width'], plan['state_width']
    f32 = jnp.float32
    operands = {'dy': dy.astype(x.dtype), 'h': states, 'x': x, 'b': b,
                'c': c, 'dt': dt, 'a': a[None], 'd': d[None]}
    outs = {'dx': _out_struct(x.shape, x.dtype, x),
            'db': _out_struct(b.shape, f32, x),
            'dc': _out_struct(c.shape, f32, x),
            'ddt': _out_struct(dt.shape, f32, x),
            'da': _out_struct((bsz, 1, h), f32, x),
            'dd': _out_struct((bsz, 1, h), f32, x)}
    n = t // chunk
    dx, db, dc, ddt, da, dd = _call(
        _backward_kernel, lambda i: n - 1 - i, operands, outs,
        _scratch(h, s, state, lanes, chunk, 2), groups, s, chunk,
        impl == 'pallas:interpret')
    return (dx, db.astype(b.dtype), dc.astype(c.dtype), ddt,
            jnp.sum(da, axis=(0, 1)).astype(a.dtype),
            jnp.sum(dd, axis=(0, 1)).astype(d.dtype))


_rule.defvjp(_rule_fwd, _rule_bwd)


# --------------------------------------------------------------------------
# the public function
# --------------------------------------------------------------------------

def ssd_rule(x, b, c, dt, a, d, groups, chunk=128, impl='xla'):
    """``x [B, T, H P]``, ``b, c [B, T, G N]`` (head ``h`` reads group ``h //
    (H / G)``), ``dt [B, T, H]`` float32 (after the softplus, ``>= 0``), ``a
    [H]`` (``< 0``) and ``d [H]`` float32 -> ``y [B, T, H P]`` in ``x``'s
    dtype. Arrays stay as the projections write them.

    ``impl``: ``'xla'`` (``jax.numpy``, float32), ``'pallas'`` (compiled, a
    TPU; a band's lanes and ``N`` whole multiples of 128),
    ``'pallas:interpret'``.
    ``T`` is padded to a multiple of ``chunk`` with tokens that write and
    forget nothing (``dt`` 0). A ``kernel.ssd_plan`` instant on the global
    tracer says what a call runs, once a plan."""
    if impl not in IMPLS:
        raise ValueError('unknown impl {!r}: one of {}'.format(impl, IMPLS))
    h = dt.shape[-1]
    if h % groups or x.shape[-1] % h or b.shape[-1] % groups:
        raise ValueError('{} heads of {} lanes in {} groups of {}'.format(
            h, x.shape[-1], groups, b.shape[-1]))
    plan = report_plan('kernel.ssd_plan', _plan(x, b, dt, groups, chunk, impl))
    if impl == 'pallas':
        if jax.devices()[0].platform != 'tpu':
            raise RuntimeError(
                "ssd_rule(impl='pallas') compiles Pallas TPU kernels but the "
                'default jax backend is {!r}; use impl=\'pallas:interpret\' '
                "or 'xla'".format(jax.devices()[0].platform))
        lanes = plan['heads_per_step'] * plan['head_width']
        if lanes % 128 or plan['state_width'] % 128:
            raise ValueError('the compiled kernels read a band of {} lanes '
                             'and states {} wide: whole 128-lane blocks'
                             .format(lanes, plan['state_width']))
    t = x.shape[1]
    pad = plan['t_pad'] - t

    def padded(v):
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0)))

    f32 = jnp.float32
    operands = (padded(x), padded(b.astype(x.dtype)),
                padded(c.astype(x.dtype)), padded(dt.astype(f32)),
                a.astype(f32), d.astype(f32))
    if impl == 'xla':
        y = _ssd_xla(*operands, groups, chunk)
    else:
        y = _rule(*operands, groups, chunk, impl)
    return y[:, :t]
