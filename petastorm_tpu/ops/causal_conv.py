"""Depthwise causal convolution along the sequence, then SiLU, with a
backward pass written out.

The convolution of the linear-attention mixers (``models/hybrid.py``'s gated
delta, ``models/ling_hybrid.py``'s Kimi delta, ``models/nemotron_h.py``'s
Mamba-2): position ``t`` sees ``t - K + 1 .. t`` of its row, zeros before the
row's start, each channel with taps of its own, a bias where one is given.

Differentiated as written, the float32 pad and shifted slices of the forward
pass come back as pads of float32 updates, summed and sliced, a reduction over
time for each tap and SiLU's derivative on a float32 pre-activation kept from
the forward pass: compiled for a v5e, three fusions a call that write five
float32 arrays of the row's shape between them. The ``custom_vjp`` keeps ``x``
in its own dtype (bf16 in the models), the taps and the bias, recomputes the
pre-activation in float32 from ``x`` and goes back in one pass over the row:
``dz = g · silu'(y)``, the input's gradient as the same taps run backwards
over ``dz`` (zeros past the row's end), the taps' and the bias's gradients as
float32 sums of the same ``dz`` over rows and time. The forward pass is the
expression the gradient of which used to be taken, so its output is unchanged
to the bit; the input's gradient is the same float32 sum rounded once, and the
taps' are float32 sums in another order.

Two implementations of the backward pass, chosen by what the call can see:

* ``'pallas'``, on a TPU, for ``x`` of ``[B, T, heads, width]`` (the delta
  mixers): XLA lays such arrays out with time in the lanes (the reductions
  over a head's width then run down sublanes), so one Pallas call reads them
  as ``[B, heads · width, T]`` rows without a copy. A grid step takes up to
  :data:`STEP_COLS` time steps of up to :data:`STEP_ROWS` channels with the
  128 steps before and after it, shifts by lane rotations, and adds the taps'
  and bias's sums into blocks that stay resident along time.
* ``'xla'`` otherwise (flat ``[B, T, C]`` rows, whose channels XLA puts in the
  lanes, and every CPU): the same arithmetic in ``jax.numpy``, which XLA
  fuses into two passes, the first writing ``dz`` in float32 with the taps'
  sums beside it.

One ``step.conv_plan`` instant on the global tracer a distinct plan a process
says which one a run built.
"""

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from petastorm_tpu.trace import get_global_tracer

#: Time steps (lanes) a grid step of the kernel takes at most.
STEP_COLS = 2048
#: Channels (sublanes) a grid step of the kernel takes at most.
STEP_ROWS = 128
_LANE = 128
_BF16_ROWS = 16         # the sublane tile of a bf16 array

_plans_reported = set()


def _largest_divisor(n, most, step):
    """The largest multiple of ``step`` up to ``most`` that divides ``n``,
    or None."""
    for d in range(most - most % step, 0, -step):
        if n % d == 0:
            return d
    return None


def _channels_per_step(channels):
    return _largest_divisor(channels, STEP_ROWS, _BF16_ROWS)


def _cols_per_step(t):
    return _largest_divisor(t, STEP_COLS, _LANE)


def implementation_for(shape, platform):
    """``'pallas'`` or ``'xla'``: which backward pass a call on ``x`` of
    ``shape`` runs on a device of ``platform``."""
    fits = (len(shape) == 4 and _cols_per_step(shape[1]) is not None
            and _channels_per_step(math.prod(shape[2:])) is not None)
    return 'pallas' if platform == 'tpu' and fits else 'xla'


def conv_plan(shape, taps, bias, dtype, implementation):
    """What a call on ``x`` of ``shape`` and ``dtype`` runs: the account the
    ``step.conv_plan`` instant carries. ``residual_bytes``: what is kept
    between the passes, ``x`` in its dtype with the float32 taps and bias."""
    dtype = jnp.dtype(dtype)
    channels = math.prod(shape[2:])
    return {'shape': list(shape), 'taps': taps, 'bias': bias,
            'dtype': dtype.name, 'implementation': implementation,
            'residual_bytes': math.prod(shape) * dtype.itemsize
            + (taps + bias) * channels * 4}


def _report_plan(x, kernel, bias, implementation):
    key = (tuple(x.shape), kernel.shape[0], bias is not None,
           jnp.dtype(x.dtype).name, implementation)
    if key not in _plans_reported:
        _plans_reported.add(key)
        get_global_tracer().instant('step.conv_plan', cat='step',
                                    args=conv_plan(*key))


def _pre_activation(x32, kernel, bias):
    taps, t = kernel.shape[0], x32.shape[1]
    padded = jnp.pad(x32, ((0, 0), (taps - 1, 0)) + ((0, 0),) * (x32.ndim - 2))
    y = sum(padded[:, i:i + t] * kernel[i] for i in range(taps))
    return y if bias is None else y + bias


def _silu_grad(y, g):
    s = jax.nn.sigmoid(y)
    return g * s * (1.0 + y * (1.0 - s))


# -- the backward pass in jax.numpy ------------------------------------------

def _shifted(a, s):
    """``a[:, t - s]`` along the sequence, zeros where that lies outside the
    row: later by ``s > 0``, earlier by ``s < 0``."""
    pads = [(0, 0, 0)] * a.ndim
    pads[1] = (s, -s, 0)
    return lax.pad(a, jnp.zeros((), a.dtype), pads)


def _xla_backward(x, kernel, bias, g):
    taps = kernel.shape[0]
    x32 = x.astype(jnp.float32)
    dz = _silu_grad(_pre_activation(x32, kernel, bias), g.astype(jnp.float32))
    # tap i reads x[t - (taps - 1 - i)]: its transpose reads dz that late
    dx = sum(_shifted(dz, i - taps + 1) * kernel[i] for i in range(taps))
    dkernel = jnp.stack([jnp.sum(_shifted(x32, taps - 1 - i) * dz, axis=(0, 1))
                         for i in range(taps)])
    dbias = None if bias is None else jnp.sum(dz, axis=(0, 1))
    return dx, dkernel, dbias


# -- the backward pass as one Pallas call ------------------------------------

def _backward_kernel(x_ref, x_before_ref, x_after_ref, g_ref, g_after_ref,
                     taps_ref, bias_ref, dx_ref, dtaps_ref, dbias_ref, *,
                     steps):
    """One grid step: channels on sublanes, time on lanes. Column ``j`` of
    ``xx`` is time ``t0 - 128 + j``, of ``y`` and ``dz`` time ``t0 + j``;
    what lies outside the row is zero (``dz`` past its end because ``g``
    is)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    row, step = pl.program_id(1), pl.program_id(2)
    first, last = step == 0, step == steps - 1
    cols = x_ref.shape[-1]
    width = cols + _LANE
    k = taps_ref[...]                                 # [channels, taps]
    taps = k.shape[1]
    xx = jnp.concatenate([
        jnp.where(first, 0.0, x_before_ref[...].astype(f32)),
        x_ref[...].astype(f32),
        jnp.where(last, 0.0, x_after_ref[...].astype(f32))], axis=1)
    gg = jnp.concatenate([
        g_ref[...].astype(f32),
        jnp.where(last, 0.0, g_after_ref[...].astype(f32))], axis=1)
    # tap i reads x[t - (taps - 1 - i)], a rotation right by that much
    x_tap = [pltpu.roll(xx, taps - 1 - i, 1)[:, _LANE:_LANE + width]
             for i in range(taps - 1)] + [xx[:, _LANE:]]
    y = sum(x_tap[i] * k[:, i:i + 1] for i in range(taps)) + bias_ref[...]
    dz = _silu_grad(y, gg)
    # and its transpose reads dz[t + taps - 1 - i], a rotation left
    dz_tap = [pltpu.roll(dz, width - (taps - 1 - i), 1)[:, :cols]
              for i in range(taps - 1)] + [dz[:, :cols]]
    dx_ref[...] = sum(dz_tap[i] * k[:, i:i + 1]
                      for i in range(taps)).astype(dx_ref.dtype)
    own = dz[:, :cols]

    @pl.when((row == 0) & first)
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    dtaps_ref[...] += jnp.concatenate(
        [jnp.sum(x_tap[i][:, :cols] * own, axis=1, keepdims=True)
         for i in range(taps)], axis=1)
    dbias_ref[...] += jnp.sum(own, axis=1, keepdims=True)


def _kernel_call(xt, gt, taps_t, bias_col, interpret):
    """``xt``, ``gt [B, C, T]``, ``taps_t [C, K]``, ``bias_col [C, 1]`` ->
    ``dx [B, C, T]`` in ``xt``'s dtype, float32 ``dtaps [C, K]`` and
    ``dbias [C, 1]`` summed over rows and time."""
    from jax.experimental import pallas as pl
    b, c, t = xt.shape
    rows, cols = _channels_per_step(c), _cols_per_step(t)
    steps, per, halos = t // cols, cols // _LANE, t // _LANE
    main = pl.BlockSpec((None, rows, cols), lambda j, r, i: (r, j, i))
    before = pl.BlockSpec((None, rows, _LANE), lambda j, r, i: (
        r, j, jnp.maximum(i * per - 1, 0)))
    after = pl.BlockSpec((None, rows, _LANE), lambda j, r, i: (
        r, j, jnp.minimum((i + 1) * per, halos - 1)))
    taps = pl.BlockSpec((rows, taps_t.shape[1]), lambda j, r, i: (j, 0))
    column = pl.BlockSpec((rows, 1), lambda j, r, i: (j, 0))
    params = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu
        # the taps' and bias's blocks stay resident along rows and time
        params['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary'))
    return pl.pallas_call(
        functools.partial(_backward_kernel, steps=steps),
        grid=(c // rows, b, steps),
        in_specs=[main, before, after, main, after, taps, column],
        out_specs=[main, taps, column],
        # varying as the rows are, inside a shard_map that checks it
        out_shape=[jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(xt).vma)
                   for shape, dtype in ((xt.shape, xt.dtype),
                                        (taps_t.shape, jnp.float32),
                                        (bias_col.shape, jnp.float32))],
        interpret=interpret, **params)(xt, xt, xt, gt, gt, taps_t, bias_col)


def _pallas_backward(x, kernel, bias, g, mesh, batch_axis, interpret):
    """The kernel on ``x [B, T, H, D]`` seen as ``[B, H·D, T]``; mapped over
    the batch's shards where a mesh is given (a Pallas call is opaque to the
    SPMD partitioner), the taps' and bias's sums added over them."""
    b, t = x.shape[:2]
    c = math.prod(x.shape[2:])

    def rows(a):
        return jnp.transpose(a, (0, 2, 3, 1)).reshape(b, c, t)

    taps_t = kernel.reshape(kernel.shape[0], c).T
    bias_col = (jnp.zeros((c, 1), jnp.float32) if bias is None
                else bias.reshape(c, 1).astype(jnp.float32))

    def call(xt, gt):
        return _kernel_call(xt, gt, taps_t, bias_col, interpret)

    if mesh is None:
        dxt, dtaps, dbias = call(rows(x), rows(g))
    else:
        from jax.sharding import PartitionSpec
        from petastorm_tpu.models.transformer import usable_axis
        axis = usable_axis(mesh, batch_axis, b)

        def shard(xt, gt):
            dxt, dtaps, dbias = call(xt, gt)
            if axis is not None:
                dtaps, dbias = lax.psum((dtaps, dbias), axis)
            return dxt, dtaps, dbias

        spec = PartitionSpec(axis)
        dxt, dtaps, dbias = jax.shard_map(
            shard, mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec, PartitionSpec(), PartitionSpec()),
            check_vma=not interpret)(rows(x), rows(g))
    dx = jnp.transpose(dxt.reshape((b,) + x.shape[2:] + (t,)), (0, 3, 1, 2))
    return (dx, dtaps.T.reshape(kernel.shape),
            None if bias is None else dbias.reshape(bias.shape))


# -- the op -------------------------------------------------------------------

def _forward(x, kernel, bias):
    with jax.named_scope('conv_silu'):
        y = _pre_activation(x.astype(jnp.float32), kernel, bias)
        return nn.silu(y).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu(x, kernel, bias, backward):
    return _forward(x, kernel, bias)


def _vjp_forward(x, kernel, bias, backward):
    return _forward(x, kernel, bias), (x, kernel, bias)


def _vjp_backward(backward, residuals, g):
    """``backward``: ``(implementation, mesh, batch_axis)``."""
    x, kernel, bias = residuals
    implementation, mesh, batch_axis = backward
    with jax.named_scope('conv_silu'):
        if implementation.startswith('pallas'):
            dx, dkernel, dbias = _pallas_backward(
                x, kernel, bias, g, mesh, batch_axis,
                implementation == 'pallas:interpret')
        else:
            dx, dkernel, dbias = _xla_backward(x, kernel, bias, g)
        return (dx.astype(x.dtype), dkernel.astype(kernel.dtype),
                None if bias is None else dbias.astype(bias.dtype))


_conv_silu.defvjp(_vjp_forward, _vjp_backward)


def causal_conv_silu(x, kernel, bias=None, mesh=None, batch_axis='data'):
    """Depthwise causal convolution along the sequence, plus ``bias`` where
    one is given, then SiLU: ``x [B, T, ...]``, ``kernel [K, ...]``, ``bias
    [...]``; position ``t`` sees ``t - K + 1 .. t``, zeros before the row's
    start. Differentiable in all three. ``mesh``, ``batch_axis``: where the
    rows are sharded, as the mixers' rules take them."""
    implementation = implementation_for(x.shape, jax.devices()[0].platform)
    _report_plan(x, kernel, bias, implementation)
    return _conv_silu(x, kernel, bias, (implementation, mesh, batch_axis))
