"""The SwiGLU feed-forward, ``down(silu(x W_gate) · (x W_up))``, with a
backward pass that forms its products' operands once.

Differentiated as written, every product of the backward pass takes its
operand as a fused producer, and the compiler rebuilds that operand inside
the product for every tile it reads: compiled for a v5e at ``[8192, 3840 ->
11008]``, ``gate``'s weight and input gradients recompute silu's derivative
over ``[rows, d_ff]`` from ``g``, ``u`` and ``dh`` in float32 (``exp`` and a
division among a dozen operations), ``down``'s weight gradient recomputes
``silu(g) · u`` and the output norm's backward that made ``dy``, and
``down``'s input gradient the norm's backward again, in fusions the compiler
gives small windows: a third to a half of the MXU's rate on the chip.

The ``custom_vjp`` keeps ``x``, ``g = x W_gate`` and ``u = x W_up`` in
their own dtype (recomputed with the block where the block is) and goes
back in three steps:

1. ``dh = dy W_downᵀ``;
2. one elementwise pass over ``g``, ``u`` and ``dh`` in float32 writes
   ``h = silu(g) · u``, ``dg`` and ``du``, each rounded once to the
   products' dtype, where the fused computation rounded them;
3. the weight gradients ``xᵀ dg``, ``xᵀ du``, ``hᵀ dy`` and the input
   gradient ``dg W_gateᵀ + du W_upᵀ``.

``dy``, ``dh``, ``h``, ``dg`` and ``du`` pass through
:func:`jax.lax.optimization_barrier` before a product or the elementwise
pass reads them, so XLA writes each once to HBM and can fuse neither the
norm's backward nor the elementwise pass into a product. The forward pass is
the expression whose gradient used to be taken, so its output is unchanged to
the bit; the products keep their dtypes (a weight gradient in the weights'
dtype, as a dense layer's transpose gives it).

One ``step.ffn_plan`` instant on the global tracer a distinct plan a process
says where the op was built and what it keeps.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from petastorm_tpu.trace import get_global_tracer

#: What the ``step.ffn_plan`` instant says the backward pass does.
BACKWARD = 'operands formed once'

_plans_reported = set()


def ffn_plan(rows, d_model, d_ff, dtype):
    """What a call on ``rows`` rows of ``d_model`` into ``d_ff`` in
    ``dtype`` runs: the account the ``step.ffn_plan`` instant carries.
    ``residual_bytes``: what is kept between the passes, ``x``, ``g`` and
    ``u`` and the three weights in that dtype."""
    dtype = jnp.dtype(dtype)
    return {'rows': rows, 'd_model': d_model, 'd_ff': d_ff,
            'dtype': dtype.name, 'backward': BACKWARD,
            'residual_bytes': (rows * (d_model + 2 * d_ff)
                               + 3 * d_model * d_ff) * dtype.itemsize}


def _report_plan(x, w_gate):
    d_model, d_ff = w_gate.shape
    key = (math.prod(x.shape[:-1]), d_model, d_ff, jnp.dtype(x.dtype).name)
    if key not in _plans_reported:
        _plans_reported.add(key)
        get_global_tracer().instant('step.ffn_plan', cat='step',
                                    args=ffn_plan(*key))


def _project(a, w):
    """``a [..., i] · w [i, o]``, as a dense layer contracts them."""
    return lax.dot_general(a, w, (((a.ndim - 1,), (0,)), ((), ())))


def _weight_grad(a, d):
    """``aᵀ d`` over every leading axis: ``[i, o]``."""
    lead = tuple(range(a.ndim - 1))
    return lax.dot_general(a, d, ((lead, lead), ((), ())))


def _input_grad(d, w):
    """``d [..., o] · wᵀ``: ``[..., i]``."""
    return lax.dot_general(d, w, (((d.ndim - 1,), (1,)), ((), ())))


def _forward(x, w_gate, w_up, w_down):
    with jax.named_scope('gate'):
        g = _project(x, w_gate)
    with jax.named_scope('up'):
        u = _project(x, w_up)
    h = nn.silu(g) * u
    with jax.named_scope('down'):
        return _project(h, w_down), (g, u)


def _operands(g, u, dh):
    """``h = silu(g) · u``, ``dg`` and ``du`` from one pass in float32,
    each rounded once to ``g``'s dtype."""
    f32 = jnp.float32
    g32, u32, dh32 = g.astype(f32), u.astype(f32), dh.astype(f32)
    s = jax.nn.sigmoid(g32)
    silu = g32 * s
    dg = dh32 * u32 * s * (1.0 + g32 * (1.0 - s))
    return tuple(a.astype(g.dtype) for a in (silu * u32, dg, dh32 * silu))


@jax.custom_vjp
def _swiglu(x, w_gate, w_up, w_down):
    return _forward(x, w_gate, w_up, w_down)[0]


def _vjp_forward(x, w_gate, w_up, w_down):
    y, (g, u) = _forward(x, w_gate, w_up, w_down)
    return y, (x, g, u, w_gate, w_up, w_down)


def _vjp_backward(residuals, dy):
    x, g, u, w_gate, w_up, w_down = residuals
    dy = lax.optimization_barrier(dy)
    with jax.named_scope('down'):
        dh = lax.optimization_barrier(_input_grad(dy, w_down))
    with jax.named_scope('silu'):
        h, dg, du = lax.optimization_barrier(_operands(g, u, dh))
    with jax.named_scope('down'):
        dw_down = _weight_grad(h, dy)
    with jax.named_scope('gate'):
        dw_gate = _weight_grad(x, dg)
        dx = _input_grad(dg, w_gate)
    with jax.named_scope('up'):
        dw_up = _weight_grad(x, du)
        dx = dx + _input_grad(du, w_up)
    return dx, dw_gate, dw_up, dw_down


_swiglu.defvjp(_vjp_forward, _vjp_backward)


def swiglu(x, w_gate, w_up, w_down):
    """``down(silu(x W_gate) · (x W_up))``: ``x [..., d_model]``, ``w_gate``,
    ``w_up [d_model, d_ff]``, ``w_down [d_ff, d_model]``, all of one dtype
    (the products run in it). Differentiable in all four; its backward pass
    forms the products' operands once (module docstring)."""
    _report_plan(x, w_gate)
    return _swiglu(x, w_gate, w_up, w_down)
