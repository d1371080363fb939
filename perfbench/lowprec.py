"""The control's arithmetic: the nearest precision below the one a
configuration states. Both configurations state bfloat16 products, so the
control computes every product, forward and backward, the way an fp8 recipe
does: each operand scaled by the tensor's largest magnitude into the format's
range, rounded and scaled back: e4m3 for activations and weights, e5m2 for
the gradient that comes back into a product. Accumulation stays float32. A
later PR that took the step to fp8 products would compute this.

A reference calls ``quant.operand(a)`` on both operands of a product and
``quant.cotangent(y)`` on its result; ``None`` is the reference itself.
"""

import functools

import jax
import jax.numpy as jnp


def _rounded(a, dtype):
    if jnp.finfo(dtype).maxexp >= jnp.finfo(a.dtype).maxexp:
        return a.astype(dtype).astype(a.dtype)      # same range: no scaling
    limit = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / limit
    return (a / scale).astype(dtype).astype(a.dtype) * scale


class Rounding(object):
    """Operands rounded to ``forward``; the gradient entering a product's
    backward pass rounded to ``backward`` (``None``: left as it is)."""

    def __init__(self, forward, backward):
        self.forward, self.backward = forward, backward

        @jax.custom_vjp
        def operand(a):
            return _rounded(a, forward)

        operand.defvjp(lambda a: (_rounded(a, forward), None),
                       lambda _, g: (g,))

        @jax.custom_vjp
        def cotangent(y):
            return y

        cotangent.defvjp(
            lambda y: (y, None),
            lambda _, g: (g if backward is None else _rounded(g, backward),))
        self.operand, self.cotangent = operand, cotangent

    def __repr__(self):
        return 'Rounding({}, {})'.format(self.forward, self.backward)


FP8 = Rounding(jnp.float8_e4m3fn, jnp.float8_e5m2)
# The program's own precision, for the tests' sanity: it must pass where the
# control fails.
BF16 = Rounding(jnp.bfloat16, jnp.bfloat16)

CONTROLS = {'bfloat16': FP8}
