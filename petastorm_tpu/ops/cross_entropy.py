"""Fused softmax cross-entropy over integer labels, forward and backward.

The training step's loss (``models/train.py``). From one look at the logits
the op returns what the step needs: each row's loss in float32 and whether
the row's ``argmax`` is its label. Its ``custom_vjp`` keeps as residuals the
logits it was given, the float32 log-sum-exp of each row and the labels, so
for bf16 logits no float32 value of their shape lives between the forward
and the backward pass; the cotangent is ``(exp(x - lse) - onehot) * g``,
computed in float32 and rounded once, to the logits' dtype. Maximum,
exponential, sum and log-sum-exp are float32 whatever the logits are: the
cotangent is rounded where autodiff of
``optax.softmax_cross_entropy_with_integer_labels(logits.astype(float32))``
rounds it too, at the transpose of the cast, ahead of the head's two
backward products.

The models hand the step float32 logits, a cast of their head's bf16
product. Inside one jitted program XLA fuses that cast into the op's reads,
so what lies in HBM is the bf16 product, and it fuses the cotangent into
its readers (the head's two backward products and the bias's column sum),
so no dlogits is written (PERF.md has the traced operations).

Plain ``jax.numpy`` inside the ``custom_vjp``: the SPMD partitioner shards it
with the batch over one mesh axis and with the vocabulary over another, and
on the chip the forward pass is the row maximum (in the product's epilogue)
and one fusion of three sibling reductions.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from petastorm_tpu.trace import get_global_tracer

_plans_reported = set()


def loss_plan(rows, vocab, dtype):
    """What a call on ``rows`` rows of ``vocab`` logits runs: the account
    the ``step.loss_plan`` instant carries."""
    dtype = jnp.dtype(dtype).name
    return {'rows': rows, 'vocab': vocab, 'logits_dtype': dtype,
            'residual_dtype': dtype, 'impl': 'xla', 'argmax_fused': True}


def _report_plan(logits):
    """The first time a process traces the loss with a plan, one
    ``step.loss_plan`` instant on the global tracer carries it."""
    key = (math.prod(logits.shape[:-1]), logits.shape[-1],
           jnp.dtype(logits.dtype).name)
    if key not in _plans_reported:
        _plans_reported.add(key)
        get_global_tracer().instant('step.loss_plan', cat='step',
                                    args=loss_plan(*key))


def _columns(logits, labels):
    """Every logit's column index, and each row's label beside them."""
    cols = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return cols, labels[..., None].astype(jnp.int32)


def _forward(logits, labels):
    _report_plan(logits)
    x = logits.astype(jnp.float32)
    cols, label = _columns(logits, labels)
    top = jnp.max(x, axis=-1, keepdims=True)
    # Three sums of one read, all float32 so that XLA makes them siblings of
    # one fusion. The label's logit is a masked sum, not a gather; the label
    # is the argmax if its logit is the maximum and no column before it is
    # (``jnp.argmax`` names the first of equal maxima), not a pass of its own.
    sum_exp = jnp.sum(jnp.exp(x - top), axis=-1)
    picked = jnp.sum(jnp.where(cols == label, x, 0.0), axis=-1)
    earlier_tops = jnp.sum(
        jnp.where((x == top) & (cols < label), 1.0, 0.0), axis=-1)
    top = top[..., 0]
    log_sum = jnp.log(sum_exp)
    hit = (picked == top) & (earlier_tops == 0)
    # The loss as optax rounds it: log-sum-exp and the label's logit both
    # less the row's maximum.
    return log_sum - (picked - top), hit, top + log_sum


@jax.custom_vjp
def softmax_cross_entropy(logits, labels):
    """``(logits [..., V], integer labels [...]) -> (loss [...] float32,
    hit [...] bool)``: the loss of
    ``optax.softmax_cross_entropy_with_integer_labels`` on a float32 copy of
    the logits, and ``argmax(logits, -1) == labels``. Differentiable in the
    logits; the gradient has their dtype."""
    loss, hit, _ = _forward(logits, labels)
    return loss, hit


def _vjp_forward(logits, labels):
    loss, hit, lse = _forward(logits, labels)
    return (loss, hit), (logits, lse, labels)


def _vjp_backward(residuals, cotangents):
    logits, lse, labels = residuals
    g = cotangents[0]
    cols, label = _columns(logits, labels)
    softmax = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    dlogits = (softmax - (cols == label).astype(jnp.float32)) * g[..., None]
    return dlogits.astype(logits.dtype), None


softmax_cross_entropy.defvjp(_vjp_forward, _vjp_backward)
