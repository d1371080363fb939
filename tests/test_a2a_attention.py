"""Ulysses (all-to-all) sequence parallelism tests (8 virtual CPU devices).

Contract mirrors ring attention's: ``a2a_self_attention`` over a
sequence-sharded mesh equals dense attention on the unsharded arrays,
causal and non-causal, composing with data and tensor parallelism, and
training end-to-end through ``TransformerLM(attention='a2a')``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from functools import partial

from petastorm_tpu.models import attention as attention_mod
from petastorm_tpu.models.attention import dense_attention
from petastorm_tpu.parallel import make_mesh

# a2a's per-device block compute is the Pallas flash kernel: on the CPU
# mesh it runs in the Pallas interpreter, by name.
a2a_self_attention = partial(attention_mod.a2a_self_attention, interpret=True)


# Heavyweight (jit compiles of full models / interpret-mode Pallas):
# excluded from the fast CI lane; run the full suite before shipping.
pytestmark = pytest.mark.slow


def _qkv(key, b=2, t=64, h=8, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, t, h, d)
    return (jax.random.normal(kq, shape, dtype),
            jax.random.normal(kk, shape, dtype),
            jax.random.normal(kv, shape, dtype))


@pytest.mark.parametrize('causal', [False, True])
def test_a2a_matches_dense(causal):
    mesh = make_mesh({'sp': 8})
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = a2a_self_attention(q, k, v, mesh, 'sp', causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_a2a_dp_sp_mesh():
    """Batch on 'data', sequence on 'sp' — dp x sp at once."""
    mesh = make_mesh({'data': 2, 'sp': 4})
    q, k, v = _qkv(jax.random.PRNGKey(1), b=4, t=32, h=4)
    out = a2a_self_attention(q, k, v, mesh, 'sp', causal=True,
                             batch_axis='data')
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_a2a_with_tensor_parallel_heads():
    """sp x tp: heads sharded over 'model' AND a2a over 'sp' — the per-device
    head count (H/tp) must still divide by sp, which 8/2/2 satisfies."""
    mesh = make_mesh({'sp': 2, 'model': 2, 'data': 2})
    q, k, v = _qkv(jax.random.PRNGKey(2), b=2, t=32, h=8)
    out = a2a_self_attention(q, k, v, mesh, 'sp', causal=True,
                             batch_axis='data', head_axis='model')
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_a2a_indivisible_heads_raises():
    mesh = make_mesh({'sp': 8})
    q, k, v = _qkv(jax.random.PRNGKey(3), h=4)   # 4 heads, 8-way sp
    with pytest.raises(ValueError, match='divisible'):
        a2a_self_attention(q, k, v, mesh, 'sp')


def test_transformer_lm_a2a_trains_under_jit():
    import optax

    from petastorm_tpu.models import TransformerLM

    mesh = make_mesh({'data': 2, 'sp': 4})
    seq, vocab = 32, 64
    model = TransformerLM(vocab_size=vocab, d_model=32, num_heads=4,
                          num_layers=1, max_len=seq, attention='a2a:interpret',
                          mesh=mesh, seq_axis='sp', dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, seq), 0, vocab)
    params = model.init(jax.random.PRNGKey(5), tokens)['params']

    @jax.jit
    def step(params, tokens):
        def loss_fn(p):
            logits = model.apply({'params': p}, tokens)
            tgt = jnp.roll(tokens, -1, axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tgt[:, :-1]).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        return jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params,
                                      grads), loss

    losses = []
    for _ in range(3):
        params, loss = step(params, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_a2a_grads_match_dense():
    mesh = make_mesh({'sp': 8})
    q, k, v = _qkv(jax.random.PRNGKey(6), t=32)

    def loss_a2a(q, k, v):
        return a2a_self_attention(q, k, v, mesh, 'sp', causal=True).sum()

    def loss_dense(q, k, v):
        return dense_attention(q, k, v, causal=True).sum()

    ga = jax.grad(loss_a2a, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, d in zip(ga, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(d),
                                   rtol=1e-4, atol=1e-4)
