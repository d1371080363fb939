"""Pallas flash-attention kernel tests (interpreter mode; no TPU needed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.models.attention import dense_attention
from petastorm_tpu.ops.flash_attention import (_bands, _flash_fwd,
                                               _from_lanes, _plan_for,
                                               _to_lanes, flash_attention)


# Heavyweight (jit compiles of full models / interpret-mode Pallas):
# excluded from the fast CI lane; run the full suite before shipping.
pytestmark = pytest.mark.slow

@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('shape,blocks', [
    ((2, 64, 2, 16), (16, 16)),
    ((1, 100, 2, 8), (32, 16)),      # padded tail (100 % 16 != 0)
    ((1, 7, 1, 4), (8, 8)),          # seq shorter than a block
    ((2, 48, 3, 8), (16, 24)),       # block_q != block_k
    # DMA blocks of several 128-wide compute sub-tiles, T no multiple of one:
    ((1, 600, 2, 16), (512, 1024)),  # one 512 block a side; sub-tiles of pad
    ((1, 1100, 1, 8), (512, 1024)),  # two kv blocks: grid skip + band bounds
    ((1, 700, 1, 8), (256, 1024)),   # a 256-row q block against a 512 kv one
    ((1, 520, 1, 8), (512, 64)),     # sub_q 128 > sub_k 64: rows of a q
                                     # sub-tile fully masked in a computed one
])
def test_matches_dense(shape, blocks, causal):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for _ in range(3))
    ref = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=blocks[0],
                          block_k=blocks[1], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_bfloat16_inputs():
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 32, 2, 8)), jnp.bfloat16)
               for _ in range(3))
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    ref = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


def test_gradients_flow():
    """custom_vjp backward (Pallas dq/dk/dv passes) matches dense grads."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 32, 2, 8)), jnp.float32)
               for _ in range(3))

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                               interpret=True).sum()

    def loss_dense(q, k, v):
        return dense_attention(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_compiled_kernel_off_tpu_raises():
    """Nothing substitutes for the device: without ``interpret=True`` a
    backend that is not a TPU is an error, not a silent dense fallback."""
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 16, 1, 4)), jnp.float32)
               for _ in range(3))
    with pytest.raises(RuntimeError, match='interpret=True'):
        flash_attention(q, k, v, causal=False)


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('shape,blocks', [
    ((2, 64, 2, 16), (16, 16)),
    ((1, 100, 2, 8), (32, 16)),      # padded tail exercises zero-dO rows
    ((2, 48, 3, 8), (16, 24)),       # uneven blocks
    ((1, 600, 2, 16), (512, 1024)),  # several sub-tiles a DMA block, ragged T
    ((1, 1100, 1, 8), (512, 1024)),  # two kv blocks of four sub-tiles
    ((1, 520, 1, 8), (512, 64)),     # sub_q 128 > sub_k 64
])
def test_pallas_backward_matches_dense(shape, blocks, causal):
    """The dq/dk/dv Pallas kernels reproduce dense-attention gradients."""
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for _ in range(3))
    cot = jnp.asarray(rng.standard_normal(shape), jnp.float32)  # nontrivial dO

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=blocks[0],
                              block_k=blocks[1], interpret=True)
        return jnp.vdot(out, cot)

    def dense_loss(q, k, v):
        return jnp.vdot(dense_attention(q, k, v, causal=causal), cot)

    g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip('qkv', g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg='d{} mismatch'.format(name))


def test_rows_fully_masked_in_a_computed_tile_keep_their_statistics():
    """T=520 at (512, 64) blocks: sub_q 128 > sub_k 64, so the q block's
    first rows lie wholly above kv block 1 and are still in the masked tile
    computed there (all their scores ``NEG_INF``: the running max must not
    move and the probabilities must come out 0, not ``exp(0)``). The saved
    logsumexp rows say whether they did; the padding rows stay finite."""
    rng = np.random.default_rng(5)
    t, d = 520, 16
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, 2, d)), jnp.float32)
               for _ in range(3))
    plan = _plan_for(q, True, 512, 64)
    fwd = plan['passes']['fwd']
    assert (plan['t_pad'], fwd['sub_q'], fwd['sub_k']) == (1024, 128, 64)
    assert (plan['heads_per_block'], plan['pad_heads']) == (8, 6)
    # q block 0 against kv block 1: rows 0..127 in one masked 128 x 64 tile.
    assert (0, 128, 0, 64) in _bands((-64, 64), 512, 64, 128, 64)
    out, lse = _flash_fwd(*(_to_lanes(x, plan) for x in (q, k, v)), plan,
                          True, True)
    assert out.shape == lse.shape == (1, 1024, 128)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(lse).all())
    scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) / np.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    # A head's logsumexp stands in each of its d lanes.
    np.testing.assert_allclose(
        np.asarray(lse[0, :t, :2 * d:d].T),
        np.asarray(jax.nn.logsumexp(scores, axis=-1)[0]),
        atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(lse[:, :, 0:2 * d:d]),
                                  np.asarray(lse[:, :, d - 1:2 * d:d]))
    want = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(scores, axis=-1), v)
    np.testing.assert_allclose(
        np.asarray(_from_lanes(out, q.shape, plan)), np.asarray(want),
        atol=1e-4, rtol=1e-4)
