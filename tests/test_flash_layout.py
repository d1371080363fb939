"""The flash kernels on the model's own ``[B, T, H*D]`` arrays: heads by
128-lane blocks, ``dd`` inside the backward kernels. Interpreter parity for
every way a call's heads can lie in (or short of) a lane block, and the
compiled attention layer for a v5e that is described, not attached."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.models.attention import dense_attention

# The package exports the function under the module's name.
fa = importlib.import_module('petastorm_tpu.ops.flash_attention')

# shape [B, T, H, D], dtype: what the lane plan has to say of it
LAYOUTS = [
    ((1, 256, 2, 64), 'float32', (128, 2, 0, 0)),    # two heads a block
    ((1, 256, 1, 128), 'float32', (128, 1, 0, 0)),   # one head a block
    ((1, 128, 3, 64), 'float32', (128, 2, 1, 0)),    # odd heads: one padded
    ((2, 48, 3, 8), 'float32', (128, 16, 13, 0)),    # H*D < 128
    ((1, 100, 2, 8), 'float32', (128, 16, 14, 0)),   # padded T as well
    ((1, 256, 2, 64), 'bfloat16', (128, 2, 0, 0)),
    ((1, 64, 2, 96), 'float32', (128, 1, 0, 32)),    # a width 128 is no multiple of
    ((1, 64, 1, 160), 'float32', (256, 1, 0, 96)),   # wider than a vreg
]


@pytest.mark.parametrize('shape,dtype,lanes', LAYOUTS)
def test_lane_plan(shape, dtype, lanes):
    plan = fa.lane_plan(*shape[2:])
    assert (plan['lane_block'], plan['heads_per_block'], plan['pad_heads'],
            plan['pad_lanes']) == lanes
    width = shape[3] + plan['pad_lanes']
    assert plan['heads_per_block'] * width == plan['lane_block']
    assert (shape[2] + plan['pad_heads']) % plan['heads_per_block'] == 0


def _operands(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(shape), jnp.dtype(dtype))
            for _ in range(4)]


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('shape,dtype,lanes', LAYOUTS)
def test_forward_and_gradients_match_dense(shape, dtype, lanes, causal):
    q, k, v, cot = _operands(shape, dtype)

    def loss(attend):
        return lambda q, k, v: jnp.vdot(
            attend(q, k, v).astype(jnp.float32), cot.astype(jnp.float32))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, block_q=128,
                                  block_k=128, interpret=True)

    def dense(q, k, v):
        return dense_attention(q, k, v, causal=causal)

    tol = dict(atol=5e-2, rtol=5e-2) if dtype == 'bfloat16' else dict(
        atol=2e-5, rtol=2e-5)
    out = flash(q, k, v)
    assert out.shape == shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(dense(q, k, v), np.float32), **tol)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip('qkv', got, want):
        assert a.shape == shape and a.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   err_msg='d{}'.format(name), **tol)


def test_a_head_s_lse_stands_in_each_of_its_lanes_and_no_other():
    """``lse`` is float32 ``[B, T_pad, H*D]``: equal across the D lanes of a
    head, and the two heads of a lane block keep their own."""
    shape = (1, 256, 2, 64)
    q, k, v, _ = _operands(shape, 'float32', seed=3)
    plan = fa._plan_for(q, True, 128, 128)
    out, lse = fa._flash_fwd(*(fa._to_lanes(x, plan) for x in (q, k, v)),
                             plan, True, True)
    assert out.shape == lse.shape == (1, 256, 128) and lse.dtype == jnp.float32
    scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) / 8.0
    scores = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), scores, -jnp.inf)
    want = np.asarray(jax.nn.logsumexp(scores, axis=-1))[0]      # [H, T]
    lse = np.asarray(lse)[0].reshape(256, 2, 64)
    np.testing.assert_array_equal(lse, np.broadcast_to(lse[:, :, :1],
                                                       lse.shape))
    np.testing.assert_allclose(lse[:, :, 0].T, want, atol=1e-5, rtol=1e-5)
    assert np.abs(lse[:, 0, 0] - lse[:, 1, 0]).max() > 0.1


def test_what_pads_a_lane_block_is_stripped_and_takes_no_gradient():
    """Three 8-wide heads in a block of sixteen: the thirteen zero heads
    (uniform ``p`` over zero ``v``) come out finite inside and nowhere
    outside."""
    shape = (2, 48, 3, 8)
    q, k, v, _ = _operands(shape, 'float32', seed=4)
    plan = fa._plan_for(q, False, 128, 128)
    assert (plan['t_pad'], plan['pad_heads']) == (32 * 2, 13)
    lanes = [fa._to_lanes(x, plan) for x in (q, k, v)]
    assert lanes[0].shape == (2, 64, 128)
    assert not np.asarray(lanes[0])[:, :, 24:].any()
    out, lse = fa._flash_fwd(*lanes, plan, True, True)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(lse).all())
    assert not np.asarray(out)[:, :, 24:].any()
    np.testing.assert_array_equal(
        np.asarray(fa._from_lanes(out, shape, plan)),
        np.asarray(out)[:, :48, :24].reshape(shape))


# -- the projections that write what the kernels read ------------------------------

@pytest.mark.parametrize('features,contract,use_bias,x_shape', [
    ((4, 8), 1, True, (2, 16, 32)),        # q, k, v of MultiHeadAttention
    ((4, 8), 1, False, (2, 16, 32)),
    (32, 2, True, (2, 16, 4, 8)),          # its output projection
])
def test_flat_dense_general_is_dense_general(features, contract, use_bias,
                                             x_shape):
    """Same parameter names, shapes and initial values as the
    ``nn.DenseGeneral`` it stands in for, and the same values out: only the
    shape of the product differs (a checkpoint, the benchmark's reference
    and ``transformer_param_spec`` see no change)."""
    import flax.linen as nn

    from petastorm_tpu.models.transformer import FlatDenseGeneral

    x = jax.random.normal(jax.random.PRNGKey(1), x_shape, jnp.float32)
    flat = FlatDenseGeneral(features, contract=contract, use_bias=use_bias,
                            name='query')
    dense = nn.DenseGeneral(features, axis=tuple(range(-contract, 0)),
                            use_bias=use_bias, dtype=jnp.bfloat16,
                            name='query')
    params = dense.init(jax.random.PRNGKey(0), x)
    mine = flat.init(jax.random.PRNGKey(0), x)
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(params))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    params = jax.tree_util.tree_map(lambda a: a + 0.25, params)   # a bias too
    got, want = flat.apply(params, x), dense.apply(params, x)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2)


# -- compiled for the chip that is described, not attached -----------------------

@pytest.fixture(scope='module')
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - whatever says there is no compiler
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))
    return topo.devices


def _layout_changes(text, *shapes):
    """The ``copy`` and ``transpose`` operations of a compiled program whose
    result has one of ``shapes`` (``'16,1024,768'``), whatever its dtype."""
    found = []
    for line in text.splitlines():
        m = re.search(r'= \w+\[([\d,]+)\]\S* (copy|transpose)\(', line)
        if m and m.group(1) in shapes:
            found.append(line.strip()[:160])
    return found


def test_the_attention_layer_compiles_for_a_v5e_without_layout_copies(
        v5e, monkeypatch):
    """One ``MultiHeadAttention`` at ``gpt2s.tokens``'s shape, bf16 ``[16,
    1024, 12, 64]``, its four projections and the three Pallas calls, forward
    and backward: Mosaic takes the kernels, the compiler puts no copy or
    transpose of a q-sized array between a projection and a kernel (the
    parent had eight of ``bf16[16,12,1024,64]``), and neither ``lse`` nor
    ``dd`` is a lane-broadcast ``f32[192,1024,128]`` any more."""
    from jax.sharding import SingleDeviceSharding

    from petastorm_tpu.models.transformer import MultiHeadAttention

    one_chip = SingleDeviceSharding(v5e[0])

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    x = jax.ShapeDtypeStruct((16, 1024, 768), jnp.bfloat16)
    params = jax.eval_shape(
        MultiHeadAttention(num_heads=12, attention='dense').init,
        jax.random.PRNGKey(0), x)
    layer = MultiHeadAttention(num_heads=12, attention='flash')

    def loss(params, x):
        return jnp.sum(layer.apply(params, x).astype(jnp.float32) ** 2)

    # flash_attention asks the attached backend whether it may compile.
    monkeypatch.setattr(jax, 'devices', lambda *a: v5e)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree_util.tree_map(on_chip, params), on_chip(x))
    monkeypatch.undo()
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert not _layout_changes(text, '16,12,1024,64', '16,1024,12,64',
                               '16,1024,768', '192,1024,64')
    assert 'f32[192,1024,128]' not in text
    assert 'f32[16,1024,768]' in text          # lse, as wide as q


def test_the_kernels_compile_for_a_v5e_at_128_wide_heads_and_8192_tokens(v5e):
    """The kernels alone as ``olmohybrid.tokens8k`` runs them: bf16 ``[1,
    8192, 15, 128]``, one head a lane block, blocks (512, 1024), forward and
    backward (VMEM in the dk/dv pass is the chip's compiler's to refuse)."""
    from jax.sharding import SingleDeviceSharding

    q = jax.ShapeDtypeStruct((1, 8192, 15, 128), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))

    def loss(q, k, v):
        return jnp.sum(fa._flash_diff(q, k, v, True, 512, 1024, False)
                       .astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert 'f32[1,8192,1920]' in text and 'f32[15,8192,128]' not in text
