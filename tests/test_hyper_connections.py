"""``ops.hyper_connections``: a sub-layer between its stream maps through the
Pallas kernels (interpret mode) against the ``jax.numpy`` formulation of
``models.latent_moe`` and against the Xing4.0 configuration's plain reference,
forward and every gradient; the clamp; a token count that is no multiple of
the block; which path a width takes and what the plans say; a mesh; and the
four kernels compiled for a described v5e at the widths ``xing4.tokens4k``
runs."""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu import trace
from petastorm_tpu.models import latent_moe
from petastorm_tpu.ops import hyper_connections as hc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, 'perfbench', 'configs')
NAME = 'xing4-29b-a4b-ctx4096'
N, D = 4, 128


@pytest.fixture(scope='module')
def ref():
    spec = importlib.util.spec_from_file_location(
        'xing4_ref', os.path.join(CONFIGS, NAME + '.reference.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope='module')
def cfg():
    return json.load(open(os.path.join(CONFIGS, NAME + '.json')))


def _case(diagonal, alpha, b, t, seed=0, spread=0.1):
    """Streams, a sub-layer's leaves moved off their start (every gradient
    then has something to say), a fixed map's weights, an offset ``y0`` whose
    gradient is ``dy``, and the weights of the sum that stands for a loss."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((b, t, N * D)), jnp.float32)
    sub = latent_moe.StreamSubLayer(alpha_init=alpha,
                                    res_diagonal_init=diagonal,
                                    dtype=jnp.float32)
    params = sub.init(jax.random.PRNGKey(seed), x, lambda inner: inner)[
        'params']
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + spread * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)])
    w = jnp.asarray(rng.standard_normal((D, D)) / np.sqrt(D), jnp.float32)
    y0 = jnp.asarray(rng.standard_normal((b, t, D)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((b, t, N * D)), jnp.float32)
    return x, params, w, y0, c


def _through_kernels(params, x, y0, w, c, alpha, diagonal, dtype=jnp.float32,
                     **kwargs):
    sub = latent_moe.StreamSubLayer(alpha_init=alpha,
                                    res_diagonal_init=diagonal, dtype=dtype,
                                    **kwargs)
    out, _ = sub.apply({'params': params}, x.astype(dtype),
                       lambda inner: jnp.tanh(inner @ w.astype(dtype))
                       + y0.astype(dtype))
    return jnp.sum(out.astype(jnp.float32) * c), out


def _through_jax_numpy(params, x, y0, w, c, alpha, diagonal,
                       dtype=jnp.float32):
    b, t, _ = x.shape
    x4 = x.astype(dtype).reshape(b, t, N, D)
    maps = latent_moe.StreamMaps(alpha_init=alpha, res_diagonal_init=diagonal,
                                 dtype=dtype)
    out, _ = latent_moe.mix_streams(
        x4, *maps.apply({'params': params}, x4),
        lambda inner: jnp.tanh(inner @ w.astype(dtype)) + y0.astype(dtype))
    out = out.reshape(b, t, N * D)
    return jnp.sum(out.astype(jnp.float32) * c), out


def _run(route, params, x, y0, w, c, *args, **kwargs):
    """``(x', {name: gradient})`` over ``x``, ``y`` and every leaf."""
    (_, out), (dp, dx, dy) = jax.jit(jax.value_and_grad(
        lambda p, x, y0: route(p, x, y0, w, c, *args, **kwargs),
        argnums=(0, 1, 2), has_aux=True))(params, x, y0)
    grads = {'/'.join(str(k.key) for k in path): leaf for path, leaf in
             jax.tree_util.tree_flatten_with_path(dp)[0]}
    return out, dict(grads, x=dx, y=dy)


LEAVES = {'alpha_pre', 'alpha_post', 'alpha_res', 'b_pre', 'b_post', 'b_res',
          'phi_pre', 'phi_post', 'phi_res', 'norm/scale', 'x', 'y'}


@pytest.mark.parametrize('diagonal', [0.0, 4.0])
def test_the_kernels_equal_jax_numpy_forward_and_every_gradient(diagonal):
    """float32, 2 x 100 tokens (200 rows: a whole block of 128 and one of 72
    rows and 56 of padding), so the two routes differ by the order of sums
    alone: 2e-5 of a leaf's largest value."""
    x, params, w, y0, c = _case(diagonal, 0.5, 2, 100)
    got, got_grads = _run(_through_kernels, params, x, y0, w, c, 0.5, diagonal)
    want, want_grads = _run(_through_jax_numpy, params, x, y0, w, c, 0.5,
                            diagonal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert set(got_grads) == LEAVES
    for name, leaf in want_grads.items():
        assert got_grads[name].shape == leaf.shape
        np.testing.assert_allclose(
            np.asarray(got_grads[name]), np.asarray(leaf), rtol=2e-5,
            atol=2e-5 * float(jnp.abs(leaf).max()), err_msg=name)


@pytest.mark.parametrize('diagonal', [0.0, 4.0])
def test_the_kernels_equal_the_reference_s_maps_and_sub_layer(ref, cfg,
                                                              diagonal):
    """Against ``reference.py``: the maps row's ``H`` lanes are
    ``stream_maps``' three maps, the sub-layer and every gradient
    ``_sub_layer``'s (the reference norms the sub-layer's input itself)."""
    x, params, _, _, c = _case(diagonal, 0.5, 2, 64)
    b, t, _ = x.shape
    scale = {'scale': jnp.ones((D,), jnp.float32)}

    def program(p, x):
        out, _ = latent_moe.StreamSubLayer(
            alpha_init=0.5, res_diagonal_init=diagonal,
            dtype=jnp.float32).apply(
                {'params': p}, x, lambda inner: jnp.tanh(
                    ref._rms(inner, scale['scale'], 1e-6)))
        return jnp.sum(out * c), out

    def reference(p, x):
        out = ref._sub_layer(p, scale, x.reshape(b, t, N, D), jnp.tanh, cfg)
        out = out.reshape(b, t, N * D)
        return jnp.sum(out * c), out

    (_, got), got_grads = jax.value_and_grad(program, argnums=(0, 1),
                                             has_aux=True)(params, x)
    (_, want), want_grads = jax.value_and_grad(reference, argnums=(0, 1),
                                               has_aux=True)(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    for a, b_ in zip(jax.tree_util.tree_leaves(got_grads),
                     jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-5,
                                   atol=2e-5 * float(jnp.abs(b_).max()))
    # the maps row the pre kernel writes, lane by lane
    leaves = dict(params, scale=params['norm']['scale'])
    _, maps = hc._pre_call((N, 20, 1e-6, (-30.0, 30.0), 128, True),
                           x.reshape(b * t, N * D), *hc._operands(leaves, N))
    pre, post, res = ref.stream_maps(params, x.reshape(b, t, N, D), cfg)
    want = jnp.concatenate([pre, post, res.reshape(b, t, 16)], -1)
    np.testing.assert_allclose(np.asarray(maps[:, :24]),
                               np.asarray(want.reshape(b * t, 24)),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(maps[:, 8:24].reshape(-1, 4, 4)
                                          .sum(-2)), 1.0, atol=1e-5)


def test_bfloat16_kernels_stand_as_near_the_float32_answer_as_jax_numpy():
    """bfloat16 streams and operands, float32 inside: against the float32
    answer the kernels' output and gradients stand no further off than the
    ``jax.numpy`` formulation's in bfloat16 (which rounds ``dx`` twice and
    ``dPhi`` once more), and both within a few bfloat16 roundings."""
    x, params, w, y0, c = _case(4.0, 0.1, 2, 128)
    x = x.astype(jnp.bfloat16).astype(jnp.float32)
    _, truth = _run(_through_jax_numpy, params, x, y0, w, c, 0.1, 4.0)
    out, got = _run(_through_kernels, params, x, y0, w, c, 0.1, 4.0,
                    dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16 and got['x'].dtype == jnp.float32
    _, plain = _run(_through_jax_numpy, params, x, y0, w, c, 0.1, 4.0,
                    dtype=jnp.bfloat16)

    def gap(a, b):
        return float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                     / jnp.linalg.norm(b))

    for name, leaf in truth.items():
        assert gap(got[name], leaf) < max(1.5 * gap(plain[name], leaf),
                                          4e-3), name
        assert gap(got[name], leaf) < 3e-2, name


def test_logits_far_beyond_the_clamp_stay_finite_and_take_no_gradient():
    """``alpha_res`` 400 puts the residual map's logits at +-500 and beyond
    before the clamp: everything finite and equal to ``jax.numpy`` (the
    gradients to 5e-3: Sinkhorn over entries ``exp(-30)`` to ``exp(30)``
    apart is as ill-conditioned as float32 allows, and a reciprocal in place
    of a division shows), and the clamped entries hand ``phi_res``
    nothing."""
    x, params, w, y0, c = _case(0.0, 0.5, 1, 128, spread=0.0)
    rng = np.random.default_rng(5)
    params = dict(params, alpha_res=jnp.float32(400.0), phi_res=jnp.asarray(
        rng.standard_normal((N * D, N * N)), jnp.float32))
    got, got_grads = _run(_through_kernels, params, x, y0, w, c, 0.5, 0.0)
    want, want_grads = _run(_through_jax_numpy, params, x, y0, w, c, 0.5, 0.0)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    for name, leaf in want_grads.items():
        assert bool(jnp.isfinite(got_grads[name]).all()), name
        np.testing.assert_allclose(
            np.asarray(got_grads[name]), np.asarray(leaf), rtol=5e-3,
            atol=5e-3 * float(jnp.abs(leaf).max()) + 1e-6, err_msg=name)
    assert float((want_grads['phi_res'] == 0).mean()) > 0.5
    np.testing.assert_array_equal(np.asarray(got_grads['phi_res'] == 0),
                                  np.asarray(want_grads['phi_res'] == 0))


def test_which_path_a_width_takes_and_what_the_plans_say(monkeypatch):
    """Streams of whole vregs take the kernels (interpreted off a TPU),
    64-wide ones ``jax.numpy``; the model's plan says which; one
    ``kernel.hc_plan`` instant a distinct shape."""
    assert hc.implementation(4, 64) == 'xla'
    assert hc.implementation(4, 3584) == hc.implementation(4, 128) \
        == 'pallas:interpret'
    assert hc.implementation(4, 128, interpret=False) == 'pallas'
    assert hc.implementation(8, 128) == 'xla'       # 80 maps: no room a row
    narrow = jnp.zeros((1, 8, 4 * 64), jnp.float32)
    with pytest.raises(ValueError, match='whole vregs'):
        hc.hyper_connection(narrow, lambda inner: inner, {}, 4)

    def model(d):
        return latent_moe.LatentMoELM(vocab_size=32, d_model=d, d_ff=32,
                                      num_layers=1, dense_layers=1,
                                      heads_held=1)

    assert model(64).layer_plan()['stream_mixing'] == 'xla'
    assert model(128).layer_plan()['stream_mixing'] == 'pallas:interpret'

    monkeypatch.setattr(hc, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        x, params, w, y0, c = _case(0.0, 0.5, 2, 100)
        for _ in range(2):
            jax.eval_shape(lambda p, x: _through_kernels(
                p, x, y0, w, c, 0.5, 0.0)[1], params, x)
        sub = latent_moe.StreamSubLayer(dtype=jnp.float32)
        jax.eval_shape(lambda x: sub.init(jax.random.PRNGKey(0), x,
                                          lambda inner: inner), narrow)
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'kernel.hc_plan']
    assert len(plans) == 1 and plans[0][1] == 'kernel'
    assert plans[0][7] == hc.hc_plan(200, 4, 128, jnp.float32, 128,
                                     'pallas:interpret')
    real = hc.hc_plan(4096, 4, 3584, jnp.bfloat16, 128, 'pallas')
    assert (real['blocks'], real['block_tokens']) == (32, 128)
    # forward: x twice in and once out, inner and y; 411 MB with the maps rows
    assert real['hbm_bytes_forward'] == 4096 * (3 * 28672 + 2 * 7168 + 1024)
    assert real['hbm_bytes_backward'] == 4096 * (5 * 28672 + 3 * 7168 + 2048)
    assert 32e6 < real['vmem_bytes'] < hc._VMEM_LIMIT


def test_the_same_answers_on_a_mesh():
    """Eight rows over eight devices: every shard pads its 24 tokens to a
    block of its own; the leaves' gradients are summed over the shards."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:8]), ('data',))
    x, params, w, y0, c = _case(4.0, 0.1, 8, 24)
    got, got_grads = _run(_through_kernels, params, x, y0, w, c, 0.1, 4.0,
                          mesh=mesh)
    want, want_grads = _run(_through_kernels, params, x, y0, w, c, 0.1, 4.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    for name, leaf in want_grads.items():
        np.testing.assert_allclose(
            np.asarray(got_grads[name]), np.asarray(leaf), rtol=2e-5,
            atol=2e-5 * float(jnp.abs(leaf).max()), err_msg=name)


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


def test_the_sub_layer_compiles_for_a_v5e_at_4096_tokens_of_4_by_3584(v5e):
    """The whole sub-layer as ``xing4.tokens4k`` runs it, bf16 ``[1, 4096, 4
    x 3584]``, forward and backward: Mosaic takes the four kernels (VMEM is
    the chip's compiler's to refuse), the compiler puts no ``copy`` of a
    stream-sized array around them, nothing ``[.., 4, 4]`` is an array, and
    the calls are named ``hc``."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(v5e[0])

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    n, d, t = 4, 3584, 4096
    leaves = {'scale': struct((n * d,)), 'phi_pre': struct((n * d, n)),
              'phi_post': struct((n * d, n)), 'phi_res': struct((n * d, n * n)),
              'alpha_pre': struct(()), 'alpha_post': struct(()),
              'alpha_res': struct(()), 'b_pre': struct((n,)),
              'b_post': struct((n,)), 'b_res': struct((n, n))}

    def loss(x, leaves, w):
        out, _ = hc.hyper_connection(
            x, lambda inner: jnp.tanh(inner @ w), leaves, n, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        struct((1, t, n * d), jnp.bfloat16), leaves,
        struct((d, d), jnp.bfloat16)).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 4
    assert all(re.match(r'\s*%\w*hc_', line) for line in calls), calls
    copies = [line.strip()[:160] for line in text.splitlines() if re.search(
        r'= \w+\[(1,)?4096,(14336|4,3584)\]\S* copy\(', line)]
    assert not copies, copies
    assert not re.search(r'f32\[(1,)?4096,4,4\]', text)
