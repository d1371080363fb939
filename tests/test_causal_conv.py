"""``ops.causal_conv.causal_conv_silu``: its hand-written backward pass against
``jax.vjp`` of the expression it replaced (the shapes of the gated-delta, Kimi
delta and Mamba-2 mixers, a row shorter than the taps, under
``jax.checkpoint`` and under ``jax.jit`` in bf16), what it keeps between the
passes, its ``step.conv_plan`` instant, where its instructions land in the
compiled step of a small ``KimiDeltaMixer``; the Pallas backward interpreted
against the same reference and over the row shards of a mesh, which
implementation a shape gets, and what each leaves in a Kimi delta step
compiled for a described v5e."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from petastorm_tpu import trace
from petastorm_tpu.models.ling_hybrid import KimiDeltaMixer
from petastorm_tpu.models.train import TrainState, make_train_step
from petastorm_tpu.ops import causal_conv as cc


def reference(x, kernel, bias=None):
    """The convolution as it stood before its backward was written out,
    differentiated by jax."""
    taps, t = kernel.shape[0], x.shape[1]
    x32 = x.astype(jnp.float32)
    padded = jnp.pad(x32, ((0, 0), (taps - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    y = sum(padded[:, i:i + t] * kernel[i] for i in range(taps))
    if bias is not None:
        y = y + bias
    return nn.silu(y).astype(x.dtype)


CASES = {
    # name: (x's shape, a bias, x's dtype, what the call runs under, the
    # backward pass: the jax.numpy one, or the Pallas kernel interpreted)
    'gated_delta': ((2, 64, 3, 96), False, jnp.float32, None, 'xla'),
    'kimi_delta': ((2, 64, 2, 128), False, jnp.float32, None, 'xla'),
    'mamba2': ((2, 64, 256), True, jnp.float32, None, 'xla'),
    'row_shorter_than_taps': ((2, 3, 256), True, jnp.float32, None, 'xla'),
    'checkpoint': ((2, 64, 2, 128), False, jnp.bfloat16, 'checkpoint',
                   'xla'),
    'jit_bf16': ((2, 64, 256), True, jnp.bfloat16, 'jit', 'xla'),
    # two grid steps along time, so the rows around a step's own are read
    'kernel_kimi_delta': ((1, 4096, 2, 128), False, jnp.bfloat16, 'jit',
                          'pallas:interpret'),
    # 96-wide heads: a grid step of 96 channels
    'kernel_gated_delta': ((2, 1024, 3, 96), True, jnp.bfloat16,
                           'checkpoint', 'pallas:interpret'),
}


def _f32(a):
    return np.asarray(jnp.asarray(a, jnp.float32))


@pytest.mark.parametrize('case', sorted(CASES))
def test_output_and_gradients_match_autodiff_of_the_expression(case):
    shape, has_bias, dtype, wrap, implementation = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    x = jax.random.normal(keys[0], shape, jnp.float32).astype(dtype)
    kernel = 0.5 * jax.random.normal(keys[1], (4,) + shape[2:], jnp.float32)
    bias = (0.5 * jax.random.normal(keys[2], shape[2:], jnp.float32)
            if has_bias else None)
    g = jax.random.normal(keys[3], shape, jnp.float32).astype(dtype)

    def vjp_of(f):
        def run(x, kernel, bias, g):
            if wrap == 'checkpoint':
                f_ = jax.checkpoint(f)
            else:
                f_ = f
            out, pullback = jax.vjp(f_, x, kernel, bias)
            return (out,) + pullback(g)
        return jax.jit(run) if wrap == 'jit' else run

    got = vjp_of(lambda x, kernel, bias: cc._conv_silu(
        x, kernel, bias, (implementation, None, None)))(x, kernel, bias, g)
    want = vjp_of(reference)(x, kernel, bias, g)
    for a, b in zip(got[:2], want[:2]):
        assert a.dtype == b.dtype and a.shape == b.shape
    if wrap is None:            # the forward pass is the expression itself
        np.testing.assert_array_equal(got[0], want[0])
    else:
        np.testing.assert_allclose(_f32(got[0]), _f32(want[0]),
                                   rtol=2.0 ** -7, atol=1e-6)
    # The input's gradient: the same float32 sum, rounded once to x's dtype
    # (one unit in its last place for bf16, float32 rounding otherwise).
    scale = float(np.max(np.abs(_f32(want[1]))))
    rtol = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(_f32(got[1]), _f32(want[1]), rtol=rtol,
                               atol=rtol * 1e-2 * scale)
    # The taps' and bias's gradients: float32 sums in another order.
    for a, b in zip(got[2:], want[2:]):
        if b is None:
            assert a is None
            continue
        assert a.dtype == b.dtype == jnp.float32
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-6 * float(np.max(np.abs(b))))


def test_the_residuals_are_x_in_its_dtype_the_taps_and_the_bias():
    """What lives between the passes is the bf16 input and the float32
    leaves, no float32 value of the row's shape; the plan counts it."""
    shape = (1, 32, 2, 128)
    x = jnp.ones(shape, jnp.bfloat16)
    kernel, bias = jnp.ones((4, 2, 128)), jnp.zeros((2, 128))
    _, pullback = jax.vjp(cc.causal_conv_silu, x, kernel, bias)
    kept = jax.tree_util.tree_leaves(pullback)
    assert sorted((a.shape, a.dtype.name) for a in kept) == sorted([
        (shape, 'bfloat16'), ((4, 2, 128), 'float32'),
        ((2, 128), 'float32')])
    plan = cc.conv_plan(shape, 4, True, jnp.bfloat16, 'xla')
    assert plan['residual_bytes'] == sum(a.nbytes for a in kept)
    assert plan == {'shape': [1, 32, 2, 128], 'taps': 4, 'bias': True,
                    'dtype': 'bfloat16', 'implementation': 'xla',
                    'residual_bytes': 16384 + 5 * 1024}


class _TinyKda(nn.Module):
    """An embedding, one recomputed ``KimiDeltaMixer`` and a head: keys 16
    wide and values 8, so that q and k share a convolution's plan and v has
    one of its own."""

    @nn.compact
    def __call__(self, tokens, train=True):
        x = nn.Embed(32, 16, name='embed')(tokens)
        x = x + nn.remat(KimiDeltaMixer)(
            heads_held=2, key_dim=16, value_dim=8, chunk=8, sub_block=4,
            impl='chunked', name='mixer')(x)
        return nn.Dense(32, name='head')(x)


def test_the_backward_is_the_mixer_s_and_one_plan_instant_a_plan(
        monkeypatch):
    """On the compiled step of a small ``KimiDeltaMixer``, every instruction
    under ``conv_silu`` is the mixer's, the taps' gradients (what only the
    backward pass computes there) run in the backward pass, and the ring has
    one ``step.conv_plan`` a distinct plan though the convolution was traced
    forward, recomputed and differentiated."""
    monkeypatch.setattr(cc, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        model = _TinyKda()
        tokens = jnp.arange(2 * 17).reshape(2, 17) % 32
        params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])['params']
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=optax.adamw(1e-3))
        step = make_train_step()
        state, metrics = step(state.replace(step=jnp.zeros((), jnp.int32)),
                              tokens[:, :-1], tokens[:, 1:])
        assert np.isfinite(float(metrics['loss']))
        table, = tracer.op_scopes().values()
    finally:
        trace.set_global_tracer(previous)
    rows = [r for r in table['instructions'].values()
            if 'conv_silu' in r['path'].split('/')]
    assert rows
    assert {r['part'] for r in rows} == {'mixer'}
    # the taps' gradients, [4, heads, width] each of the three convolutions
    taps = [r for r in rows if r['result'] in ('f32[4,2,16]', 'f32[4,2,8]')]
    assert len(taps) == 3
    assert {r['pass'] for r in taps} <= {'backward', 'recompute'}
    assert {r['pass'] for r in rows} >= {'forward', 'backward'}
    plans = [r for r in tracer.records() if r[0] == 'step.conv_plan']
    assert all(r[1] == 'step' and r[3] is None for r in plans)  # instants
    assert sorted((r[7]['shape'], r[7]['dtype'], r[7]['implementation'])
                  for r in plans) == [([2, 16, 2, 8], 'bfloat16', 'xla'),
                                      ([2, 16, 2, 16], 'bfloat16', 'xla')]


@pytest.mark.parametrize('shape, platform, implementation', [
    ((1, 8192, 32, 128), 'tpu', 'pallas'),     # ling3.tokens8k's q, k, v
    ((1, 8192, 15, 96), 'tpu', 'pallas'),      # olmohybrid.tokens8k's q, k
    ((1, 8192, 15, 192), 'tpu', 'pallas'),     # and v
    ((1, 8192, 2048), 'tpu', 'xla'),           # flat rows: channels in lanes
    ((1, 8192, 32, 128), 'cpu', 'xla'),
    ((1, 8000, 32, 128), 'tpu', 'xla'),        # no whole grid step of time
    ((1, 8192, 3, 5), 'tpu', 'xla'),           # no bf16 tile of channels
])
def test_the_implementation_follows_the_shape_and_the_platform(
        shape, platform, implementation):
    assert cc.implementation_for(shape, platform) == implementation


def test_the_channels_and_time_steps_of_a_grid_step():
    assert [cc._channels_per_step(c) for c in (4096, 1440, 2880, 256, 48)] \
        == [128, 96, 96, 128, 48]
    assert [cc._cols_per_step(t) for t in (8192, 1024, 3072, 8000)] \
        == [2048, 1024, 1536, None]


def test_the_kernel_over_the_row_shards_of_a_mesh_adds_the_taps_sums():
    """On two CPU devices, each row of the batch on its own: the kernel runs
    a shard at a time and the taps' and bias's sums are added over them."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()[:2]), ('data',))
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    shape = (2, 1024, 2, 64)
    x = jax.random.normal(keys[0], shape).astype(jnp.bfloat16)
    g = jax.random.normal(keys[1], shape).astype(jnp.bfloat16)
    kernel = 0.5 * jax.random.normal(keys[2], (4, 2, 64))
    bias = 0.5 * jax.random.normal(keys[3], (2, 64))

    def grads(how, x, g):
        return jax.jit(lambda x, g: jax.vjp(
            lambda x, k, b: cc._conv_silu(x, k, b, how), x, kernel,
            bias)[1](g))(x, g)

    rows = NamedSharding(mesh, PartitionSpec('data'))
    got = grads(('pallas:interpret', mesh, 'data'),
                jax.device_put(x, rows), jax.device_put(g, rows))
    want = grads(('xla', None, None), x, g)
    assert got[0].sharding.is_equivalent_to(rows, 4)
    np.testing.assert_allclose(_f32(got[0]), _f32(want[0]), rtol=2.0 ** -7,
                               atol=1e-4)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# -- compiled for the chip that is described, not attached ---------------

@pytest.fixture(scope='module')
def v5e():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - whatever says there is no compiler
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))


@pytest.mark.parametrize('implementation', ['pallas', 'xla'])
def test_a_kimi_delta_step_for_a_v5e_goes_back_without_a_copy(
        v5e, monkeypatch, implementation):
    """The step of one recomputed ``KimiDeltaMixer`` at the benchmark's
    heads (32 of 128 wide, 1,024 tokens, the ``kda`` kernels, on a one-chip
    mesh as the benchmark builds it) compiled for a v5e. Each of its three
    convolutions goes back as one Pallas call that reads the mixer's arrays
    as they lie, no copy of the row's size beside it; or, in ``jax.numpy``,
    in two fusions (``dz`` with the taps' sums, then ``dx``), where
    differentiated as written it took three."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from petastorm_tpu.models import scopes
    from petastorm_tpu.models.train import make_train_step_fn

    mesh = Mesh(np.array(v5e.devices[:1]), ('data',))

    class Model(nn.Module):
        @nn.compact
        def __call__(self, tokens, train=True):
            x = nn.Embed(64, 256, name='embed')(tokens)
            x = x + nn.remat(KimiDeltaMixer)(heads_held=32, mesh=mesh,
                                             name='mixer')(x)
            return nn.Dense(64, name='head')(x)

    # the kernels' choices read jax.devices()
    monkeypatch.setattr(jax, 'devices', lambda *a, **k: v5e.devices)
    monkeypatch.setattr(cc, 'implementation_for',
                        lambda shape, platform: implementation)
    model = Model()
    replicated = NamedSharding(mesh, PartitionSpec())
    tokens = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=NamedSharding(
        mesh, PartitionSpec('data')))

    def fresh(rng):
        params = model.init(rng, jnp.zeros((1, 1024), jnp.int32))['params']
        return TrainState.create(apply_fn=model.apply, params=params,
                                 tx=optax.adamw(1e-3))

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        jax.eval_shape(fresh, jax.random.PRNGKey(0)))
    text = jax.jit(make_train_step_fn(mesh=mesh)).lower(
        state, tokens, tokens).compile().as_text()
    rows = [r for r in scopes.parse_hlo_scopes(text)['instructions'].values()
            if 'conv_silu' in r['path'].split('/') and r['pass'] == 'backward']
    assert {r['part'] for r in rows} == {'mixer'}

    def elements(result):
        return int(np.prod([int(d) for d in
                            result[result.index('[') + 1:-1].split(',')]))

    large = [r for r in rows if elements(r['result']) >= 1024 * 32 * 128]
    if implementation == 'pallas':
        assert [r['opcode'] for r in large] == ['custom-call'] * 3, large
        assert all(r['path'].endswith('conv_silu/pallas_call') for r in large)
    else:
        fusions = [r for r in rows if r['opcode'] == 'fusion']
        assert len(fusions) == 2 * 3, fusions
