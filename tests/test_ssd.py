"""Mamba-2's state-space rule: the ``jax.numpy`` chunked form and the Pallas
kernels (in the interpreter) against the recurrence token by token, forward
and all six gradients: several chunks and a row that is no whole number of
them, two 64-wide heads to a lane block and several bands a group, groups
whose heads read their own ``B`` and ``C``, decays from nearly none to the
strongest the published initialisation gives; the plan instant; the kernels
compiled for a TPU at the benchmark's widths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu import trace
from petastorm_tpu.ops import gated_delta as gd
from petastorm_tpu.ops import ssd


def _operands(t, h, g, p, n, decay, seed=0, dtype=jnp.float32):
    """``decay`` ``'mild'``: ``dt A`` near 0 (``dt`` about 1e-3, ``A`` about
    -1); ``'strong'``: ``dt`` near 0.1 and ``A`` near -16, a chunk of 128
    decaying by ``e^-205``, the most the published ``time_step_max`` and
    ``A_log`` range give."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (1, t, h * p))
    b = jax.random.normal(ks[1], (1, t, g * n)) / np.sqrt(n)
    c = jax.random.normal(ks[2], (1, t, g * n))
    u = jax.random.uniform(ks[3], (1, t, h))
    if decay == 'mild':
        dt, a = 1e-3 * (0.5 + u), -1.0 - jax.random.uniform(ks[4], (h,))
    else:
        dt, a = 0.1 * (0.9 + 0.1 * u), -16.0 + jax.random.uniform(ks[4], (h,))
    d = jax.random.normal(ks[5], (h,))
    return (x.astype(dtype), b.astype(dtype), c.astype(dtype), dt, a, d)


def _value_and_grads(impl, groups, chunk, operands):
    """``impl`` ``'scan'``: the recurrence as written, the reference."""
    def f(*a):
        y = ssd.ssd_scan(*a, groups) if impl == 'scan' else \
            ssd.ssd_rule(*a, groups, chunk=chunk, impl=impl)
        return jnp.sum(jnp.sin(y) * y), y
    (_, y), grads = jax.value_and_grad(f, argnums=tuple(range(6)),
                                       has_aux=True)(*operands)
    return y, grads


@functools.lru_cache(maxsize=None)
def _reference(shape, decay):
    operands = _operands(*shape, decay)
    return (operands,) + _value_and_grads('scan', shape[2], None, operands)


# (T, H, G, P, N, chunk): several chunks; a row that is no whole number of
# chunks (the last padded with tokens that write and forget nothing); four
# heads of 64, two to a lane block, two bands a group; groups of one head.
SHAPES = [((48, 4, 2, 8, 16), 16), ((75, 4, 1, 16, 8), 32),
          ((40, 8, 2, 64, 16), 16), ((32, 2, 2, 32, 8), 8)]


@pytest.mark.parametrize('impl', ['xla', 'pallas:interpret'])
@pytest.mark.parametrize('decay', ['mild', 'strong'])
@pytest.mark.parametrize('shape,chunk', SHAPES)
def test_chunked_forms_agree_with_the_recurrence(impl, decay, shape, chunk):
    operands, y_ref, g_ref = _reference(shape, decay)
    y, grads = _value_and_grads(impl, shape[2], chunk, operands)
    assert np.isfinite(np.asarray(y)).all()
    assert all(np.isfinite(np.asarray(v)).all() for v in grads)
    # float32 throughout: what is left is the order of summation (a chunk's
    # products against a token's state). Each gradient to a share of its own
    # largest value; dA sums over every token and head's decay, dt's over a
    # chunk's: both sums of terms that partly cancel.
    np.testing.assert_allclose(y, y_ref, atol=2e-5 * float(
        jnp.max(jnp.abs(y_ref))))
    for name, got, want in zip('x b c dt a d'.split(), grads, g_ref):
        own = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, err_msg=name,
                                   atol=(1e-4 if name in ('dt', 'a') else 2e-5)
                                   * own)


def test_in_bfloat16_the_kernels_stay_with_the_float32_rule():
    """The cell's dtype: bfloat16 operands of every product, float32
    accumulation, decays and states; the output and gradients to the
    rounding of bfloat16 operands."""
    shape = (64, 4, 2, 64, 128)
    operands = _operands(*shape, 'strong', dtype=jnp.bfloat16)
    want = _value_and_grads('scan', 2, None, operands)
    got = _value_and_grads('pallas:interpret', 2, 32, operands)
    for a, b in zip((got[0],) + got[1], (want[0],) + want[1]):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        np.testing.assert_allclose(a, b, rtol=0, atol=3e-2 * np.abs(b).max())


def test_the_plan_takes_a_group_s_heads_a_lane_block_at_a_time():
    def plan(h, g, p):
        return ssd.ssd_plan(8192, h, g, p, 128, 128, 'pallas', 'bfloat16')

    cell = plan(32, 2, 64)
    assert cell['heads_per_step'] == 2 and cell['chunks_per_row'] == 64
    assert cell['vmem_bytes'] < 4 * 2 ** 20
    assert [plan(h, g, p)['heads_per_step'] for h, g, p in (
        (8, 8, 64), (16, 2, 32), (12, 2, 32), (4, 1, 128), (4, 1, 256))] == \
        [1, 4, 3, 1, 1]


def test_unknown_impl_odd_groups_and_compiled_kernels_off_a_tpu_are_refused():
    operands = _operands(16, 4, 2, 8, 8, 'mild')
    with pytest.raises(ValueError, match='unknown impl'):
        ssd.ssd_rule(*operands, 2, impl='scan')
    with pytest.raises(ValueError, match='groups'):
        ssd.ssd_rule(*operands, 3)
    with pytest.raises(RuntimeError, match='pallas:interpret'):
        ssd.ssd_rule(*operands, 2, impl='pallas')


def test_ssd_plan_instant_once_per_distinct_plan(monkeypatch):
    monkeypatch.setattr(gd, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in _operands(
            100, 4, 2, 64, 128, 'mild', dtype=jnp.bfloat16)]

        def layers(impl, *a):
            def loss(x, b, c, dt, a_, d):
                for _ in range(3):              # three layers, one plan
                    x = ssd.ssd_rule(x, b, c, dt, a_, d, 2, impl=impl)
                return jnp.sum(x.astype(jnp.float32))
            return jax.grad(loss, argnums=tuple(range(6)))(*a)

        for impl in ('pallas:interpret', 'pallas:interpret', 'xla'):
            jax.eval_shape(lambda *a: layers(impl, *a), *shapes)
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'kernel.ssd_plan']
    assert len(plans) == 2
    assert all(r[1] == 'kernel' and r[3] is None for r in plans)   # instants
    assert plans[0][7] == {
        't': 100, 'chunk': 128, 'chunks_per_row': 1, 't_pad': 128,
        'heads': 4, 'groups': 2, 'head_width': 64, 'state_width': 128,
        'heads_per_step': 2, 'vmem_bytes': plans[0][7]['vmem_bytes'],
        'impl': 'pallas:interpret', 'dtype': 'bfloat16'}
    assert 200_000 < plans[0][7]['vmem_bytes'] < 2_000_000
    assert plans[1][7]['impl'] == 'xla'


# -- compiled for the chip that is described, not attached -----------------------

@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - whatever says there is no compiler
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_for_a_v5e_at_the_benchmark_s_widths(one_chip):
    """Mosaic takes what the interpreter cannot refuse: 32 heads of 64 in two
    groups, states 128 wide, chunks of 128, bfloat16, forward and backward,
    as ``nemotron3.tokens8k`` runs them (fewer chunks a row)."""
    b, t, h, g, p, n, c = 1, 512, 32, 2, 64, 128, 128
    bf, f32 = jnp.bfloat16, jnp.float32

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x, bc = shape((b, t, h * p), bf), shape((b, t, g * n), bf)
    dt, a = shape((b, t, h), f32), shape((h,), f32)
    forward = jax.jit(lambda *o: ssd._forward(*o, g, c, 'pallas', True)
                      ).lower(x, bc, bc, dt, a, a).compile()
    s = ssd.ssd_plan(t, h, g, p, n, c, 'pallas', 'bfloat16')['heads_per_step']
    states = shape((b, h // s, t // c, n, s * p), bf)
    backward = jax.jit(lambda st, *o: ssd._rule_bwd(
        g, c, 'pallas', (st,) + o[:-1], o[-1])).lower(
            states, x, bc, bc, dt, a, a, x).compile()
    assert 'tpu_custom_call' in forward.as_text()
    assert 'tpu_custom_call' in backward.as_text()
