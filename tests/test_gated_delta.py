"""The gated delta rule: the chunked ``jax.numpy`` form and the Pallas
kernels (in the interpreter) against the recurrence token by token, forward
and gradients; the plan instant; the kernels compiled for a TPU at the
benchmark's widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu import trace
from petastorm_tpu.ops import gated_delta as gd


def _operands(t, b=2, h=3, dk=8, dv=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    # decays from nearly none to e^-1.5 a token, writes over the whole [0, 2]
    g = -1.5 * jax.random.uniform(ks[3], (b, t, h))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (b, t, h)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _value_and_grads(impl, chunk, operands):
    """``impl`` ``'scan'``: the recurrence as written, the reference."""
    def f(*a):
        o = gd.gated_delta_scan(*a) if impl == 'scan' else \
            gd.gated_delta_rule(*a, chunk=chunk, impl=impl)
        return jnp.sum(jnp.sin(o) * o), o
    (_, o), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(*operands)
    return o, grads


# chunk counts 1, 2 and 5, and T that is no multiple of the chunk (the last
# chunk padded with tokens that write and forget nothing)
@pytest.mark.parametrize('impl', ['chunked', 'pallas:interpret'])
@pytest.mark.parametrize('t,chunk', [(16, 16), (32, 16), (80, 16), (75, 16),
                                     (70, 64)])
def test_chunked_forms_agree_with_the_recurrence(impl, t, chunk):
    operands = _operands(t)
    o_ref, g_ref = _value_and_grads('scan', chunk, operands)
    o, grads = _value_and_grads(impl, chunk, operands)
    # float32 throughout: what is left is the order of summation (a chunk's
    # sums against a token's), a few units in the seventh place
    np.testing.assert_allclose(o, o_ref, atol=5e-6)
    for got, want in zip(grads, g_ref):
        np.testing.assert_allclose(got, want, atol=2e-5 * float(
            jnp.max(jnp.abs(want))))


def test_pallas_interpreter_runs_the_jax_numpy_pass_exactly():
    """Both run ``_chunk_forward`` / ``_chunk_backward``: the same numbers."""
    operands = _operands(48, dtype=jnp.bfloat16)
    o_a, g_a = _value_and_grads('chunked', 16, operands)
    o_b, g_b = _value_and_grads('pallas:interpret', 16, operands)
    np.testing.assert_array_equal(np.asarray(o_a, np.float32),
                                  np.asarray(o_b, np.float32))
    for a, b in zip(g_a, g_b):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_strong_decay_overflows_nothing():
    """A decay of e^-20 a token: every exponent the chunked form takes is a
    difference that is not positive, so nothing is inf or nan, forward or
    backward."""
    q, k, v, g, beta = _operands(64)
    g = jnp.full_like(g, -20.0)
    o, grads = _value_and_grads('chunked', 32, (q, k, v, g, beta))
    o_ref, _ = _value_and_grads('scan', 32, (q, k, v, g, beta))
    assert np.isfinite(np.asarray(o)).all()
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)
    np.testing.assert_allclose(o, o_ref, atol=5e-6)


def test_unknown_impl_and_compiled_kernels_off_a_tpu_are_refused():
    operands = _operands(16)
    with pytest.raises(ValueError, match='unknown impl'):
        gd.gated_delta_rule(*operands, impl='scan')
    with pytest.raises(RuntimeError, match='pallas:interpret'):
        gd.gated_delta_rule(*operands, impl='pallas')


def test_gdn_plan_instant_once_per_distinct_plan(monkeypatch):
    monkeypatch.setattr(gd, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for a in _operands(100, b=1, h=2, dk=96, dv=192,
                                     dtype=jnp.bfloat16)]

        def layers(impl, *a):
            def loss(q, k, v, g, beta):
                x = v
                for _ in range(3):              # three layers, one plan
                    x = gd.gated_delta_rule(q, k, x, g, beta, chunk=64,
                                            impl=impl)
                return jnp.sum(x.astype(jnp.float32))
            return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*a)

        for impl in ('pallas:interpret', 'pallas:interpret', 'chunked'):
            jax.eval_shape(lambda *a: layers(impl, *a), *shapes)
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'kernel.gdn_plan']
    assert len(plans) == 2
    assert all(r[1] == 'kernel' and r[3] is None for r in plans)   # instants
    assert plans[0][7] == {
        't': 100, 'chunk': 64, 'chunks_per_row': 2, 't_pad': 128,
        'heads_held': 2, 'key_width': 96,
        'value_width': 192, 'state_bytes_per_head': 4 * 96 * 192,
        'impl': 'pallas:interpret', 'dtype': 'bfloat16'}
    assert plans[1][7]['impl'] == 'chunked'


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
    """Mosaic takes what the interpreter cannot refuse: 96-wide keys, 192-wide
    values, 64-token chunks, five heads a grid step, bfloat16, forward and
    backward, as ``olmohybrid.tokens8k`` runs them (fewer chunks a row)."""
    bh, n, c, dk, dv = 15, 4, 64, 96, 192

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qg = kg = w = spec(bh, n, c, dk)
    u = do = v_new = spec(bh, n, c, dv)
    p, h = spec(bh, n, c, c), spec(bh, n, dk, dv)
    ec = spec(bh, n, 1, 1, dtype=jnp.float32)
    forward = jax.jit(lambda *a: gd._pass_forward_pallas(
        *a, interpret=False)).lower(qg, p, kg, w, u, ec).compile()
    backward = jax.jit(lambda *a: gd._pass_backward_pallas(
        *a, interpret=False)).lower(do, qg, p, kg, w, ec, h, v_new).compile()
    assert 'tpu_custom_call' in forward.as_text()
    assert 'tpu_custom_call' in backward.as_text()
