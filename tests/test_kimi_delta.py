"""Kimi delta attention's rule (a delta rule whose decay is a vector a head
and token): the chunked ``jax.numpy`` form and the Pallas kernels (in the
interpreter) against the recurrence token by token, forward and all five
gradients, with a mild gate and with every token's gate at its bound, and on
the exact path with a decay of no bound and write strengths in (0, 2); the
kernels with several heads a grid step against one, to the bit; the plan
instant and the rule that picks the heads a step; the kernels of both paths
compiled for a TPU at the benchmarks' widths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu import trace
from petastorm_tpu.ops import gated_delta as gd
from petastorm_tpu.ops import kimi_delta as kd


def _operands(t, gate, b=1, h=2, dk=8, dv=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    u = jax.random.uniform(ks[3], (b, t, h, dk))
    if gate == 'mild':          # from nearly none to e^-2.5 a token and channel
        g = kd.GATE_LOWER_BOUND * jax.nn.sigmoid(4 * u - 4)
    else:                       # every token's gate within 1e-3 of its bound
        g = kd.GATE_LOWER_BOUND + 1e-3 * u
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[4], (b, t, h)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _value_and_grads(impl, chunk, sub, operands):
    """``impl`` ``'scan'``: the recurrence as written, the reference."""
    def f(*a):
        o = kd.kda_scan(*a) if impl == 'scan' else \
            kd.kda_rule(*a, chunk=chunk, sub_block=sub, impl=impl)
        return jnp.sum(jnp.sin(o) * o), o
    (_, o), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(*operands)
    return o, grads


@functools.lru_cache(maxsize=None)
def _reference(t, gate):
    operands = _operands(t, gate)
    return (operands,) + _value_and_grads('scan', None, None, operands)


# several chunks, a row that is no whole number of chunks (the last
# chunk padded with tokens that write and forget nothing), and the
# benchmark's own chunk of 64 in sub-blocks of 16
@pytest.mark.parametrize('impl', ['chunked', 'pallas:interpret'])
@pytest.mark.parametrize('gate', ['mild', 'bound'])
@pytest.mark.parametrize('t,chunk,sub', [(48, 16, 4), (75, 16, 8),
                                         (70, 64, 16)])
def test_chunked_forms_agree_with_the_recurrence(impl, gate, t, chunk, sub):
    operands, o_ref, g_ref = _reference(t, gate)
    o, grads = _value_and_grads(impl, chunk, sub, operands)
    assert np.isfinite(np.asarray(o)).all()
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)
    # float32 throughout: what is left is the order of summation (a chunk's
    # sums against a token's). At the bound the state all but vanishes a
    # token (e^-5), the gate's gradient is a sum of terms that all but cancel
    # and a thousandth of the others' size: its tolerance is of the keys'.
    np.testing.assert_allclose(o, o_ref, atol=1e-5)
    scale = max(float(jnp.max(jnp.abs(x))) for x in g_ref)
    for name, got, want in zip('q k v g beta'.split(), grads, g_ref):
        own = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(
            got, want, err_msg=name,
            atol=4e-5 * (scale if name == 'g' else own))


def _unbounded(t, b=1, h=2, dk=8, dv=16, seed=0):
    """Operands of the exact path: a decay from 0 down to -40 a token and
    channel (most near 0, a tail far past the bounded path's floor: a
    sub-block of 16 spans up to e^640) and write strengths in (0, 2), many
    near 2, float32."""
    q, k, v, _, _ = _operands(t, 'mild', b=b, h=h, dk=dk, dv=dv, seed=seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 2)
    g = -40.0 * jax.random.uniform(ks[0], (b, t, h, dk)) ** 3
    beta = 2 * jax.nn.sigmoid(3 * jax.random.normal(ks[1], (b, t, h)))
    return q, k, v, g, beta


@functools.lru_cache(maxsize=None)
def _unbounded_reference(t):
    operands = _unbounded(t)
    return (operands,) + _value_and_grads('scan', None, None, operands)


def _exact_value_and_grads(impl, chunk, sub, operands, exact=True):
    def f(*a):
        o = kd.kda_rule(*a, chunk=chunk, sub_block=sub, impl=impl,
                        exact=exact)
        return jnp.sum(jnp.sin(o) * o), o
    (_, o), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(*operands)
    return o, grads


@pytest.mark.parametrize('impl', ['chunked', 'pallas:interpret'])
@pytest.mark.parametrize('t,chunk,sub', [(48, 16, 4), (75, 16, 8),
                                         (70, 64, 16)])
def test_the_exact_path_agrees_with_the_recurrence_for_any_decay(impl, t,
                                                                   chunk, sub):
    """``g`` down to -40 and ``beta`` up to 2, float32: ``o`` and the five
    gradients within 1e-4 of each array's largest value (read: 1.4e-5 for
    ``o``, up to 3.4e-5 for the gate's gradient at chunks of 64: with
    ``beta`` near 2 the unit-lower inverse grows, and what is left is the
    order of a chunk's float32 sums against a token's; bfloat16 products
    would read 1e-3 and more). The bounded path on the same operands leaves
    float32 (its sub-block columns ``exp(r_a - G_j)`` overflow)."""
    operands, o_ref, g_ref = _unbounded_reference(t)
    o, grads = _exact_value_and_grads(impl, chunk, sub, operands)
    for name, got, want in zip('o q k v g beta'.split(), (o,) + grads,
                               (o_ref,) + g_ref):
        assert np.isfinite(np.asarray(got)).all(), name
        np.testing.assert_allclose(
            got, want, rtol=0, err_msg=name,
            atol=1e-4 * float(jnp.max(jnp.abs(want))))
    if impl == 'chunked':
        bounded, _ = _exact_value_and_grads(impl, chunk, sub, operands,
                                            exact=False)
        assert not np.isfinite(np.asarray(bounded)).all()


def _aligned(t, b=1, h=2, dk=128, dv=16, seed=0):
    """Keys that align (a shared direction and noise of a third of its size:
    cosines near 0.9), a decay that all but keeps the state (``g`` in
    (-1e-3, 0)) and ``beta`` at 1.99: where a chunk's unit-lower system is
    hardest, float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(ks[0], (1, 1, h, dk)) \
        + 0.3 * jax.random.normal(ks[1], (b, t, h, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -1e-3 * jax.random.uniform(ks[3], (b, t, h, dk))
    return k / np.sqrt(dk), k, v, g, jnp.full((b, t, h), 1.99)


@pytest.mark.parametrize('impl', ['chunked', 'pallas:interpret'])
def test_the_exact_path_solves_the_chunk_where_keys_align(impl):
    """``T = (I + L)^-1`` by substitution: ``o`` and the five gradients
    within 1e-4 of each array's largest value of the recurrence's (read: 1e-5
    and under). The finite Neumann product (the bounded path's) forms powers
    of ``L`` near 1e20 here and loses every digit: shown on the inverse
    alone."""
    operands = _aligned(128)
    o_ref, g_ref = _value_and_grads('scan', None, None, operands)
    o, grads = _exact_value_and_grads(impl, 64, 16, operands)
    for name, got, want in zip('o q k v g beta'.split(), (o,) + grads,
                               (o_ref,) + g_ref):
        assert np.isfinite(np.asarray(got)).all(), name
        np.testing.assert_allclose(
            got, want, rtol=0, err_msg=name,
            atol=1e-4 * float(jnp.max(jnp.abs(want))))
    low = 1.99 * 0.9 * jnp.tril(jnp.ones((64, 64)), -1)
    eye = jnp.eye(64)
    exact = kd._inverse_by_substitution(low, 16)
    assert float(jnp.max(jnp.abs(exact @ (eye + low) - eye))) < 1e-4
    neumann = gd._inverse_of_unit_lower(low)
    assert not float(jnp.max(jnp.abs(neumann @ (eye + low) - eye))) < 1.0


def test_the_exact_path_s_plan_says_so_and_holds_fewer_heads_a_step():
    plan = kd.kda_plan(8192, 16, 128, 128, 64, 16, 'pallas', 'bfloat16',
                       exact=True)
    assert (plan['path'], plan['gate_lower_bound'],
            plan['largest_exponent']) == ('exact', None, 0.0)
    assert plan['heads_per_step'] == kd.EXACT_HEADS_PER_STEP == 4
    bounded = kd.kda_plan(8192, 16, 128, 128, 64, 16, 'pallas', 'bfloat16')
    assert (bounded['path'], bounded['largest_exponent'],
            bounded['heads_per_step']) == ('bounded', 75.0, 8)


def test_a_whole_chunk_formulation_overflows_where_the_sub_blocks_do_not():
    """What the sub-blocks exist for: with every gate at -5 the factors
    ``exp(G_i)`` and ``exp(-G_j)`` over a chunk of 64 leave float32 (e^-315
    is 0, e^315 inf, their product nan), while no exponent of the sub-block
    form passes 75."""
    q, k, v, g, beta = _operands(64, 'bound')
    big_g = jnp.cumsum(g[0, :, 0], axis=0)                          # [64, dk]
    whole = (k[0, :, 0] * jnp.exp(big_g)) @ (k[0, :, 0] * jnp.exp(-big_g)).T
    assert not np.isfinite(np.asarray(jnp.tril(whole))).all()
    plan = kd.kda_plan(64, 2, 8, 16, 64, 16, 'chunked', 'float32')
    assert plan['largest_exponent'] == 75.0
    o = kd.kda_rule(q, k, v, g, beta, chunk=64, sub_block=16)
    np.testing.assert_allclose(o, kd.kda_scan(q, k, v, g, beta), atol=1e-5)


def test_pallas_interpreter_runs_the_jax_numpy_bodies_exactly():
    """Both run ``_chunk_forward_kda`` / ``_chunk_backward_kda`` in bfloat16:
    the same numbers (the float32 gradients of the gate and of the write
    strength to the last place or two: XLA fuses their sums its own way)."""
    operands = _operands(48, 'mild', dtype=jnp.bfloat16)
    o_a, g_a = _value_and_grads('chunked', 16, 4, operands)
    o_b, g_b = _value_and_grads('pallas:interpret', 16, 4, operands)
    np.testing.assert_array_equal(np.asarray(o_a, np.float32),
                                  np.asarray(o_b, np.float32))
    for a, b in zip(g_a, g_b):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-6,
                                   atol=1e-7)


def _kernels(operands, do, heads):
    """``o``, the saved states and ``T``, and the five gradients of the two
    Pallas calls (interpreted, the benchmark's chunks of 64 in sub-blocks of
    16) with ``heads`` heads a grid step, each call jitted as the rule's
    are."""
    o, states, inverses = jax.jit(kd._forward_pallas, static_argnums=(
        5, 6, 7, 8, 9))(*operands, 64, 16, heads, True, True)
    return (o, states, inverses) + jax.jit(
        kd._backward_pallas, static_argnums=(8, 9, 10, 11))(
            do, states, inverses, *operands, 64, 16, heads, True)


# four and six heads, which the rule takes whole, and twelve, which
# HEADS_PER_STEP (8) does not divide: the rule falls to 6
@pytest.mark.parametrize('h,gate', [(4, 'mild'), (6, 'bound'), (12, 'mild')])
@pytest.mark.parametrize('heads', [2, 'rule'])
def test_several_heads_a_grid_step_change_no_bit(h, gate, heads):
    """A grid step runs the one-head body batched over its heads, each
    head's operations today's, so ``o``, what the reverse pass reads and the
    five gradients are those of one head a step to the bit; ``'rule'``:
    through :func:`kda_rule` and its ``custom_vjp``, at the heads
    :func:`kda_plan` chooses. At the cell's widths: with 8- or 16-wide heads
    or chunks of 16 XLA's CPU products, which the interpreter runs, sum
    otherwise batched four or more than alone, in a few last places; the
    chip at the cell's shape does not (PERF.md section 6, PR 38)."""
    operands = _operands(128, gate, h=h, dk=128, dv=128, dtype=jnp.bfloat16)
    do = jax.random.normal(jax.random.PRNGKey(1), operands[2].shape,
                           jnp.bfloat16)
    want = _kernels(operands, do, 1)
    if heads == 'rule':
        plan = kd.kda_plan(128, h, 128, 128, 64, 16, 'pallas:interpret',
                           'bfloat16')
        assert plan['heads_per_step'] == {4: 4, 6: 6, 12: 6}[h]
        o, vjp = jax.vjp(lambda *a: kd.kda_rule(
            *a, impl='pallas:interpret'), *operands)
        got = (o,) + vjp(do)
        want = want[:1] + want[3:]
    else:
        got = _kernels(operands, do, heads)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(b).view(np.uint8))


def test_heads_a_step_are_a_divisor_under_the_constant_and_the_budget(
        monkeypatch):
    monkeypatch.setattr(kd, 'HEADS_PER_STEP', 4)

    def heads(h, width=128, t=8192):
        return kd.kda_plan(t, h, width, width, 64, 16, 'pallas',
                           'bfloat16')['heads_per_step']

    assert [heads(h) for h in (1, 2, 3, 4, 6, 7, 8, 32)] == \
        [1, 2, 3, 4, 3, 1, 4, 4]
    # the blocks of a step stay under the budget: wider heads, longer rows
    # take fewer a step, down to one
    per_step = kd.kda_plan(8192, 32, 128, 128, 64, 16, 'pallas', 'bfloat16')
    assert per_step['vmem_bytes'] <= kd.STEP_VMEM_BUDGET
    assert heads(32, width=512) == 2
    assert heads(32, width=1024) == 1
    assert heads(32, t=1 << 20) == 1
    monkeypatch.setattr(kd, 'HEADS_PER_STEP', 8)
    assert [heads(h) for h in (6, 12, 32)] == [6, 6, 8]
    assert heads(32, width=256) == 4


def test_unknown_impl_sub_blocks_and_compiled_kernels_off_a_tpu_are_refused():
    operands = _operands(16, 'mild')
    with pytest.raises(ValueError, match='unknown impl'):
        kd.kda_rule(*operands, impl='scan')
    with pytest.raises(ValueError, match='whole sub-blocks'):
        kd.kda_rule(*operands, chunk=16, sub_block=5)
    with pytest.raises(RuntimeError, match='pallas:interpret'):
        kd.kda_rule(*operands, impl='pallas')


def test_kda_plan_instant_once_per_distinct_plan(monkeypatch):
    monkeypatch.setattr(gd, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for a in _operands(100, 'mild', h=2, dk=128, dv=128,
                                     dtype=jnp.bfloat16)]

        def layers(impl, *a):
            def loss(q, k, v, g, beta):
                x = v
                for _ in range(3):              # three layers, one plan
                    x = kd.kda_rule(q, k, x, g, beta, impl=impl)
                return jnp.sum(x.astype(jnp.float32))
            return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*a)

        for impl in ('pallas:interpret', 'pallas:interpret', 'chunked'):
            jax.eval_shape(lambda *a: layers(impl, *a), *shapes)
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'kernel.kda_plan']
    assert len(plans) == 2
    assert all(r[1] == 'kernel' and r[3] is None for r in plans)   # instants
    assert plans[0][7] == {
        't': 100, 'chunk': 64, 'sub_block': 16, 'chunks_per_row': 2,
        't_pad': 128, 'heads_held': 2, 'heads_per_step': 2,
        'key_width': 128, 'value_width': 128, 'path': 'bounded',
        'gate_lower_bound': -5.0, 'largest_exponent': 75.0,
        'state_bytes_per_head': 4 * 128 * 128,
        'vmem_bytes': plans[0][7]['vmem_bytes'],
        'impl': 'pallas:interpret', 'dtype': 'bfloat16'}
    # a grid step's: both heads' blocks twice and their states' gradients
    assert 400_000 < plans[0][7]['vmem_bytes'] < 2_000_000
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


@pytest.mark.parametrize('heads', [1, 'rule'])
def test_the_kernels_compile_for_a_v5e_at_the_benchmark_s_widths(one_chip,
                                                                  heads):
    """Mosaic takes what the interpreter cannot refuse: 128-wide keys and
    values read as bands of heads of ``[B, T, H 128]``, 64-token chunks in
    sub-blocks of 16, bfloat16, forward and backward, as ``ling3.tokens8k``
    runs them (its 32 heads at the heads a step the rule chooses, and one
    head a step, the body unbatched; fewer chunks a row)."""
    b, t, h, d, c = 1, 256, 32, 128, 64
    bf, f32 = jnp.bfloat16, jnp.float32
    if heads == 'rule':
        heads = kd.kda_plan(8192, h, d, d, c, 16, 'pallas',
                            'bfloat16')['heads_per_step']
        assert heads == kd.HEADS_PER_STEP

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    wide, g, beta = (shape((b, t, h, d), bf), shape((b, t, h, d), f32),
                     shape((b, t, h), f32))
    forward = jax.jit(lambda *a: kd._forward_pallas(
        *a, c, 16, heads, True, False)).lower(wide, wide, wide, g,
                                              beta).compile()
    backward = jax.jit(lambda *a: kd._backward_pallas(
        *a, c, 16, heads, False)).lower(
            wide, shape((b, h, t // c, d, d), bf),
            shape((b, h, t // c, c, c), bf), wide, wide, wide, g,
            beta).compile()
    assert 'tpu_custom_call' in forward.as_text()
    assert 'tpu_custom_call' in backward.as_text()


def test_the_exact_kernels_compile_for_a_v5e_at_the_benchmark_s_widths(
        one_chip):
    """The exact path as ``solar2.tokens8k`` runs it: 16 heads of 128 at the
    heads a step its plan chooses (4: at 8 the reverse kernel's pairs ask
    Mosaic for more scoped VMEM than a v5e allows), chunks of 64 in
    sub-blocks of 16, bfloat16, forward and backward; fewer chunks a row."""
    b, t, h, d, c = 1, 256, 16, 128, 64
    bf, f32 = jnp.bfloat16, jnp.float32
    heads = kd.kda_plan(8192, h, d, d, c, 16, 'pallas', 'bfloat16',
                        exact=True)['heads_per_step']

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    wide, g, beta = (shape((b, t, h, d), bf), shape((b, t, h, d), f32),
                     shape((b, t, h), f32))
    forward = jax.jit(lambda *a: kd._forward_pallas(
        *a, c, 16, heads, True, False, True)).lower(
            wide, wide, wide, g, beta).compile()
    backward = jax.jit(lambda *a: kd._backward_pallas(
        *a, c, 16, heads, False, True)).lower(
            wide, shape((b, h, t // c, d, d), bf),
            shape((b, h, t // c, c, c), bf), wide, wide, wide, g,
            beta).compile()
    assert 'tpu_custom_call' in forward.as_text()
    assert 'tpu_custom_call' in backward.as_text()
