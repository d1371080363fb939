"""``ops.grouped_matmul``: the Pallas grouped product (interpret mode) against
``jax.lax.ragged_dot``, forward and both gradients, over groups of no row,
one row and uneven sizes; the tile tables; and the three kernels compiled for
a described v5e at the widths ``xing4.tokens4k`` runs."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu import trace

gm = importlib.import_module('petastorm_tpu.ops.grouped_matmul')

# true pairs a group, in the order the groups lie
COUNTS = [
    [0, 1, 37, 5, 16],          # none, one, uneven, a whole tile twice
    [0, 0, 0],                  # nothing routed here at all
    [24],                       # one group
    [3, 0, 0, 9, 0, 8, 1, 2],   # eight experts, most nearly empty
]


def _case(counts, tile_m, k, n, extra_tiles, dtype, seed=0):
    rng = np.random.default_rng(seed)
    counts = jnp.asarray(counts, jnp.int32)
    sizes, starts = gm.aligned_layout(counts, tile_m)
    rows = int(counts.sum()) + len(counts) * tile_m + extra_tiles * tile_m
    rows = -(-rows // tile_m) * tile_m
    x = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    w = jnp.asarray(rng.standard_normal((len(counts), k, n)) / np.sqrt(k), dtype)
    c = jnp.asarray(rng.standard_normal((rows, n)), jnp.float32)
    return x, w, sizes, starts, c


def _both(x, w, sizes, c, tile_m):
    def run(impl):
        def loss(x, w):
            y = gm.grouped_matmul(x, w, sizes, tile_m=tile_m, impl=impl)
            return jnp.sum(y.astype(jnp.float32) * c), y
        (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                           has_aux=True)(x, w)
        return (y,) + grads
    return run('pallas:interpret'), run('ragged_dot')


@pytest.mark.parametrize('counts', COUNTS)
@pytest.mark.parametrize('tile_m', [8, 16])
def test_the_kernels_equal_ragged_dot_forward_and_both_gradients(counts, tile_m):
    """float32, so the two routes differ by the order of a sum alone: 1e-5
    of the largest value (a bf16 product would differ by 4e-3)."""
    x, w, sizes, _, c = _case(counts, tile_m, 32, 48, 2, jnp.float32)
    got, want = _both(x, w, sizes, c, tile_m)
    for a, b, name in zip(got, want, ('y', 'dx', 'dw')):
        scale = max(float(jnp.abs(b).max()), 1.0)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5 * scale,
                                   rtol=0, err_msg=name)
    # the rows after the last group: zeros out, and no gradient comes back
    used = int(sizes.sum())
    assert not np.asarray(got[0])[used:].any()
    assert not np.asarray(got[1])[used:].any()


def test_bf16_products_accumulate_in_float32():
    """bf16 operands as the model hands them: the kernel's float32
    accumulator rounds once, as ``ragged_dot``'s does (2 ulp of bf16)."""
    x, w, sizes, _, c = _case([5, 0, 19], 16, 64, 128, 1, jnp.bfloat16)
    got, want = _both(x, w, sizes, c, 16)
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 2 ** -7 * max(np.abs(b).max(), 1.0)


@pytest.mark.parametrize('counts,tile_m,sizes,starts', [
    ([0, 1, 37, 5, 16], 8, [8, 8, 40, 8, 16], [0, 8, 16, 56, 64]),
    ([256, 255, 257], 128, [256, 256, 384], [0, 256, 512]),
    ([0, 0], 128, [128, 128], [0, 128]),
])
def test_aligned_layout_gives_every_group_whole_tiles_and_at_least_one(
        counts, tile_m, sizes, starts):
    got_sizes, got_starts = gm.aligned_layout(jnp.asarray(counts, jnp.int32),
                                              tile_m)
    assert got_sizes.tolist() == sizes and got_starts.tolist() == starts


def test_tile_groups_names_each_tile_s_group_and_stops_at_the_last():
    sizes = jnp.asarray([8, 8, 40, 8, 16], jnp.int32)
    group, used = gm.tile_groups(sizes, 8, 14)
    assert used.tolist() == [10]
    # a tile past the last group keeps the last used tile's group: its
    # blocks are the ones already in VMEM, and dw's block is not left
    assert group.tolist() == [0, 1, 2, 2, 2, 2, 2, 3, 4, 4] + [4] * 4


def test_what_the_route_refuses():
    x = jnp.zeros((24, 8), jnp.float32)
    w = jnp.zeros((2, 8, 8), jnp.float32)
    sizes = jnp.asarray([8, 8], jnp.int32)
    with pytest.raises(ValueError, match='impl'):
        gm.grouped_matmul(x, w, sizes, tile_m=8, impl='dense')
    with pytest.raises(ValueError, match='tiles of 16 rows'):
        gm.grouped_matmul(x, w, sizes, tile_m=16, impl='pallas:interpret')
    with pytest.raises(RuntimeError, match='default jax backend'):
        gm.grouped_matmul(x, w, sizes, tile_m=8)     # compiled: a TPU only


def test_moe_plan_instant_once_a_distinct_plan(monkeypatch):
    monkeypatch.setattr(gm, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        x, w, sizes, _, _ = _case([3, 9], 8, 16, 128, 0, jnp.float32)
        for _ in range(2):          # two layers, one plan
            jax.eval_shape(lambda x, w: gm.grouped_matmul(
                x, w, sizes, tile_m=8, impl='pallas:interpret'), x, w)
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'kernel.moe_plan']
    assert len(plans) == 1 and plans[0][1] == 'kernel'
    plan = plans[0][7]
    assert (plan['groups'], plan['rows_capacity'], plan['tiles'], plan['k'],
            plan['n'], plan['tile_m'], plan['impl'], plan['dtype']) == (
                2, x.shape[0], x.shape[0] // 8, 16, 128, 8, 'pallas:interpret',
                'float32')
    # the cell's first product: [16384 + 8 x 128, 3584] x [8, 3584, 2048] bf16
    real = gm.moe_plan(17408, 3584, 2048, 8, 128, jnp.bfloat16, 'pallas')
    assert (real['tiles'], real['block_n'], real['block_k_dw'],
            real['block_n_dw']) == (136, 512, 3584, 2048)


@pytest.fixture(scope='module')
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - whatever says there is no compiler
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))
    return topo.devices


@pytest.mark.parametrize('k,n', [(3584, 2048), (1024, 3584)])
def test_the_kernels_compile_for_a_v5e_at_the_cell_s_widths(v5e, k, n):
    """``xing4.tokens4k``'s two products, bf16, 136 tiles of 128 rows against
    eight experts, forward and both gradients: Mosaic takes the blocks (a
    ``[3584, 512]`` block of weights twice in VMEM, the contraction whole)."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(v5e[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def grads(x, w, sizes, c):
        return jax.value_and_grad(lambda x, w: jnp.sum(gm._grouped(
            x, w, sizes, 128, False).astype(jnp.float32) * c),
            argnums=(0, 1))(x, w)

    rows = 4096 * 4 + 8 * 128
    compiled = jax.jit(grads).lower(
        struct((rows, k), jnp.bfloat16), struct((8, k, n), jnp.bfloat16),
        struct((8,), jnp.int32), struct((rows, n), jnp.float32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 3
