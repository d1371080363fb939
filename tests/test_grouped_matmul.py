"""``ops.grouped_matmul``: the Pallas grouped product (interpret mode) against
``jax.lax.ragged_dot``, forward and both gradients, over groups of no row,
one row and uneven sizes; the tile tables; the rows summed into tokens against
``jax.ops.segment_sum``; and the kernels compiled for a described v5e at the
widths ``xing4.tokens4k`` and ``ling3.tokens8k`` run."""

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
    # the rows hold all but a tile a group
    assert plan['pairs_capacity'] == x.shape[0] - 2 * 8
    # ling3.tokens8k's: 5,120 rows for 4,096 pairs
    compact = gm.moe_plan(5120, 2560, 1536, 8, 128, jnp.bfloat16, 'pallas')
    assert (compact['rows_capacity'], compact['pairs_capacity'],
            compact['tiles']) == (5120, 4096, 40)
    # the cell's first product: [16384 + 8 x 128, 3584] x [8, 3584, 2048] bf16
    real = gm.moe_plan(17408, 3584, 2048, 8, 128, jnp.bfloat16, 'pallas')
    assert (real['tiles'], real['block_n'], real['block_k_dw'],
            real['block_n_dw']) == (136, 512, 3584, 2048)


@pytest.mark.parametrize('counts', COUNTS)
def test_the_gradients_by_hand_are_what_differentiating_gives(counts):
    x, w, sizes, _, c = _case(counts, 8, 32, 48, 2, jnp.float32)
    for impl in ('pallas:interpret', 'ragged_dot'):
        _, back = jax.vjp(lambda x, w: gm.grouped_matmul(
            x, w, sizes, tile_m=8, impl=impl), x, w)
        for a, b in zip(gm.grouped_matmul_grads(x, w, sizes, c, 8, impl),
                        back(c)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize('rows,d,n,tile_m', [
    (64, 32, 50, 8),        # tokens that end inside a block
    (128, 16, 9, 8),        # more tiles than blocks: a block over many tiles
    (256, 128, 1000, 8),    # more blocks than tiles: most of them empty
    (512, 256, 384, 128),   # the cells' tile
])
@pytest.mark.parametrize('tokens', ['any', 'one', 'none'])
def test_rows_summed_into_tokens_equal_the_segment_sum(rows, d, n, tile_m,
                                                       tokens):
    """Rows whose tokens lie anywhere (some outside ``[0, n)``: in no sum),
    all on one token, all outside. float32 rows in three bfloat16 pieces
    against ones and zeros: the sums differ by their order alone."""
    rng = np.random.default_rng(rows + n)
    values = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    weights = jnp.asarray(rng.random(rows), jnp.float32)
    at = {'any': rng.integers(-1, n + 3, rows), 'one': np.full(rows, n // 2),
          'none': np.full(rows, n)}[tokens].astype(np.int32)
    got = jax.jit(lambda *a: gm.token_sums(*a, n, tile_m, 'pallas:interpret'))(
        values, weights, jnp.asarray(at))
    want = np.zeros((n, d), np.float64)
    inside = (at >= 0) & (at < n)
    np.add.at(want, at[inside], np.asarray(values, np.float64)[inside]
              * np.asarray(weights, np.float64)[inside, None])
    assert got.shape == (n, d) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=2e-6 * max(1.0, np.abs(want).max()))
    np.testing.assert_allclose(
        np.asarray(gm.token_sums(values, weights, jnp.asarray(at), n, tile_m,
                                 'ragged_dot')), want, rtol=0,
        atol=2e-6 * max(1.0, np.abs(want).max()))


def test_bfloat16_rows_are_summed_in_float32():
    """Two rows of a token whose bfloat16 sum would round: the weighted rows
    are formed and summed in float32 and rounded once."""
    values = jnp.asarray([[1.0] * 8, [2.0 ** -9] * 8] * 4, jnp.bfloat16)
    weights = jnp.asarray([1.0, 3.0] * 4, jnp.float32)
    tokens = jnp.asarray([0, 0, 1, 1, 2, 2, 3, 3], jnp.int32)
    got = gm.token_sums(values, weights, tokens, 4, 8, 'pallas:interpret')
    assert got.dtype == jnp.bfloat16
    want = jnp.asarray(1.0 + 3.0 * 2.0 ** -9, jnp.float32).astype(jnp.bfloat16)
    assert float(want) != 1.0
    assert np.asarray(got, np.float32).tolist() == [[float(want)] * 8] * 4


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


def test_the_weights_gradient_blocks_fit_the_vmem_they_may_use():
    """A grid step of ``x^T dy`` holds the float32 accumulator, the output
    block twice and both operands' blocks twice: at ``solar2.tokens8k``'s
    ``[4096, 2560]`` the blocks of the rule before (the whole axes, 83 MiB)
    passed Mosaic's 64 MiB and the wider block takes the next size down;
    the accepted cells' shapes keep the blocks they had."""
    def held(k, n, item=2):
        bk, bn = gm._dw_blocks(k, n, item)
        return bk * bn * (4 + 2 * item) + 2 * 128 * (bk + bn) * item

    for (k, n), blocks in {(3584, 2048): (3584, 2048),      # xing4
                           (1024, 3584): (1024, 3584),
                           (2560, 1536): (2560, 1536),      # ling3
                           (768, 2560): (768, 2560),
                           (1024, 2688): (1024, 2688),      # nemotron3
                           (2688, 1024): (2688, 1024),
                           (4096, 2560): (2048, 2560),      # solar2
                           (1280, 4096): (1280, 4096)}.items():
        assert gm._dw_blocks(k, n, 2) == blocks, (k, n)
        assert held(k, n) <= gm._VMEM_LIMIT, (k, n)
    plan = gm.moe_plan(7680, 4096, 2560, 8, 128, jnp.bfloat16, 'pallas')
    assert (plan['block_k_dw'], plan['block_n_dw']) == (2048, 2560)


@pytest.mark.parametrize('k,n', [(4096, 2560), (1280, 4096)])
def test_solar_s_products_compile_for_a_v5e(v5e, k, n):
    """``solar2.tokens8k``'s two products, bf16, 60 tiles of 128 rows (four
    times the held share of 8 of 320 experts' pairs) against eight experts,
    forward and both gradients: Mosaic takes the weights' gradient's blocks
    within its 64 MiB."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(v5e[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def grads(x, w, sizes, c):
        return jax.value_and_grad(lambda x, w: jnp.sum(gm._grouped(
            x, w, sizes, 128, False).astype(jnp.float32) * c),
            argnums=(0, 1))(x, w)

    rows = 7680
    compiled = jax.jit(grads).lower(
        struct((rows, k), jnp.bfloat16), struct((8, k, n), jnp.bfloat16),
        struct((8,), jnp.int32), struct((rows, n), jnp.float32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize('n,rows,d', [(8192, 5120, 2560), (4096, 9216, 3584),
                                      (8192, 66560, 2560)])
def test_the_token_sums_compile_for_a_v5e_at_the_cells_shapes(v5e, n, rows, d):
    """``ling3.tokens8k``'s and ``xing4.tokens4k``'s compact rows into their
    tokens, and the rows of every pair there could be (the fallback): bf16,
    blocks of 128 tokens. The call is named ``token_sums``, not by its
    caller's scope."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(v5e[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def sums(values, weights, tokens):
        with jax.named_scope('moe'):
            return gm._token_sums(values, weights, tokens, n, 128, False)

    text = jax.jit(sums).lower(
        struct((rows, d), jnp.bfloat16), struct((rows,), jnp.float32),
        struct((rows,), jnp.int32)).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and '%token_sums' in calls[0].split('=')[0]
