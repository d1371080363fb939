"""Grouped matrix product over row groups of uneven, data-dependent size, as
Pallas TPU kernels (forward and both gradients).

``grouped_matmul(x [M, K], w [G, K, N], group_sizes [G]) -> [M, N]``: the
rows of ``x`` lie group after group (``group_sizes[g]`` rows of group ``g``,
then those of ``g + 1``), each group is multiplied by its own ``w[g]``, and
the rows after the last group come out zero: what
``jax.lax.ragged_dot(x, w, group_sizes)`` computes, which is the plain route
(``impl='ragged_dot'``) the tests hold the kernels against. It is the product
of a dropless mixture-of-experts layer (:class:`petastorm_tpu.models.moe.
RoutedMoE`): the groups are the experts held here, their sizes are how many
(token, expert) pairs the router sent to each in this step.

**Sizes are data, shapes are static.** ``M`` is the capacity (the rows the
layer laid out: for a multiple of the pairs its held share expects, or for
every pair it could be sent), the sizes arrive as an int32 array, and the work
follows the sizes: the kernels run over *row tiles* of ``tile_m`` rows, a
scalar-prefetched table says which group a tile belongs to
(:func:`tile_groups`), and a tile past the last group is not multiplied (its
rows of the output are written as zeros and nothing is fetched for it). So
that a tile belongs to one group the Pallas route asks for **aligned
groups**: every ``group_sizes[g]`` a multiple of ``tile_m``, and at least one
tile (a group of no tile would leave its ``dw`` unwritten).
:func:`aligned_layout` makes such sizes from the true counts, and the rows a
group is padded with are ordinary rows of the product: they cost their
multiplications (under one tile a group) and whoever laid the rows out
leaves them unread.

Three kernels. ``x w`` and ``dy w^T`` are one kernel with the contraction
held whole in VMEM (``K`` = 3584 at the shapes of PERF.md: a ``[tile_m, K]``
block of rows against a ``[K, block_n]`` block of the tile's expert), on a
grid ``(N blocks, row tiles)``: the row tiles are the inner axis, so the
consecutive tiles of a group meet the same block of weights and it is
fetched once a group and column block, not once a tile. ``x^T dy`` (the
weights' gradient) runs on a grid ``(K blocks, N blocks, row tiles)`` and
accumulates the tiles of a group in float32 scratch, written when the group
ends. ``interpret=True`` runs all three in the Pallas interpreter (the CPU
tests); the compiled kernels on a backend that is not a TPU raise.

A fourth kernel sums rows into the tokens they came from
(:func:`token_sums`): the transpose of the gather that laid the tokens out in
rows, as a one-hot product over blocks of ``tile_m`` tokens.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from petastorm_tpu.ops.flash_attention import _out_struct
from petastorm_tpu.trace import get_global_tracer

_LANES = 128
#: Rows of a tile: the expected group of PERF.md's cell is 256 rows, and a
#: tile is what a group is padded to.
TILE_M = 128
#: Bytes of one weight block held in VMEM (twice, as the pipeline
#: double-buffers): 3584 x 512 bf16.
_BLOCK_BYTES = 4 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def aligned_layout(counts, tile_m):
    """Where the rows of groups of ``counts [G]`` rows lie once every group
    starts on a tile: ``(group_sizes, starts)``, int32 ``[G]`` each. A group
    takes ``max(1, ceil(count / tile_m))`` tiles; ``group_sizes`` is that in
    rows and ``starts`` the first row of each group. An array of ``rows + G *
    tile_m`` rows holds any counts that sum to ``rows``."""
    per_group = jnp.maximum(1, -(-counts // tile_m)).astype(jnp.int32)
    sizes = per_group * tile_m
    return sizes, jnp.cumsum(sizes) - sizes


def tile_groups(group_sizes, tile_m, tiles):
    """``(group [tiles], used [1])``, int32: the group of every row tile and
    how many tiles the groups fill. A tile past the last group takes the
    last used tile's group, so that its blocks are the ones already there."""
    ends = jnp.cumsum(group_sizes) // tile_m
    used = ends[-1].astype(jnp.int32)
    at = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), used - 1)
    group = jnp.searchsorted(ends, at, side='right').astype(jnp.int32)
    return jnp.minimum(group, group_sizes.shape[0] - 1), used[None]


def _block(n, row_bytes):
    """The largest multiple of 128 that divides ``n`` and keeps a block of
    ``row_bytes`` a column within :data:`_BLOCK_BYTES`; ``n`` itself where
    128 does not divide it (a block may always be the whole axis)."""
    if n % _LANES:
        return n
    best = _LANES
    for size in range(_LANES, n + 1, _LANES):
        if n % size == 0 and size * row_bytes <= _BLOCK_BYTES:
            best = size
    return best


def _dw_blocks(k, n, itemsize, tile_m=TILE_M):
    """``(block_k, block_n)`` of the weights' gradient: a ``[tile_m, block]``
    block of rows of either operand stays within 512 rows' worth of a block's
    bytes, and the float32 accumulator is ``block_k x block_n``. A grid step
    holds the accumulator, the output block twice and both operands' blocks
    twice, which has to stay within :data:`_VMEM_LIMIT`: where it would not
    (``[4096, 2560]``: 83 MiB), the wider block takes the next size down
    that divides its axis until it does."""
    blocks = [_block(k, 512 * itemsize), _block(n, 512 * itemsize)]

    def held(bk, bn):
        return bk * bn * (4 + 2 * itemsize) + 2 * tile_m * (bk + bn) * itemsize

    while held(*blocks) > _VMEM_LIMIT:
        wide = int(blocks[1] >= blocks[0])
        axis = (k, n)[wide]
        smaller = [size for size in range(_LANES, blocks[wide], _LANES)
                   if axis % size == 0]
        if not smaller:
            break
        blocks[wide] = smaller[-1]
    return tuple(blocks)


def moe_plan(rows, k, n, groups, tile_m, dtype, impl):
    """The account a ``kernel.moe_plan`` instant carries: what one product of
    ``[rows, k]`` by ``[groups, k, n]`` runs. ``rows`` are the capacity the
    caller laid out, which holds any ``pairs_capacity = rows - groups *
    tile_m`` pairs (:func:`aligned_layout`)."""
    item = jnp.dtype(dtype).itemsize
    block_k_dw, block_n_dw = _dw_blocks(k, n, item, tile_m)
    return {'groups': groups, 'rows_capacity': rows,
            'pairs_capacity': rows - groups * tile_m, 'k': k, 'n': n,
            'tile_m': tile_m, 'tiles': rows // tile_m,
            'block_n': _block(n, k * item), 'block_k_dw': block_k_dw,
            'block_n_dw': block_n_dw,
            'dtype': jnp.dtype(dtype).name, 'impl': impl}


_plans_reported = set()


def _report_plan(x, w, tile_m, impl):
    key = (x.shape[0], w.shape[1], w.shape[2], w.shape[0], tile_m,
           jnp.dtype(x.dtype).name, impl)
    if key not in _plans_reported:      # once a plan a process
        _plans_reported.add(key)
        get_global_tracer().instant('kernel.moe_plan', cat='kernel',
                                    args=moe_plan(*key))


def _params(interpret, semantics):
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)}


# -- x w and dy w^T ------------------------------------------------------------

def _product_kernel(group_ref, used_ref, x_ref, w_ref, o_ref, *, transposed):
    import jax.experimental.pallas as pl

    del group_ref
    m = pl.program_id(1)

    @pl.when(m < used_ref[0])
    def _multiply():
        dims = (((1,), (1 if transposed else 0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(m >= used_ref[0])
    def _past_the_groups():
        o_ref[...] = jnp.zeros_like(o_ref)


def _product(x, w, group, used, tile_m, transposed, interpret):
    """``x [M, C] w[g] [C, N]`` a tile, or ``x [M, C] w[g]^T`` with ``w [G, N,
    C]`` where ``transposed``: the contraction ``C`` whole in a block."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, c = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    block_n = _block(n, c * x.dtype.itemsize)

    def x_map(j, m, group, used):
        return (jnp.minimum(m, used[0] - 1), 0)

    def w_map(j, m, group, used):
        return (group[m], j, 0) if transposed else (group[m], 0, j)

    w_block = (None, block_n, c) if transposed else (None, c, block_n)
    return pl.pallas_call(
        functools.partial(_product_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // block_n, rows // tile_m),
            in_specs=[pl.BlockSpec((tile_m, c), x_map),
                      pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec((tile_m, block_n),
                                   lambda j, m, group, used: (m, j))),
        out_shape=_out_struct((rows, n), x.dtype, x),
        interpret=interpret,
        **_params(interpret, ('arbitrary', 'arbitrary')),
    )(group, used, x, w)


# -- x^T dy: the weights' gradient ---------------------------------------------

def _dw_kernel(group_ref, used_ref, x_ref, dy_ref, o_ref, acc_ref, *, tiles):
    import jax.experimental.pallas as pl

    m, used = pl.program_id(2), used_ref[0]
    mine = group_ref[m]
    first = (m == 0) | (group_ref[jnp.maximum(m - 1, 0)] != mine)
    last = (m == used - 1) | (group_ref[jnp.minimum(m + 1, tiles - 1)] != mine)

    @pl.when(m < used)
    def _accumulate():
        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _dw(x, dy, group, used, groups, tile_m, interpret):
    """``dw[g] = sum over the tiles of g of x_tile^T dy_tile``, ``[G, K, N]``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    n = dy.shape[1]
    block_k, block_n = _dw_blocks(k, n, x.dtype.itemsize, tile_m)
    tiles = rows // tile_m

    def rows_of(axis):
        def index(i, j, m, group, used):
            return (jnp.minimum(m, used[0] - 1), (i, j)[axis])
        return index

    return pl.pallas_call(
        functools.partial(_dw_kernel, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(k // block_k, n // block_n, tiles),
            in_specs=[pl.BlockSpec((tile_m, block_k), rows_of(0)),
                      pl.BlockSpec((tile_m, block_n), rows_of(1))],
            out_specs=pl.BlockSpec(
                (None, block_k, block_n),
                lambda i, j, m, group, used: (group[m], i, j)),
            scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)]),
        out_shape=_out_struct((groups, k, n), x.dtype, x),
        interpret=interpret,
        **_params(interpret, ('parallel', 'parallel', 'arbitrary')),
    )(group, used, x, dy)


# -- rows summed into tokens ---------------------------------------------------

def _token_sums_kernel(block_ref, tile_ref, total_ref, token_ref, w_ref, v_ref,
                       o_ref, acc_ref, *, steps, tile_m):
    import jax.experimental.pallas as pl

    del tile_ref
    s, total = pl.program_id(1), total_ref[0]
    mine = block_ref[s]
    first = (s == 0) | (block_ref[jnp.maximum(s - 1, 0)] != mine)
    last = (s == total - 1) | (block_ref[jnp.minimum(s + 1, steps - 1)] != mine)

    @pl.when(s < total)
    def _accumulate():
        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # [tokens of the block, rows of the tile]: a row under its token.
        tokens = mine * tile_m + jax.lax.broadcasted_iota(
            jnp.int32, (tile_m, tile_m), 0)
        onehot = jnp.where(token_ref[...] == tokens, 1.0, 0.0).astype(
            jnp.bfloat16)
        # The weighted rows, float32, in three bfloat16 pieces: against ones
        # and zeros the three passes of the MXU add up to the float32 sum.
        rest = v_ref[...].astype(jnp.float32) * w_ref[...]
        for _ in range(3):
            piece = rest.astype(jnp.bfloat16)
            acc_ref[...] += jax.lax.dot_general(
                onehot, piece, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            rest = rest - piece.astype(jnp.float32)

        @pl.when(last)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _token_sums(values, weights, tokens, n, tile_m, interpret):
    """The Pallas route of :func:`token_sums`."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = values.shape
    tiles, blocks = rows // tile_m, -(-n // tile_m)
    steps = tiles + blocks
    nowhere = blocks * tile_m
    tokens, order = jax.lax.sort(
        (jnp.where((tokens >= 0) & (tokens < n), tokens, nowhere),
         jnp.arange(rows, dtype=jnp.int32)), num_keys=1)
    # The tiles a block's rows lie in, at least one (an empty block's sum is
    # written as zeros from a tile that holds nothing of it), then the
    # (block, tile) pair of every step, the last pair again past the end.
    edges = jnp.searchsorted(tokens, jnp.arange(blocks + 1) * tile_m).astype(
        jnp.int32)
    first = jnp.minimum(edges[:-1] // tile_m, tiles - 1)
    count = jnp.where(edges[1:] > edges[:-1],
                      (edges[1:] - 1) // tile_m - first, 0) + 1
    ends = jnp.cumsum(count)
    at = jnp.minimum(jnp.arange(steps, dtype=jnp.int32), ends[-1] - 1)
    block = jnp.searchsorted(ends, at, side='right').astype(jnp.int32)
    tile = first[block] + at - (ends - count)[block]
    block_d = _block(d, 512 * values.dtype.itemsize)

    with jax.named_scope('token_sums'):     # not the caller's ``moe*``
        out = pl.pallas_call(
            functools.partial(_token_sums_kernel, steps=steps, tile_m=tile_m),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(d // block_d, steps),
                in_specs=[
                    pl.BlockSpec((None, 1, tile_m),
                                 lambda j, s, block, tile, total:
                                 (tile[s], 0, 0)),
                    pl.BlockSpec((tile_m, 1),
                                 lambda j, s, block, tile, total:
                                 (tile[s], 0)),
                    pl.BlockSpec((tile_m, block_d),
                                 lambda j, s, block, tile, total:
                                 (tile[s], j))],
                out_specs=pl.BlockSpec(
                    (tile_m, block_d),
                    lambda j, s, block, tile, total: (block[s], j)),
                scratch_shapes=[pltpu.VMEM((tile_m, block_d), jnp.float32)]),
            out_shape=_out_struct((nowhere, d), values.dtype, values),
            interpret=interpret,
            **_params(interpret, ('parallel', 'arbitrary')),
        )(block, tile, ends[-1:].astype(jnp.int32),
          tokens.reshape(tiles, 1, tile_m),
          weights[order].astype(jnp.float32)[:, None], values[order])
    return out[:n]


# -- public entry + custom vjp -------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(x, w, group_sizes, tile_m, interpret):
    group, used = tile_groups(group_sizes, tile_m, x.shape[0] // tile_m)
    return _product(x, w, group, used, tile_m, False, interpret)


def _grouped_fwd(x, w, group_sizes, tile_m, interpret):
    return _grouped(x, w, group_sizes, tile_m, interpret), (x, w, group_sizes)


def _grouped_bwd(tile_m, interpret, residuals, dy):
    x, w, group_sizes = residuals
    group, used = tile_groups(group_sizes, tile_m, x.shape[0] // tile_m)
    dx = _product(dy, w, group, used, tile_m, True, interpret)
    dw = _dw(x, dy, group, used, w.shape[0], tile_m, interpret)
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def _check(x, k, tile_m, impl):
    """``interpret`` of a Pallas ``impl``, for rows ``x [M, k]`` the kernels
    take in tiles of ``tile_m``."""
    if impl not in ('pallas', 'pallas:interpret'):
        raise ValueError('impl {!r}: pallas, pallas:interpret or ragged_dot'
                         .format(impl))
    interpret = impl == 'pallas:interpret'
    if not interpret and jax.devices()[0].platform != 'tpu':
        raise RuntimeError(
            'grouped_matmul compiles Pallas TPU kernels but the default jax '
            'backend is {!r}; use impl=\'pallas:interpret\' or \'ragged_dot\''
            .format(jax.devices()[0].platform))
    if x.shape[0] % tile_m or x.shape[1] != k:
        raise ValueError('x {} against a contraction of {} in tiles of {} '
                         'rows'.format(x.shape, k, tile_m))
    if tile_m % (8 * 4 // np.dtype(x.dtype).itemsize) and not interpret:
        raise ValueError('tile_m {} is no whole number of {} sublane tiles'
                         .format(tile_m, x.dtype))
    return interpret


def grouped_matmul(x, w, group_sizes, tile_m=TILE_M, impl='pallas'):
    """``[M, K] x [G, K, N] -> [M, N]``, group ``g``'s rows by ``w[g]``, the
    rows after the last group zero. ``impl``: ``'pallas'`` (compiled, a TPU),
    ``'pallas:interpret'``, or ``'ragged_dot'`` (``jax.lax.ragged_dot``, any
    sizes). The Pallas route asks for aligned groups (module docstring):
    ``M`` and every size a multiple of ``tile_m``, every group at least one
    tile. Differentiable in ``x`` and ``w``; ``dw`` has ``w``'s dtype."""
    if impl == 'ragged_dot':
        return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32))
    interpret = _check(x, w.shape[1], tile_m, impl)
    _report_plan(x, w, tile_m, impl)
    return _grouped(x, w.astype(x.dtype), group_sizes.astype(jnp.int32),
                    tile_m, interpret)


def grouped_matmul_grads(x, w, group_sizes, dy, tile_m=TILE_M, impl='pallas'):
    """``(dx, dw)`` of :func:`grouped_matmul` at ``(x, w)`` against ``dy [M,
    N]``: what differentiating it gives, for a caller that writes a backward
    pass out by hand (:mod:`petastorm_tpu.models.moe`); nothing of the forward
    product runs."""
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == 'ragged_dot':
        _, back = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, group_sizes),
                          x, w)
        return back(dy)
    interpret = _check(x, w.shape[1], tile_m, impl)
    dx, dw, _ = _grouped_bwd(tile_m, interpret,
                             (x, w.astype(x.dtype), group_sizes), dy)
    return dx, dw.astype(w.dtype)


def token_sums(values, weights, tokens, n, tile_m=TILE_M, impl='pallas'):
    """``out[t] = sum over the rows r with tokens[r] == t of weights[r] *
    values[r]``: ``values [M, D]``, ``weights [M]`` float32, ``tokens [M]``
    int32 (a row whose token is not in ``[0, n)`` is in no sum) -> ``[n, D]``
    in ``values``' dtype, summed in float32. The transpose of the row gather
    ``x[tokens]``, which as an XLA scatter of row updates runs update by
    update (PERF.md section 6, PR 36). ``impl='ragged_dot'`` is the plain route
    (``jax.ops.segment_sum``); the Pallas route sorts the rows by token and
    sums each block of ``tile_m`` tokens as the product of a one-hot with the
    tiles of rows its tokens lie in, one grid step a (block, tile) pair: the
    pairs are data, at most ``M / tile_m + ceil(n / tile_m)`` of them. ``M``
    a multiple of ``tile_m``. A device trace names the call ``token_sums*``
    whatever scope it runs in."""
    if impl == 'ragged_dot':
        return jax.ops.segment_sum(
            values.astype(jnp.float32) * weights[:, None],
            jnp.where((tokens >= 0) & (tokens < n), tokens, n),
            num_segments=n).astype(values.dtype)
    return _token_sums(values, weights, tokens, n, tile_m,
                       _check(values, values.shape[1], tile_m, impl))
