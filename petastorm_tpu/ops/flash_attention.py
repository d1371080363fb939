"""Blocked (flash) attention as Pallas TPU kernels (forward + backward).

Single-device exact attention without materializing the ``[T, T]`` score
matrix: a 4-D grid ``(batch, lane blocks, q_blocks, kv_blocks)`` streams one
``[block_q, lanes]`` query block and one ``[block_k, lanes]`` kv block into
VMEM per step — VMEM use is O(block) regardless of sequence length, so
context is bounded by HBM, not VMEM. The online softmax (running max /
normalizer) lives in VMEM scratch that persists across the kv-block axis (TPU
grids execute sequentially, innermost axis fastest), and every matmul runs on
the MXU. The backward is two more Pallas passes (dq over kv blocks; dk+dv
over q blocks) that reconstruct ``P = exp(S - lse)`` tile by tile from the
logsumexp rows the training forward saves — O(block) memory in both
directions. Role parity: the attention compute the reference's training
stacks get from fused CUDA kernels — rebuilt the TPU way.

**The arrays are the model's own.** q, k, v, the output, ``dO`` and the three
gradients are ``[B, T, H*D]``, which a ``[B, T, H, D]`` array is by a bitcast:
what a projection wrote is what a kernel reads, and no transpose, row sum or
broadcast stands beside the three calls (a ``[B*H, T, D]`` kernel costs eight
layout copies of a q-sized array a layer). A *lane block* (:func:`lane_plan`)
is 128 lanes of ``H*D``: two 64-wide heads, or one of 128 (a wider head has
a block of its width). The
heads of a block are computed one after another over the same bands; a
head's lanes are chosen by a lane mask, not a transpose (:func:`_head_of`:
q and dO with the other heads' lanes zeroed contract over all 128 lanes in
the passes a 64-deep contraction takes on a 128 x 128 MXU; of a ``[rows,
128]`` result the head's lanes are kept by a select, :func:`_by_head`), and
with one head a block no mask is traced at all. ``lse`` is float32 ``[B, T,
H*D]``, a head's value in each of its lanes, and ``D = rowsum(dO * O)`` is
taken inside both backward kernels from the ``dO`` and output blocks. What
does not fill a lane block (an odd head, ``H*D < 128``, a width like 96) is
padded with zero heads or lanes by the wrapper and stripped: one kernel
family, nothing to choose.

**Keys and values of two widths.** Where ``v`` is narrower or wider a head
than ``q`` and ``k`` (latent attention: 128 content + 64 rotary lanes of key
against 128 of value) the two sides have lane blocks of their own, one head a
block: q, k, dq and dk by blocks of the key width padded to whole vregs (192
to 256: ``pad_lanes`` 64), v, the output, ``dO``, ``lse`` and dv by blocks
of the value width. The score is one product over the padded key block;
bands, cases and the executed-work account are the same plan. ``scale`` is
the call's (default ``D ** -0.5`` of the key width as given).

**Two tile sizes** (:func:`tile_plan`, the one place they are decided). The
*DMA block* ``(block_q, block_k)`` is what one grid step holds in VMEM; it
is large (``(512, 1024)`` for bf16) because a grid step costs about 0.35 µs
whatever it does. The *compute sub-tile* ``(sub_q, sub_k)`` (:data:`_SUB_TILES`,
128 or 256 on a side) is the grain at which work inside a block is left out: of a
block pair the kernels compute, for every q sub-tile, the columns up to the
causal diagonal and the last real column in one piece (:func:`_bands`; the
dk/dv pass, for every kv sub-tile, the rows from the diagonal on:
:func:`_col_bands`), so a sub-tile that holds no unmasked score is never
multiplied, exponentiated or masked, and only the sub-tiles that the
diagonal or the kv tail crosses pay for the mask's iota / compare /
selects. The causal bound therefore engages from ``T > 128`` on, not from
``T > block_k``: at ``T = 1024`` the grid has one kv block and skips
nothing, the bands compute 36 of 64 sub-tiles (20 of 32 in the dk/dv pass). How a q block lies against a
kv block (:func:`_block_case`: where the diagonal enters it, how many of its
columns are real) takes a handful of values that are known when the kernel
is traced, so each is straight-line code over static slices under a
``pl.when``, not a loop: a rolled loop over 256 x 256 tiles ran the same
shape at half the parent's speed on a v5e (PERF.md §6, PR 27), because
nothing overlaps across its iterations. A block below the diagonal is
still one piece, as it was before there were sub-tiles; whole DMA blocks
above it are skipped at the grid level, and their ``index_map`` points at
the last needed block so that nothing is fetched for them.

Composes with :mod:`petastorm_tpu.models.attention`: ring attention shards
the sequence across a mesh axis and rotates kv blocks over ICI; within a
device, this kernel is the block compute. ``flash_attention`` always runs
the Pallas kernels: compiled by Mosaic on a TPU, or — only when the caller
passes ``interpret=True`` — in the Pallas interpreter (how the CPU tests
validate the numerics). Asking for the compiled kernel on any other backend
raises; nothing substitutes the dense reference silently.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp

from petastorm_tpu.trace import get_global_tracer

NEG_INF = -1e30  # large-finite: -inf breaks the running-max rescale at init

_LANES = 128     # VPU lane width: the lane block of heads no wider than it

#: Compute sub-tile ``(rows, columns)`` of each pass, clamped to the DMA
#: block. Chosen on a v5e at ``[192, 1024, 64]`` bf16 causal and confirmed at
#: T = 8192 (PERF.md §6, PR 27): the forward and dq passes are fastest with
#: 128-row bands cut to the nearest 128 columns; the dk/dv pass, whose bands
#: run down the columns, pays for narrow ones (1.35 ms a layer at 128
#: columns against 1.03 at 256). Multiples of 128, so that a band's masked
#: part starts on a vreg boundary.
_SUB_TILES = {'fwd': (128, 128), 'dq': (128, 128), 'dkv': (128, 256)}


def _mosaic_params(interpret):
    """Compiler hints for the compiled path: all three kernels carry their
    online-softmax / accumulator state only along the LAST grid axis, so the
    first three axes (batch, lane block, outer block) are declared parallel
    — Mosaic may then reorder/pipeline them freely. Interpret mode takes no
    TPU compiler params."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel', 'parallel', 'parallel', 'arbitrary'))}


def _out_struct(shape, dtype, like):
    """``ShapeDtypeStruct`` for a kernel output that varies over the same
    mesh axes as the input ``like``: inside ``jax.shard_map`` (the a2a
    sequence-parallel path) ``pallas_call`` refuses an ``out_shape`` that
    does not say so; outside one the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


# --------------------------------------------------------------------------
# the lane plan: how heads lie in the 128-lane blocks of a [B, T, H*D] array
# --------------------------------------------------------------------------

def lane_plan(h, d, dv=None):
    """How ``h`` heads of width ``d`` fill the lane blocks the kernels read,
    from what the call can see. A head no wider than a vreg is padded to the
    next power of two (which divides 128) and ``128 // width`` heads share a
    128-lane block; a wider one is padded to the next multiple of 128 and
    has a block of its own. Heads are padded up to whole blocks. At GPT-2's
    ``(12, 64)`` two heads a block and no padding; at ``(15, 128)`` one.

    JSON-safe: ``lane_block`` (the block's lanes), ``heads_per_block``,
    ``pad_heads`` (zero heads appended) and ``pad_lanes`` (zero lanes
    appended to every head); ``v_lane_block`` and ``v_pad_lanes`` are the
    same of the value side, which differ where ``dv`` is another width than
    ``d``: then both sides have one head a block, each width padded to whole
    vregs (``(4, 192, 128)``: 256 and 128 lanes, 64 lanes of padding a key)."""
    if dv not in (None, d):
        wide, v_wide = (-(-w // _LANES) * _LANES for w in (d, dv))
        return {'lane_block': wide, 'heads_per_block': 1, 'pad_heads': 0,
                'pad_lanes': wide - d, 'v_lane_block': v_wide,
                'v_pad_lanes': v_wide - dv}
    if d <= _LANES:
        width = 1 << (d - 1).bit_length()
        lane_block = _LANES
    else:
        width = lane_block = -(-d // _LANES) * _LANES
    per_block = lane_block // width
    return {'lane_block': lane_block, 'heads_per_block': per_block,
            'pad_heads': -h % per_block, 'pad_lanes': width - d,
            'v_lane_block': lane_block, 'v_pad_lanes': width - d}


def _to_lanes(x, plan, side=''):
    """``[B, T, H, D]`` -> ``[B, T_pad, lanes]``: a bitcast where nothing is
    padded (the array a projection wrote is the array a kernel reads); else
    zero rows, heads and lanes up to whole blocks. ``side='v_'``: an array
    of the value side (v, the output, their gradients)."""
    b, t, h, d = x.shape
    pad = (plan['t_pad'] - t, plan['pad_heads'], plan[side + 'pad_lanes'])
    if any(pad):
        x = jnp.pad(x, ((0, 0),) + tuple((0, p) for p in pad))
    return x.reshape(b, plan['t_pad'], -1)


def _from_lanes(x, shape, plan, side=''):
    """The inverse of :func:`_to_lanes`: ``[B, T_pad, lanes]`` -> ``shape``,
    the padding stripped."""
    b, t, h, d = shape
    x = x.reshape(b, plan['t_pad'], h + plan['pad_heads'],
                  d + plan[side + 'pad_lanes'])
    return x[:, :t, :h, :d]


def _pad_plan(t, block_q, block_k):
    """(block_q, block_k, t_pad): blocks clamped to ``t`` and rounded down
    to powers of two (min 8), ``t`` padded to a multiple of both.

    The power-of-two rounding is load-bearing: clamping alone can hand back
    a block that shares no factors with the other one, and padding to their
    raw lcm then explodes — e.g. ``block_q=512`` against a T=1000 clamp of
    ``block_k=1000`` gives lcm 64,000, a 64x memory/compute cliff for the
    'arbitrary per-device slice lengths' ring attention feeds us. With
    power-of-two blocks the lcm IS the larger block, so padding overhead is
    bounded by ``max_block - 1``. The floor of 8 keeps the sublane dimension
    Mosaic-legal for tiny sequences (the kernel masks the pad via
    ``seq_len``)."""
    def _pow2_floor(b):
        return 1 << (b.bit_length() - 1)

    block_q = max(8, _pow2_floor(min(block_q, t)))
    block_k = max(8, _pow2_floor(min(block_k, t)))
    lcm = block_q * block_k // math.gcd(block_q, block_k)
    return block_q, block_k, -(-t // lcm) * lcm


# --------------------------------------------------------------------------
# the tile plan: which sub-tiles run, which of those are masked
# --------------------------------------------------------------------------

def _kv_range(q0, n_k, rows, sub_k, limit, causal):
    """``(n_full, n_run)`` for rows ``q0 .. q0 + rows - 1`` against ``n_k``
    kv sub-tiles of ``sub_k`` columns from column 0 on, of which ``limit``
    are real: sub-tiles ``[0, n_full)`` hold no masked score, ``[n_full,
    n_run)`` are crossed by the diagonal or the kv tail and take the mask,
    the rest hold no unmasked score and are not run."""
    # A sub-tile runs if its first column is real and on or below the
    # diagonal of the last row; it is unmasked if its last column is real
    # and on or below the diagonal of the first row.
    first_unseen = min(limit, q0 + rows) if causal else limit
    first_masked = min(limit, q0 + 1) if causal else limit
    n_run = min(max(first_unseen + sub_k - 1, 0) // sub_k, n_k)
    n_full = min(max(first_masked, 0) // sub_k, n_k)
    return n_full, n_run


def _block_case(qi, ki, block_q, block_k, seq_len, causal):
    """How q block ``qi`` lies against kv block ``ki``: ``(off, rem)``, or
    ``None`` where they share no unmasked score (the kv block is padding, or
    wholly above the diagonal). ``rem`` is how many of the kv block's
    columns are real; ``off`` is the q block's first row counted from the kv
    block's first column, ``None`` where every row sees every column (no
    diagonal, or the block lies wholly below it)."""
    rem = min(seq_len - ki * block_k, block_k)
    if rem <= 0:
        return None
    off = qi * block_q - ki * block_k
    if not causal or off >= block_k - 1:
        return (None, rem)
    return (off, rem) if off + block_q > 0 else None


def _bands(case, block_q, block_k, sub_q, sub_k):
    """What is computed of a block pair, in the block's own coordinates: row
    bands ``(r0, rows, c_full, c_run)``. Band rows ``r0 .. r0 + rows - 1``
    take columns ``[0, c_run)`` in one piece, of which ``[c_full, c_run)``
    (the sub-tiles that the diagonal or the kv tail crosses) get the mask;
    columns from ``c_run`` on hold no unmasked score and are never touched.
    One band a q sub-tile, or one for the whole block where the mask is the
    same for every row; a band with nothing to compute is left out."""
    off, rem = case
    rows = block_q if off is None else sub_q
    bands = []
    for r0 in range(0, block_q, rows):
        n_full, n_run = _kv_range(0 if off is None else off + r0,
                                  block_k // sub_k, rows, sub_k, rem,
                                  off is not None)
        if n_run:
            bands.append((r0, rows, n_full * sub_k, n_run * sub_k))
    return bands


def _col_bands(case, block_q, block_k, sub_q, sub_k):
    """The same region as :func:`_bands`, cut the other way for the dk/dv
    pass, whose accumulators follow the columns: column bands ``(c0, cols,
    r_lo, r_full)``. Band columns ``c0 .. c0 + cols - 1`` take rows ``[r_lo,
    block_q)`` in one piece, of which ``[r_lo, r_full)`` get the mask; rows
    before ``r_lo`` lie wholly above the diagonal. One band a kv sub-tile,
    neighbours that every row sees unmasked joined into one."""
    off, rem = case
    n_q = block_q // sub_q
    bands = []
    for c0 in range(0, rem, sub_k):
        i_lo = i_full = 0
        if off is not None:
            # First q sub-tile whose last row sees column c0, and first
            # whose first row sees the band's last column.
            i_lo = min(max((c0 - off) // sub_q, 0), n_q)
            i_full = min(max(-((off - c0 - sub_k + 1) // sub_q), i_lo), n_q)
        if c0 + sub_k > rem:        # the tail crosses it: every row masked
            i_full = n_q
        if i_lo == n_q:
            continue
        if bands and i_full == 0 and bands[-1][2:] == (0, 0):
            bands[-1] = (bands[-1][0], bands[-1][1] + sub_k, 0, 0)
        else:
            bands.append((c0, sub_k, i_lo * sub_q, i_full * sub_q))
    return bands


@functools.lru_cache(maxsize=None)
def tile_plan(t, causal, dtype, hd, block_q, block_k):
    """The tiling of one ``flash_attention`` call, from what the call can
    see: sequence length, ``causal``, dtype, head width and the caller's
    blocks. Nothing else decides it: each kernel computes its bands
    (:func:`_bands`, :func:`_col_bands`) for the ``cases`` listed here and
    nothing more.

    Returns a JSON-safe dict: the DMA blocks (``block_q``, ``block_k``,
    clamped as :func:`_pad_plan` says), ``t_pad``, the distinct ``cases`` of
    :func:`_block_case` that the grid meets, and under ``passes`` for each of
    ``fwd``, ``dq`` and ``dkv`` its compute sub-tile (``sub_q``, ``sub_k``:
    :data:`_SUB_TILES` clamped to the DMA block), how many sub-tiles it
    computes (``tiles_run``), how many of those take the mask
    (``tiles_masked``) and how many the padded square holds
    (``tiles_total``). ``share`` is the part of the three padded squares
    that is computed: 1 where nothing can be left out (``causal=False`` with
    no sub-tile of padding), 0.583 at T = 1024 causal (36 of 64 sub-tiles
    forward and dq, 20 of 32 dk/dv), towards a half as T grows."""
    block_q, block_k, t_pad = _pad_plan(t, block_q, block_k)
    met = collections.Counter(
        _block_case(qi, ki, block_q, block_k, t, causal)
        for qi in range(t_pad // block_q) for ki in range(t_pad // block_k))
    met.pop(None, None)
    passes, computed = {}, 0
    for name, (sub_q, sub_k) in _SUB_TILES.items():
        sub_q, sub_k = min(block_q, sub_q), min(block_k, sub_k)
        run = masked = 0            # in scores; a sub-tile holds sub_q * sub_k
        for case, times in met.items():
            if name == 'dkv':
                for _, cols, r_lo, r_full in _col_bands(
                        case, block_q, block_k, sub_q, sub_k):
                    run += times * cols * (block_q - r_lo)
                    masked += times * cols * (r_full - r_lo)
            else:
                for _, rows, c_full, c_run in _bands(
                        case, block_q, block_k, sub_q, sub_k):
                    run += times * rows * c_run
                    masked += times * rows * (c_run - c_full)
        computed += run
        passes[name] = {'sub_q': sub_q, 'sub_k': sub_k,
                        'tiles_run': run // (sub_q * sub_k),
                        'tiles_masked': masked // (sub_q * sub_k),
                        'tiles_total': t_pad * t_pad // (sub_q * sub_k)}
    return {'t': t, 't_pad': t_pad, 'causal': bool(causal),
            'dtype': jnp.dtype(dtype).name, 'hd': hd,
            'block_q': block_q, 'block_k': block_k,
            'cases': sorted(met, key=lambda c: (c[0] is None, c)),
            'passes': passes, 'share': computed / (3 * t_pad * t_pad)}


_plans_reported = set()


def _plan_for(q, causal, block_q, block_k, v=None, scale=None):
    """The plan of a call on ``[B, T, H, D]`` operands: :func:`tile_plan`
    with :func:`lane_plan`, the widths of the two sides (``qk_width``,
    ``v_width``) and the scale of the scores. The first time a process
    traces a kernel with it, one ``kernel.flash_plan`` instant on the global
    tracer carries it (a model's layers share one plan, so one record, not
    one a layer)."""
    _, t, h, hd = q.shape
    dv = hd if v is None else v.shape[-1]
    scale = 1.0 / math.sqrt(hd) if scale is None else float(scale)
    key = (t, bool(causal), jnp.dtype(q.dtype).name, hd, block_q, block_k)
    plan = dict(tile_plan(*key), heads=h, dd='in-kernel', qk_width=hd,
                v_width=dv, scale=scale, **lane_plan(h, hd, dv))
    key += (h, dv, scale)
    if key not in _plans_reported:
        _plans_reported.add(key)
        get_global_tracer().instant('kernel.flash_plan', cat='kernel',
                                    args=plan)
    return plan


def _is_case(case, off, rem, block_k, causal):
    """Whether a grid step whose q block starts ``off`` rows after its kv
    block, ``rem`` of whose columns are real (capped at the block), is
    ``case``; on Python ints or on traced grid indices."""
    is_case = rem == case[1]
    if causal:
        is_case &= (off >= block_k - 1) if case[0] is None else (
            off == case[0])
    return is_case


def _for_each_case(args, q_axis, kv_axis, body):
    """Run ``body(case)`` under a ``pl.when`` for each case of the plan: the
    grid indices say which one this step is, and a step that is none of
    them (a kv block above the diagonal, or of padding) does nothing."""
    import jax.experimental.pallas as pl

    block_q, block_k = args['tiling'][:2]
    qi, ki = pl.program_id(q_axis), pl.program_id(kv_axis)
    off = qi * block_q - ki * block_k
    rem = jnp.minimum(args['seq_len'] - ki * block_k, block_k)
    for case in args['cases']:
        pl.when(_is_case(case, off, rem, block_k, args['causal']))(
            functools.partial(body, case))


def _band_mask(case, r0, rows, c0, cols):
    """[rows, cols] validity mask of the masked part of a band, whose first
    row and column in the block's own coordinates are ``r0`` and ``c0``: kv
    tail padding + causal triangle; None where that part is empty."""
    if rows == 0 or cols == 0:
        return None
    off, rem = case
    k_pos = c0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    mask = k_pos < rem
    if off is not None:
        q_pos = off + r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        mask = mask & (q_pos >= k_pos)
    return mask


def _masked(x, mask, fill):
    """``x`` with ``fill`` where ``mask`` says so; ``mask`` covers the last
    columns of ``x`` (a row band) or its first rows (a column band), and the
    selects run over that part only."""
    if mask is None:
        return x
    if mask.shape[0] == x.shape[0]:
        c_full = x.shape[1] - mask.shape[1]
        tail = jnp.where(mask, x[:, c_full:], fill)
        return tail if c_full == 0 else jnp.concatenate(
            [x[:, :c_full], tail], axis=1)
    head = jnp.where(mask, x[:mask.shape[0]], fill)
    return jnp.concatenate([head, x[mask.shape[0]:]], axis=0)


def _scores(q, k_sub, scale):
    """``scale * Q K^T`` of one band. Operands stay in their input dtype
    (bf16 matmuls run the MXU at twice the f32 rate); the product
    accumulates in f32 and the scalar scale is applied to the f32 product —
    scale*(QK) == (scale*Q)K up to rounding, and post-scaling in f32 keeps
    more bits than pre-scaling bf16 Q."""
    return jax.lax.dot_general(q, k_sub, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale


def _recompute_p(q, k_sub, lse, mask, scale):
    """Rebuild a band's probabilities ``P = exp(S - lse)`` (backward)."""
    return _masked(jnp.exp(_scores(q, k_sub, scale) - lse), mask, 0.0)


def _index_maps(block_q, block_k, seq_len, causal):
    """``(q_map, kv_map)`` over grid ``(b, lane block, qi, ki)``: the q side
    follows ``qi``; the kv side follows ``ki`` as far as the last block this
    q block needs and stays there, so the steps that compute nothing fetch
    nothing new."""
    last_real = (seq_len - 1) // block_k

    def q_map(b, j, qi, ki):
        return (b, qi, j)

    def kv_map(b, j, qi, ki):
        last = last_real
        if causal:
            last = jnp.minimum(last, (qi * block_q + block_q - 1) // block_k)
        return (b, jnp.minimum(ki, last), j)

    return q_map, kv_map


def _index_maps_dkv(block_q, block_k, causal):
    """``(q_map, kv_map)`` over the dk/dv grid ``(b, lane block, ki, qi)``:
    the q side starts at the first q block this kv block needs."""
    def q_map(b, j, ki, qi):
        if causal:
            qi = jnp.maximum(qi, (ki * block_k) // block_q)
        return (b, qi, j)

    def kv_map(b, j, ki, qi):
        return (b, ki, j)

    return q_map, kv_map


def _kernel_args(plan, name):
    """The static arguments of pass ``name``'s kernel. ``heads`` is how the
    heads of a lane block lie in it: how many, and how many lanes each, on
    the key side (q, k and their gradients); ``v_heads`` on the value side
    (v, the output, ``dO``, ``lse``, ``D``)."""
    tiling = (plan['block_q'], plan['block_k'],
              plan['passes'][name]['sub_q'], plan['passes'][name]['sub_k'])
    per_block = plan['heads_per_block']
    return dict(tiling=tiling, seq_len=plan['t'], causal=plan['causal'],
                cases=tuple(plan['cases']), scale=plan['scale'],
                heads=(per_block, plan['lane_block'] // per_block),
                v_heads=(per_block, plan['v_lane_block'] // per_block))


# --------------------------------------------------------------------------
# the heads of a lane block
# --------------------------------------------------------------------------

def _head_of(x, head, heads):
    """``[rows, lanes]`` ``x`` with the lanes of every head but ``head``
    zeroed: as an operand it contracts over the whole lane block and gives
    what the head's own lanes give (on a 128 x 128 MXU in the passes its
    narrower slice would take); as the operand that is not contracted it
    leaves the other heads' lanes of the product zero. With one head a
    block it is ``x``: no mask is traced."""
    count, width = heads
    if count == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    mine = (lane >= head * width) & (lane < (head + 1) * width)
    return jnp.where(mine, x, jnp.zeros_like(x))


def _by_head(parts, heads):
    """``[rows, lanes]`` holding ``parts[h]`` (``[rows, 1]``, or ``[rows,
    lanes]``) in the lanes of head ``h``: one select a head after the
    first."""
    count, width = heads
    shape = (parts[0].shape[0], count * width)
    out = jnp.broadcast_to(parts[0], shape)
    if count > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        for head in range(1, count):
            out = jnp.where(lane >= head * width, parts[head], out)
    return out


def _column(ref, at, head, heads):
    """``[rows, 1]``: the value ``ref`` holds for ``head`` in rows ``at`` (a
    head's statistic stands in each of its lanes)."""
    lane = head * heads[1]
    return ref[at, lane:lane + 1]


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, emit_lse, **args):
    """One grid step: one ``(block_q, lanes)`` query block x one ``(block_k,
    lanes)`` kv block of one lane block, computed in the bands of
    :func:`_bands`, the block's heads one after another in each band.

    acc/m/l scratch persists across the kv axis (axis 3, innermost): init at
    ki == 0, accumulate every step, normalize + store at the last ki. All
    three are ``[block_q, lanes]``; m and l hold a head's value in each of
    that head's lanes, so the last step is elementwise.
    """
    import jax.experimental.pallas as pl

    if emit_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        lse_ref, (acc_ref, m_ref, l_ref) = None, rest
    scale, heads, v_heads = args['scale'], args['heads'], args['v_heads']
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(case):
        for r0, rows, c_full, c_run in _bands(case, *args['tiling']):
            at, to = pl.ds(r0, rows), pl.ds(0, c_run)
            mask = _band_mask(case, r0, rows, c_full, c_run - c_full)
            q = q_ref[at, :]
            corrections, m_news, l_news, pvs = [], [], [], []
            for head in range(heads[0]):
                # Native-dtype operands, f32 accumulation.
                s = _masked(_scores(_head_of(q, head, heads), k_ref[to, :],
                                    scale), mask, NEG_INF)
                m_prev = _column(m_ref, at, head, v_heads)
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                correction = jnp.exp(m_prev - m_new)
                # A row with nothing unmasked so far has m_new == NEG_INF and
                # p == 1 where it is masked: the second select zeroes it.
                p = _masked(jnp.exp(s - m_new), mask, 0.0)
                l_news.append(_column(l_ref, at, head, v_heads) * correction
                              + p.sum(axis=-1, keepdims=True))
                # [rows, lanes]: of it the head's own lanes are kept.
                v_sub = v_ref[to, :]
                pvs.append(jax.lax.dot_general(
                    p.astype(v_sub.dtype), v_sub, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                corrections.append(correction)
                m_news.append(m_new)
            acc_ref[at, :] = (acc_ref[at, :] * _by_head(corrections, v_heads)
                              + _by_head(pvs, v_heads))
            m_ref[at, :] = _by_head(m_news, v_heads)
            l_ref[at, :] = _by_head(l_news, v_heads)

    _for_each_case(args, 2, 3, step)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)                   # fully masked rows
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        if emit_lse:
            # logsumexp rows: the backward kernels reconstruct P without
            # re-running the online softmax.
            lse_ref[...] = m_ref[...] + jnp.log(l)


def _block_specs(plan, q_map, kv_map):
    """``(q_spec, k_spec, o_spec, v_spec)``: the blocks of q and dq, of k
    and dk (key-side lanes), of o, dO and lse, and of v and dv (value-side
    lanes, the same where the widths are)."""
    import jax.experimental.pallas as pl

    return tuple(pl.BlockSpec((None, plan[rows], plan[lanes]), index)
                 for lanes in ('lane_block', 'v_lane_block')
                 for rows, index in (('block_q', q_map), ('block_k', kv_map)))


def _flash_fwd(q, k, v, plan, interpret, emit_lse):
    """Padded ``[B, T_pad, lanes]`` -> ``out`` (+ ``lse``, float32 of the
    same shape, a head's value in each of its lanes, when ``emit_lse`` — the
    training forward; inference skips the write)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t_pad, lanes = q.shape
    block_q, block_k, lane_block, v_block = (
        plan['block_q'], plan['block_k'], plan['lane_block'],
        plan['v_lane_block'])
    kernel = functools.partial(_flash_kernel, emit_lse=emit_lse,
                               **_kernel_args(plan, 'fwd'))
    q_spec, k_spec, o_spec, v_spec = _block_specs(plan, *_index_maps(
        block_q, block_k, plan['t'], plan['causal']))
    # o/lse blocks ignore ki: revisited across the kv axis, written at the
    # last ki only. A (block_q,) rank-1 or (1, block_q) lse block violates
    # Mosaic's (8,128)-or-full rule on real chips (found on first hardware
    # contact), so lse has o's shape: every lane of a head carries its value.
    out_specs = [o_spec]
    out_shape = [_out_struct(v.shape, q.dtype, q)]
    if emit_lse:
        out_specs.append(o_spec)
        out_shape.append(_out_struct(v.shape, jnp.float32, q))
    out = pl.pallas_call(
        kernel,
        grid=(b, lanes // lane_block, t_pad // block_q, t_pad // block_k),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, v_block), jnp.float32),  # acc
            pltpu.VMEM((block_q, v_block), jnp.float32),  # running max
            pltpu.VMEM((block_q, v_block), jnp.float32),  # running denom
        ],
        interpret=interpret,
        **_mosaic_params(interpret),
    )(q, k, v)
    return (out[0], out[1]) if emit_lse else (out[0], None)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _row_dot(do, o32):
    """``D = rowsum(dO * O)`` of one head, ``[rows, 1]`` float32, from the
    head's ``dO`` (the other heads' lanes zeroed) and the block's ``O``."""
    return jnp.sum(do.astype(jnp.float32) * o32, axis=-1, keepdims=True)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, dq_ref,
                     acc_ref, dd_ref, **args):
    """dQ pass: grid (b, lane block, q_blocks, kv_blocks); dq accumulates
    across ki, over the same bands as the forward.

    dS = P * (dO V^T - D);  dQ = scale * dS K, with D = rowsum(dO * O) taken
    here from the dO and O blocks, once a q block (at its first kv block)
    into scratch that holds a head's D in each of the head's lanes.
    """
    import jax.experimental.pallas as pl

    scale, heads, v_heads = args['scale'], args['heads'], args['v_heads']
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        do, o32 = do_ref[...], o_ref[...].astype(jnp.float32)
        dd_ref[...] = _by_head([_row_dot(_head_of(do, head, v_heads), o32)
                                for head in range(heads[0])], v_heads)

    def step(case):
        for r0, rows, c_full, c_run in _bands(case, *args['tiling']):
            at, to = pl.ds(r0, rows), pl.ds(0, c_run)
            mask = _band_mask(case, r0, rows, c_full, c_run - c_full)
            q, do = q_ref[at, :], do_ref[at, :]
            dqs = []
            for head in range(heads[0]):
                k_sub = k_ref[to, :]
                p = _recompute_p(_head_of(q, head, heads), k_sub,
                                 _column(lse_ref, at, head, v_heads), mask,
                                 scale)
                dp = jax.lax.dot_general(
                    _head_of(do, head, v_heads), v_ref[to, :],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = p * (dp - _column(dd_ref, at, head, v_heads))
                dqs.append(jax.lax.dot_general(
                    ds.astype(k_sub.dtype), k_sub, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            acc_ref[at, :] += scale * _by_head(dqs, heads)

    _for_each_case(args, 2, 3, step)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                      dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, **args):
    """dK/dV pass: grid (b, lane block, kv_blocks, q_blocks); accumulates
    across qi, over the column bands of :func:`_col_bands`.

    dV = P^T dO;  dK = dS^T (scale * Q). A head's q and dO (the other heads'
    lanes zeroed, so that its products leave their lanes of the accumulators
    alone) and its D are formed once a grid step, not once a band.
    """
    import jax.experimental.pallas as pl

    scale, heads, v_heads = args['scale'], args['heads'], args['v_heads']
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def step(case):
        block_q = args['tiling'][0]
        q, do, o32 = q_ref[...], do_ref[...], o_ref[...].astype(jnp.float32)
        of_head = []
        for head in range(heads[0]):
            do_h = _head_of(do, head, v_heads)
            of_head.append((_head_of(q, head, heads), do_h,
                            _row_dot(do_h, o32)))
        for c0, cols, r_lo, r_full in _col_bands(case, *args['tiling']):
            at, to = pl.ds(r_lo, block_q - r_lo), pl.ds(c0, cols)
            mask = _band_mask(case, r_lo, r_full - r_lo, c0, cols)
            dv = dk = 0.0
            for head, (q_h, do_h, dd) in enumerate(of_head):
                q_h, do_h, dd = q_h[r_lo:], do_h[r_lo:], dd[r_lo:]
                p = _recompute_p(q_h, k_ref[to, :],
                                 _column(lse_ref, at, head, v_heads), mask,
                                 scale)
                dv += jax.lax.dot_general(
                    p.astype(do_h.dtype), do_h, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(do_h, v_ref[to, :],
                                         (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                ds = p * (dp - dd)
                # dK = dS^T (scale*Q): scale folds onto the f32 accumulator
                # so Q stays a native-dtype operand.
                dk += jax.lax.dot_general(
                    ds.astype(q_h.dtype), q_h, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            dv_acc_ref[to, :] += dv
            dk_acc_ref[to, :] += scale * dk

    _for_each_case(args, 3, 2, step)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, do, lse, o, plan, interpret):
    """Backward over padded ``[B, T_pad, lanes]`` arrays -> (dq, dk, dv)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t_pad, lanes = q.shape
    block_q, block_k, lane_block, v_block = (
        plan['block_q'], plan['block_k'], plan['lane_block'],
        plan['v_lane_block'])
    q_spec, k_spec, o_spec, v_spec = _block_specs(plan, *_index_maps(
        block_q, block_k, plan['t'], plan['causal']))
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, **_kernel_args(plan, 'dq')),
        grid=(b, lanes // lane_block, t_pad // block_q, t_pad // block_k),
        in_specs=[q_spec, k_spec, v_spec, o_spec, o_spec, o_spec],
        out_specs=q_spec,
        out_shape=_out_struct(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, lane_block), jnp.float32),
                        pltpu.VMEM((block_q, v_block), jnp.float32)],
        interpret=interpret,
        **_mosaic_params(interpret),
    )(q, k, v, do, lse, o)

    q_spec, k_spec, o_spec, v_spec = _block_specs(plan, *_index_maps_dkv(
        block_q, block_k, plan['causal']))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **_kernel_args(plan, 'dkv')),
        grid=(b, lanes // lane_block, t_pad // block_k, t_pad // block_q),
        in_specs=[q_spec, k_spec, v_spec, o_spec, o_spec, o_spec],
        out_specs=[k_spec, v_spec],
        out_shape=[_out_struct(k.shape, k.dtype, k),
                   _out_struct(v.shape, v.dtype, v)],
        scratch_shapes=[
            pltpu.VMEM((block_k, lane_block), jnp.float32),
            pltpu.VMEM((block_k, v_block), jnp.float32),
        ],
        interpret=interpret,
        **_mosaic_params(interpret),
    )(q, k, v, do, lse, o)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public entry + custom vjp
# --------------------------------------------------------------------------

def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    interpret=False, scale=None):
    """Exact multi-head attention, ``[B, T, H, D]`` -> ``[B, T, H, D]``
    (``v`` and the output ``[B, T, H, Dv]`` where a value has another width
    than a key); ``scale`` multiplies the scores, ``D ** -0.5`` by default.

    Runs the Pallas blocked kernels compiled for the TPU; ``interpret=True``
    runs them in the Pallas interpreter instead (any backend — the CPU
    tests). The compiled kernel on a backend that is not a TPU raises.

    The kernels read q, k, v (and ``dO``) and write the output (and dq, dk,
    dv) as ``[B, T, H*D]``: the reshape is a bitcast, so what a projection
    wrote is what a kernel reads and no layout copy stands between them.
    Heads go to the kernels by 128-lane blocks (:func:`lane_plan`: two
    64-wide heads a block, one of 128), a grid ``(B, H*D / 128, q blocks, kv
    blocks)``; what does not fill a block (an odd head, ``H*D < 128``, a
    width like 96) is padded with zero heads or lanes here and stripped.

    ``block_q``/``block_k`` are the *DMA block*: what one grid step holds in
    VMEM. They default per dtype on TPU — ``(512, 1024)`` for bf16, ``(256,
    512)`` for f32, whose operands take twice the VMEM — and to ``(128,
    128)`` under the interpreter; large, because the grid pays per step
    ((128, 128) blocks ran at a quarter of (512, 1024)'s rate in the v5e
    sweep at T=8192 that chose them). Blocks are clamped to the sequence
    length and rounded down to powers of two (keeping pad overhead bounded
    by one block — see ``_pad_plan``); sequences are zero-padded up to a
    block multiple and the pad is masked/stripped (padding tolerance is what
    lets ring attention hand this kernel arbitrary per-device slice
    lengths). Inside a block the kernels leave work out by *compute
    sub-tiles* of 128 x 128 (128 x 256 in the dk/dv pass): what lies above
    the causal diagonal or beyond the last real column is not computed, and
    only the sub-tiles those cross are masked. So ``causal=True`` skips
    masked work from T > 128 on, whatever the DMA block (0.583 of the three
    squares is computed at T = 1024, 0.54 at T = 2048; the limit is a half),
    and ``causal=False`` computes everything that is not padding. :func:`tile_plan` is the
    account, and a ``kernel.flash_plan`` instant on the global tracer
    reports it with the lane plan once a plan (PERF.md has the times
    measured on a v5e).

    Differentiable end to end in O(block) memory: the training forward saves
    the logsumexp rows (float32 ``[B, T, H*D]``, a head's value in each of
    its lanes) and the backward runs two more Pallas passes (a dq pass over
    kv blocks and a dk/dv pass over q blocks) that reconstruct ``P = exp(S -
    lse)`` tile by tile and take ``D = rowsum(dO * O)`` from the ``dO`` and
    output blocks they are handed — no ``[T, T]`` materialization in either
    direction and no XLA operation beside the three calls. The inference
    (non-differentiated) path skips the lse write entirely.
    """
    if not interpret and jax.devices()[0].platform != 'tpu':
        raise RuntimeError(
            'flash_attention compiles Pallas TPU kernels but the default jax '
            'backend is {!r}; pass interpret=True to run them in the Pallas '
            'interpreter, or use dense_attention'.format(
                jax.devices()[0].platform))
    if block_q is None or block_k is None:
        if interpret:
            dq, dk = 128, 128
        elif q.dtype == jnp.bfloat16:
            dq, dk = 512, 1024
        else:
            dq, dk = 256, 512
        block_q = dq if block_q is None else block_q
        block_k = dk if block_k is None else block_k
    scale = None if scale is None else float(scale)
    return _flash_diff(q, k, v, causal, block_q, block_k, interpret, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diff(q, k, v, causal, block_q, block_k, interpret, scale=None):
    out, _ = _flash_pallas(q, k, v, causal, block_q, block_k, interpret,
                           False, scale)
    return out


def _flash_diff_fwd(q, k, v, causal, block_q, block_k, interpret, scale):
    out, lse = _flash_pallas(q, k, v, causal, block_q, block_k, interpret,
                             True, scale)
    return out, (q, k, v, out, lse)


# One trace a shape, not one a layer: a model's layers call the kernels with
# the same shapes, and tracing three kernel bodies for each of twelve layers
# cost a train step 25 s of set-up on a TPU host (PERF.md §6, PR 27).
# ``inline`` leaves no trace of the jit in the caller's program: the kernels
# keep the name of the scope they were called in (a device trace names them
# by it).
_once_a_shape = functools.partial(jax.jit, inline=True)


@functools.partial(_once_a_shape, static_argnums=(0, 1, 2, 3, 4))
def _flash_diff_bwd(causal, block_q, block_k, interpret, scale, residuals, g):
    q, k, v, out, lse = residuals
    plan = _plan_for(q, causal, block_q, block_k, v, scale)
    # lse is in the kernels' layout already (saved as the forward wrote it).
    dq, dk, dv = _flash_bwd(
        _to_lanes(q, plan), _to_lanes(k, plan), _to_lanes(v, plan, 'v_'),
        _to_lanes(g, plan, 'v_'), lse, _to_lanes(out, plan, 'v_'), plan,
        interpret)
    return (_from_lanes(dq, q.shape, plan), _from_lanes(dk, q.shape, plan),
            _from_lanes(dv, v.shape, plan, 'v_'))


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


@functools.partial(_once_a_shape, static_argnums=(3, 4, 5, 6, 7, 8))
def _flash_pallas(q, k, v, causal, block_q, block_k, interpret, emit_lse,
                  scale=None):
    """Returns ``(out [B, T, H, Dv], lse [B, T_pad, value lanes] | None)``."""
    plan = _plan_for(q, causal, block_q, block_k, v, scale)
    out, lse = _flash_fwd(_to_lanes(q, plan), _to_lanes(k, plan),
                          _to_lanes(v, plan, 'v_'), plan, interpret, emit_lse)
    return _from_lanes(out, v.shape, plan, 'v_'), lse
