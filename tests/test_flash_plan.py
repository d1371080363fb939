"""The flash kernels' tile plan (``ops.flash_attention.tile_plan``): which
compute sub-tiles each pass runs and which of those take the mask. Pure
arithmetic and one abstract trace: no kernel runs here (the interpreter
runs are ``tests/test_flash_attention.py``, slow lane)."""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu import trace

# ``petastorm_tpu.ops.flash_attention`` the attribute is the function.
fa = importlib.import_module('petastorm_tpu.ops.flash_attention')


def _passes(plan):
    return {name: (p['tiles_run'], p['tiles_masked'], p['tiles_total'])
            for name, p in plan['passes'].items()}


def _subs(plan):
    return {name: (p['sub_q'], p['sub_k'])
            for name, p in plan['passes'].items()}


_LM_SUBS = {'fwd': (128, 128), 'dq': (128, 128), 'dkv': (128, 256)}


def _same(value):
    return {name: value for name in ('fwd', 'dq', 'dkv')}


@pytest.mark.parametrize('args,blocks,subs,cases,passes,share', [
    # The benchmark's LM cell: one kv DMA block, so the grid skips nothing;
    # inside it the sub-tiles on or below the diagonal are computed (36 of
    # 64 at 128 x 128, 20 of 32 at 128 x 256), the ones on it masked.
    ((1024, True, 'bfloat16', 64, 512, 1024), (512, 1024, 1024), _LM_SUBS,
     [(0, 1024), (512, 1024)],
     {'fwd': (36, 8, 64), 'dq': (36, 8, 64), 'dkv': (20, 8, 32)}, 7 / 12),
    # No diagonal and no padding: every sub-tile runs, none is masked.
    ((1024, False, 'bfloat16', 64, 512, 1024), (512, 1024, 1024), _LM_SUBS,
     [(None, 1024)],
     {'fwd': (64, 0, 64), 'dq': (64, 0, 64), 'dkv': (32, 0, 32)}, 1.0),
    # T=1000 padded to 1024: the last kv sub-tile crosses the tail.
    ((1000, True, 'float32', 64, 256, 512), (256, 512, 1024), _LM_SUBS,
     [(0, 488), (0, 512), (256, 488), (256, 512), (None, 512)],
     {'fwd': (36, 8, 64), 'dq': (36, 8, 64), 'dkv': (20, 8, 32)}, 7 / 12),
    ((1000, False, 'float32', 64, 256, 512), (256, 512, 1024), _LM_SUBS,
     [(None, 488), (None, 512)],
     {'fwd': (64, 8, 64), 'dq': (64, 8, 64), 'dkv': (32, 8, 32)}, 1.0),
    # Shorter than a tile: one masked sub-tile.
    ((7, True, 'float32', 4, 8, 8), (8, 8, 8), _same((8, 8)),
     [(0, 7)], _same((1, 1, 1)), 1.0),
    # chip_smoke's long shape: the triangle runs.
    ((8192, True, 'bfloat16', 64, 512, 1024), (512, 1024, 8192), _LM_SUBS,
     [(0, 1024), (512, 1024), (None, 1024)],
     {'fwd': (2080, 64, 4096), 'dq': (2080, 64, 4096),
      'dkv': (1056, 64, 2048)}, (2 * 2080 / 4096 + 1056 / 2048) / 3),
    # block_q != block_k, both under the sub-tile: the blocks are the tiles.
    ((256, True, 'float32', 16, 128, 32), (128, 32, 256), _same((128, 32)),
     [(-96, 32), (-64, 32), (-32, 32), (0, 32), (None, 32)],
     _same((12, 8, 16)), 0.75),
    # Whole sub-tiles of padding columns (T=600 in 1024) are skipped, and
    # the one the tail crosses takes the mask for every row.
    ((600, False, 'bfloat16', 64, 512, 1024), (512, 512, 1024), _LM_SUBS,
     [(None, 88), (None, 512)],
     {'fwd': (40, 8, 64), 'dq': (40, 8, 64), 'dkv': (24, 8, 32)},
     (2 * 40 / 64 + 24 / 32) / 3),
])
def test_tile_plan(args, blocks, subs, cases, passes, share):
    plan = fa.tile_plan(*args)
    assert (plan['block_q'], plan['block_k'], plan['t_pad']) == blocks
    assert _subs(plan) == subs
    assert plan['cases'] == cases
    assert _passes(plan) == passes
    assert plan['share'] == pytest.approx(share)
    if args[1] and args[0] > 128:
        assert plan['share'] < 1          # the causal bound engages
        assert all(run < total for run, _, total in passes.values())
    json.dumps(plan)                      # the instant's args


def test_tile_plan_names_the_masked_sub_tiles_of_the_lm_shape():
    """At T=1024 causal the q block of rows 0..511 computes, of its kv
    block's 1,024 columns, the first 256 for its first 256 rows (all of them
    the diagonal's sub-tile, masked) and the first 512 for its second (the
    last 256 of them masked); the q block of rows 512..1023 computes 768 and
    1,024 columns, the last 256 masked. ``(r0, rows, c_full, c_run)``."""
    assert fa._bands((0, 1024), 512, 1024, 256, 256) == [
        (0, 256, 0, 256), (256, 256, 256, 512)]
    assert fa._bands((512, 1024), 512, 1024, 256, 256) == [
        (0, 256, 512, 768), (256, 256, 768, 1024)]
    # Below the diagonal the whole DMA block is one unmasked piece, as it
    # was before there were sub-tiles.
    assert fa._bands((None, 1024), 512, 1024, 256, 256) == [
        (0, 512, 1024, 1024)]
    # A q block taller than its kv block: its first rows lie above it.
    assert fa._bands((-256, 256), 512, 256, 256, 256) == [(256, 256, 0, 256)]


def _brute_force(t, t_pad, sub_q, sub_k, causal):
    """(run, masked) by looking at every score of the padded square: a
    sub-tile is computed if it holds a score the mask keeps, and takes the
    mask if it also holds one the mask drops."""
    rows = np.arange(t_pad)[:, None]
    cols = np.arange(t_pad)[None, :]
    keep = np.broadcast_to(cols < t, (t_pad, t_pad))
    if causal:
        keep = keep & (cols <= rows)
    run = masked = 0
    for r in range(0, t_pad, sub_q):
        for c in range(0, t_pad, sub_k):
            if keep[r:r + sub_q, c:c + sub_k].any():
                run += 1
                masked += not keep[r:r + sub_q, c:c + sub_k].all()
    return run, masked


_SHAPES = [
    (1024, 512, 1024), (1000, 512, 1024), (1100, 512, 1024), (600, 512, 1024),
    (257, 512, 1024), (197, 512, 1024), (2048, 512, 1024), (1500, 256, 512),
    (100, 32, 16), (48, 16, 24), (7, 8, 8), (300, 128, 32), (300, 32, 128),
    (700, 1024, 256),
]


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('t,block_q,block_k', _SHAPES)
def test_exactly_the_tiles_that_hold_an_unmasked_score_are_computed(
        t, block_q, block_k, causal):
    """The rectangles the kernels compute (what ``tile_plan`` counts)
    against a look at every score: no sub-tile with an unmasked score is
    dropped, none without one is computed, and the unmasked path is taken
    only where no score is masked."""
    plan = fa.tile_plan(t, causal, 'float32', 64, block_q, block_k)
    for name, p in plan['passes'].items():
        run, masked = _brute_force(t, plan['t_pad'], p['sub_q'], p['sub_k'],
                                   causal)
        total = (plan['t_pad'] // p['sub_q']) * (plan['t_pad'] // p['sub_k'])
        assert (p['tiles_run'], p['tiles_masked'], p['tiles_total']) == (
            run, masked, total), name


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('t,block_q,block_k', _SHAPES)
def test_every_grid_step_is_one_case_of_the_plan_or_none(
        t, block_q, block_k, causal):
    """What a kernel asks of its grid indices (``_is_case``) picks, for
    every pair of blocks, the one case ``_block_case`` names — and none for
    a pair that shares no unmasked score."""
    plan = fa.tile_plan(t, causal, 'float32', 64, block_q, block_k)
    block_q, block_k = plan['block_q'], plan['block_k']
    for qi in range(plan['t_pad'] // block_q):
        for ki in range(plan['t_pad'] // block_k):
            off = qi * block_q - ki * block_k
            rem = min(t - ki * block_k, block_k)
            hits = [case for case in plan['cases']
                    if fa._is_case(case, off, rem, block_k, causal)]
            want = fa._block_case(qi, ki, block_q, block_k, t, causal)
            assert hits == ([] if want is None else [want]), (qi, ki)


def test_flash_plan_instant_once_per_distinct_plan(monkeypatch):
    """Tracing the kernels writes one ``kernel.flash_plan`` instant per
    distinct plan to the global tracer, not one per layer or per pass."""
    monkeypatch.setattr(fa, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        q = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)

        def layers(q, causal):
            def loss(q):
                x = q
                for _ in range(3):          # three layers, one plan
                    x = fa.flash_attention(x, x, x, causal=causal,
                                           block_q=512, block_k=1024,
                                           interpret=True)
                return jnp.sum(x.astype(jnp.float32))
            return jax.grad(loss)(q)        # forward and both backward passes

        jax.eval_shape(lambda q: layers(q, True), q)
        jax.eval_shape(lambda q: layers(q, True), q)
        jax.eval_shape(lambda q: layers(q, False), q)
        # The heads of a call are part of its plan: three heads pad a fourth.
        jax.eval_shape(lambda q: layers(q, False),
                       jax.ShapeDtypeStruct((1, 1024, 3, 64), jnp.bfloat16))
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'kernel.flash_plan']
    assert len(plans) == 3
    assert all(r[1] == 'kernel' and r[3] is None for r in plans)  # instants
    causal, full, odd = (r[7] for r in plans)
    assert (odd['heads'], odd['pad_heads']) == (3, 1)
    assert causal['causal'] and causal['share'] == pytest.approx(7 / 12)
    # How the call's heads lie in the lane blocks the kernels read, and
    # where D = rowsum(dO * O) is taken.
    for plan in (causal, full):
        assert (plan['heads'], plan['lane_block'], plan['heads_per_block'],
                plan['pad_heads'], plan['pad_lanes'], plan['dd']) == (
                    2, 128, 2, 0, 0, 'in-kernel')
    assert causal['passes']['fwd'] == {
        'sub_q': 128, 'sub_k': 128, 'tiles_run': 36, 'tiles_masked': 8,
        'tiles_total': 64}
    assert causal['passes']['dkv'] == {
        'sub_q': 128, 'sub_k': 256, 'tiles_run': 20, 'tiles_masked': 8,
        'tiles_total': 32}
    assert not full['causal'] and full['share'] == 1.0
    assert all(p['tiles_run'] == p['tiles_total']
               for p in full['passes'].values())
