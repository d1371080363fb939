"""``models.moe.RoutedMoE``: dropless top-k routing over the experts held of
a wider router. The shares of an expert-parallel group add up to the uncut
layer of the plain reference, nothing is dropped whatever the routing, and
the dispatch's tables say where every pair lies."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu import trace
from petastorm_tpu.models import moe
from petastorm_tpu.models.moe import RoutedMoE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, 'perfbench', 'configs')
NAME = 'xing4-29b-a4b-ctx4096'


@pytest.fixture(scope='module')
def ref():
    spec = importlib.util.spec_from_file_location(
        'xing4_reference', os.path.join(CONFIGS, NAME + '.reference.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope='module')
def cfg():
    """The configuration's own file with every one of 16 experts held: the
    uncut layer the shares are held against."""
    cfg = json.load(open(os.path.join(CONFIGS, NAME + '.json')))
    cfg.update(hidden_size=32, moe_intermediate_size=16, n_routed_experts=16)
    cfg['published'] = dict(cfg['published'], n_routed_experts=16)
    cfg['assumed'] = dict(cfg['assumed'], experts_held=list(range(16)))
    return cfg


def _layer_params(seed=0, d=32, f=16, experts=16):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(0.3 * rng.standard_normal(shape), jnp.float32)

    return {'router': {'kernel': normal(d, experts)},
            'shared': {'gate': {'kernel': normal(d, f)},
                       'up': {'kernel': normal(d, f)},
                       'down': {'kernel': normal(f, d)}},
            'experts_gate_up': normal(experts, d, 2 * f),
            'experts_down': normal(experts, f, d)}


def _share(params, held, with_shared):
    held = list(held)
    p = {'router': params['router'],
         'experts_gate_up': params['experts_gate_up'][jnp.asarray(held)],
         'experts_down': params['experts_down'][jnp.asarray(held)]}
    if with_shared:
        p['shared'] = params['shared']
    return p


def _module(cfg, held, impl, shared=True, tile_m=8):
    return RoutedMoE(
        experts_published=cfg['published']['n_routed_experts'],
        held=tuple(held), top_k=cfg['num_experts_per_tok'],
        scale=cfg['routed_scaling_factor'], d_ff=cfg['moe_intermediate_size'],
        shared_d_ff=cfg['moe_intermediate_size'] if shared else 0,
        impl=impl, tile_m=tile_m, dtype=jnp.float32)


@pytest.mark.parametrize('impl', ['pallas:interpret', 'ragged_dot'])
def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        cfg, ref, impl):
    """Eight chips hold two experts each of sixteen; every chip routes over
    all sixteen and computes its own experts' part; the shared expert, which
    every chip computes alike, is counted once. float32 on both sides, so
    what differs is the order of sums: 2e-5 of the largest output."""
    params = _layer_params()
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 24, 32)),
                    jnp.float32)
    whole = ref._experts(params, x, cfg, None)
    total, loads = 0.0, []
    for chip in range(8):
        held = [2 * chip, 2 * chip + 1]
        y, load = _module(cfg, held, impl, shared=chip == 0).apply(
            {'params': _share(params, held, chip == 0)}, x)
        total = total + y
        loads.append(np.asarray(load['expert_load']))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5 * float(jnp.abs(whole).max()), rtol=0)
    # every pair of every token landed on exactly one chip
    assert int(np.sum(loads)) == 2 * 24 * cfg['num_experts_per_tok']


@pytest.mark.parametrize('impl', ['pallas:interpret', 'ragged_dot'])
def test_a_share_s_gradients_equal_the_reference_s_for_the_same_share(
        cfg, ref, impl):
    """The cut as the benchmark's cell has it: some experts held, the
    reference given the same share; loss and every leaf's gradient, the
    router's through the weights included."""
    held = [1, 2, 5, 11, 12]
    params = _share(_layer_params(seed=2), held, True)
    share = dict(cfg, n_routed_experts=len(held),
                 assumed=dict(cfg['assumed'], experts_held=held))
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 40, 32)),
                    jnp.float32)
    c = jnp.asarray(np.random.default_rng(4).standard_normal((1, 40, 32)),
                    jnp.float32)
    module = _module(cfg, held, impl)
    got = jax.value_and_grad(lambda p, x: jnp.sum(
        module.apply({'params': p}, x)[0] * c), argnums=(0, 1))(params, x)
    want = jax.value_and_grad(lambda p, x: jnp.sum(
        ref._experts(p, x, share, None) * c), argnums=(0, 1))(params, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=3e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize('expert', [0, 3])
@pytest.mark.parametrize('impl', ['pallas:interpret', 'ragged_dot'])
def test_dropless_every_token_to_one_held_expert(impl, expert):
    """All 96 tokens pick the same held expert (and three absent ones): a
    capacity of 1.25 x 96 / 4 would drop 66 of them; here every token's
    output is that expert's, and the other held experts see nothing."""
    rng = np.random.default_rng(5)
    d, f, held = 16, 8, (2, 4, 6, 9)
    x = jnp.asarray(rng.standard_normal((96, d)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((4, d, 2 * f)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((4, f, d)), jnp.float32)
    experts = jnp.tile(jnp.asarray([[held[expert], 0, 1, 3]], jnp.int32),
                       (96, 1))
    weights = jnp.tile(jnp.asarray([[0.7, 0.1, 0.1, 0.1]], jnp.float32),
                       (96, 1))
    y, counts = moe.routed_experts(x, experts, weights, w1, w2, held, 12,
                                   tile_m=8, impl=impl)
    hidden = x @ w1[expert]
    want = 0.7 * ((jax.nn.silu(hidden[:, :f]) * hidden[:, f:]) @ w2[expert])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert counts.tolist() == [96 if i == expert else 0 for i in range(4)]


@pytest.mark.parametrize('capacity', [200, 64, 48, 40])
def test_the_layout_places_every_held_pair_once(capacity):
    """Every pair there could be, two capacities with room to spare and the
    held pairs' own number rounded up to tiles."""
    rng = np.random.default_rng(6)
    n, k, held, tile_m = 50, 4, (3, 7, 8, 15), 8
    experts = np.stack([rng.permutation(16)[:k] for _ in range(n)])
    plan = moe.layout(*moe.sorted_pairs(jnp.asarray(experts, jnp.int32), held),
                      k, tile_m, capacity)
    is_held = np.isin(experts, held)
    counts = [int((experts == e).sum()) for e in held]
    assert 32 < sum(counts) <= 40
    assert plan.counts.tolist() == counts
    assert plan.group_sizes.tolist() == [max(1, -(-c // tile_m)) * tile_m
                                         for c in counts]
    rows = capacity + len(held) * tile_m
    assert plan.row_token.shape == plan.row_pair.shape == \
        plan.row_valid.shape == (rows,)
    # a row knows its pair, and every held pair has one row
    valid = np.asarray(plan.row_valid)
    pair = np.asarray(plan.row_pair)[valid]
    assert sorted(pair.tolist()) == np.flatnonzero(is_held).tolist()
    assert np.array_equal(np.asarray(plan.row_token)[valid], pair // k)
    assert not np.asarray(plan.row_token)[~valid].any()
    # groups lie in the order of ``held``, each from a tile's first row, a
    # group's pairs in their own order
    starts = np.cumsum([0] + plan.group_sizes.tolist()[:-1])
    for slot, e in enumerate(held):
        mine = np.flatnonzero(valid)[experts.reshape(-1)[pair] == e]
        assert mine.tolist() == list(range(starts[slot],
                                           starts[slot] + counts[slot]))
        assert np.all(np.diff(np.asarray(plan.row_pair)[mine]) > 0)


def _routed_case(n, k, published, held, d=16, f=8, seed=7):
    """Every token picks ``k`` distinct experts of ``published`` at random."""
    rng = np.random.default_rng(seed)
    experts = np.stack([rng.permutation(published)[:k] for _ in range(n)])
    weights = rng.random((n, k)).astype(np.float32)
    x, c = (jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
            for _ in range(2))
    w1 = jnp.asarray(rng.standard_normal((len(held), d, 2 * f)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((len(held), f, d)), jnp.float32)
    return experts, jnp.asarray(weights), x, c, w1, w2


def _out_counts_grads(experts, weights, x, c, w1, w2, held, published, impl):
    def loss(x, weights, w1, w2):
        y, counts = moe.routed_experts(x, jnp.asarray(experts, jnp.int32),
                                       weights, w1, w2, held, published,
                                       tile_m=8, impl=impl)
        return jnp.sum(y * c), (y, counts)
    (_, (y, counts)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True))(x, weights, w1, w2)
    return y, counts, grads


def _assert_same(got, want):
    """``(out, counts, gradients)``: counts to the bit, the float32 arrays
    to the order of their sums."""
    assert got[1].tolist() == want[1].tolist()
    for a, b in zip((got[0],) + got[2], (want[0],) + want[2]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=3e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize('impl', ['pallas:interpret', 'ragged_dot'])
@pytest.mark.parametrize('published,held,n,capacity', [
    (16, (3, 12), 64, 128), (64, (41,), 128, 32)])
def test_the_compact_layout_equals_the_layout_of_every_pair(
        monkeypatch, published, held, n, capacity, impl):
    """Shares of an eighth and a sixty-fourth: rows for four times the share
    against rows for every pair there could be (a capacity over the share so
    large that it is every pair), in ``out``, ``counts`` and the gradients of
    ``x``, the weights and both expert leaves."""
    case = _routed_case(n, 4, published, held)
    assert moe.pairs_capacity(n * 4, len(held), published, 8) == capacity
    compact = _out_counts_grads(*case, held, published, impl)
    assert 0 < int(compact[1].sum()) < capacity
    monkeypatch.setattr(moe, 'CAPACITY_OVER_SHARE', published)
    assert moe.pairs_capacity(n * 4, len(held), published, 8) == n * 4
    _assert_same(compact, _out_counts_grads(*case, held, published, impl))


def _held_pairs(n, k, published, held, pairs_held):
    """``experts [n, k]`` whose first ``pairs_held`` pairs (token after token)
    go to held experts and no other does."""
    absent = [e for e in range(published) if e not in held]
    experts = np.tile(np.asarray(absent[:k]), (n, 1)).reshape(-1)
    slot = np.arange(n * k) % k
    experts[:pairs_held] = np.asarray(held)[slot[:pairs_held]]
    return experts.reshape(n, k)


@pytest.mark.parametrize('impl', ['pallas:interpret', 'ragged_dot'])
@pytest.mark.parametrize('over', [128, 1, 0, -1])
def test_one_pair_over_the_capacity_goes_to_every_token_and_loses_nothing(
        monkeypatch, impl, over):
    """Four of 32 experts held, 64 tokens, top 4: rows for 128 held pairs.
    129 of them pass the rows (the tables place 128: a pair would be lost)
    and the held experts are applied to every token, as with all 256 held
    (the collapse); 128 and 127 run over the rows; all four equal the rows of
    every pair."""
    held, published, n, k = (5, 6, 20, 31), 32, 64, 4
    capacity = moe.pairs_capacity(n * k, len(held), published, 8)
    assert capacity == 128
    _, weights, x, c, w1, w2 = _routed_case(n, k, published, held)
    experts = _held_pairs(n, k, published, held, capacity + over)
    got = _out_counts_grads(experts, weights, x, c, w1, w2, held, published,
                            impl)
    assert int(got[1].sum()) == capacity + over
    monkeypatch.setattr(moe, 'CAPACITY_OVER_SHARE', published)
    _assert_same(got, _out_counts_grads(experts, weights, x, c, w1, w2, held,
                                        published, impl))
    # every held pair's weight has a gradient: none was left out of the rows
    assert int((np.asarray(got[2][1]) != 0).sum()) == capacity + over


@pytest.mark.parametrize('over,fallbacks', [(1, 1), (0, 0), (-1, 0)])
def test_the_layer_counts_a_fallback_one_pair_over_and_none_one_under(
        monkeypatch, over, fallbacks):
    held, published, n, k = (5, 6, 20, 31), 32, 64, 4
    experts = jnp.asarray(_held_pairs(n, k, published, held, 128 + over),
                          jnp.int32).reshape(1, n, k)
    monkeypatch.setattr(moe, 'top_k_routing', lambda scores, *a: (
        experts, jnp.full((1, n, k), 0.25, jnp.float32)))
    layer = RoutedMoE(experts_published=published, held=held, top_k=k,
                      d_ff=8, impl='pallas:interpret', tile_m=8,
                      dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(8).standard_normal((1, n, 16)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    _, load = jax.jit(layer.apply)(params, x)
    assert int(load['layout_fallbacks']) == fallbacks
    assert int(load['expert_load'].sum()) == 128 + over


def _sub_jaxprs(jaxpr, skip):
    """``jaxpr`` and every jaxpr inside it but those of ``skip`` equations."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in skip:
            continue
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(inner, 'jaxpr', inner)
                if hasattr(inner, 'eqns'):
                    for found in _sub_jaxprs(inner, skip):
                        yield found


def _gradient_jaxpr(held, published, n, impl='pallas:interpret'):
    case = _routed_case(n, 4, published, held)
    return jax.make_jaxpr(lambda *a: _out_counts_grads(
        case[0], *a, held, published, impl))(*case[1:]).jaxpr


def _primitives(held, published):
    """Of the gradient's jaxpr, the kernels' own bodies left out (a
    ``pl.when`` is a ``cond`` too)."""
    return {eqn.primitive.name for jaxpr in _sub_jaxprs(
        _gradient_jaxpr(held, published, 32), skip=('pallas_call',))
            for eqn in jaxpr.eqns}


def test_every_expert_held_traces_no_cond():
    """A chip that holds every expert (or a quarter of them) lays out every
    pair there could be: one layout, no choice."""
    for held, published in ((tuple(range(8)), 8), ((0, 1), 8)):
        names = _primitives(held, published)
        assert 'cond' not in names and 'pallas_call' in names
    assert 'cond' in _primitives((0,), 8)


def test_no_array_of_every_pair_anywhere_and_none_of_every_token_outside():
    """A share of a sixty-fourth, forward and backward. Nowhere, the branches
    included, has an array ``N k`` rows (or those and a tile a group) by
    ``d``, ``f`` or ``2 f``: the layout of every pair is gone. The arrays of
    every token by every held expert live inside the second branches alone:
    outside (the ``cond``s' operands and results included, what the common
    path writes whichever branch runs) there are the rows' and no more.
    Differentiating through a ``cond`` would hand the second branch's
    residuals out of it as zeros."""
    n, k, d, f, tile_m = 128, 4, 16, 8, 8
    every = list(_sub_jaxprs(_gradient_jaxpr((41,), 64, n), skip=()))
    outer = list(_sub_jaxprs(_gradient_jaxpr((41,), 64, n), skip=('cond',)))
    conds = [eqn for jaxpr in outer for eqn in jaxpr.eqns
             if eqn.primitive.name == 'cond']
    assert len(conds) == 2                  # the forward's and the backward's

    def shapes(jaxprs):
        return {tuple(v.aval.shape) for jaxpr in jaxprs for eqn in jaxpr.eqns
                for v in list(eqn.invars) + list(eqn.outvars)
                if hasattr(v.aval, 'shape')}

    rows = moe.pairs_capacity(n * k, 1, 64, tile_m) + tile_m
    assert (rows, d) in shapes(outer) and (rows, 2 * f) in shapes(outer)
    assert not [s for s in shapes(every)
                if len(s) >= 2 and s[-1] in (d, f, 2 * f)
                and int(np.prod(s[:-1])) in (n * k + tile_m, n * k)]
    assert (n, 1, 2 * f) in shapes(every) and (n, 1, f) in shapes(every)
    assert not [s for s in shapes(outer) if len(s) == 3 and s[0] == n
                and s[-1] in (f, 2 * f)]


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize('held_pairs', [0, 37, 256])
def test_the_held_experts_on_every_token_equal_the_rows_of_every_pair(
        held_pairs, dtype):
    """No held pair, some, and every pair held (the collapse): the dense
    form against the rows laid out for every pair, forward and the four
    gradients; in bfloat16 to the rounding of two products."""
    held, published, n, k = (5, 6, 20, 31), 32, 64, 4
    _, weights, x, c, w1, w2 = _routed_case(n, k, published, held)
    experts = jnp.asarray(_held_pairs(n, k, published, held, held_pairs),
                          jnp.int32)
    x, c, w1, w2 = (a.astype(dtype) for a in (x, c, w1 / 4, w2 / 4))

    def dense(x, weights, w1, w2):
        return jnp.sum((moe.held_experts_on_every_token(
            x, experts, weights, w1, w2, held) * c).astype(jnp.float32))

    def rows(x, weights, w1, w2):
        plan = moe.layout(*moe.sorted_pairs(experts, held), k, 8, n * k)
        out, _ = moe._rows_forward(x, weights, w1, w2, plan, 8, 'ragged_dot')
        return jnp.sum((out * c).astype(jnp.float32))

    got = jax.value_and_grad(dense, argnums=(0, 1, 2, 3))(x, weights, w1, w2)
    want = jax.value_and_grad(rows, argnums=(0, 1, 2, 3))(x, weights, w1, w2)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    for a, b in zip((got[0],) + got[1], (want[0],) + want[1]):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=tol * max(1e-6, np.abs(b).max()))


def test_top_k_routing_normalises_over_the_picked_and_scales():
    scores = jnp.asarray([[0.1, 0.8, 0.3, 0.6, 0.2]], jnp.float32)
    experts, weights = moe.top_k_routing(scores, 2, scale=2.0)
    assert experts.tolist() == [[1, 3]]
    np.testing.assert_allclose(np.asarray(weights), [[2 * 0.8 / 1.4,
                                                      2 * 0.6 / 1.4]], rtol=1e-6)
    experts, weights = moe.top_k_routing(scores, 3, normalise=False)
    assert experts.tolist() == [[1, 3, 2]]
    np.testing.assert_allclose(np.asarray(weights), [[0.8, 0.6, 0.3]],
                               rtol=1e-6)


LING = 'ling3-flash-ctx8192'


@pytest.fixture(scope='module')
def ling_ref():
    spec = importlib.util.spec_from_file_location(
        'ling3_reference', os.path.join(CONFIGS, LING + '.reference.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope='module')
def ling_cfg():
    """Ling-3.0-flash's file with every one of 16 experts held, in 4 groups
    of which the best 2 are kept, top 4: the uncut layer under selection by
    groups."""
    cfg = json.load(open(os.path.join(CONFIGS, LING + '.json')))
    cfg.update(hidden_size=32, moe_intermediate_size=16,
               moe_shared_expert_intermediate_size=16, num_experts=16,
               n_group=4, topk_group=2, num_experts_per_tok=4)
    cfg['published'] = dict(cfg['published'], num_experts=16)
    cfg['assumed'] = dict(cfg['assumed'], experts_held=list(range(16)))
    return cfg


def test_selection_by_groups_is_the_reference_s_and_one_group_is_today_s(
        ling_cfg, ling_ref):
    scores = jax.nn.sigmoid(jnp.asarray(
        np.random.default_rng(3).standard_normal((200, 16)), jnp.float32))
    experts, weights = moe.top_k_routing(scores, 4, scale=2.5, n_group=4,
                                         topk_group=2)
    want = ling_ref.select(scores, ling_cfg)
    assert experts.tolist() == want.tolist()
    # by hand: a group's score is the sum of its two best, the best two
    # groups are kept and every pick lies in one of them
    by_group = np.sort(np.asarray(scores).reshape(200, 4, 4), axis=-1)
    kept = np.argsort(-(by_group[..., -1] + by_group[..., -2]), axis=-1)[:, :2]
    assert all(set((np.asarray(experts[i]) // 4).tolist()) <= set(kept[i])
               for i in range(200))
    # and the selection differs from the ungrouped one somewhere: the
    # groups bind
    plain, plain_weights = moe.top_k_routing(scores, 4, scale=2.5)
    assert plain.tolist() != experts.tolist()
    picked = np.take_along_axis(np.asarray(scores), np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # one group of all the experts is no group: today's picks to the bit
    same, same_weights = moe.top_k_routing(scores, 4, scale=2.5, n_group=1,
                                           topk_group=1)
    assert same.tolist() == plain.tolist() == \
        jax.lax.top_k(scores, 4)[1].tolist()
    np.testing.assert_array_equal(np.asarray(same_weights),
                                  np.asarray(plain_weights))


@pytest.mark.parametrize('impl', ['pallas:interpret', 'ragged_dot'])
def test_four_shares_under_group_selection_add_up_to_the_uncut_layer(
        ling_cfg, ling_ref, impl):
    """Four chips hold a group of four experts each of sixteen; every chip
    selects by groups over all sixteen and computes its own experts' part;
    the shared expert is counted once. A chip whose group a token did not
    keep is sent nothing by that token."""
    params = _layer_params()
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 24, 32)),
                    jnp.float32)
    whole = ling_ref._experts(params, x, ling_cfg, None)
    total, loads = 0.0, []
    for chip in range(4):
        held = list(range(4 * chip, 4 * chip + 4))
        layer = RoutedMoE(
            experts_published=16, held=tuple(held), top_k=4, scale=2.5,
            d_ff=16, shared_d_ff=16 if chip == 0 else 0, n_group=4,
            topk_group=2, impl=impl, tile_m=8, dtype=jnp.float32)
        y, load = layer.apply({'params': _share(params, held, chip == 0)}, x)
        total = total + y
        loads.append(np.asarray(load['expert_load']))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5 * float(jnp.abs(whole).max()), rtol=0)
    assert int(np.sum(loads)) == 2 * 24 * 4
    # each token keeps two of the four groups: a chip sees about half of them
    assert all(0 < int(load.sum()) < 2 * 24 * 4 for load in loads)


def test_the_expert_load_counter_writes_running_totals_a_step_late():
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        counter = moe.ExpertLoadCounter()
        for step in range(5):
            counter.add({'expert_load': jnp.asarray([step, 10], jnp.int32),
                         'layout_fallbacks': jnp.asarray(step % 2, jnp.int32)})
    finally:
        trace.set_global_tracer(previous)
    records = [r for r in tracer.records() if r[0].startswith('moe.')]
    # five steps handed in, three read (the last LOAD_LAG may be in flight)
    assert moe.LOAD_LAG == 2
    assert [r[3] for r in records if r[0].endswith('.e0')] == [0, 1, 3]
    assert [r[3] for r in records if r[0].endswith('.e1')] == [10, 20, 30]
    assert [r[3] for r in records if r[0] == 'moe.layout_fallbacks'] == \
        [0, 1, 1]
    assert {r[0] for r in records} == {
        'moe.expert_load.e0', 'moe.expert_load.e1', 'moe.layout_fallbacks'}
    assert all(r[1] == 'step' and len(r) == 4 for r in records)
