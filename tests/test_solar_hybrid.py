"""``models.LingHybridLM`` laid out as Solar-Open2-250B (gated grouped-query
attention in the first layer of every four, Kimi delta attention with an
unbounded decay, low-rank decay and output gates and write strengths in
(0, 2) in the three others, top-8 routing over 320 experts with a shared one
in every layer) at a small size on seeded weights: against the plain
reference of the configuration (loss, every leaf's gradient, one AdamW update
through ``make_train_step``), the shares of the experts and of both kinds of
mixer against the uncut reference layer, the gated attention against dense
attention, the parameters counted by hand, the layer plan and the count of
decay entries under the bounded kernels' floor."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from petastorm_tpu import trace
from petastorm_tpu.models import LingHybridLM, ling_hybrid, nemotron_h
from petastorm_tpu.models.moe import ExpertLoadCounter, RoutedMoE
from petastorm_tpu.models.train import (TrainState, make_train_step,
                                        summed_loss)
from petastorm_tpu.ops import gated_delta, kimi_delta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, 'perfbench', 'configs')
NAME = 'solar-open2-250b-ctx8192'


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope='module')
def ref():
    return _load(os.path.join(CONFIGS, NAME + '.reference.py'), 'solar2_ref')


@pytest.fixture(scope='module')
def program():
    return _load(os.path.join(CONFIGS, NAME + '.program.py'), 'solar2_prog')


@pytest.fixture(scope='module')
def real():
    return json.load(open(os.path.join(CONFIGS, NAME + '.json')))


def _small(real, heads=2, kv=1, experts=8, held=(1, 2, 5, 6, 9, 10, 13, 14)):
    """The configuration's own file at widths a CPU holds: hidden 64, heads
    of 16, a low rank of 8, experts of 32 (top 4 of 16 published), 128 rows
    of the vocabulary, one period of four layers, 40 tokens in chunks of 16
    and sub-blocks of 4."""
    cfg = json.loads(json.dumps(real))
    cfg.update(hidden_size=64, head_dim=16, num_attention_heads=heads,
               num_key_value_heads=kv, moe_intermediate_size=32,
               vocab_size=128, n_routed_experts=experts,
               num_experts_per_tok=4)
    cfg['linear_attn_config'] = dict(cfg['linear_attn_config'], head_dim=16,
                                     num_heads=heads)
    cfg['published'] = dict(cfg['published'], n_routed_experts=16,
                            vocab_size=1024)
    cfg['assumed'] = dict(cfg['assumed'], sequence_length=40, chunk=16,
                          sub_block=4, low_rank=8, experts_held=list(held),
                          expert_tile_rows=8)
    return cfg


@pytest.fixture(scope='module')
def cfg(real):
    return _small(real)


@pytest.fixture(scope='module')
def tokens(cfg):
    return jax.random.randint(jax.random.PRNGKey(0), (2, 41), 0,
                              cfg['vocab_size'])


def _flat(tree):
    return {'/'.join(str(getattr(k, 'key', k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _gaps(got, want):
    """Every leaf's distance from the reference's over the reference's norm
    (or the median leaf's, where a leaf's own is nearly nothing)."""
    got, want = _flat(got), _flat(want)
    norms = {k: float(jnp.linalg.norm(v)) for k, v in want.items()}
    floor = float(np.median(list(norms.values())))
    return {k: float(jnp.linalg.norm(got[k] - v)) / max(norms[k], floor)
            for k, v in want.items()}


@pytest.fixture(scope='module')
def both(cfg, ref, program, tokens):
    """Loss, gradients and metrics of the program (float32, the kernels in
    interpret mode) and loss and gradients of the reference, on the same
    seeded weights."""
    params = ref.init_params(cfg, 7)
    model = program.model_for(cfg, None, interpret=True, dtype=jnp.float32)

    def loss(p):
        out = model.apply({'params': p}, tokens[:, :-1])
        return summed_loss(out['logits'], tokens[:, 1:])[0], out['metrics']

    got = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    want = ref.loss_and_grad(params, {'tokens': tokens}, cfg)
    return params, model, got, want


def test_the_module_reads_the_tree_the_reference_makes(cfg, ref, program,
                                                        tokens, real):
    params = ref.init_params(cfg, 3)
    model = program.model_for(cfg, None, interpret=True)
    made = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          tokens[:, :-1])['params']
    assert {k: v.shape for k, v in _flat(made).items()} == \
        {k: v.shape for k, v in _flat(params).items()}
    assert {'/'.join(k) for k in ref.param_shapes(cfg)} == set(_flat(params))
    assert ref.layer_kinds(real) == [('gqa', 'moe')] + [('kda', 'moe')] * 3
    assert model.kinds() == ['gqa', 'kda', 'kda', 'kda']


def test_loss_and_every_leaf_s_gradient_equal_the_reference_s(both):
    """Float32 against float32, the rule on its exact path: the loss to
    1e-6, every leaf's gradient to 1e-4 of its norm (or the median leaf's;
    read: 1.2e-5, the order of a chunk's sums against a token's in the
    rule, and the attention's blocks against dense scores)."""
    _, _, ((loss, _), grads), (want, want_grads) = both
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    gaps = _gaps(grads, want_grads)
    assert max(gaps.values()) < 1e-4, max(gaps.items(), key=lambda kv: kv[1])


def test_the_step_counts_the_decay_under_the_bounded_floor(both, cfg, ref,
                                                           tokens):
    """One count a Kimi-delta layer: the entries of its ``g`` under -5 in
    the step, what the bounded kernels could not have taken; at the assumed
    init (``A_log`` log-uniform(1, 16), ``dt_bias`` 0) the heads whose
    ``exp(A_log)`` passes about 7 run past it."""
    params, _, ((_, metrics), _), _ = both
    counts = np.asarray(metrics['decay_below_bound'])
    assert counts.dtype == np.int32 and counts.shape == (3,)
    x = ref._block(params['block_0'],
                   params['embed']['embedding'][tokens[:, :-1]], 'gqa', 'moe',
                   cfg, None)
    inner = ref._rms(x, params['block_1']['mixer_norm']['scale'],
                     cfg['rms_norm_eps'])
    g = ref.decay(params['block_1']['mixer'], inner)
    assert int(counts[0]) == int(jnp.sum(g < kimi_delta.GATE_LOWER_BOUND))
    assert 0 < counts.sum() < 3 * g.size


def test_the_counter_records_the_decay_counts_two_steps_late():
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        counter = ExpertLoadCounter()
        for step in range(4):
            counter.add({'expert_load': np.ones(2, np.int32),
                         'layout_fallbacks': np.int32(0),
                         'decay_below_bound': np.asarray([step, 10 * step],
                                                         np.int32)})
    finally:
        trace.set_global_tracer(previous)
    counters = [(r[0], r[3]) for r in tracer.records()
                if r[0].startswith('kda.decay_below_bound')]
    # steps 0 and 1 counted after the third and fourth call, running totals
    assert counters == [('kda.decay_below_bound.0', 0),
                        ('kda.decay_below_bound.1', 0),
                        ('kda.decay_below_bound.0', 1),
                        ('kda.decay_below_bound.1', 10)]


def test_one_update_through_make_train_step_equals_the_reference_s(
        cfg, ref, tokens, both):
    """``optax.adamw`` against the reference's AdamW written out: after one
    step every leaf has moved by ``lr`` times a unit step plus decay; the
    moved trees differ by 1e-2 of the step at most (where a gradient's sign
    is noise, Adam's unit step turns it into a step of its own size). Held
    to it: the entries whose reference gradient is at least a hundred times
    Adam's ``eps`` (1e-8). Below that an entry's step is a fraction of
    ``lr`` set by the gradient's own size, and round-off in a gradient of
    1e-9 (a ``dt_bias`` of a layer whose decay all but erases its state)
    moves it: those entries are held to a step of at most ``lr`` plus
    decay."""
    params, model, _, (_, want_grads) = both
    a = cfg['assumed']
    tx = optax.adamw(a['learning_rate'], b1=a['b1'], b2=a['b2'], eps=a['eps'],
                     weight_decay=a['weight_decay'])
    # the step donates its state: a copy of the weights goes in
    state = TrainState.create(apply_fn=model.apply, tx=tx,
                              params=jax.tree_util.tree_map(jnp.copy, params))
    state, metrics = make_train_step()(state, tokens[:, :-1], tokens[:, 1:])
    want, _ = ref.opt_apply(params, ref.opt_init(params, cfg), want_grads,
                            cfg, 1)
    assert metrics['expert_load'].shape == (8,)
    assert int(jnp.sum(metrics['expert_load'])) > 0
    assert metrics['decay_below_bound'].shape == (3,)
    moved, wanted = _flat(state.params), _flat(want)
    start, grads = _flat(params), _flat(want_grads)
    held = total = 0
    for name, leaf in wanted.items():
        step = float(jnp.max(jnp.abs(leaf - start[name])))
        assert step > 0, name
        sure = np.abs(np.asarray(grads[name])) >= 100 * a['eps']
        got, leaf, first = (np.asarray(x) for x in (moved[name], leaf,
                                                    start[name]))
        np.testing.assert_allclose(got[sure], leaf[sure], rtol=0,
                                   atol=max(1e-2 * step, 1e-9), err_msg=name)
        bound = a['learning_rate'] * (1 + a['weight_decay'] * np.abs(first))
        assert (np.abs(got - first) <= 1.01 * bound).all(), name
        held, total = held + sure.sum(), total + sure.size
    # read: 75 %; rows of the vocabulary no token reads and experts no token
    # picks have no gradient at all
    assert held > total / 2


def _x(d=64, t=40, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((2, t, d)),
                       jnp.float32)


def test_forty_expert_shares_add_up_to_the_uncut_layer(real, ref):
    """40 chips hold 2 of 80 experts each (the router 80 wide, top 4): the
    shares' routed parts summed, with the shared expert every chip computes
    alike counted once, are the reference's layer with every expert held."""
    whole = _small(real, experts=80, held=range(80))
    whole['published'] = dict(whole['published'], n_routed_experts=80)
    params = ref.init_params(whole, 4)['block_1']['moe']
    x = _x()
    want = ref._experts(params, x, whole, None)
    shared = ref._swiglu(x, *(params['shared'][n]['kernel']
                              for n in ('gate', 'up', 'down')), None)
    total = shared
    for chip in range(40):
        held = (2 * chip, 2 * chip + 1)
        layer = RoutedMoE(experts_published=80, held=held, top_k=4,
                          d_ff=32, shared_d_ff=0, impl='ragged_dot',
                          tile_m=8, dtype=jnp.float32)
        mine = {'router': params['router'],
                'experts_gate_up': params['experts_gate_up'][held[0]:
                                                             held[1] + 1],
                'experts_down': params['experts_down'][held[0]:held[1] + 1]}
        y, load = layer.apply({'params': mine}, x)
        total = total + y
        assert int(load['layout_fallbacks']) == 0
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


def _heads_of(tree, cut):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: cut('/'.join(str(getattr(k, 'key', k))
                                        for k in path), leaf), tree)


def test_four_shares_of_a_kimi_delta_mixer_add_up_to_the_uncut_layer(real,
                                                                     ref):
    """Four chips hold 2 of 8 heads each: ``W_fa``, ``W_ga`` and the head
    norm's one scale are what every chip holds whole (their work is done
    alike on each), every other leaf is a head's own, and the partial outputs
    add up through ``W_o``'s sum to the reference's uncut layer (exact path,
    write strengths in (0, 2))."""
    whole = _small(real, heads=8, kv=4)
    params = ref.init_params(whole, 5)['block_1']['mixer']
    x = _x()
    want = ref._kda(params, x, whole, None)
    total = 0.0
    for lo in range(0, 8, 2):
        def cut(name, leaf):
            if name.startswith(('f_a_proj', 'g_a_proj', 'o_norm')):
                return leaf
            axis = 0 if name.startswith(('o_proj', 'A_log', 'dt_bias')) else 1
            return jax.lax.slice_in_dim(leaf, lo, lo + 2, axis=axis)

        share = ling_hybrid.KimiDeltaMixer(
            heads_held=2, key_dim=16, value_dim=16, decay='softplus',
            low_rank=8, gate='channel', beta_scale=2.0, eps=1e-5, chunk=16,
            sub_block=4, impl='chunked', dtype=jnp.float32)
        y, below = share.apply({'params': _heads_of(params, cut)}, x)
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=1e-4 * float(jnp.abs(want).max()))
    assert int(below) >= 0


def test_gated_attention_against_dense_attention_and_its_shares(real, ref):
    """The gated grouped-query layer through the flash kernels (interpreted)
    against the reference's dense causal softmax by blocks with the gate a
    channel; and four chips' shares (2 of 8 query heads, 1 of 4 KV heads)
    add up through ``W_o`` to the uncut layer."""
    whole = _small(real, heads=8, kv=4)
    params = ref.init_params(whole, 6)['block_0']['attn']
    x = _x(t=64)
    want = ref._attention(params, x, whole, None)
    layer = nemotron_h.GroupedQueryAttention(
        heads_held=8, kv_heads_held=4, head_dim=16, attention='flash:interpret',
        gate=True, dtype=jnp.float32)
    got = layer.apply({'params': params}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    total = 0.0
    for chip in range(4):
        def cut(name, leaf):
            if name.startswith(('k_proj', 'v_proj')):
                return leaf[:, chip:chip + 1]
            if name.startswith('o_proj'):
                return leaf[2 * chip:2 * chip + 2]
            return leaf[:, 2 * chip:2 * chip + 2]

        share = nemotron_h.GroupedQueryAttention(
            heads_held=2, kv_heads_held=1, head_dim=16, attention='dense',
            gate=True, dtype=jnp.float32)
        total = total + share.apply({'params': _heads_of(params, cut)}, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    # the gate matters: without it the layer reads otherwise
    plain = nemotron_h.GroupedQueryAttention(
        heads_held=8, kv_heads_held=4, head_dim=16, attention='dense',
        dtype=jnp.float32)
    rest = {k: v for k, v in params.items() if k != 'gate_proj'}
    assert float(jnp.abs(plain.apply({'params': rest}, x) - want).max()) > \
        0.1 * float(jnp.abs(want).max())


def test_parameters_counted_by_hand(real, ref):
    d, v, hd, r = 4096, 24576, 128, 128
    gqa = d * hd * (16 + 2 + 2 + 16) + 16 * hd * d + d
    kda = (3 * d * 16 * hd + 3 * 4 * 16 * hd        # q, k, v, taps
           + 2 * (d * r + r * 16 * hd)              # W_fa W_fb, W_ga W_gb
           + 16 + 16 * hd + d * 16 + hd             # A_log, dt_bias, b, norm
           + 16 * hd * d + d)                       # W_o, the mixer's norm
    moe = d * 320 + 3 * d * 1280 + 8 * 3 * d * 1280 + d
    vocabulary = 2 * v * d + d
    assert (gqa, kda, moe) == (27267072, 35223696, 142872576)
    by_hand = gqa + 3 * kda + 4 * moe + vocabulary
    shapes = ref.param_shapes(real)
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert count == by_hand == real['parameters'] == 905759152
    # at 16 bytes a parameter (f32 weight, gradient, AdamW's two moments),
    # 12 at rest
    assert round(16 * count / 1e9, 2) == 14.49
    assert round(12 * count / 1e9, 2) == 10.87
    # the eight-way fallback: 8 of 64 heads, KV head 0
    eight = dict(real, num_attention_heads=8, num_key_value_heads=1,
                 linear_attn_config=dict(real['linear_attn_config'],
                                         num_heads=8))
    assert sum(int(np.prod(s)) for s in ref.param_shapes(eight).values()) \
        == 840871320
    b = ('block_1', 'mixer')
    assert shapes[b + ('f_a_proj', 'kernel')] == (4096, 128)
    assert shapes[b + ('f_b_proj', 'kernel')] == (128, 16, 128)
    assert shapes[b + ('g_b_proj', 'kernel')] == (128, 16, 128)
    assert shapes[('block_0', 'attn', 'gate_proj', 'kernel')] == (4096, 16, 128)
    assert shapes[('block_0', 'attn', 'k_proj', 'kernel')] == (4096, 2, 128)
    assert shapes[('block_2', 'moe', 'router', 'kernel')] == (4096, 320)
    assert shapes[('block_2', 'moe', 'experts_gate_up')] == (8, 4096, 2560)


def test_the_layer_plan_instant_names_kinds_heads_decay_and_beta(
        cfg, program, tokens, monkeypatch):
    monkeypatch.setattr(ling_hybrid, '_plans_reported', set())
    monkeypatch.setattr(gated_delta, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        model = program.model_for(cfg, None, interpret=True)
        for _ in range(2):                      # twice traced, once reported
            jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens[:, :-1])
    finally:
        trace.set_global_tracer(previous)
    plans = [r[7] for r in tracer.records() if r[0] == 'model.layer_plan']
    assert len(plans) == 1
    plan = plans[0]
    assert plan['layer_kinds'] == ['gqa', 'kda', 'kda', 'kda']
    assert (plan['heads_held'], plan['heads_published'],
            plan['kv_heads_held'], plan['kv_heads_published']) == (2, 64, 1, 8)
    assert (plan['decay'], plan['beta_scale'], plan['low_rank'],
            plan['output_gate']) == ('softplus', 2.0, 8, 'channel')
    assert plan['dense_layers'] == 0 and plan['experts_published'] == 16
    kda = [r[7] for r in tracer.records() if r[0] == 'kernel.kda_plan']
    assert kda and all(p['path'] == 'exact' for p in kda)
    assert isinstance(model, LingHybridLM)


def test_the_program_refuses_what_it_does_not_build(cfg, program):
    with pytest.raises(ValueError, match='positions'):
        program.model_for(dict(cfg, use_rope=True))
    with pytest.raises(ValueError, match='low-rank'):
        program.model_for(dict(cfg, kda_use_full_proj=True))
    with pytest.raises(ValueError, match='every layer'):
        program.model_for(dict(cfg, first_k_dense_replace=1))
    with pytest.raises(ValueError, match='layer_pattern'):
        LingHybridLM(vocab_size=8, d_model=8, d_ff=8, num_layers=2,
                     layer_pattern=('gqa', 'full')).kinds()
