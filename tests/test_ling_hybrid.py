"""``models.LingHybridLM`` at a small size on seeded weights: against the plain
reference of the Ling-3.0-flash configuration (loss, every leaf's gradient,
one AdamW update through ``make_train_step``), the shares of the heads of both
kinds of mixer, the layer plan, and a vocabulary slice."""

import hashlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from petastorm_tpu import trace
from petastorm_tpu.models import LingHybridLM, latent_moe, ling_hybrid
from petastorm_tpu.models.train import (TrainState, make_train_step,
                                        summed_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, 'perfbench', 'configs')
NAME = 'ling3-flash-ctx8192'


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope='module')
def ref():
    return _load(os.path.join(CONFIGS, NAME + '.reference.py'), 'ling3_ref')


@pytest.fixture(scope='module')
def program():
    return _load(os.path.join(CONFIGS, NAME + '.program.py'), 'ling3_prog')


@pytest.fixture(scope='module')
def cfg():
    """The configuration's own file at widths a CPU holds: hidden 64, two
    heads of 16, 4 of 16 experts in 4 groups (the best 2 groups, top 4), 128
    of 1,024 rows of the vocabulary; a period of three layers, one of each
    kind: dense KDA, expert KDA, expert latent attention."""
    cfg = json.load(open(os.path.join(CONFIGS, NAME + '.json')))
    cfg.update(hidden_size=64, intermediate_size=96, head_dim=16,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               qk_head_dim=24, v_head_dim=16, moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=32, vocab_size=128,
               num_attention_heads=2, num_key_value_heads=2, num_experts=4,
               n_group=4, topk_group=2, num_experts_per_tok=4,
               layer_group_size=3, num_hidden_layers=3)
    cfg['published'] = dict(cfg['published'], num_experts=16, vocab_size=1024)
    cfg['assumed'] = dict(cfg['assumed'], sequence_length=40, chunk=16,
                          sub_block=4, experts_held=[1, 2, 5, 6],
                          expert_tile_rows=8)
    return cfg


@pytest.fixture(scope='module')
def tokens(cfg):
    return jax.random.randint(jax.random.PRNGKey(0), (2, 41), 0,
                              cfg['vocab_size'])


@pytest.fixture(scope='module')
def both(cfg, ref, program, tokens):
    """Loss and gradients of the program (float32, the kernels in interpret
    mode) and of the reference, on the same seeded weights."""
    params = ref.init_params(cfg, 7)
    model = program.model_for(cfg, None, interpret=True, dtype=jnp.float32)

    def loss(p):
        out = model.apply({'params': p}, tokens[:, :-1])
        return summed_loss(out['logits'], tokens[:, 1:])[0]

    got = jax.jit(jax.value_and_grad(loss))(params)
    want = ref.loss_and_grad(params, {'tokens': tokens}, cfg)
    return params, model, got, want


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


def test_the_module_reads_the_tree_the_reference_makes(cfg, ref, program,
                                                        tokens):
    params = ref.init_params(cfg, 3)
    model = program.model_for(cfg, None, interpret=True).clone(
        attention='dense', linear_attention='chunked', experts='ragged_dot')
    made = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          tokens[:, :-1])['params']
    assert {k: v.shape for k, v in _flat(made).items()} == \
        {k: v.shape for k, v in _flat(params).items()}
    assert {'/'.join(k) for k in ref.param_shapes(cfg)} == set(_flat(params))
    assert ref.layer_kinds(cfg) == [('kda', 'dense'), ('kda', 'moe'),
                                    ('latent', 'moe')]
    real = json.load(open(os.path.join(CONFIGS, NAME + '.json')))
    assert ref.layer_kinds(real) == [('kda', 'dense')] + [('kda', 'moe')] * 4 \
        + [('latent', 'moe')]


def test_loss_and_every_leaf_s_gradient_equal_the_reference_s(both):
    """Float32 against float32: the loss to 1e-6, every leaf's gradient to
    1e-4 of its norm (read: 2.3e-6, the order of a chunk's sums against a
    token's in the rule; ``tests/test_kimi_delta.py`` holds the rule in
    bfloat16)."""
    _, _, (loss, grads), (want, want_grads) = both
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    gaps = _gaps(grads, want_grads)
    assert max(gaps.values()) < 1e-4, max(gaps.items(), key=lambda kv: kv[1])


def test_one_update_through_make_train_step_equals_the_reference_s(
        cfg, ref, program, tokens, both):
    """``optax.adamw`` against the reference's AdamW written out: after one
    step every leaf has moved by ``lr`` times a unit step plus decay; the
    moved trees differ by 1e-2 of the step at most (where a gradient's sign
    is noise, Adam's unit step turns it into a step of its own size)."""
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
    assert metrics['expert_load'].shape == (4,)
    assert int(jnp.sum(metrics['expert_load'])) > 0
    moved, wanted = _flat(state.params), _flat(want)
    start = _flat(params)
    for name, leaf in wanted.items():
        step = float(jnp.max(jnp.abs(leaf - start[name])))
        assert step > 0, name
        np.testing.assert_allclose(moved[name], leaf, rtol=0,
                                   atol=max(1e-2 * step, 1e-9), err_msg=name)


def _heads_of(params, axis_of, lo, hi):
    def cut(path, leaf):
        name = '/'.join(str(getattr(k, 'key', k)) for k in path)
        axis = axis_of(name, leaf)
        return leaf if axis is None else jax.lax.slice_in_dim(leaf, lo, hi,
                                                              axis=axis)
    return jax.tree_util.tree_map_with_path(cut, params)


def test_two_shares_of_a_kimi_delta_mixer_add_up_to_the_uncut_mixer():
    """Two chips hold two heads each: every leaf but the head norm's one
    scale is a head's own, and the partial outputs add up through ``W_o``'s
    sum."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    args = dict(key_dim=8, value_dim=16, chunk=16, sub_block=4,
                impl='chunked', dtype=jnp.float32)
    whole = ling_hybrid.KimiDeltaMixer(heads_held=4, **args)
    params = whole.init(jax.random.PRNGKey(2), x)['params']
    params['A_log'] = jnp.log(jnp.linspace(1.0, 8.0, 4))
    params['dt_bias'] = jax.random.uniform(jax.random.PRNGKey(3), (4, 8),
                                           minval=-1.0, maxval=1.0)

    def axis_of(name, leaf):
        if name.startswith('o_norm'):
            return None             # one scale for every head's 16 values
        if name.startswith(('o_proj', 'A_log', 'dt_bias')):
            return 0                # [H, dv, D], [H], [H, dk]
        return 1                    # [D, H, .], [D, H], conv [K, H, .]

    share = ling_hybrid.KimiDeltaMixer(heads_held=2, **args)
    parts = [share.apply({'params': _heads_of(params, axis_of, lo, lo + 2)}, x)
             for lo in (0, 2)]
    np.testing.assert_allclose(parts[0] + parts[1],
                               whole.apply({'params': params}, x), atol=5e-6)
    assert float(jnp.max(jnp.abs(parts[1]))) > 1e-3    # the second half counts


def test_two_shares_of_the_latent_mixer_add_up_through_w_o(cfg, ref):
    """With no query latent a chip's ``W_q``, ``W_UKV`` and ``W_O`` are its
    heads'; ``W_DKV`` and the latent's norm are what every chip computes
    alike."""
    whole = dict(cfg, num_attention_heads=4, num_key_value_heads=4)
    params = ref.init_params(whole, 5)['block_2']['attn']
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 64, 64)),
                    jnp.float32)
    want = ref._attention(params, x, whole, None)
    total = 0.0
    for lo in (0, 2):
        share = dict(
            params, q_proj={'kernel': params['q_proj']['kernel'][:, lo:lo + 2]},
            kv_up={'kernel': params['kv_up']['kernel'][:, lo:lo + 2]},
            out={'kernel': params['out']['kernel'][lo:lo + 2]})
        layer = latent_moe.LatentAttention(
            heads_held=2, q_rank=None, kv_rank=32, nope=16, rope=8, v_dim=16,
            frequencies=latent_moe.yarn_frequencies(8, cfg['rope_theta']),
            softmax_scale=latent_moe.yarn_softmax_scale(24),
            attention='dense', dtype=jnp.float32)
        assert set(share) == {'q_proj', 'kv_down', 'kv_norm', 'kv_up', 'out'}
        total = total + layer.apply({'params': share}, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    # plain rotary positions: the table at factor 1 is theta^(-2i/rope)
    np.testing.assert_allclose(
        latent_moe.yarn_frequencies(8, cfg['rope_theta']), ref.inv_freq(cfg))


def test_a_vocabulary_slice_never_sees_an_id_outside_it(cfg, program):
    model = program.model_for(cfg, None, interpret=True)
    assert model.vocab_size == cfg['vocab_size'] == 128
    assert cfg['published']['vocab_size'] == 8 * cfg['vocab_size']


def test_the_layer_plan_instant_says_what_was_built(cfg, program, tokens,
                                                    monkeypatch):
    monkeypatch.setattr(ling_hybrid, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        model = program.model_for(cfg, None, interpret=True)
        for _ in range(2):                      # twice traced, once reported
            jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens[:, :-1])
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'model.layer_plan']
    assert len(plans) == 1 and plans[0][1] == 'model'
    assert plans[0][7] == {
        'layer_kinds': ['kda', 'kda', 'latent'], 'dense_layers': 1,
        'heads_held': 2, 'heads_published': 2,
        'experts_held': [1, 2, 5, 6], 'experts_published': 16,
        'n_group': 4, 'topk_group': 2, 'top_k': 4, 'vocab_rows_held': 128,
        'next_token_depth': 0, 'recompute': True,
        'attention': 'flash:interpret',
        'linear_attention': 'pallas:interpret',
        'experts': 'pallas:interpret'}
    assert isinstance(model, LingHybridLM)


def test_the_program_refuses_what_it_does_not_build(cfg, program):
    with pytest.raises(ValueError, match='next-token'):
        program.model_for(dict(cfg, num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match='query latent'):
        program.model_for(dict(cfg, q_lora_rank=768))
    clamped = dict(cfg, expert_swiglu_limit_list=[0, 0, 0, 4] + [0] * 38)
    with pytest.raises(ValueError, match='clamps'):
        program.model_for(clamped)
    with pytest.raises(ValueError, match='unknown layer kind'):
        ling_hybrid.LingHybridBlock('full', False, {}, {}, {'held': (0,)},
                                    8).init(jax.random.PRNGKey(0),
                                            jnp.zeros((1, 4, 8)))


#: sha256 of the printed jaxpr of the small configuration's loss and
#: gradient (one row of 40 tokens, bfloat16, the weights of seed 7; object
#: addresses left out) as the commit before the rule's exact path traced it:
#: with the kernels interpreted, and with the rule ``chunked``, attention
#: dense and the experts by ``ragged_dot``.
BEFORE_THE_EXACT_PATH = {
    'pallas:interpret':
        '7684aff1cebd53545879276fdc45ee1cb21316ca3272043a0271f1759ee0e2cf',
    'chunked':
        '22c8f2e40ee97e1f0d531006b44e5a72ea1d394ecd0c17ecc3c3ae635c2a1a61'}


@pytest.mark.parametrize('impl', sorted(BEFORE_THE_EXACT_PATH))
def test_the_bounded_path_traces_the_program_it_traced_before(cfg, ref,
                                                              program, impl):
    """The bounded decay is chosen statically: the Ling model traces, to the
    equation, the program it traced before the rule gained its exact path
    (and the model its options), so it computes the same bits on any
    backend, the kernels and their backward pass included."""
    model = program.model_for(cfg, None, interpret=True)
    if impl == 'chunked':
        model = model.clone(attention='dense', linear_attention='chunked',
                            experts='ragged_dot')
    params = ref.init_params(cfg, 7)
    tokens = jnp.zeros((1, 41), jnp.int32)

    def loss(p):
        out = model.apply({'params': p}, tokens[:, :-1])
        return summed_loss(out['logits'], tokens[:, 1:])[0]

    text = re.sub(r' at 0x[0-9a-f]+', '',
                  str(jax.make_jaxpr(jax.value_and_grad(loss))(params)))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        BEFORE_THE_EXACT_PATH[impl]
