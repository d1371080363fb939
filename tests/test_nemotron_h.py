"""``models.NemotronHLM`` at a small size on seeded weights: against the plain
reference of the Nemotron-3-Super configuration (loss, every leaf's gradient,
one AdamW update through ``make_train_step``), the shares of each kind of
layer, relu² experts in a latent space on both of ``RoutedMoE``'s paths,
grouped-query attention against dense attention, the layer plan, and the
parts the step's instructions fall in."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from petastorm_tpu import trace
from petastorm_tpu.models import NemotronHLM, moe, nemotron_h, scopes
from petastorm_tpu.models.attention import dense_attention
from petastorm_tpu.models.train import (TrainState, make_train_step,
                                        summed_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, 'perfbench', 'configs')
NAME = 'nemotron3-super-ctx8192'


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope='module')
def ref():
    return _load(os.path.join(CONFIGS, NAME + '.reference.py'), 'nemo_ref')


@pytest.fixture(scope='module')
def program():
    return _load(os.path.join(CONFIGS, NAME + '.program.py'), 'nemo_prog')


@pytest.fixture(scope='module')
def cfg():
    """The configuration's own file at widths a CPU holds: hidden 64; 4 of
    16 Mamba-2 heads 8 wide in 2 of 8 groups, states 16 wide, chunks of 16;
    2 of 8 query heads over 1 of 4 KV heads 16 wide; 4 of 16 experts, top 4,
    a latent 24 wide; 128 of 1,024 rows of the vocabulary; one block of each
    kind and two more: MEM*E."""
    cfg = json.load(open(os.path.join(CONFIGS, NAME + '.json')))
    cfg.update(hidden_size=64, mamba_num_heads=4, n_groups=2,
               mamba_head_dim=8, ssm_state_size=16, chunk_size=16,
               num_attention_heads=2, num_key_value_heads=1, head_dim=16,
               moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=48, moe_latent_size=24,
               n_routed_experts=4, num_experts_per_tok=4, vocab_size=128,
               hybrid_override_pattern='MEM*E', num_hidden_layers=5)
    cfg['published'] = dict(cfg['published'], mamba_num_heads=16, n_groups=8,
                            num_attention_heads=8, num_key_value_heads=4,
                            n_routed_experts=16, vocab_size=1024)
    cfg['assumed'] = dict(cfg['assumed'], sequence_length=40,
                          experts_held=[1, 2, 5, 6], expert_tile_rows=8)
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
        attention='dense', ssm='xla', experts='ragged_dot')
    made = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          tokens[:, :-1])['params']
    assert {k: v.shape for k, v in _flat(made).items()} == \
        {k: v.shape for k, v in _flat(params).items()}
    assert {'/'.join(k) for k in ref.param_shapes(cfg)} == set(_flat(params))
    real = json.load(open(os.path.join(CONFIGS, NAME + '.json')))
    assert ref.layer_kinds(real) == [
        {'M': 'mamba', 'E': 'moe', '*': 'attention'}[k] for k in 'MEMEMEM*EME']
    # published layers 0 to 10 of the published pattern
    # MODEL_CATALOG: a JSON-lines catalog of published model configs
    catalog = os.environ.get('MODEL_CATALOG', '')
    if catalog and os.path.exists(catalog):
        row = [json.loads(line) for line in open(catalog)
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line][0]
        assert row['config']['hybrid_override_pattern'][:11] == 'MEMEMEM*EME'


def test_loss_and_every_leaf_s_gradient_equal_the_reference_s(both):
    """Float32 against float32: the loss to 1e-6, every leaf's gradient to
    1e-4 of its norm (the order of a chunk's sums against a token's in the
    rule; ``tests/test_ssd.py`` holds the rule in bfloat16)."""
    _, _, (loss, grads), (want, want_grads) = both
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    gaps = _gaps(grads, want_grads)
    assert max(gaps.values()) < 1e-4, max(gaps.items(), key=lambda kv: kv[1])


def test_one_update_through_make_train_step_equals_the_reference_s(
        cfg, ref, tokens, both):
    """``optax.adamw`` against the reference's AdamW written out: after one
    step every leaf has moved by ``lr`` times a unit step plus decay; the
    moved trees differ by 1e-2 of the step at most (where a gradient's sign
    is noise, Adam's unit step turns it into a step of its own size)."""
    params, model, _, (_, want_grads) = both
    a = cfg['assumed']
    tx = optax.adamw(a['learning_rate'], b1=a['b1'], b2=a['b2'], eps=a['eps'],
                     weight_decay=a['weight_decay'])
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


def _cut(tree, axis_of, lo, hi):
    def cut(path, leaf):
        axis = axis_of('/'.join(str(getattr(k, 'key', k)) for k in path))
        return leaf if axis is None else jax.lax.slice_in_dim(leaf, lo, hi,
                                                              axis=axis)
    return jax.tree_util.tree_map_with_path(cut, tree)


def test_the_expert_shares_add_up_to_the_uncut_latent_layer(cfg, ref):
    """Two chips hold 2 of the 4 experts each: their routed outputs summed,
    the shared expert (which every chip computes alike) counted once, are
    the uncut layer's, latent projections and all (``W_up`` is linear, so
    the shares' latent sums add up through it)."""
    whole = dict(cfg, n_routed_experts=4, assumed=dict(
        cfg['assumed'], experts_held=[0, 1, 2, 3]))
    whole['published'] = dict(cfg['published'], n_routed_experts=4)
    params = ref.init_params(whole, 5)['block_1']['moe']
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 24, 64)),
                    jnp.float32)

    def layer(held):
        return moe.RoutedMoE(
            experts_published=4, held=held, top_k=2, scale=5.0, d_ff=32,
            shared_d_ff=48, activation='relu2', latent=24, impl='ragged_dot',
            tile_m=8, dtype=jnp.float32)

    total = 0.0
    for lo in (0, 2):
        share = _cut(params, lambda name: 0 if name.startswith('experts_')
                     else None, lo, lo + 2)
        y, load = layer((lo, lo + 1)).apply({'params': share}, x)
        total = total + y
        assert int(jnp.sum(load['expert_load'])) > 0
    shared = moe.ReluSquaredMLP(48, dtype=jnp.float32).apply(
        {'params': params['shared']}, x)
    # top 2 here: the reference's route reads num_experts_per_tok
    want = ref._experts(params, x, dict(whole, num_experts_per_tok=2), None)
    np.testing.assert_allclose(np.asarray(total - shared), np.asarray(want),
                               rtol=0, atol=2e-5 * float(jnp.abs(want).max()))


def test_two_shares_of_a_mamba_mixer_add_up_through_w_out(cfg, ref):
    """Two chips hold a group each, with its heads: every leaf is a head's or
    a group's own (the gated norm takes a group's channels), and the partial
    outputs add up through ``W_out``'s sum."""
    whole = dict(cfg, mamba_num_heads=8, n_groups=4)
    params = ref.init_params(whole, 4)['block_0']['mixer']
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 40, 64)),
                    jnp.float32)
    want = ref._mamba(params, x, whole, None)
    inner, width = 8 * 8, 4 * 16

    def axis_of(name):
        if name.startswith(('z_', 'x_', 'b_', 'c_', 'dt_proj')):
            return 1                                # [d, features]
        if name.startswith('conv_') and not name.endswith('bias'):
            return 1                                # [taps, features]
        return 0                                    # the rest lead with it

    total = 0.0
    for part in (0, 1):
        def cut(path, leaf):
            name = '/'.join(str(getattr(k, 'key', k)) for k in path)
            features = leaf.shape[axis_of(name)]
            share = features // 2
            return jax.lax.slice_in_dim(leaf, part * share,
                                        (part + 1) * share, axis=axis_of(name))
        share = jax.tree_util.tree_map_with_path(cut, params)
        assert share['out_proj']['kernel'].shape == (inner // 2, 64)
        assert share['conv_b'].shape == (4, width // 2)
        mixer = nemotron_h.MambaMixer(heads_held=4, groups_held=2, head_dim=8,
                                      state=16, chunk=16, impl='xla',
                                      dtype=jnp.float32)
        total = total + mixer.apply({'params': share}, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_grouped_query_attention_equals_dense_attention_and_its_shares(cfg,
                                                                       ref):
    """Four query heads over two KV heads: the flash kernels (interpreted) on
    the repeated KV heads against dense attention head by head on the KV
    head of each, gradients too; and two chips of one KV head each add up
    through ``W_o``."""
    layer = nemotron_h.GroupedQueryAttention(heads_held=4, kv_heads_held=2,
                                             head_dim=16,
                                             attention='flash:interpret',
                                             dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 40, 64)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)['params']

    def by_hand(p, x):
        q = jnp.einsum('btd,dhk->bthk', x, p['q_proj']['kernel'])
        k = jnp.einsum('btd,dhk->bthk', x, p['k_proj']['kernel'])
        v = jnp.einsum('btd,dhk->bthk', x, p['v_proj']['kernel'])
        heads = [dense_attention(q[:, :, i:i + 1], k[:, :, i // 2:i // 2 + 1],
                                 v[:, :, i // 2:i // 2 + 1], causal=True)
                 for i in range(4)]
        return jnp.einsum('bthk,hkd->btd', jnp.concatenate(heads, axis=2),
                          p['o_proj']['kernel'])

    def loss(fn):
        return lambda p: jnp.sum(jnp.sin(fn(p, x)))

    got, got_g = jax.value_and_grad(loss(
        lambda p, x: layer.apply({'params': p}, x)))(params)
    want, want_g = jax.value_and_grad(loss(by_hand))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for name, g in _flat(got_g).items():
        np.testing.assert_allclose(g, _flat(want_g)[name], rtol=0,
                                   atol=1e-5 * float(jnp.abs(g).max()))
    half = nemotron_h.GroupedQueryAttention(heads_held=2, kv_heads_held=1,
                                            head_dim=16, attention='dense',
                                            dtype=jnp.float32)
    total = 0.0
    for part in (0, 1):
        queries, kv = slice(2 * part, 2 * part + 2), slice(part, part + 1)
        share = {'q_proj': {'kernel': params['q_proj']['kernel'][:, queries]},
                 'k_proj': {'kernel': params['k_proj']['kernel'][:, kv]},
                 'v_proj': {'kernel': params['v_proj']['kernel'][:, kv]},
                 'o_proj': {'kernel': params['o_proj']['kernel'][queries]}}
        total = total + half.apply({'params': share}, x)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(by_hand(params, x)),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize('held_pairs', ['some', 'every'])
def test_relu2_experts_in_a_latent_space_the_rows_against_every_token(
        held_pairs):
    """``RoutedMoE(activation='relu2', latent=...)`` through its rows (the
    held pairs within the layout's capacity) and through every token (a
    routing that sends every pair to a held expert passes it): the same
    layer, values and every gradient."""
    layer = moe.RoutedMoE(experts_published=32, held=(0, 1, 2, 3), top_k=2,
                          scale=5.0, d_ff=32, shared_d_ff=48,
                          activation='relu2', latent=24, impl='ragged_dot',
                          tile_m=8, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 32, 64)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(2), x)['params']
    if held_pairs == 'every':
        # every token's scores near 1 for the held experts and near 0 for
        # the others: 128 held pairs where the rows hold 64
        x = x + 3.0
        params['router']['kernel'] = jnp.where(
            jnp.arange(32) < 4, 0.1, -0.1) * jnp.ones((64, 32))
    assert params['experts_up'].shape == (4, 24, 32)
    assert params['experts_down'].shape == (4, 32, 24)
    assert params['latent_down']['kernel'].shape == (64, 24)
    assert set(params['shared']) == {'up', 'down'}
    y, load = layer.apply({'params': params}, x)
    assert int(load['layout_fallbacks']) == (held_pairs == 'every')

    def dense(p, x):
        """Every held expert on every token, by hand."""
        scores = jax.nn.sigmoid(jnp.dot(x, p['router']['kernel'],
                                        precision='highest'))
        experts, weights = moe.top_k_routing(scores, 2, 5.0)
        u = x @ p['latent_down']['kernel']
        out = 0.0
        for slot in range(4):
            mine = jnp.sum(jnp.where(experts == slot, weights, 0.0), axis=-1)
            h = jnp.square(jax.nn.relu(u @ p['experts_up'][slot]))
            out = out + mine[..., None] * (h @ p['experts_down'][slot])
        shared = jnp.square(jax.nn.relu(x @ p['shared']['up']['kernel'])) \
            @ p['shared']['down']['kernel']
        return out @ p['latent_up']['kernel'] + shared

    np.testing.assert_allclose(np.asarray(y), np.asarray(dense(params, x)),
                               rtol=0, atol=1e-4 * float(jnp.abs(y).max()))

    def loss(fn):
        return lambda p: jnp.sum(jnp.sin(fn(p)))

    got = jax.grad(loss(lambda p: layer.apply({'params': p}, x)[0]))(params)
    want = jax.grad(loss(lambda p: dense(p, x)))(params)
    for name, g in _flat(want).items():
        np.testing.assert_allclose(_flat(got)[name], g, rtol=0, err_msg=name,
                                   atol=1e-4 * float(jnp.abs(g).max()))


def test_an_unknown_expert_activation_is_refused():
    with pytest.raises(ValueError, match='unknown expert activation'):
        moe.RoutedMoE(experts_published=4, held=(0,), activation='gelu',
                      impl='ragged_dot').init(jax.random.PRNGKey(0),
                                              jnp.zeros((1, 8, 16)))


def test_the_layer_plan_instant_says_what_was_built(cfg, program, tokens,
                                                    monkeypatch):
    monkeypatch.setattr(nemotron_h, '_plans_reported', set())
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
        'pattern': 'MEM*E',
        'layer_kinds': ['mamba', 'moe', 'mamba', 'attention', 'moe'],
        'mamba_heads_held': 4, 'mamba_heads_published': 16,
        'mamba_groups_held': 2, 'mamba_groups_published': 8,
        'heads_held': 2, 'heads_published': 8, 'kv_heads_held': 1,
        'kv_heads_published': 4, 'experts_held': [1, 2, 5, 6],
        'experts_published': 16, 'top_k': 4, 'latent': 24,
        'expert_activation': 'relu2', 'vocab_rows_held': 128,
        'next_token_depth': 0, 'recompute': True,
        'attention': 'flash:interpret', 'ssm': 'pallas:interpret',
        'experts': 'pallas:interpret'}
    assert isinstance(model, NemotronHLM)


def test_every_instruction_under_ssd_is_part_of_the_mixer(cfg, program,
                                                          tokens):
    """The step compiled (the kernels interpreted, so their bodies are
    instructions of the step): what runs under a Mamba-2 layer's ``ssd``
    scope counts as ``mixer``, in every pass."""
    assert scopes.part_of('block_0/mixer/ssd') == 'mixer'
    assert scopes.part_of('block_0/mixer/mixer/ssd/dot_general') == 'mixer'
    assert scopes.part_of(['ssd']) == 'mixer'
    model = program.model_for(cfg, None, interpret=True)
    a = cfg['assumed']
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            model.init, jax.random.PRNGKey(0), tokens[:, :-1])['params'])
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.adamw(a['learning_rate']))
    text = make_train_step().lower(state, tokens[:, :-1],
                                   tokens[:, 1:]).compile().as_text()
    parsed = scopes.parse_hlo_scopes(text)['instructions']
    under = [i for i in parsed.values() if '/ssd' in '/' + i['path']]
    assert under
    assert {i['part'] for i in under} == {'mixer'}
    assert {'forward', 'backward', 'recompute'} <= {i['pass'] for i in under}


def test_the_program_refuses_what_it_does_not_build(cfg, program):
    with pytest.raises(ValueError, match='next-token'):
        program.model_for(dict(cfg, num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match='groups'):
        program.model_for(dict(cfg, n_group=8))
    with pytest.raises(ValueError, match='letter'):
        program.model_for(dict(cfg, hybrid_override_pattern='MEM-E'))
    with pytest.raises(ValueError, match='unknown block kind'):
        nemotron_h.NemotronHBlock('-', {}, {}, {}).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))
