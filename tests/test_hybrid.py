"""``models.HybridLM`` at a small size on seeded weights: against the plain
reference of the Olmo-Hybrid configuration (loss and gradients), the shares
of a layer against the uncut layer, the vocabulary's slice, the layer plan,
and one train step through ``make_train_step``."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from petastorm_tpu import trace
from petastorm_tpu.models import HybridLM, hybrid
from petastorm_tpu.models.train import TrainState, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, 'perfbench', 'configs')
NAME = 'olmo-hybrid-7b-ctx8192'


def _load(path):
    spec = importlib.util.spec_from_file_location('hybrid_reference', path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope='module')
def ref():
    return _load(os.path.join(CONFIGS, NAME + '.reference.py'))


@pytest.fixture(scope='module')
def cfg():
    """The configuration's own file at widths a CPU holds: two of four heads
    of each kind held, 128 of 1,024 rows of the vocabulary."""
    cfg = json.load(open(os.path.join(CONFIGS, NAME + '.json')))
    cfg.update(vocab_size=128, hidden_size=32, intermediate_size=64,
               num_attention_heads=2, num_key_value_heads=2,
               linear_num_key_heads=2, linear_num_value_heads=2,
               linear_key_head_dim=8, linear_value_head_dim=16, head_dim=8)
    cfg['assumed'] = dict(cfg['assumed'], sequence_length=80, chunk=16)
    return cfg


def _model(cfg, linear_attention, attention, dtype=jnp.float32, remat=True):
    return HybridLM(
        vocab_size=cfg['vocab_size'], d_model=cfg['hidden_size'],
        d_ff=cfg['intermediate_size'], layer_types=tuple(cfg['layer_types']),
        heads_held=cfg['num_attention_heads'],
        heads_published=cfg['hidden_size'] // cfg['head_dim'],
        key_dim=cfg['linear_key_head_dim'],
        value_dim=cfg['linear_value_head_dim'],
        conv_kernel=cfg['linear_conv_kernel_dim'],
        chunk=cfg['assumed']['chunk'], attention=attention,
        linear_attention=linear_attention, remat=remat, dtype=dtype)


def _tokens(cfg, rows=2, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed),
                              (rows, cfg['assumed']['sequence_length'] + 1),
                              0, cfg['vocab_size'])


def _loss_and_grad(model, params, tokens):
    def loss(p):
        z = model.apply({'params': p}, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            z, tokens[:, 1:]).mean()
    return jax.jit(jax.value_and_grad(loss))(params)


def test_the_module_reads_the_tree_the_reference_makes(cfg, ref):
    params = ref.init_params(cfg, 3)
    made = _model(cfg, 'chunked', 'dense').init(
        jax.random.PRNGKey(0), _tokens(cfg)[:, :-1])['params']
    shape = lambda tree: jax.tree_util.tree_map(lambda a: a.shape, tree)  # noqa: E731
    assert shape(made) == shape(params)
    count = sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values())
    assert count == sum(a.size for a in jax.tree_util.tree_leaves(params))


# the rule chunked in jax.numpy, with and without the layers recomputed, and
# the Pallas kernels in the interpreter with flash attention's beside them
# (the reference follows the rule token by token)
@pytest.mark.parametrize('linear_attention,attention,remat', [
    ('chunked', 'dense', False), ('chunked', 'dense', True),
    ('pallas:interpret', 'flash:interpret', True)])
def test_loss_and_gradients_are_the_reference_s(cfg, ref, linear_attention,
                                                attention, remat):
    params, tokens = ref.init_params(cfg, 3), _tokens(cfg)
    want, want_grads = ref.loss_and_grad(params, {'tokens': tokens}, cfg)
    got, grads = _loss_and_grad(
        _model(cfg, linear_attention, attention, remat=remat), params, tokens)
    # float32 on both sides, the reference at Precision.HIGHEST and a CPU's
    # float32 products exact: what differs is the order of summation
    assert abs(float(got) - float(want)) < 2e-6 * float(want)
    gaps = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
        grads, want_grads)
    assert max(jax.tree_util.tree_leaves(gaps)) < 5e-5, gaps


def test_bfloat16_stays_near_the_reference(cfg, ref):
    """The benchmark's own precision: bfloat16 products keep eight bits, so
    a leaf's gradient norm lies within a few percent (two-element leaves, a
    head's decay, are sums that nearly cancel: measured against the median
    leaf); a wrong term would move a norm by its whole size."""
    params, tokens = ref.init_params(cfg, 5), _tokens(cfg, seed=1)
    want, want_grads = ref.loss_and_grad(params, {'tokens': tokens}, cfg)
    got, grads = _loss_and_grad(
        _model(cfg, 'pallas:interpret', 'flash:interpret', jnp.bfloat16),
        params, tokens)
    assert abs(float(got) - float(want)) < 2e-3 * float(want)
    # as ``perfbench/check.py`` measures a leaf: the gap of norms over the
    # reference's norm of that leaf or of the median leaf, whichever is larger
    norms = [(float(jnp.linalg.norm(a)), float(jnp.linalg.norm(b)))
             for a, b in zip(jax.tree_util.tree_leaves(grads),
                             jax.tree_util.tree_leaves(want_grads))]
    floor = float(np.median([b for _, b in norms]))
    assert max(abs(a - b) / max(b, floor) for a, b in norms) < 0.05, norms


def test_half_a_row_is_what_rows_used_nought_takes(cfg, ref):
    """``calibrate.py`` leaves half of a batch out; where a step is one row,
    the reference takes the first half of its positions."""
    params, tokens = ref.init_params(cfg, 3), _tokens(cfg, rows=1)
    whole, _ = ref.loss_and_grad(params, {'tokens': tokens}, cfg)
    half, half_grads = ref.loss_and_grad(params, {'tokens': tokens}, cfg,
                                         rows_used=0)
    t = cfg['assumed']['sequence_length']
    first, _ = ref.loss_and_grad(
        params, {'tokens': tokens[:, :t // 2 + 1]}, cfg)
    assert float(half) == pytest.approx(float(first), rel=1e-5)
    assert float(half) != pytest.approx(float(whole), rel=1e-4)
    assert float(jnp.linalg.norm(half_grads['head']['kernel'])) > 0


# -- the share ---------------------------------------------------------------------

def _slice_heads(tree, axis_of, lo, hi):
    """The heads ``lo .. hi`` of every leaf that has a heads axis."""
    def cut(path, leaf):
        name = '/'.join(str(p.key) for p in path)
        axis = axis_of(name, leaf)
        if axis is None:
            return leaf
        return jax.lax.slice_in_dim(leaf, lo, hi, axis=axis)
    return jax.tree_util.tree_map_with_path(cut, tree)


def test_two_shares_of_a_linear_layer_add_up_to_the_uncut_layer():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    whole = hybrid.GatedDeltaMixer(heads_held=4, key_dim=8, value_dim=16,
                                   chunk=16, impl='chunked',
                                   dtype=jnp.float32)
    params = whole.init(jax.random.PRNGKey(2), x)['params']
    params['A_log'] = jnp.log(jnp.linspace(1.0, 8.0, 4))
    params['dt_bias'] = jnp.linspace(-3.0, 0.0, 4)

    def axis_of(name, leaf):
        if name.startswith('o_norm'):
            return None             # one scale for every head's 16 values
        if name.startswith('o_proj'):
            return 0                # [H, dv, D]
        return leaf.ndim - 1 if leaf.ndim <= 2 and 'conv' not in name else 1

    share = hybrid.GatedDeltaMixer(heads_held=2, key_dim=8, value_dim=16,
                                   chunk=16, impl='chunked',
                                   dtype=jnp.float32)
    parts = [share.apply({'params': _slice_heads(params, axis_of, lo, lo + 2)},
                         x) for lo in (0, 2)]
    np.testing.assert_allclose(parts[0] + parts[1],
                               whole.apply({'params': params}, x), atol=2e-6)
    assert float(jnp.max(jnp.abs(parts[1]))) > 1e-3    # the second half counts


def test_two_shares_of_a_full_layer_add_up_with_the_norm_s_statistic_whole():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    whole = hybrid.FullAttentionMixer(heads_held=4, attention='dense',
                                      dtype=jnp.float32)
    params = whole.init(jax.random.PRNGKey(2), x)['params']
    for name in ('q_norm', 'k_norm'):       # scales that differ by column
        params[name]['scale'] = jnp.linspace(0.5, 1.5, 32)
    # The statistic every chip would hold after exchanging its partial sums:
    # the mean square over all 32 columns of the projection.
    stats = tuple(jnp.mean(jnp.square(jnp.einsum(
        'btd,dhk->bthk', x, params[name]['kernel']).reshape(2, 24, 32)),
        axis=-1, keepdims=True) for name in ('query', 'key'))

    def axis_of(name, leaf):
        if 'norm' in name:
            return None
        return 0 if name.startswith('out') else 1

    def cut(lo):
        p = _slice_heads(params, axis_of, lo, lo + 2)
        for name in ('q_norm', 'k_norm'):
            p[name] = {'scale': params[name]['scale'][8 * lo:8 * (lo + 2)]}
        return p

    share = hybrid.FullAttentionMixer(heads_held=2, heads_published=4,
                                      attention='dense', dtype=jnp.float32)
    parts = [share.apply({'params': cut(lo)}, x, stats) for lo in (0, 2)]
    want = whole.apply({'params': params}, x)
    np.testing.assert_allclose(parts[0] + parts[1], want, atol=2e-6)
    # and the one-chip program's way, the statistic over its own columns, is
    # another number: the configuration lists it under its departures
    own = [share.apply({'params': cut(lo)}, x) for lo in (0, 2)]
    assert float(jnp.max(jnp.abs(own[0] + own[1] - want))) > 1e-4


def test_a_vocabulary_slice_never_sees_an_id_outside_it(cfg):
    """The traffic draws from the rows held (``vocab_size`` in the
    configuration's file is the slice), and logits and loss are over them."""
    real = json.load(open(os.path.join(CONFIGS, NAME + '.json')))
    store = _load(os.path.join(ROOT, 'perfbench', 'stores', 'token_rows.py'))
    small = dict(real, assumed=dict(real['assumed'], sequence_length=512))
    for group in range(4):
        ids = store._group(small, 3000000101, group, 16)
        assert ids.min() >= 0 and ids.max() < real['vocab_size'] == 12544
    assert real['published']['vocab_size'] == 100352 == 8 * real['vocab_size']
    logits = jax.eval_shape(
        lambda p, t: _model(cfg, 'chunked', 'dense').apply({'params': p}, t),
        jax.eval_shape(lambda: _model(cfg, 'chunked', 'dense').init(
            jax.random.PRNGKey(0), _tokens(cfg)[:, :-1])['params']),
        _tokens(cfg)[:, :-1])
    assert logits.shape[-1] == cfg['vocab_size'] and logits.dtype == jnp.float32


def test_layer_plan_instant_once_a_process(cfg, monkeypatch):
    monkeypatch.setattr(hybrid, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        model = _model(cfg, 'chunked', 'dense')
        tokens = _tokens(cfg)[:, :-1]
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        jax.eval_shape(model.apply, params, tokens)     # traced twice: once
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'model.layer_plan']
    assert len(plans) == 1 and plans[0][1] == 'model' and plans[0][3] is None
    assert plans[0][7] == {
        'layer_types': ['linear_attention'] * 3 + ['full_attention'],
        'heads_held': 2, 'heads_published': 4, 'vocab_rows_held': 128,
        'recompute': True,
        'attention': 'dense', 'linear_attention': 'chunked'}


def test_unknown_layer_type_is_refused(cfg):
    model = HybridLM(vocab_size=16, d_model=32, d_ff=64,
                     layer_types=('sliding_attention',), heads_held=2)
    with pytest.raises(ValueError, match='unknown layer type'):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_one_train_step_on_a_mesh_moves_every_leaf(cfg, ref):
    """Through the unchanged ``make_train_step`` on a two-device mesh, the
    kernels under ``shard_map`` in the interpreter: the loss is the
    reference's and no parameter stays where it was."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(jax.devices()[:2]), ('data',))
    params, tokens = ref.init_params(cfg, 7), _tokens(cfg, rows=2, seed=2)
    model = _model(cfg, 'pallas:interpret', 'flash:interpret')
    model = model.clone(mesh=mesh)
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.adamw(1e-3, weight_decay=0.1))
    state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
    before = jax.tree_util.tree_map(np.asarray, state.params)
    state, metrics = make_train_step(mesh=mesh)(state, tokens[:, :-1],
                                                tokens[:, 1:])
    want, _ = ref.loss_and_grad(params, {'tokens': tokens}, cfg)
    assert float(metrics['loss']) == pytest.approx(float(want), rel=1e-5)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - b))), state.params,
        before)
    assert min(jax.tree_util.tree_leaves(moved)) > 0, moved
