"""``models.LatentMoELM`` at a small size on seeded weights: against the
plain reference of the Xing4.0 configuration (both losses, every leaf's
gradient, one AdamW update through ``make_train_step``), the stream maps and
Sinkhorn, the shares of the heads through ``W_O``, the rotary frequencies,
the layer plan, and the flash kernels with keys 192 and values 128 wide."""

import importlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from petastorm_tpu import trace
from petastorm_tpu.models import latent_moe
from petastorm_tpu.models.attention import dense_attention
from petastorm_tpu.models.train import (TrainState, make_train_step,
                                        summed_loss)
from petastorm_tpu.ops.flash_attention import flash_attention

fa = importlib.import_module('petastorm_tpu.ops.flash_attention')

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, 'perfbench', 'configs')
NAME = 'xing4-29b-a4b-ctx4096'


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope='module')
def ref():
    return _load(os.path.join(CONFIGS, NAME + '.reference.py'), 'xing4_ref')


@pytest.fixture(scope='module')
def program():
    return _load(os.path.join(CONFIGS, NAME + '.program.py'), 'xing4_prog')


@pytest.fixture(scope='module')
def cfg():
    """The configuration's own file at widths a CPU holds: 2 of 4 heads, 4
    of 8 experts, 128 of 1,024 rows of the vocabulary; one dense layer and
    one expert layer, the next-token module, four streams."""
    cfg = json.load(open(os.path.join(CONFIGS, NAME + '.json')))
    cfg.update(hidden_size=64, intermediate_size=96, kv_lora_rank=32,
               q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, moe_intermediate_size=32, num_hidden_layers=2,
               vocab_size=128, num_attention_heads=2, num_key_value_heads=2,
               n_routed_experts=4)
    cfg['published'] = dict(cfg['published'], n_routed_experts=8,
                            num_attention_heads=4, vocab_size=1024)
    cfg['assumed'] = dict(cfg['assumed'], sequence_length=48,
                          experts_held=[1, 2, 5, 6], expert_tile_rows=8)
    return cfg


@pytest.fixture(scope='module')
def tokens(cfg):
    return jax.random.randint(jax.random.PRNGKey(0), (2, 49), 0,
                              cfg['vocab_size'])


@pytest.fixture(scope='module')
def both(cfg, ref, program, tokens):
    """Loss and gradients of the program (float32, the kernels in interpret
    mode) and of the reference, on the same seeded weights."""
    params = ref.init_params(cfg, 7)
    model = program.model_for(cfg, None, interpret=True, dtype=jnp.float32)

    def loss(p):
        out = model.apply({'params': p}, tokens)
        return summed_loss(out['logits'], program.targets_for(tokens, cfg))[0]

    got = jax.jit(jax.value_and_grad(loss))(params)
    want = ref.loss_and_grad(params, {'tokens': tokens}, cfg)
    return params, model, got, want


def _flat(tree):
    return {'/'.join(str(getattr(k, 'key', k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_the_module_reads_the_tree_the_reference_makes(cfg, ref, program,
                                                        tokens):
    params = ref.init_params(cfg, 3)
    made = jax.eval_shape(program.model_for(cfg, None, interpret=True).init,
                          jax.random.PRNGKey(0), tokens)['params']
    assert {k: v.shape for k, v in _flat(made).items()} == \
        {k: v.shape for k, v in _flat(params).items()}
    shapes = ref.param_shapes(cfg)
    assert {'/'.join(k) for k in shapes} == set(_flat(params))


def test_the_two_losses_equal_the_reference_s(both):
    """Next token plus 0.3 times the second next, the row's last position
    left out of the second: float32 against float32, 1e-6 (bf16 products
    move the loss by 1e-4, test below)."""
    _, _, (loss, _), (want, _) = both
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)


def test_every_leaf_s_gradient_equals_the_reference_s(both):
    """The distance of every leaf's gradient from the reference's over the
    reference's norm (or the median leaf's, where a leaf's gradient is
    nothing: the first block's ``H_pre`` weighs four equal streams ahead of
    an rmsnorm): 1e-4 with both sides float32 (read: 7e-6)."""
    _, _, (_, grads), (_, want) = both
    grads, want = _flat(grads), _flat(want)
    norms = {k: float(jnp.linalg.norm(v)) for k, v in want.items()}
    floor = float(np.median(list(norms.values())))
    for name, leaf in want.items():
        gap = float(jnp.linalg.norm(grads[name] - leaf)) / max(norms[name], floor)
        assert gap < 1e-4, (name, gap)


def test_bf16_in_the_program_s_place_fails_the_same_tolerances(
        cfg, program, tokens, both):
    """The tolerances above are tight enough to tell a precision: the same
    program in bfloat16 misses some leaf's gradient by 3e-3, thirty times the
    1e-4 allowed (the loss, near ln 128 on random weights, by 2.5e-6)."""
    params, _, _, (want_loss, want) = both
    model = program.model_for(cfg, None, interpret=True, dtype=jnp.bfloat16)

    def loss(p):
        out = model.apply({'params': p}, tokens)
        return summed_loss(out['logits'], program.targets_for(tokens, cfg))[0]

    got_loss, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert abs(float(got_loss) - float(want_loss)) / float(want_loss) > 1e-6
    grads, want = _flat(grads), _flat(want)
    gaps = [float(jnp.linalg.norm(grads[k] - v)) / float(jnp.linalg.norm(v))
            for k, v in want.items() if float(jnp.linalg.norm(v)) > 1e-4]
    assert max(gaps) > 3e-3


def test_one_update_through_make_train_step_equals_the_reference_s(
        cfg, ref, program, tokens, both):
    """``make_train_step`` with the heads' (labels, weights) pairs, AdamW as
    the configuration states it: every leaf after one step against the
    reference's ``opt_apply`` (to 2e-3 of how far the leaf moved, about lr;
    1e-6 besides, which is what AdamW makes of the rounding noise in a
    gradient that is nothing), and what the step's metrics carry."""
    params, model, (loss, _), (_, grads) = both
    a = cfg['assumed']
    tx = optax.adamw(a['learning_rate'], b1=a['b1'], b2=a['b2'], eps=a['eps'],
                     weight_decay=a['weight_decay'])
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    state, metrics = make_train_step()(
        jax.tree_util.tree_map(jnp.copy, state), tokens,
        program.targets_for(tokens, cfg))
    np.testing.assert_allclose(float(metrics['loss']), float(loss), rtol=1e-6)
    want, _ = ref.opt_apply(params, ref.opt_init(params, cfg), grads, cfg, 1)
    for (name, leaf), new in zip(_flat(want).items(),
                                 jax.tree_util.tree_leaves(state.params)):
        moved = float(jnp.abs(leaf - _flat(params)[name]).max())
        np.testing.assert_allclose(
            np.asarray(new), np.asarray(leaf), rtol=0, err_msg=name,
            atol=2e-3 * moved + 4e-7 * float(jnp.abs(leaf).max()) + 1e-6)
    # pairs sent to the four held experts of two expert blocks (one in the
    # trunk, one in the next-token module), of 2 x 48 tokens x top 4 each
    load = np.asarray(metrics['expert_load'])
    assert load.shape == (4,) and 0 < load.sum() < 2 * 2 * 48 * 4
    assert 0.0 <= float(metrics['accuracy']) <= 1.0


def test_the_step_hands_a_one_array_model_the_loss_it_had():
    """A model that hands back an array: the mean of the fused loss, as
    before there were heads."""
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((4, 9)),
                         jnp.float32)
    labels = jnp.asarray([1, 0, 8, 3])
    loss, hit = summed_loss(logits, labels)
    want = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(float(loss), float(want.mean()), rtol=1e-6)
    assert hit.shape == (4,)
    with pytest.raises(ValueError, match='heads'):
        summed_loss((logits, logits), ((labels, jnp.ones(4)),))


@pytest.mark.parametrize('diagonal', [0.0, 4.0])
def test_sinkhorn_is_doubly_stochastic_and_the_stream_block_equals_the_reference(
        cfg, ref, diagonal):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 16, 4, 64)), jnp.float32)
    maps = latent_moe.StreamMaps(alpha_init=0.5, res_diagonal_init=diagonal,
                                 dtype=jnp.float32)
    variables = maps.init(jax.random.PRNGKey(2), x)
    # the maps start as the reference's init_params starts them
    made = variables['params']
    assert float(made['alpha_res']) == 0.5 and not made['b_pre'].any()
    np.testing.assert_array_equal(made['b_res'], diagonal * np.eye(4))
    pre, post, res = maps.apply(variables, x)
    # Columns are normalised last: exact. Rows after 20 iterations: to 1e-5
    # from well-mixed logits; from a heavy diagonal (exp(4) against 1, how
    # the configuration starts ``b_res``) Sinkhorn contracts slowly, 2e-3.
    np.testing.assert_allclose(np.asarray(res.sum(-2)), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.sum(-1)), 1.0,
                               atol=5e-3 if diagonal else 1e-5)
    assert float(res.min()) > 0 and float(jnp.abs(res - 0.25).max()) > 0.01
    want = ref.stream_maps(variables['params'], x, cfg)
    for a, b in zip((pre, post, res), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=1e-6)
    # X <- H_res X + H_post^T F(rmsnorm(H_pre X)), F a fixed map here
    scale = {'scale': jnp.ones((64,), jnp.float32)}
    got, _ = latent_moe.mix_streams(
        x, pre, post, res, lambda inner: jnp.tanh(ref._rms(
            inner, scale['scale'], 1e-6)))
    want = ref._sub_layer(variables['params'], scale, x, jnp.tanh, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_the_clamp_keeps_sinkhorn_finite():
    logits = jnp.asarray([[[500.0, -500.0], [0.0, 3.0]]], jnp.float32)
    m = latent_moe.sinkhorn(jnp.clip(logits, -30.0, 30.0), 20, 1e-6)
    assert bool(jnp.isfinite(m).all())
    assert not bool(jnp.isfinite(latent_moe.sinkhorn(logits, 20, 1e-6)).all())


def test_four_shares_of_the_heads_add_up_through_w_o(cfg, ref):
    """Four chips hold one head each: the latents and their norms are what
    every chip computes alike, a chip's ``W_UQ``, ``W_UKV`` and ``W_O`` are
    its head's, and the partial outputs add up to the uncut layer's."""
    whole = dict(cfg, num_attention_heads=4, num_key_value_heads=4)
    params = ref.init_params(whole, 5)['block_0']['attn']
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 64, 64)),
                    jnp.float32)
    want = ref._attention(params, x, whole, None)
    frequencies = latent_moe.yarn_frequencies(8, 10000.0, 64.0, 4096, 32, 1)
    total = 0.0
    for head in range(4):
        share = jax.tree_util.tree_map(lambda a: a, params)
        share = dict(share, q_up={'kernel': params['q_up']['kernel'][:, head:head + 1]},
                     kv_up={'kernel': params['kv_up']['kernel'][:, head:head + 1]},
                     out={'kernel': params['out']['kernel'][head:head + 1]})
        layer = latent_moe.LatentAttention(
            heads_held=1, q_rank=48, kv_rank=32, nope=16, rope=8, v_dim=16,
            frequencies=frequencies, softmax_scale=ref.softmax_scale(whole),
            attention='flash:interpret', dtype=jnp.float32)
        total = total + layer.apply({'params': share}, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_yarn_frequencies_and_scale_are_the_reference_s(cfg, ref):
    """64 rotary lanes, theta 10000, factor 64 over 4096 positions: the
    fastest pairs keep their frequency, the slowest take it over 64, and the
    scores' scale is 192^-1/2 (0.1 ln 64 + 1)^2."""
    real = json.load(open(os.path.join(CONFIGS, NAME + '.json')))
    got = latent_moe.yarn_frequencies(64, 10000, 64, 4096, 32, 1)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(real), rtol=1e-12)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-12)
    np.testing.assert_allclose(got[-8:], plain[-8:] / 64, rtol=1e-12)
    assert plain[12] / 64 < got[12] < plain[12]
    scale = latent_moe.yarn_softmax_scale(192, 64, 1)
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert scale == pytest.approx(ref.softmax_scale(real))
    assert latent_moe.yarn_softmax_scale(192) == pytest.approx(192 ** -0.5)


def test_rotary_products_are_those_of_interleaved_pairs(cfg, ref):
    """The program rotates into a half-split layout, the reference in place:
    a permutation both operands share, so every q k product agrees."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 32, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 32, 8)), jnp.float32)
    frequencies = ref.yarn_inv_freq(cfg)
    got = jnp.einsum('bqhd,bkd->bhqk', latent_moe.rotate(q, frequencies),
                     latent_moe.rotate(k, frequencies))
    want = jnp.einsum('bqhd,bkd->bhqk', ref._rotate(q, cfg), ref._rotate(k, cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # a rotation: position 0 is left as it is (up to the layout), norms kept
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(
        latent_moe.rotate(q, frequencies), axis=-1)),
        np.asarray(jnp.linalg.norm(q, axis=-1)), rtol=1e-5)


def test_the_layer_plan_instant_says_what_was_built(cfg, program, tokens,
                                                    monkeypatch):
    monkeypatch.setattr(latent_moe, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        model = program.model_for(cfg, None, interpret=True)
        jax.eval_shape(lambda t: model.init(jax.random.PRNGKey(0), t), tokens)
        jax.eval_shape(lambda t: model.init(jax.random.PRNGKey(0), t), tokens)
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'model.layer_plan']
    assert len(plans) == 1 and plans[0][1] == 'model'
    assert plans[0][7] == {
        'layer_kinds': ['dense', 'moe'], 'heads_held': 2,
        'heads_published': 4, 'experts_held': [1, 2, 5, 6],
        'experts_published': 8, 'top_k': 4, 'vocab_rows_held': 128,
        'streams': 4, 'next_token_depth': 1, 'recompute': True,
        'attention': 'flash:interpret', 'experts': 'pallas:interpret',
        'stream_mixing': 'xla'}      # 64-wide streams are no whole vregs


# -- the flash kernels with keys and values of two widths ----------------------

@pytest.mark.parametrize('shape,dv,causal', [
    ((1, 300, 3, 192), 128, True),      # the cell's widths, T no block multiple
    ((2, 256, 2, 24), 16, True),        # narrow heads: one a block all the same
    ((1, 128, 1, 192), 128, False),
])
def test_flash_with_two_widths_equals_dense_forward_and_gradients(shape, dv,
                                                                  causal):
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(2))
    v, c = (jnp.asarray(rng.standard_normal(shape[:3] + (dv,)), jnp.float32)
            for _ in range(2))
    scale = 0.13

    def run(attend):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            attend(q, k, v) * c), argnums=(0, 1, 2))(q, k, v)

    got = run(lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                              interpret=True, scale=scale))
    want = run(lambda q, k, v: dense_attention(q, k, v, causal=causal,
                                               scale=scale))
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5)


def test_the_flash_plan_says_the_widths_and_the_old_plans_stand(monkeypatch):
    """GPT-2's and Olmo-Hybrid's lane plans as they were; 192/128 takes one
    head a block, 256 key lanes (64 of padding) and 128 value lanes; the
    instant carries the widths and the scale."""
    assert fa.lane_plan(12, 64) == {
        'lane_block': 128, 'heads_per_block': 2, 'pad_heads': 0,
        'pad_lanes': 0, 'v_lane_block': 128, 'v_pad_lanes': 0}
    assert fa.lane_plan(15, 128) == fa.lane_plan(15, 128, 128) == {
        'lane_block': 128, 'heads_per_block': 1, 'pad_heads': 0,
        'pad_lanes': 0, 'v_lane_block': 128, 'v_pad_lanes': 0}
    assert fa.lane_plan(4, 192, 128) == {
        'lane_block': 256, 'heads_per_block': 1, 'pad_heads': 0,
        'pad_lanes': 64, 'v_lane_block': 128, 'v_pad_lanes': 0}
    monkeypatch.setattr(fa, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        q = jax.ShapeDtypeStruct((1, 4096, 4, 192), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, 4096, 4, 128), jnp.bfloat16)
        jax.eval_shape(lambda q, v: jax.grad(lambda q: jnp.sum(flash_attention(
            q, q, v, causal=True, block_q=512, block_k=1024, interpret=True,
            scale=0.1).astype(jnp.float32)))(q), q, v)
    finally:
        trace.set_global_tracer(previous)
    plans = [r[7] for r in tracer.records() if r[0] == 'kernel.flash_plan']
    assert len(plans) == 1
    plan = plans[0]
    assert (plan['qk_width'], plan['v_width'], plan['pad_lanes'],
            plan['lane_block'], plan['v_lane_block'], plan['heads'],
            plan['scale']) == (192, 128, 64, 256, 128, 4, 0.1)
    # the executed-work account is the tile plan's, whatever the widths
    assert {k: plan[k] for k in ('passes', 'share', 'cases')} == {
        k: fa.tile_plan(4096, True, 'bfloat16', 192, 512, 1024)[k]
        for k in ('passes', 'share', 'cases')}
    same = fa._plan_for(jnp.zeros((1, 1024, 12, 64), jnp.bfloat16), True,
                        512, 1024)
    assert (same['qk_width'], same['v_width'], same['scale']) == (64, 64, 0.125)


@pytest.fixture(scope='module')
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - whatever says there is no compiler
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))
    return topo.devices


def test_the_kernels_compile_for_a_v5e_at_192_and_128_lanes_and_4096_tokens(v5e):
    """The kernels alone as ``xing4.tokens4k`` runs them: bf16 ``[1, 4096, 4,
    192]`` keys against ``[1, 4096, 4, 128]`` values, blocks (512, 1024),
    forward and backward."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(v5e[0])

    def struct(width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, 4096, 4, width), dtype, sharding=one)

    def grads(q, k, v, c):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(fa._flash_diff(
            q, k, v, True, 512, 1024, False, 0.1).astype(jnp.float32) * c),
            argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(struct(192), struct(192), struct(128),
                                struct(128, jnp.float32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
