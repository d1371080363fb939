"""The step's loss (``ops.cross_entropy.softmax_cross_entropy``) against
``optax.softmax_cross_entropy_with_integer_labels`` on float32 copies of the
logits: loss, ``argmax`` hit and gradient; what it saves for the backward
pass; the same answers on a mesh; its ``step.loss_plan`` instant; and
``make_train_step`` against the formulation it replaced."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from petastorm_tpu import trace
from petastorm_tpu.models import TransformerLM, resnet
from petastorm_tpu.models.train import (TrainState, make_eval_step,
                                        make_train_step)
from petastorm_tpu.parallel.mesh import make_mesh

ce = importlib.import_module('petastorm_tpu.ops.cross_entropy')

_DTYPES = [jnp.bfloat16, jnp.float32]
_VOCABS = [1000, 257, 128 * 3 + 81]


def _case(dtype, vocab, rows=(3, 8), seed=0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=rows + (vocab,)) * 4, dtype)
    labels = jnp.asarray(rng.integers(0, vocab, rows), jnp.int32)
    return logits, labels


def _optax_loss(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


def _grad_rtol(dtype):
    """One rounding to the logits' dtype on either side, of float32 values
    a few ulps apart (``exp(x - lse)`` against ``exp(x - max) / sum``)."""
    return max(2 * float(jnp.finfo(dtype).eps), 4e-6)


@pytest.mark.parametrize('vocab', _VOCABS)
@pytest.mark.parametrize('dtype', _DTYPES)
def test_loss_and_hit_are_optax_s_and_argmax_s(dtype, vocab):
    logits, labels = _case(dtype, vocab)
    # Half the rows carry their own argmax as the label, so both answers of
    # ``hit`` are met.
    top = jnp.argmax(logits, -1).astype(jnp.int32)
    labels = labels.at[:, ::2].set(top[:, ::2])
    loss, hit = jax.jit(ce.softmax_cross_entropy)(logits, labels)
    assert loss.dtype == jnp.float32 and loss.shape == labels.shape
    assert hit.dtype == jnp.bool_ and hit.shape == labels.shape
    np.testing.assert_allclose(loss, _optax_loss(logits, labels),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(hit, top == labels)
    assert hit[:, ::2].all() and not hit.all()


@pytest.mark.parametrize('vocab', _VOCABS)
@pytest.mark.parametrize('dtype', _DTYPES)
def test_gradient_is_optax_s_in_the_logits_dtype(dtype, vocab):
    logits, labels = _case(dtype, vocab, seed=1)
    weights = jnp.asarray(
        np.random.default_rng(2).uniform(0.5, 2, labels.shape), jnp.float32)

    def ours(x):
        return jnp.sum(ce.softmax_cross_entropy(x, labels)[0] * weights)

    def theirs(x):
        return jnp.sum(_optax_loss(x, labels) * weights)

    got, want = jax.jit(jax.grad(ours))(logits), jax.grad(theirs)(logits)
    assert got.dtype == logits.dtype == want.dtype
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=_grad_rtol(dtype),
                               atol=1e-7)
    # The rows of a softmax's gradient sum to nought, and the label's
    # column is the negative one.
    col = jnp.take_along_axis(got, labels[..., None], -1)[..., 0]
    assert (_f32(col) < 0).all()


@pytest.mark.parametrize('dtype', _DTYPES)
def test_labels_at_the_first_and_the_last_column(dtype):
    vocab = 128 * 2 + 81                       # a ragged last tile of lanes
    logits, _ = _case(dtype, vocab, rows=(4,), seed=3)
    labels = jnp.asarray([0, vocab - 1, 0, vocab - 1], jnp.int32)
    logits = logits.at[0, 0].set(30.0).at[1, vocab - 1].set(30.0)
    loss, hit = ce.softmax_cross_entropy(logits, labels)
    np.testing.assert_allclose(loss, _optax_loss(logits, labels),
                               rtol=1e-6, atol=1e-6)
    assert hit.tolist() == [True, True, False, False]
    grad = jax.grad(lambda x: ce.softmax_cross_entropy(x, labels)[0].sum())(
        logits)
    want = jax.grad(lambda x: _optax_loss(x, labels).sum())(logits)
    np.testing.assert_allclose(_f32(grad), _f32(want), rtol=_grad_rtol(dtype),
                               atol=1e-7)
    assert float(grad[2, 0]) < 0 and float(grad[3, vocab - 1]) < 0


@pytest.mark.parametrize('dtype', _DTYPES)
def test_of_equal_maxima_the_first_is_the_argmax(dtype):
    """``jnp.argmax`` names the first of equal maxima, so a label on the
    second is a miss; a row of one value everywhere hits at column 0 only."""
    vocab = 257
    logits, _ = _case(dtype, vocab, rows=(5,), seed=4)
    logits = logits.at[:3, 7].set(25.0).at[:3, 200].set(25.0)
    logits = logits.at[3:].set(1.5)
    labels = jnp.asarray([7, 200, 9, 0, vocab - 1], jnp.int32)
    loss, hit = ce.softmax_cross_entropy(logits, labels)
    np.testing.assert_array_equal(hit, jnp.argmax(logits, -1) == labels)
    assert hit.tolist() == [True, False, False, True, False]
    np.testing.assert_allclose(loss, _optax_loss(logits, labels),
                               rtol=1e-6, atol=1e-6)
    assert float(loss[3]) == pytest.approx(np.log(vocab), rel=1e-6)


def _saved(fn, logits):
    """What ``jax.vjp`` keeps for the backward pass: the leaves of the
    pull-back it returns."""
    return jax.tree_util.tree_leaves(
        jax.eval_shape(lambda x: jax.vjp(fn, x)[1], logits))


@pytest.mark.parametrize('vocab', _VOCABS)
def test_no_float32_value_of_the_logits_shape_is_saved(vocab):
    """Between the passes live the bf16 logits it was given, the float32
    log-sum-exp of each row and the labels; the formulation it replaced
    keeps a float32 ``[N, V]``."""
    logits, labels = _case(jnp.bfloat16, vocab)

    def ours(x):
        return ce.softmax_cross_entropy(x, labels)[0].mean()

    def theirs(x):
        return _optax_loss(x, labels).mean()

    def wide(avals):
        return [a for a in avals
                if a.shape == logits.shape and a.dtype == jnp.float32]

    saved = _saved(ours, logits)
    assert not wide(saved)
    assert sorted((a.shape, a.dtype.name) for a in saved
                  if a.shape[:2] == labels.shape) == sorted([
        (logits.shape, 'bfloat16'), (labels.shape, 'float32'),
        (labels.shape, 'int32')])
    assert wide(_saved(theirs, logits))        # the test can tell


@pytest.mark.parametrize('spec', [
    PartitionSpec('data', None, None),         # the batch over 'data'
    PartitionSpec(None, None, 'model'),        # the vocabulary over 'model'
    PartitionSpec('data', None, 'model'),
], ids=['batch', 'vocab', 'both'])
@pytest.mark.parametrize('dtype', _DTYPES)
def test_sharded_logits_give_the_same_answers(dtype, spec):
    mesh = make_mesh({'data': 4, 'model': 2})
    logits, labels = _case(dtype, 1000, rows=(4, 8), seed=5)
    labels = labels.at[:, ::2].set(
        jnp.argmax(logits, -1).astype(jnp.int32)[:, ::2])

    def run(x, y):
        (loss, hit), pull = jax.vjp(
            lambda x: ce.softmax_cross_entropy(x, y), x)
        return loss, hit, pull((jnp.ones_like(loss),
                                np.zeros(hit.shape, jax.dtypes.float0)))[0]

    want = jax.jit(run)(logits, labels)
    sharded = jax.device_put(logits, NamedSharding(mesh, spec))
    labels_on = jax.device_put(
        labels, NamedSharding(mesh, PartitionSpec(*spec[:2])))
    got = jax.jit(run)(sharded, labels_on)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(_f32(got[2]), _f32(want[2]),
                               rtol=_grad_rtol(dtype), atol=1e-7)
    assert got[2].sharding.is_equivalent_to(sharded.sharding, 3)


def test_loss_plan_instant_once_per_distinct_plan(monkeypatch):
    """Tracing the loss writes one ``step.loss_plan`` instant a distinct
    plan to the global tracer, not one a trace or a pass."""
    monkeypatch.setattr(ce, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        labels = jax.ShapeDtypeStruct((16, 1024), jnp.int32)

        def grad(x, y):
            return jax.grad(
                lambda x: ce.softmax_cross_entropy(x, y)[0].mean())(x)

        lm = jax.ShapeDtypeStruct((16, 1024, 50257), jnp.bfloat16)
        jax.eval_shape(grad, lm, labels)
        jax.eval_shape(grad, lm, labels)                 # the same plan
        jax.eval_shape(ce.softmax_cross_entropy, lm, labels)
        jax.eval_shape(ce.softmax_cross_entropy,
                       jax.ShapeDtypeStruct((256, 1000), jnp.float32),
                       jax.ShapeDtypeStruct((256,), jnp.int32))
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'step.loss_plan']
    assert all(r[1] == 'step' and r[3] is None for r in plans)  # instants
    assert [r[7] for r in plans] == [
        {'rows': 16384, 'vocab': 50257, 'logits_dtype': 'bfloat16',
         'residual_dtype': 'bfloat16', 'impl': 'xla',
         'argmax_fused': True},
        {'rows': 256, 'vocab': 1000, 'logits_dtype': 'float32',
         'residual_dtype': 'float32', 'impl': 'xla',
         'argmax_fused': True}]


# -- through make_train_step ------------------------------------------------

def _old_train_step(state, inputs, labels):
    """``make_train_step_fn`` as it stood before the fused loss: optax on
    the model's float32 logits, autodiff through it, ``argmax`` apart."""
    def loss_fn(params):
        variables = {'params': params}
        if state.batch_stats is not None:
            variables['batch_stats'] = state.batch_stats
            logits, updates = state.apply_fn(variables, inputs, train=True,
                                             mutable=['batch_stats'])
            stats = updates['batch_stats']
        else:
            logits, stats = state.apply_fn(variables, inputs, train=True), None
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, (logits, stats)

    (loss, (logits, stats)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(state.params)
    state = state.apply_gradients(grads=grads)
    if stats is not None:
        state = state.replace(batch_stats=stats)
    return state, {'loss': loss,
                   'accuracy': jnp.mean(jnp.argmax(logits, -1) == labels)}


def _lm(dtype):
    model = TransformerLM(vocab_size=128 + 81, d_model=32, num_heads=2,
                          num_layers=1, max_len=16, dtype=dtype)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128 + 81, (4, 17)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
    return model, variables, tokens[:, :-1], tokens[:, 1:]


def _resnet(dtype):
    model = resnet.ResNetTiny(num_classes=10, dtype=dtype)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(8, 32, 32, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), images, train=False)
    return model, variables, images, labels


@pytest.mark.parametrize('dtype', _DTYPES)
@pytest.mark.parametrize('build', [_lm, _resnet], ids=['lm', 'resnet'])
def test_train_step_is_the_old_formulation_s(build, dtype):
    """``loss``, ``accuracy`` and the updated parameters of one step: to
    float32 rounding where the head's product is float32, to the rounding
    of the bf16 cotangent where it is bf16 (both formulations round it once,
    from float32 values an ulp apart)."""
    model, variables, inputs, labels = build(dtype)

    variables = jax.tree_util.tree_map(np.asarray, variables)

    def fresh():                                # the step donates its state
        params, stats = jax.tree_util.tree_map(
            jnp.array, (variables['params'], variables.get('batch_stats')))
        return TrainState.create(apply_fn=model.apply, params=params,
                                 tx=optax.sgd(0.1), batch_stats=stats)

    want_state, want = jax.jit(_old_train_step)(fresh(), inputs, labels)
    got_state, got = make_train_step()(fresh(), inputs, labels)
    assert set(got) == {'loss', 'accuracy'}
    assert float(got['loss']) == pytest.approx(float(want['loss']), rel=1e-6)
    assert float(got['accuracy']) == float(want['accuracy'])
    evaluated = make_eval_step()(fresh(), inputs, labels)
    assert 0 <= float(evaluated['accuracy']) <= 1
    assert np.isfinite(float(evaluated['loss']))

    moved = jax.tree_util.tree_map(
        lambda new, old: np.asarray(new) - np.asarray(old),
        want_state.params, variables['params'])
    largest = max(float(np.abs(m).max())
                  for m in jax.tree_util.tree_leaves(moved))
    assert largest > 1e-4                       # the step moved something
    tol = (1e-5 if dtype == jnp.float32 else 2.0 ** -8) * largest
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(got_state.params),
            jax.tree_util.tree_leaves(want_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=tol, err_msg=str(path))
    if want_state.batch_stats is not None:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            got_state.batch_stats, want_state.batch_stats)
