"""TransformerLM tests: attention backends agree; ring runs sequence-sharded
on the virtual 8-device mesh (long-context flagship, SURVEY §5.7/§7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.models import TransformerLM
from petastorm_tpu.parallel import make_mesh

# Heavyweight (jit compiles of full models / interpret-mode Pallas):
# excluded from the fast CI lane; run the full suite before shipping.
pytestmark = pytest.mark.slow

VOCAB = 64


def _tokens(b=2, t=32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, VOCAB, (b, t)), jnp.int32)


def _make(attention, mesh=None, seq_axis=None, dtype=jnp.float32):
    return TransformerLM(vocab_size=VOCAB, d_model=32, num_heads=2,
                         num_layers=2, max_len=64, attention=attention,
                         mesh=mesh, seq_axis=seq_axis, dtype=dtype)


def test_forward_shapes_and_finite():
    model = _make('dense')
    tokens = _tokens()
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 32, VOCAB)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_ring_matches_dense_on_mesh():
    """Sequence-parallel ring attention gives the same logits as dense —
    the module code is identical, only the attention backend changes."""
    mesh = make_mesh({'sp': 8})
    tokens = _tokens(b=2, t=32)
    dense = _make('dense')
    params = dense.init(jax.random.PRNGKey(0), tokens)
    ref = dense.apply(params, tokens)

    ring = _make('ring', mesh=mesh, seq_axis='sp')
    got = ring.apply(params, tokens)    # same param tree by construction
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_ring_trains_under_jit():
    """One causal-LM SGD step through ring attention on the mesh: grads flow
    through ppermute/scan and the loss is finite."""
    import optax

    mesh = make_mesh({'sp': 8})
    tokens = _tokens(b=2, t=32, seed=1)
    model = _make('ring', mesh=mesh, seq_axis='sp')
    params = model.init(jax.random.PRNGKey(0), tokens)
    tx = optax.sgd(0.1)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, tokens):
        def loss_fn(p):
            logits = model.apply(p, tokens)
            targets = jnp.roll(tokens, -1, axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], targets[:, :-1]).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, loss0 = step(params, opt_state, tokens)
    params, opt_state, loss1 = step(params, opt_state, tokens)
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss1))
    assert float(loss1) < float(loss0)   # SGD on the same batch must descend


def test_flash_backend_selectable():
    """attention='flash:interpret' runs the Pallas kernel (in the
    interpreter) through the module and matches dense; plain 'flash' is the
    compiled kernel, which a CPU cannot run and must not quietly replace."""
    tokens = _tokens()
    dense = _make('dense')
    params = dense.init(jax.random.PRNGKey(0), tokens)
    ref = dense.apply(params, tokens)
    got = _make('flash:interpret').apply(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)
    with pytest.raises(RuntimeError, match='interpret=True'):
        _make('flash').apply(params, tokens)
    with pytest.raises(ValueError, match='unknown attention'):
        _make('ring:interpret').apply(params, tokens)


def test_flash_on_a_data_mesh_stays_batch_sharded():
    """On a mesh the module maps the kernel over the batch shards
    (shard_map): the SPMD partitioner cannot split a Pallas call and would
    gather the whole batch onto every device."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = make_mesh({'data': 8})
    tokens = _tokens(b=8, t=16)
    dense = _make('dense')
    params = dense.init(jax.random.PRNGKey(0), tokens)
    ref = dense.apply(params, tokens)
    sharded = jax.device_put(np.asarray(tokens),
                             NamedSharding(mesh, PartitionSpec('data')))
    got = jax.jit(_make('flash:interpret', mesh=mesh).apply)(params, sharded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)
    assert got.sharding.spec[0] == 'data'


def test_ring_requires_mesh():
    with pytest.raises(ValueError, match='mesh'):
        _make('ring').apply(
            _make('dense').init(jax.random.PRNGKey(0), _tokens()), _tokens())


def test_tensor_parallel_matches_replicated():
    """Megatron-style TP over 'model': sharded apply == replicated apply,
    and the intended kernels actually land sharded."""
    from jax.sharding import NamedSharding, PartitionSpec

    from petastorm_tpu.models.train import (create_train_state,
                                            transformer_param_spec)

    mesh = make_mesh({'data': 4, 'model': 2})
    tokens = _tokens(b=4, t=16)
    model = _make('dense')
    state = create_train_state(jax.random.PRNGKey(0), model, None, mesh=mesh,
                               param_spec_fn=transformer_param_spec,
                               example_input=tokens)

    # qkv sharded over heads, MLP up over features, head over vocab
    p = state.params['block_0']['attn']['query']['kernel']
    assert p.sharding.spec == PartitionSpec(None, 'model', None)
    up = [v for k, v in state.params['block_0'].items() if k.startswith('Dense')]
    assert any(w['kernel'].sharding.spec == PartitionSpec(None, 'model')
               for w in up)
    assert (state.params['head']['kernel'].sharding.spec
            == PartitionSpec(None, 'model'))

    @jax.jit
    def apply(params, tokens):
        return model.apply({'params': params}, tokens)

    sharded_tokens = jax.device_put(
        np.asarray(tokens), NamedSharding(mesh, PartitionSpec('data', None)))
    got = apply(state.params, sharded_tokens)

    ref_model = _make('dense')
    ref_params = ref_model.init(jax.random.PRNGKey(0), tokens)
    ref = ref_model.apply(ref_params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)
