"""The system under test for the Olmo-Hybrid configuration: the users' own
``models.HybridLM`` (gated delta-rule layers through the Pallas kernels of
``ops.gated_delta``, the full-attention layer through the flash kernel, each
layer recomputed in the backward pass) and ``models.train.make_train_step``,
built from the configuration's sizes and handed the benchmark's weights."""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

# At import, not in build(): a checkout whose program has no HybridLM fails
# here, before a store is written or a weight is made.
from petastorm_tpu.models import HybridLM
from petastorm_tpu.models.train import TrainState, make_train_step


def build(cfg, params, batch_stats, mesh, interpret=False):

    a, published = cfg['assumed'], cfg['published']
    held = cfg['num_attention_heads']
    if not (held == cfg['num_key_value_heads'] == cfg['linear_num_key_heads']
            == cfg['linear_num_value_heads']):
        raise ValueError('HybridLM holds the same heads of every layer')
    if cfg['head_dim'] * published['num_attention_heads'] != cfg['hidden_size']:
        raise ValueError('HybridLM takes a head as hidden_size over the '
                         'published heads')
    model = HybridLM(
        vocab_size=cfg['vocab_size'], d_model=cfg['hidden_size'],
        d_ff=cfg['intermediate_size'], layer_types=tuple(cfg['layer_types']),
        heads_held=held, heads_published=published['num_attention_heads'],
        key_dim=cfg['linear_key_head_dim'],
        value_dim=cfg['linear_value_head_dim'],
        conv_kernel=cfg['linear_conv_kernel_dim'], chunk=a['chunk'],
        attention='flash:interpret' if interpret else 'flash',
        linear_attention='pallas:interpret' if interpret else 'pallas',
        remat=a['recompute_each_layer'], mesh=mesh)
    tx = optax.adamw(a['learning_rate'], b1=a['b1'], b2=a['b2'], eps=a['eps'],
                     weight_decay=a['weight_decay'])
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    # Next-token prediction: inputs and targets are one row shifted by one.
    prepare = jax.jit(lambda tokens: (tokens[:, :-1], tokens[:, 1:]))
    # The state as the step hands it back (see the GPT-2 program).
    state = state.replace(step=jnp.zeros((), jnp.int32))
    if mesh is not None:
        state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
    train_step = make_train_step(mesh=mesh)

    def step(state, batch):
        x, y = prepare(batch.tokens)
        return train_step(state, x, y)

    return state, step


def first_gradient(opt_state, cfg):
    """The gradient as the optimizer got it, as a tree and the factor its
    norms take: Adam's first moment after one step from zero is (1 - b1)
    times the gradient."""
    return opt_state[0].mu, 1.0 / (1.0 - cfg['assumed']['b1'])
