"""The system under test for the GPT-2 configuration: the users' own
``models.TransformerLM`` with the flash kernel and
``models.train.make_train_step``, built from the configuration's sizes and
handed the benchmark's weights."""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec


def build(cfg, params, batch_stats, mesh, interpret=False):
    from petastorm_tpu.models import TransformerLM
    from petastorm_tpu.models.train import TrainState, make_train_step

    a = cfg['assumed']
    if cfg['n_inner'] != 4 * cfg['n_embd']:
        raise ValueError('TransformerLM fixes the MLP at four times n_embd')
    model = TransformerLM(vocab_size=cfg['vocab_size'], d_model=cfg['n_embd'],
                          num_heads=cfg['n_head'], num_layers=cfg['n_layer'],
                          max_len=cfg['n_positions'],
                          attention='flash:interpret' if interpret else 'flash',
                          mesh=mesh)
    tx = optax.adamw(a['learning_rate'], b1=a['b1'], b2=a['b2'], eps=a['eps'],
                     weight_decay=a['weight_decay'])
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    # Next-token prediction: inputs and targets are one row shifted by one.
    prepare = jax.jit(lambda tokens: (tokens[:, :-1], tokens[:, 1:]))
    # The state as the step hands it back: a device counter for the Python
    # 0 and every leaf placed on the mesh, so that step 2 finds step 1's
    # program and does not trace and lower a second one.
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
