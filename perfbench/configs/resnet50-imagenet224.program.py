"""The system under test for the ResNet configuration: the users' own
``models.ResNet`` and ``models.train.make_train_step``, built from the
configuration's sizes and handed the benchmark's weights."""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec


def build(cfg, params, batch_stats, mesh, interpret=False):
    from petastorm_tpu.models import resnet
    from petastorm_tpu.models.train import TrainState, make_train_step

    a = cfg['assumed']
    model = resnet.ResNet(stage_sizes=list(cfg['stage_sizes']),
                          block_cls=resnet.BottleneckBlock,
                          num_classes=cfg['num_classes'],
                          num_filters=cfg['num_filters'], stem='conv7')
    tx = optax.chain(optax.add_decayed_weights(a['weight_decay']),
                     optax.sgd(a['learning_rate'], momentum=a['momentum']))
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx,
                              batch_stats=batch_stats)
    # uint8 rides the transfer; the cast runs on the device (chip_smoke.py).
    prepare = jax.jit(lambda image, label:
                      (image.astype(jnp.float32) / 255.0, label))
    # The state as the step hands it back: a device counter for the Python
    # 0 and every leaf placed on the mesh, so that step 2 finds step 1's
    # program and does not trace and lower a second one.
    state = state.replace(step=jnp.zeros((), jnp.int32))
    if mesh is not None:
        state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
    train_step = make_train_step(mesh=mesh)

    def step(state, batch):
        x, y = prepare(batch.image, batch.label)
        return train_step(state, x, y)

    return state, step


def first_gradient(opt_state, cfg):
    """The gradient as the optimizer got it, as a tree and the factor its
    norms take: the momentum accumulator after one step from zero."""
    return opt_state[1][0].trace, 1.0
