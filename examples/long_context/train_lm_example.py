"""Sequence-parallel LM training: ring attention over the 'sp' mesh axis.

The long-context flagship (SURVEY §5.7 role): token batches stream off the
decoded-columnar tensor reader, land mesh-sharded with the *sequence*
dimension split over 'sp' (each device holds [B, T/sp]), and the
TransformerLM's ring attention rotates kv blocks around the ICI ring — exact
attention, no [T, T] materialization, context bounded by the pod's total
HBM instead of one chip's.
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from petastorm_tpu import make_tensor_reader
from petastorm_tpu.jax_loader import JaxLoader
from petastorm_tpu.models import TransformerLM
from petastorm_tpu.parallel import make_mesh, process_shard
from petastorm_tpu.utils import enable_compile_cache


def train(dataset_url, vocab_size=32000, global_batch=8, steps=20,
          d_model=256, num_heads=4, num_layers=2, seq_parallel=None,
          log_every=5):
    n_devices = len(jax.devices())
    sp = seq_parallel or n_devices
    mesh = make_mesh({'data': n_devices // sp, 'sp': sp})
    cur_shard, shard_count = process_shard()

    # Tokens: batch over 'data', SEQUENCE over 'sp' — the layout ring
    # attention consumes directly (scaling-book recipe: annotate shardings,
    # let XLA place the collectives).
    token_sharding = NamedSharding(mesh, PartitionSpec('data', 'sp'))

    model = TransformerLM(vocab_size=vocab_size, d_model=d_model,
                          num_heads=num_heads, num_layers=num_layers,
                          max_len=1 << 20, attention='ring', mesh=mesh,
                          seq_axis='sp')
    tx = optax.adamw(3e-4)

    @jax.jit
    def init(tokens):
        return model.init(jax.random.PRNGKey(0), tokens)

    @jax.jit
    def step_fn(params, opt_state, tokens):
        def loss_fn(p):
            logits = model.apply(p, tokens)
            targets = jnp.roll(tokens, -1, axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], targets[:, :-1]).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = None
    opt_state = None
    step = 0
    with make_tensor_reader(dataset_url, schema_fields=['tokens'],
                            num_epochs=None, cur_shard=cur_shard,
                            shard_count=shard_count, workers_count=4,
                            cache_type='memory', shuffle_row_groups=True,
                            seed=0) as reader:
        with JaxLoader(reader, global_batch, mesh=mesh,
                       sharding={'tokens': token_sharding}) as loader:
            for batch in loader:
                if params is None:
                    params = init(batch.tokens)
                    opt_state = tx.init(params)
                params, opt_state, loss = step_fn(params, opt_state, batch.tokens)
                step += 1
                if step % log_every == 0:
                    print('step {}: loss {:.4f}'.format(step, float(loss)))
                if step >= steps:
                    break
    return params, float(loss)


if __name__ == '__main__':
    parser = argparse.ArgumentParser()
    parser.add_argument('--dataset-url', default='file:///tmp/lm_dataset')
    parser.add_argument('--global-batch', type=int, default=8)
    parser.add_argument('--steps', type=int, default=20)
    args = parser.parse_args()
    enable_compile_cache()
    train(args.dataset_url, global_batch=args.global_batch, steps=args.steps)
