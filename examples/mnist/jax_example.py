"""Train an MLP on the MNIST-style dataset through the JAX loader.

Parity role: reference ``examples/mnist/pytorch_example.py`` /
``tf_example.py`` — end-to-end train on petastorm data (BASELINE config 1:
"MNIST Parquet -> JAX MLP train (single-host make_reader)").
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))

import argparse

import jax
import numpy as np

from petastorm_tpu import make_reader
from petastorm_tpu.jax_loader import make_jax_loader
from petastorm_tpu.models.mlp import MLP
from petastorm_tpu.models.train import (create_train_state, make_eval_step,
                                        make_train_step)
from petastorm_tpu.utils import enable_compile_cache


def train_and_test(dataset_url, epochs=5, batch_size=64, learning_rate=0.05,
                   reader_pool_type='thread'):
    model = MLP(features=(128, 64), num_classes=10)
    state = create_train_state(jax.random.PRNGKey(0), model, (1, 8, 8),
                               learning_rate=learning_rate)
    train_step = make_train_step()
    eval_step = make_eval_step()

    for epoch in range(epochs):
        with make_reader(dataset_url + '/train', num_epochs=1, seed=epoch,
                         shuffle_row_groups=True,
                         reader_pool_type=reader_pool_type) as reader:
            with make_jax_loader(reader, batch_size,
                                 shuffling_queue_capacity=500, seed=epoch) as loader:
                losses = []
                for batch in loader:
                    state, metrics = train_step(
                        state, batch.image.astype('float32') / 16.0, batch.digit)
                    losses.append(float(metrics['loss']))
        print('epoch {}: train loss {:.4f}'.format(epoch, np.mean(losses)))

    with make_reader(dataset_url + '/test', num_epochs=1,
                     reader_pool_type=reader_pool_type) as reader:
        with make_jax_loader(reader, batch_size, last_batch='partial') as loader:
            accs = []
            for batch in loader:
                metrics = eval_step(state, batch.image.astype('float32') / 16.0,
                                    batch.digit)
                accs.append((float(metrics['accuracy']), len(batch.digit)))
    accuracy = sum(a * n for a, n in accs) / sum(n for _, n in accs)
    print('test accuracy: {:.4f}'.format(accuracy))
    return accuracy


if __name__ == '__main__':
    parser = argparse.ArgumentParser()
    parser.add_argument('--dataset-url', default='file:///tmp/mnist_dataset')
    parser.add_argument('--epochs', type=int, default=5)
    parser.add_argument('--batch-size', type=int, default=64)
    args = parser.parse_args()
    enable_compile_cache()
    train_and_test(args.dataset_url, args.epochs, args.batch_size)
