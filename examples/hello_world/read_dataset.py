"""Read the hello-world dataset: pure python, batched, and JAX flavors.

Parity: reference ``examples/hello_world/petastorm_dataset/python_hello_world.py``
(+ tf/pytorch variants) collapsed into one script with a --mode flag.
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))

import argparse

from petastorm_tpu import make_batch_reader, make_reader


def python_hello_world(dataset_url):
    with make_reader(dataset_url) as reader:
        for sample in reader:
            print(sample.id, sample.image1.shape, sample.array_4d.shape)
            break


def batch_hello_world(dataset_url):
    with make_batch_reader(dataset_url) as reader:
        for batch in reader:
            print('batch of', len(batch.id), 'encoded rows')
            break


def jax_hello_world(dataset_url):
    from petastorm_tpu.jax_loader import PadTo, make_jax_loader

    with make_reader(dataset_url, num_epochs=None) as reader:
        with make_jax_loader(reader, 8,
                             shape_policies={'array_4d': PadTo((4, 128, 30, 3))}) as loader:
            batch = next(loader)
            print('jax batch:', batch.image1.shape, batch.image1.dtype,
                  'on', batch.image1.devices())


if __name__ == '__main__':
    parser = argparse.ArgumentParser()
    parser.add_argument('--dataset-url', default='file:///tmp/hello_world_dataset')
    parser.add_argument('--mode', choices=['python', 'batch', 'jax'], default='python')
    args = parser.parse_args()
    {'python': python_hello_world, 'batch': batch_hello_world,
     'jax': jax_hello_world}[args.mode](args.dataset_url)
