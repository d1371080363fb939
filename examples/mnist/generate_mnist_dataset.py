"""Materialize the sklearn digits dataset (offline MNIST stand-in) to Parquet.

Parity: reference ``examples/mnist/generate_petastorm_mnist.py:114-131`` —
same shape of pipeline (download -> encode via schema -> materialize); uses
sklearn's bundled 8x8 digits so it runs with zero egress.
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))

import argparse

import numpy as np

from examples.mnist.schema import MnistSchema
from petastorm_tpu.etl import materialize_dataset


def mnist_data_to_petastorm_dataset(output_url, train_fraction=0.8):
    from sklearn.datasets import load_digits

    digits = load_digits()
    images = digits.images.astype(np.uint8)
    labels = digits.target.astype(np.int64)
    split = int(len(images) * train_fraction)

    for name, lo, hi in (('train', 0, split), ('test', split, len(images))):
        url = output_url.rstrip('/') + '/' + name
        with materialize_dataset(url, MnistSchema, rows_per_row_group=200) as writer:
            for idx in range(lo, hi):
                writer.write({'idx': idx, 'digit': labels[idx], 'image': images[idx]})
        print('Wrote {} rows to {}'.format(hi - lo, url))


if __name__ == '__main__':
    parser = argparse.ArgumentParser()
    parser.add_argument('--output-url', default='file:///tmp/mnist_dataset')
    args = parser.parse_args()
    mnist_data_to_petastorm_dataset(args.output_url)
