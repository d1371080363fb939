"""Hello-world dataset: png images + 4-D ndarrays + scalars.

Parity: reference
``examples/hello_world/petastorm_dataset/generate_petastorm_dataset.py:29-62``
— same schema, written with the pyarrow-native writer (no Spark).
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))

import argparse

import numpy as np

from petastorm_tpu.codecs import CompressedImageCodec, NdarrayCodec, ScalarCodec
from petastorm_tpu.etl import materialize_dataset
from petastorm_tpu.unischema import Unischema, UnischemaField

HelloWorldSchema = Unischema('HelloWorldSchema', [
    UnischemaField('id', np.int32, (), ScalarCodec(np.int32), False),
    UnischemaField('image1', np.uint8, (128, 256, 3), CompressedImageCodec('png'), False),
    UnischemaField('array_4d', np.uint8, (None, 128, 30, None), NdarrayCodec(), False),
])


def row_generator(x, rng):
    return {'id': x,
            'image1': rng.integers(0, 255, (128, 256, 3), dtype=np.uint8),
            'array_4d': rng.integers(0, 255, (4, 128, 30, 3), dtype=np.uint8)}


def generate_hello_world_dataset(output_url='file:///tmp/hello_world_dataset',
                                 rows_count=100):
    rng = np.random.default_rng(0)
    with materialize_dataset(output_url, HelloWorldSchema, row_group_size_mb=32) as writer:
        for i in range(rows_count):
            writer.write(row_generator(i, rng))
    print('Wrote {} rows to {}'.format(rows_count, output_url))


if __name__ == '__main__':
    parser = argparse.ArgumentParser()
    parser.add_argument('--output-url', default='file:///tmp/hello_world_dataset')
    parser.add_argument('--rows', type=int, default=100)
    args = parser.parse_args()
    generate_hello_world_dataset(args.output_url, args.rows)
