"""Materialize a token-sequence Parquet store for LM training.

Long-context stand-in for the reference's example stores (SURVEY §2.8): each
row is one fixed-length int32 token sequence (static shape — the tensor
reader's requirement and XLA's preference), written with the standard codec
write path so the read side exercises the same machinery as images.
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))

import argparse

import numpy as np

from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.unischema import Unischema, UnischemaField


def lm_schema(seq_len):
    return Unischema('LongContextLM', [
        UnischemaField('doc_id', np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField('tokens', np.int32, (seq_len,), NdarrayCodec(), False),
    ])


def generate(url, num_docs=256, seq_len=2048, vocab_size=32000, seed=0,
             rows_per_row_group=32):
    """Synthetic Zipf-ish token streams (repetitive enough to be learnable)."""
    rng = np.random.default_rng(seed)

    def rows():
        for i in range(num_docs):
            # A small per-doc vocabulary makes next-token prediction learnable
            # by a tiny model in a few steps (example/test friendliness).
            base = rng.integers(0, vocab_size - 64)
            yield {'doc_id': i,
                   'tokens': (base + rng.integers(0, 64, seq_len)).astype(np.int32)}

    write_dataset(url, lm_schema(seq_len), rows(),
                  rows_per_row_group=rows_per_row_group)
    return url


if __name__ == '__main__':
    parser = argparse.ArgumentParser()
    parser.add_argument('--dataset-url', default='file:///tmp/lm_dataset')
    parser.add_argument('--num-docs', type=int, default=256)
    parser.add_argument('--seq-len', type=int, default=2048)
    args = parser.parse_args()
    generate(args.dataset_url, args.num_docs, args.seq_len)
    print('wrote', args.dataset_url)
