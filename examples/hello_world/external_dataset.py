"""Reading a plain (non-petastorm) Parquet store.

Parity: reference ``examples/hello_world/external_dataset/`` —
``make_batch_reader`` works on any Parquet dataset, no Unischema/codecs
required; schema is inferred from the Arrow schema. Also shows the
DataFrame converter (``make_converter``) producing mesh-ready JAX batches
from an in-memory frame.

Run: python -m examples.hello_world.external_dataset
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))


def generate_external_dataset(path, rows=100):
    """A Parquet store written by 'some other system' (plain pyarrow)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(0)
    table = pa.table({
        'id': pa.array(np.arange(rows, dtype=np.int64)),
        'value1': pa.array(rng.standard_normal(rows)),
        'value2': pa.array(rng.integers(0, 100, rows, dtype=np.int32)),
    })
    os.makedirs(path, exist_ok=True)
    pq.write_table(table, os.path.join(path, 'data.parquet'), row_group_size=25)


def python_hello_world(dataset_url):
    from petastorm_tpu import make_batch_reader

    with make_batch_reader(dataset_url, reader_pool_type='thread',
                           workers_count=2) as reader:
        total = 0
        for batch in reader:
            total += len(batch.id)
        print('read {} rows in columnar batches'.format(total))


def converter_hello_world():
    import pandas as pd

    from petastorm_tpu import make_converter

    df = pd.DataFrame({'feature': np.random.rand(64).astype(np.float64),
                       'label': np.random.randint(0, 10, 64)})
    conv = make_converter(df)  # float64 narrowed to float32 for TPU
    with conv.make_jax_loader(batch_size=16, num_epochs=1,
                              shuffle_row_groups=False) as loader:
        for batch in loader:
            pass
        print('converter produced jax batches of', batch.feature.shape,
              batch.feature.dtype)
    conv.delete()


def main():
    path = tempfile.mkdtemp(prefix='external_ds_')
    generate_external_dataset(path)
    python_hello_world('file://' + path)
    converter_hello_world()


if __name__ == '__main__':
    main()
