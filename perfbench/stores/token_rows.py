"""Token store: ``id`` int64 and ``tokens`` int32 [sequence_length + 1].

Copied from ``bench.py::_ensure_lm_dataset`` (uniform tokens as fixed-length
int32 rows), seeded from ``--seed``. The expected rows are made again from the
seed, with no read at all.
"""

import numpy as np

FIELDS = ('id', 'tokens')
CHECKED = 'tokens'


def _schema(cfg):
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    length = cfg['assumed']['sequence_length'] + 1
    return Unischema('PerfbenchTokens', [
        UnischemaField('id', np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField('tokens', np.int32, (length,), NdarrayCodec(), False)])


def row_shape(cfg):
    return {'tokens': ((cfg['assumed']['sequence_length'] + 1,), np.int32)}


def _group(cfg, seed, group, per_group):
    rng = np.random.default_rng([int(seed), group])
    return rng.integers(0, cfg['vocab_size'],
                        (per_group, cfg['assumed']['sequence_length'] + 1),
                        dtype=np.int32)


def write_part(args):
    url, cfg, seed, index, first, last, per_group = args
    from petastorm_tpu.etl.writer import DatasetWriter
    with DatasetWriter(url, _schema(cfg), rows_per_row_group=per_group,
                       writer_index=index, finalize_metadata=False) as writer:
        for group in range(first, last):
            block = _group(cfg, seed, group, per_group)
            for k in range(per_group):
                writer.write({'id': group * per_group + k, 'tokens': block[k]})
    return last - first


def finalize(url, cfg):
    from petastorm_tpu.etl.writer import finalize_dataset_metadata
    from petastorm_tpu.storage import ParquetStore
    finalize_dataset_metadata(ParquetStore(url), _schema(cfg))


class Expected(object):
    def __init__(self, url, cfg, seed, rows):
        self._cfg, self._seed = cfg, seed
        self._per_group = cfg['assumed']['rows_per_row_group']
        self._groups = {}

    def rows(self, ids):
        out = np.zeros((len(ids), self._cfg['assumed']['sequence_length'] + 1),
                       np.int32)
        for n, i in enumerate(ids):
            g, k = divmod(int(i), self._per_group)
            if g not in self._groups:
                self._groups[g] = _group(self._cfg, self._seed, g,
                                         self._per_group)
            out[n] = self._groups[g][k]
        return {'id': np.asarray(ids, np.int64), 'tokens': out}
