"""ImageNet-shaped store: ``id`` int64, ``image`` uint8 jpeg, ``label`` int64.

Copied from ``bench.py::_synthetic_image`` / ``_ensure_imagenet_dataset``
(photo-like low-frequency field plus mild noise: compresses and decodes like a
photo, unlike white noise), seeded from ``--seed`` and written by several
processes, each its own range of row groups, through the program's own
``DatasetWriter``. The expected rows come from an independent read: pyarrow
and PIL, nothing of ``petastorm_tpu``.
"""

import glob
import io
import os

import numpy as np

FIELDS = ('id', 'image', 'label')
CHECKED = 'image'


def _synthetic_image(rng, size):
    cells = max(1, size // 16)
    low = rng.integers(0, 255, (cells, cells, 3), dtype=np.uint8)
    img = np.kron(low, np.ones((size // cells, size // cells, 1), np.uint8))
    noise = rng.integers(0, 24, (size, size, 3), dtype=np.uint8)
    return np.clip(img.astype(np.int16) + noise - 12, 0, 255).astype(np.uint8)


def _schema(cfg, quality):
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    size = cfg['image_size']
    return Unischema('PerfbenchImages', [
        UnischemaField('id', np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField('image', np.uint8, (size, size, cfg['channels']),
                       CompressedImageCodec('jpeg', quality), False),
        UnischemaField('label', np.int64, (), ScalarCodec(np.int64), False)])


def row_shape(cfg):
    return {'image': ((cfg['image_size'], cfg['image_size'], cfg['channels']),
                      np.uint8)}


def write_part(args):
    """One writer process: row groups ``first..last`` into files of its own.
    A row group's rows depend on the seed and the group's index alone."""
    url, cfg, seed, index, first, last, per_group = args
    from petastorm_tpu.etl.writer import DatasetWriter
    a = cfg['assumed']
    with DatasetWriter(url, _schema(cfg, a['jpeg_quality']),
                       rows_per_row_group=per_group, writer_index=index,
                       finalize_metadata=False) as writer:
        for group in range(first, last):
            rng = np.random.default_rng([int(seed), group])
            for k in range(per_group):
                writer.write({'id': group * per_group + k,
                              'image': _synthetic_image(rng, cfg['image_size']),
                              'label': int(rng.integers(0, cfg['num_classes']))})
    return last - first


def finalize(url, cfg):
    from petastorm_tpu.etl.writer import finalize_dataset_metadata
    from petastorm_tpu.storage import ParquetStore
    finalize_dataset_metadata(ParquetStore(url), _schema(
        cfg, cfg['assumed']['jpeg_quality']))


class Expected(object):
    """Independent read of rows by id: the parquet files through pyarrow,
    the jpeg bytes through PIL."""

    def __init__(self, url, cfg, seed, rows):
        import pyarrow.parquet as pq
        self._where = {}
        self._files = {}
        path = url[len('file://'):]
        for name in sorted(glob.glob(os.path.join(path, '*.parquet'))):
            f = pq.ParquetFile(name)
            self._files[name] = f
            for g in range(f.num_row_groups):
                ids = f.read_row_group(g, columns=['id'])['id'].to_numpy()
                for k, i in enumerate(ids.tolist()):
                    self._where[i] = (name, g, k)

    def rows(self, ids):
        from PIL import Image
        by_group = {}
        for n, i in enumerate(ids):
            name, g, k = self._where[int(i)]
            by_group.setdefault((name, g), []).append((n, k))
        images = [None] * len(ids)
        labels = np.zeros(len(ids), np.int64)
        for (name, g), wanted in by_group.items():
            table = self._files[name].read_row_group(
                g, columns=['image', 'label'])
            blobs, labs = table['image'], table['label']
            for n, k in wanted:
                images[n] = np.asarray(Image.open(
                    io.BytesIO(blobs[k].as_py())).convert('RGB'))
                labels[n] = labs[k].as_py()
        return {'id': np.asarray(ids, np.int64), 'image': np.stack(images),
                'label': labels}
