"""Materialize an ImageNet-style store (synthetic images stand in for the
real corpus; point ``--image-root`` at real JPEG class folders to use it).

Parity: reference ``examples/imagenet/generate_petastorm_imagenet.py``.
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))

import argparse

import numpy as np

from examples.imagenet.schema import ImagenetSchema
from petastorm_tpu.etl import materialize_dataset


def generate_synthetic(output_url, classes=10, images_per_class=50,
                       height=256, width=256):
    rng = np.random.default_rng(0)
    with materialize_dataset(output_url, ImagenetSchema, row_group_size_mb=64) as writer:
        for label in range(classes):
            for _ in range(images_per_class):
                writer.write({
                    'noun_id': 'n{:08d}'.format(label),
                    'text': 'synthetic_class_{}'.format(label),
                    'label': label,
                    'image': rng.integers(0, 255, (height, width, 3), dtype=np.uint8),
                })
    print('Wrote {} rows to {}'.format(classes * images_per_class, output_url))


def generate_from_folders(output_url, image_root):
    import cv2
    class_dirs = sorted(d for d in os.listdir(image_root)
                        if os.path.isdir(os.path.join(image_root, d)))
    with materialize_dataset(output_url, ImagenetSchema, row_group_size_mb=64) as writer:
        for label, noun_id in enumerate(class_dirs):
            class_dir = os.path.join(image_root, noun_id)
            for fname in sorted(os.listdir(class_dir)):
                bgr = cv2.imread(os.path.join(class_dir, fname))
                if bgr is None:
                    continue
                writer.write({
                    'noun_id': noun_id,
                    'text': noun_id,
                    'label': label,
                    'image': cv2.cvtColor(bgr, cv2.COLOR_BGR2RGB),
                })
    print('Wrote dataset to {}'.format(output_url))


if __name__ == '__main__':
    parser = argparse.ArgumentParser()
    parser.add_argument('--output-url', default='file:///tmp/imagenet_dataset')
    parser.add_argument('--image-root', default=None,
                        help='Directory of class-named folders of JPEGs')
    parser.add_argument('--classes', type=int, default=10)
    parser.add_argument('--images-per-class', type=int, default=50)
    args = parser.parse_args()
    if args.image_root:
        generate_from_folders(args.output_url, args.image_root)
    else:
        generate_synthetic(args.output_url, args.classes, args.images_per_class)
