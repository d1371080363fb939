"""ResNet-50 on an ImageNet-style store: the BASELINE.json north-star workload.

Pod-sharded reading (``cur_shard=jax.process_index()``), mesh-sharded batches,
pjit train step. On a v4-32 run one process per host; this script is the same
code single-host.
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))

import argparse
import time

import jax
import numpy as np

from petastorm_tpu import make_reader
from petastorm_tpu.jax_loader import CropTo, JaxLoader
from petastorm_tpu.models.resnet import ResNet50
from petastorm_tpu.models.train import create_train_state, make_train_step
from petastorm_tpu.parallel import make_mesh, process_shard
from petastorm_tpu.utils import enable_compile_cache


def train(dataset_url, global_batch=256, steps=100, image_size=224,
          model_parallel=1, log_every=10, augment=False):
    n_devices = len(jax.devices())
    mesh = make_mesh({'data': n_devices // model_parallel, 'model': model_parallel})
    cur_shard, shard_count = process_shard()

    model = ResNet50(num_classes=1000)
    state = create_train_state(jax.random.PRNGKey(0), model,
                               (1, image_size, image_size, 3), mesh=mesh,
                               learning_rate=0.1)
    if augment:
        # Full Inception recipe ON DEVICE (random resized crop, flip,
        # color jitter, normalize): the host ships raw uint8 and XLA fuses
        # the augmentation into the first conv's input — a host-side
        # TransformSpec would pay CPU for every augmented byte and ship
        # 4x the h2d traffic as float32. Compose the UN-jitted step body
        # (make_train_step_fn) under one jit — wrapping the jitted
        # make_train_step would nest donation and forfeit the buffer.
        import functools

        from petastorm_tpu.models.train import make_train_step_fn
        from petastorm_tpu.ops.augment import imagenet_train_augment

        step_fn = make_train_step_fn(mesh=mesh)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(state, images_u8, labels, key):
            images = imagenet_train_augment(images_u8, key,
                                            out_h=image_size,
                                            out_w=image_size)
            return step_fn(state, images, labels)

        aug_key = jax.random.PRNGKey(42)
    else:
        inner_step = make_train_step(mesh=mesh)

        def train_step(state, images_u8, labels, key):
            del key
            return inner_step(state, images_u8.astype('float32') / 255.0,
                              labels)

        aug_key = None

    # Augment mode stages a LARGER canvas (the classic 256/224 ratio) so
    # the device-side random resized crop has spatial room to sample —
    # center-cropping straight to image_size first would confine the
    # "random" crop to one fixed window. True full-image diversity on
    # ragged stores would need per-sample host resize; the 8/7 canvas is
    # the standard approximation (stored images must be at least that big).
    canvas = image_size * 8 // 7 if augment else image_size
    crop = CropTo((canvas, canvas, 3))
    step = 0
    times = []
    with make_reader(dataset_url, schema_fields=['image', 'label'],
                     num_epochs=None, cur_shard=cur_shard,
                     shard_count=shard_count, workers_count=10,
                     shuffle_row_groups=True, seed=0) as reader:
        with JaxLoader(reader, global_batch, mesh=mesh,
                       shape_policies={'image': crop}) as loader:
            # time whole iterations (fetch + step) so input stall shows up
            prev = time.perf_counter()
            for batch in loader:
                key = (jax.random.fold_in(aug_key, step)
                       if aug_key is not None else None)
                state, metrics = train_step(
                    state, batch.image, batch.label, key)
                jax.block_until_ready(metrics['loss'])
                now = time.perf_counter()
                times.append(now - prev)
                prev = now
                step += 1
                if step % log_every == 0:
                    rate = global_batch / np.mean(times[-log_every:])
                    print('step {}: loss {:.4f} | {:.1f} img/s ({:.1f} img/s/chip)'.format(
                        step, float(metrics["loss"]), rate, rate / n_devices))
                if step >= steps:
                    break
    return state


if __name__ == '__main__':
    parser = argparse.ArgumentParser()
    parser.add_argument('--dataset-url', default='file:///tmp/imagenet_dataset')
    parser.add_argument('--global-batch', type=int, default=256)
    parser.add_argument('--steps', type=int, default=100)
    parser.add_argument('--image-size', type=int, default=224)
    parser.add_argument('--model-parallel', type=int, default=1)
    parser.add_argument('--augment', action='store_true',
                        help='full on-device Inception augmentation '
                             '(random resized crop, flip, color jitter)')
    args = parser.parse_args()
    enable_compile_cache()
    train(args.dataset_url, args.global_batch, args.steps, args.image_size,
          args.model_parallel, augment=args.augment)
