"""Fused on-device image preprocessing.

The last hop of the input pipeline — uint8 HBM batches -> normalized bf16 —
runs on-device so the host hands over raw bytes (4x smaller transfers than
shipping float32) and the cast/scale/shift fuses into one VMEM pass instead
of materializing float intermediates in HBM.

``normalize_images`` is a Pallas TPU kernel (VPU elementwise over (8,128)
tiles) on a TPU, where nothing catches a Mosaic compile failure;
``normalize_images_reference`` is the pure-XLA equivalent, the correctness
oracle in tests and on the chip (bit-exact there, ``chip_smoke.py``) and
what every other backend runs.
"""

import functools

import jax
import jax.numpy as jnp

_IMAGENET_MEAN = (0.485, 0.456, 0.406)
_IMAGENET_STD = (0.229, 0.224, 0.225)


def normalize_images_reference(images, mean=_IMAGENET_MEAN, std=_IMAGENET_STD,
                               dtype=jnp.bfloat16):
    """Pure-XLA: uint8 NHWC -> ((x/255) - mean)/std in ``dtype``."""
    mean = jnp.asarray(mean, jnp.float32)
    std = jnp.asarray(std, jnp.float32)
    x = images.astype(jnp.float32) / 255.0
    return ((x - mean) / std).astype(dtype)


def _normalize_kernel(images_ref, scale_ref, shift_ref, out_ref):
    # One grid step owns a (block_n, H*W*C) tile: each image is one ROW, so
    # the lane dimension is H*W*C wide and tiles (8,128) densely. Keeping
    # NHWC blocks instead would put C in the lane dimension — Mosaic pads
    # lanes to 128, a 42x VMEM blowup for C=3 that OOMs scoped vmem on real
    # chips (found on first hardware contact; interpret mode never sees it).
    x = images_ref[...]
    if x.dtype == jnp.uint8:
        # Mosaic has no direct uint8->f32 cast; widen through int32.
        x = x.astype(jnp.int32)
    x = x.astype(jnp.float32)
    # scale/shift are (1, H*W*C) rows (the per-channel constants tiled out):
    # broadcast over the batch block.
    out_ref[...] = (x * scale_ref[...] + shift_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=('dtype', 'interpret'))
def _normalize_pallas(images, scale, shift, dtype=jnp.bfloat16, interpret=False):
    from jax.experimental import pallas as pl

    n, h, w, c = images.shape
    length = h * w * c
    flat = images.reshape(n, length)
    scale_row = jnp.tile(scale.reshape(-1), length // c).reshape(1, length)
    shift_row = jnp.tile(shift.reshape(-1), length // c).reshape(1, length)
    # Mosaic requires the sublane block divisible by 8 and the lane block
    # divisible by 128. Rather than falling back to whole-dimension blocks
    # for awkward shapes (an eval tail batch of 100 rows, a 300x300x3 image
    # whose flattened length is not a 128-multiple) — which is exactly the
    # unbounded-VMEM cliff this kernel once hit on real chips — PAD: rows
    # up to a multiple of 8, lanes up to a multiple of 128, and slice the
    # pad back off after. The kernel computes garbage in the pad cells
    # (0 * scale + shift); it is never read.
    n_pad = -(-n // 8) * 8
    l_pad = -(-length // 128) * 128
    if n_pad != n:
        flat = jnp.pad(flat, ((0, n_pad - n), (0, 0)))
    if l_pad != length:
        flat = jnp.pad(flat, ((0, 0), (0, l_pad - length)))
        scale_row = jnp.pad(scale_row, ((0, 0), (0, l_pad - length)))
        shift_row = jnp.pad(shift_row, ((0, 0), (0, l_pad - length)))
    # 8 rows x <=32K lanes of f32 double-buffers under ~2MB of the 16MB
    # scoped VMEM; block_l is the largest 128-multiple divisor of l_pad
    # within that budget (always >=128 since l_pad is a 128-multiple).
    block_l = l_pad
    if l_pad > (1 << 15):
        for lanes in range(1 << 15, 0, -128):
            if l_pad % lanes == 0:
                block_l = lanes
                break
    out = pl.pallas_call(
        _normalize_kernel,
        grid=(n_pad // 8, l_pad // block_l),
        in_specs=[
            pl.BlockSpec((8, block_l), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_l), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_l), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((8, block_l), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, l_pad), dtype),
        interpret=interpret,
    )(flat, scale_row, shift_row)
    return out[:n, :length].reshape(n, h, w, c)


def normalize_images(images, mean=_IMAGENET_MEAN, std=_IMAGENET_STD,
                     dtype=jnp.bfloat16):
    """Fused uint8->normalized-``dtype`` conversion.

    The Pallas kernel on a TPU, always; the XLA reference on any other
    backend (interpret mode is only for tests — XLA fuses this fine on CPU).
    """
    if images.ndim != 4:
        raise ValueError('Expected NHWC batch, got shape {}'.format(images.shape))
    if jax.default_backend() == 'tpu':
        return _normalize_pallas(images, *_scale_shift(mean, std),
                                 dtype=dtype)
    return normalize_images_reference(images, mean, std, dtype)


def _scale_shift(mean=_IMAGENET_MEAN, std=_IMAGENET_STD):
    """The kernel's per-channel coefficients: /255, -mean and /std folded
    into one multiply-add, ``x * scale + shift``."""
    mean = jnp.asarray(mean, jnp.float32)
    std = jnp.asarray(std, jnp.float32)
    return ((1.0 / (255.0 * std)).reshape(1, 1, 1, -1),
            (-mean / std).reshape(1, 1, 1, -1))


def random_flip_and_normalize(rng, images, mean=_IMAGENET_MEAN, std=_IMAGENET_STD,
                              dtype=jnp.bfloat16):
    """Per-sample random horizontal flip + fused normalization (train-time)."""
    n = images.shape[0]
    flips = jax.random.bernoulli(rng, 0.5, (n,))
    flipped = jnp.where(flips[:, None, None, None],
                        jnp.flip(images, axis=2), images)
    return normalize_images(flipped, mean, std, dtype)
