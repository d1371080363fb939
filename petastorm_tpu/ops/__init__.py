"""On-device input-path ops (Pallas TPU kernels with XLA fallbacks)."""

from petastorm_tpu.ops.augment import (color_jitter, cutmix,  # noqa: F401
                                       imagenet_eval_preprocess,
                                       imagenet_train_augment, mixup,
                                       random_crop, random_flip,
                                       random_resized_crop, train_augment)
from petastorm_tpu.ops.flash_attention import flash_attention  # noqa: F401
from petastorm_tpu.ops.gated_delta import gated_delta_rule  # noqa: F401
from petastorm_tpu.ops.grouped_matmul import grouped_matmul  # noqa: F401
from petastorm_tpu.ops.kimi_delta import kda_rule  # noqa: F401
from petastorm_tpu.ops.ssd import ssd_rule  # noqa: F401
from petastorm_tpu.ops.image_ops import (normalize_images,  # noqa: F401
                                         normalize_images_reference,
                                         random_flip_and_normalize)
