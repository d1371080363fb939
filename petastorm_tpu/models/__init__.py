"""Reference training workloads fed by petastorm_tpu readers.

The reference ships example workloads (``examples/mnist``, ``examples/imagenet``
— SURVEY.md §2.8) that define its end-to-end story. These are their TPU-native
equivalents: flax models consumed through ``jax_loader`` with mesh sharding.
"""

from petastorm_tpu.models.hybrid import HybridLM  # noqa: F401
from petastorm_tpu.models.latent_moe import LatentMoELM  # noqa: F401
from petastorm_tpu.models.ling_hybrid import LingHybridLM  # noqa: F401
from petastorm_tpu.models.mlp import MLP  # noqa: F401
from petastorm_tpu.models.nemotron_h import NemotronHLM  # noqa: F401
from petastorm_tpu.models.resnet import ResNet, ResNet18, ResNet50  # noqa: F401
from petastorm_tpu.models.moe import RoutedMoE, SwitchMoE  # noqa: F401
from petastorm_tpu.models.pipeline import pipeline_apply  # noqa: F401
from petastorm_tpu.models.transformer import TransformerLM  # noqa: F401
from petastorm_tpu.models.vit import ViT, ViTTiny  # noqa: F401
