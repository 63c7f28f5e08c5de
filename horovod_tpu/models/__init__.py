"""Model zoo for benchmarks and examples.

The reference ships no model library — its examples lean on framework
zoos (`torchvision.models.resnet50`, `keras.applications.ResNet50`,
reference: examples/pytorch_synthetic_benchmark.py:28-30,
examples/keras_imagenet_resnet50.py). A TPU-native framework has no
such zoo to lean on, so the models the reference's examples and
benchmarks require are provided here in flax, bf16-friendly and
MXU-shaped.
"""

from horovod_tpu.models.resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101
from horovod_tpu.models.transformer import TransformerConfig, TransformerLM
from horovod_tpu.models.glm_moe import GlmMoeConfig, GlmMoeLM
from horovod_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashLM
from horovod_tpu.models.qwen3next import Qwen3NextConfig, Qwen3NextLM
from horovod_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeLM
from horovod_tpu.models.ling3flash import Ling3FlashConfig, Ling3FlashLM
from horovod_tpu.models.olmo_hybrid import OlmoHybridConfig, OlmoHybridLM
from horovod_tpu.models.smallthinker import SmallThinkerConfig, SmallThinkerLM
from horovod_tpu.models.mnist import MnistConvNet
from horovod_tpu.models.vit import ViT, ViTConfig, ViT_S16, ViT_B16

__all__ = [
    "ResNet", "ResNet18", "ResNet34", "ResNet50", "ResNet101",
    "TransformerConfig", "TransformerLM", "GlmMoeConfig", "GlmMoeLM",
    "Phi4FlashConfig", "Phi4FlashLM", "Qwen3NextConfig", "Qwen3NextLM",
    "Lfm2MoeConfig", "Lfm2MoeLM", "Ling3FlashConfig", "Ling3FlashLM",
    "OlmoHybridConfig", "OlmoHybridLM",
    "SmallThinkerConfig", "SmallThinkerLM",
    "MnistConvNet",
    "ViT", "ViTConfig", "ViT_S16", "ViT_B16",
]
