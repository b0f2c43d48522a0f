"""Model zoo for the acceptance workloads (SURVEY.md §2.8, BASELINE.md).

All flax, all TPU-first: NHWC convs / flash-attention transformers,
bfloat16 compute with float32 parameters.
"""

from horovod_tpu.models.mnist import MnistConvNet
from horovod_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from horovod_tpu.models.vgg import VGG, VGG11, VGG13, VGG16, VGG19
from horovod_tpu.models.inception import InceptionV3
from horovod_tpu.models import moe
from horovod_tpu.models.hybrid import HybridDecoder
from horovod_tpu.models.transformer import (
    BertBase,
    BertLarge,
    GPT2Medium,
    GPT2Small,
    Transformer,
    causal_lm_loss,
    masked_lm_loss,
    random_tokens,
)

__all__ = [
    "MnistConvNet",
    "ResNet", "ResNet18", "ResNet34", "ResNet50", "ResNet101", "ResNet152",
    "VGG", "VGG11", "VGG13", "VGG16", "VGG19", "InceptionV3", "moe",
    "HybridDecoder", "Transformer", "BertBase", "BertLarge", "GPT2Small",
    "GPT2Medium",
    "causal_lm_loss", "masked_lm_loss", "random_tokens",
]
