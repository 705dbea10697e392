"""Model zoo used by the examples, benchmarks, and tests.

The reference ships models only inside its examples (reference
examples/pytorch_imagenet_resnet50.py, examples/tensorflow_mnist.py,
examples/keras_mnist.py …); we promote them to a package so the benchmark
harness, the graft entry point, and users share one TPU-tuned implementation.
"""

from horovod_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from horovod_tpu.models.vgg import VGG, VGG16, VGG19  # noqa: F401
from horovod_tpu.models.inception import InceptionV3  # noqa: F401
from horovod_tpu.models.mnist import MnistCNN, MnistMLP  # noqa: F401
from horovod_tpu.models.moe import (  # noqa: F401
    MOE_LOSSES,
    MOE_STATS,
    MoEMLP,
    moe_aux_loss,
)
from horovod_tpu.models.transformer import (  # noqa: F401
    Transformer,
    TransformerConfig,
)
