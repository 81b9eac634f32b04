"""Model zoo for examples, tests, and benchmarks.

Counterpart of the reference's examples/ model usage (reference:
examples/keras/keras_mnist.py LeNet-style CNN, examples/tensorflow2
ResNet-50 via tf.keras.applications, examples/pytorch synthetic benchmark).
All models are flax.linen modules designed TPU-first: channels-last,
bfloat16-friendly, static shapes.
"""

from .mlp import MLP, MnistCNN  # noqa: F401
from .resnet import ResNet50, ResNet18, ResNet101  # noqa: F401
from .transformer import (  # noqa: F401
    TransformerLM, TransformerConfig, BertConfig, BertModel,
    looped_lm_loss, publish_exit_shares,
)
from .ssm import SSMConfig  # noqa: F401
