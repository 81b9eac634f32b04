"""Model zoo for examples, tests, and benchmarks.

Counterpart of the reference's examples/ model usage (reference:
examples/keras/keras_mnist.py LeNet-style CNN, examples/tensorflow2
ResNet-50 via tf.keras.applications, examples/pytorch synthetic benchmark).
All models are flax.linen modules designed TPU-first: channels-last,
bfloat16-friendly, static shapes.
"""

import time as _time
_T0 = _time.perf_counter()      # first line: the start-up log's span

from .mlp import MLP, MnistCNN  # noqa: F401
from .resnet import ResNet50, ResNet18, ResNet101  # noqa: F401
from .transformer import (  # noqa: F401
    TransformerLM, TransformerConfig, BertConfig, BertModel,
    looped_lm_loss, publish_exit_shares,
)
from .ssm import SSMConfig  # noqa: F401

from ..utils import compile_cache as _startup
_startup.imported(__name__, _T0)
