"""ResNet family (v1.5 bottleneck), TPU-first.

The reference benchmarks ResNet-50/101 through tf_cnn_benchmarks and
tf.keras.applications (reference: docs/benchmarks.rst:19-66,
examples/tensorflow2/tensorflow2_synthetic_benchmark.py:24 applications
ResNet50). This is a from-scratch flax implementation: channels-last (NHWC,
the TPU conv layout), bfloat16 compute with fp32 variables, and optional
cross-replica SyncBatchNorm via ``axis_name`` (the TPU-native analog of the
reference's sync_batch_norm, reference: horovod/torch/sync_batch_norm.py —
flax BatchNorm pmeans batch stats over the mesh axis when axis_name is set).
"""

from functools import partial
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: tuple = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class BasicBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: tuple = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


def _space_to_depth(x, block=2):
    """NHWC space-to-depth: (B,H,W,C) -> (B,H/b,W/b,b*b*C) with channel
    order (di*b+dj)*C + c."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // block, w // block, block * block * c)


def stem_weights_to_space_to_depth(w7):
    """Map a (7,7,C,F) stem kernel to the equivalent (4,4,4C,F)
    space-to-depth kernel (zero-pad to 8x8, fold the 2x2 phase into
    input channels) — lets checkpoints trained with either stem load
    into the other."""
    import numpy as np
    k, _, c, f = w7.shape
    assert k == 7, w7.shape
    w8 = np.zeros((8, 8, c, f), w7.dtype)
    w8[1:8, 1:8] = np.asarray(w7)
    w4 = np.zeros((4, 4, 4 * c, f), w7.dtype)
    for da in range(2):
        for db in range(2):
            w4[:, :, (da * 2 + db) * c:(da * 2 + db + 1) * c] = \
                w8[da::2, db::2]
    return w4


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    axis_name: Optional[str] = None   # set to 'hvd' for SyncBatchNorm
    # False | True/"full" | "dots" (save conv outputs, recompute
    # elementwise BN/ReLU) — trades recompute for backward-pass HBM,
    # pushing the batch-size spill cliff out (round 2 on a v5e, by
    # bench.py's clock: without it throughput halves between batch
    # 448 and 512; with it 512 fits, but the recomputed convolutions
    # cost more than the batch buys, so the benchmark runs without).
    remat: Any = False
    # "conv" (classic 7x7/s2) | "space_to_depth": reorganize the input
    # to (H/2, W/2, 4C) and run an equivalent 4x4/s1 conv — the 7x7
    # stem's contraction dim (7*7*3=147) underfills the MXU; the
    # space-to-depth form (4*4*12=192, no stride) tiles better (the
    # standard MLPerf-era TPU ResNet stem).
    stem: str = "conv"

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                       axis_name=self.axis_name if train else None)
        x = x.astype(self.dtype)
        if self.stem == "space_to_depth":
            x = _space_to_depth(x)
            # Exactly equivalent to the 7x7/s2 conv: the 7x7 kernel
            # zero-pads to 8x8 (pad (4,3) in pixels = (2,1) in blocks)
            # and folds its 2x2 phase into the input channels.
            x = conv(self.num_filters, (4, 4), (1, 1),
                     padding=[(2, 1), (2, 1)], name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        block_cls = self.block_cls
        if self.remat == "dots":
            block_cls = nn.remat(
                block_cls,
                policy=jax.checkpoint_policies.
                dots_with_no_batch_dims_saveable)
        elif self.remat:
            block_cls = nn.remat(block_cls)
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block_cls(self.num_filters * 2 ** i,
                              conv=conv, norm=norm,
                              strides=strides)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3],
                   block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3],
                    block_cls=BottleneckBlock)
