"""N-D device mesh construction and axis bookkeeping.

The reference's GLOBAL/LOCAL/CROSS communicator triple (reference:
horovod/common/common.h:166-183, gloo_context.cc:216-228) is how it runs
hierarchical algorithms. On TPU the same idea is a named mesh: axes that ride
ICI (fast, within a slice) vs DCN (across slices). MeshConfig owns the axis
layout; strategies reference axes by name.

Axis convention (outer → inner, slowest → fastest wire):
  dp   — data parallelism (pure replication of params)
  fsdp — data parallelism with parameter sharding (ZeRO-3 style)
  pp   — pipeline stages
  sp   — sequence/context parallelism (ring attention / Ulysses)
  tp   — tensor parallelism (innermost: highest-bandwidth ICI neighbors)

``ep`` (expert parallelism) does not get its own wires: experts shard over
the ('dp','fsdp') axes (the standard mapping — the token exchange of an
expert-parallel layer, not built yet, would ride the data-parallel axis),
see moe.py and ROADMAP B3.
"""

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np

AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each parallelism axis; -1 on dp = "use remaining devices"."""

    dp: int = -1
    fsdp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        known = self.fsdp * self.pp * self.sp * self.tp
        dp = self.dp
        if dp == -1:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by "
                    f"fsdp*pp*sp*tp={known}")
            dp = n_devices // known
        if dp * known != n_devices:
            raise ValueError(
                f"mesh {dp}x{self.fsdp}x{self.pp}x{self.sp}x{self.tp} != "
                f"{n_devices} devices")
        return dataclasses.replace(self, dp=dp)

    @property
    def shape(self):
        return (self.dp, self.fsdp, self.pp, self.sp, self.tp)

    @property
    def data_axes(self):
        """Axes gradients are reduced over (batch is sharded over these)."""
        return ("dp", "fsdp")


def make_mesh(config: Optional[MeshConfig] = None,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """Build the named mesh. Device order follows jax.devices(), which on
    TPU enumerates in physical-torus order so the innermost ('tp') axis
    lands on nearest ICI neighbors."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    config = (config or MeshConfig()).resolve(len(devices))
    arr = np.asarray(devices).reshape(config.shape)
    return jax.sharding.Mesh(arr, AXIS_ORDER)
