"""Parallelism strategies over TPU device meshes.

The reference is a data-parallel product whose extension point for hybrid
schemes is process sets (reference: horovod/common/process_sets.py,
SURVEY.md §2.6 — TP/PP/SP/EP are explicitly absent there). This package is
the TPU-native strategy layer built on that substrate: every strategy is a
mesh axis, every data exchange is an XLA collective over ICI.

- mesh:            N-D mesh construction + axis bookkeeping (dp/fsdp/tp/pp)
- ring_attention:  context parallelism — blockwise attention with k/v blocks
                   rotating over the 'sp' axis via ppermute
- ulysses:         sequence parallelism via head-scatter all_to_all
- sharding:        parameter/activation PartitionSpec rules (tp + fsdp)
- pipeline:        pipeline parallelism via shard_map + microbatch streaming
- moe:             sparse experts — a dropless, sigmoid-routed expert layer
                   told which experts it holds (one chip's share of an
                   expert-parallel layer; grouped products, shared expert)
"""

import time as _time
_T0 = _time.perf_counter()      # first line: the start-up log's span

from .mesh import MeshConfig, make_mesh  # noqa: F401
from .ring_attention import ring_attention  # noqa: F401
from .ulysses import ulysses_attention  # noqa: F401
from .sharding import (  # noqa: F401
    transformer_param_rules, make_param_specs, shard_params,
    constrain, batch_spec,
)
from .pipeline import pipeline_apply  # noqa: F401
from .moe import MoELayer, moe_apply  # noqa: F401

from ..utils import compile_cache as _startup
_startup.imported(__name__, _T0)
