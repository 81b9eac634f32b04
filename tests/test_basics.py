"""Runtime init/topology tests (reference analog: rank/size assertions at
the top of test/parallel/test_tensorflow.py:128+)."""

import jax
import numpy as np
import pytest


def test_init_idempotent(hvd):
    assert hvd.is_initialized()
    hvd.init()
    assert hvd.is_initialized()


def test_topology_single_mode(hvd, n_devices):
    assert hvd.size() == n_devices == 8
    assert hvd.rank() == 0
    assert hvd.local_size() == n_devices
    assert hvd.cross_rank() == 0
    assert hvd.cross_size() == 1
    assert hvd.is_homogeneous()


def test_mesh(hvd, n_devices):
    mesh = hvd.mesh()
    assert mesh.axis_names == ("hvd",)
    assert mesh.devices.size == n_devices


def test_feature_queries(hvd):
    assert hvd.xla_built()
    # TCP backend (the gloo analog) reports built only when importable.
    assert hvd.gloo_built() == hvd.gloo_enabled()
    assert not hvd.nccl_built()
    assert not hvd.cuda_built()
    assert not hvd.mpi_built()


def test_global_process_set(hvd, n_devices):
    from horovod_tpu.process_sets import global_process_set
    assert global_process_set.process_set_id == 0
    assert global_process_set.size() == n_devices
    assert global_process_set.included()
    assert global_process_set.rank() == 0


def test_not_initialized_error():
    import horovod_tpu.basics as basics
    from horovod_tpu.exceptions import NotInitializedError
    saved = basics._runtime
    basics._runtime = None
    try:
        with pytest.raises(NotInitializedError):
            basics.runtime()
    finally:
        basics._runtime = saved


def test_empty_grouped_ops_check_liveness():
    """A dynamically-empty grouped collective must still surface a dead
    runtime instead of silently succeeding."""
    import horovod_tpu as hvd
    import horovod_tpu.basics as basics
    from horovod_tpu.exceptions import NotInitializedError
    saved = basics._runtime
    basics._runtime = None
    try:
        with pytest.raises(NotInitializedError):
            hvd.grouped_allreduce([])
        with pytest.raises(NotInitializedError):
            hvd.grouped_allgather_async([])
    finally:
        basics._runtime = saved


def test_timeline_with_jax_profiler(hvd, tmp_path):
    """start_timeline with jax_profiler_dir captures a device trace
    alongside the chrome-trace host timeline."""
    import json
    import os
    import jax
    import jax.numpy as jnp

    trace = tmp_path / "tl.json"
    profdir = tmp_path / "jaxprof"
    hvd.start_timeline(str(trace), jax_profiler_dir=str(profdir))
    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    hvd.allreduce(jnp.ones((len(jax.devices()), 4)), name="tlprof")
    hvd.stop_timeline()
    events = json.load(open(trace))
    assert isinstance(events, list)
    # The profiler wrote its plugin directory structure.
    found = any("plugins" in dirs for _, dirs, _f in os.walk(profdir))
    assert found, list(os.walk(profdir))
    # The coordinator's spans are in the profiler's trace too, on its
    # clock, under the "hvd:" prefix.
    import glob
    from jax.profiler import ProfileData
    (pb,) = glob.glob(str(profdir / "plugins/profile/*/*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(pb).planes
             for line in plane.lines for e in line.events}
    assert any(n.startswith("hvd:") for n in names), sorted(names)[:50]


def test_checkpoint_save_restore(hvd, tmp_path):
    pytest.importorskip("orbax.checkpoint")
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu import checkpoint as ckpt

    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "epoch": np.asarray(4)}
    ckpt.save_step(tmp_path, 4, state)
    ckpt.save_step(tmp_path, 9, {"params": {"w": jnp.ones((2, 3)) * 7},
                                 "epoch": np.asarray(9)})
    assert ckpt.latest_step(tmp_path) == 9
    step, restored = ckpt.restore_latest(tmp_path)
    assert step == 9
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 7.0)
    assert int(restored["epoch"]) == 9
    # Direct restore of the older step.
    old = ckpt.restore(tmp_path / "step_4")
    np.testing.assert_allclose(np.asarray(old["params"]["w"]),
                               np.arange(6.0).reshape(2, 3))
    assert ckpt.restore_latest(tmp_path / "empty") == (None, None)


_SHARED_SURFACE = ["start_timeline", "stop_timeline", "ProcessSet",
                   "global_process_set", "add_process_set",
                   "remove_process_set", "Compression", "init",
                   "shutdown", "rank", "size", "elastic", "mpi_built",
                   "mpi_threads_supported", "gloo_built", "nccl_built",
                   "ddl_built", "ccl_built", "cuda_built", "rocm_built",
                   "metrics_snapshot"]


@pytest.mark.parametrize("mod_name,required,extra", [
    ("horovod_tpu.torch", "torch",
     ["SyncBatchNorm", "grouped_allreduce_", "grouped_allreduce_async",
      "grouped_allreduce_async_"]),
    ("horovod_tpu.tensorflow", "tensorflow",
     ["SyncBatchNormalization", "broadcast_", "broadcast_object_fn",
      "rank_op", "size_op", "local_rank_op", "local_size_op",
      "process_set_included_op", "gpu_available",
      "check_num_rank_power_of_2"]),
])
def test_binding_surface_parity(mod_name, required, extra):
    """Every framework binding re-exports the shared runtime surface the
    reference exposes per binding (reference: horovod/torch/__init__.py:
    48-53 — timeline start/stop + process-set API + Compression).
    Parametrized so a missing framework skips only its own row."""
    import importlib
    pytest.importorskip(required)
    m = importlib.import_module(mod_name)
    for name in _SHARED_SURFACE + extra:
        assert hasattr(m, name), (mod_name, name)


def test_keras_elastic_surface():
    pytest.importorskip("keras")
    import horovod_tpu.keras as hk
    assert hasattr(hk.elastic, "KerasState")
    assert not hasattr(hk.elastic, "definitely_not_a_name")
