"""Test fixtures: run everything on an 8-device virtual CPU mesh.

Mirrors the reference's CI strategy of simulating distribution on localhost
(reference: .buildkite/gen-pipeline.sh runs parallel tests at np=2 on one
machine). Here "multi-chip" is 8 virtual XLA CPU devices
(xla_force_host_platform_device_count), which exercises the same shard_map/
collective code paths the TPU mesh uses.
"""

import os
import sys

# Must happen before the first JAX backend initialization.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# Tests always run on the virtual CPU mesh, whatever JAX_PLATFORMS says.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Modules dominated by launcher-spawned subprocess jobs (the reference's
# horovodrun-under-CI pattern): minutes each. `pytest -m "not slow"`
# keeps the in-process suites — the fast iteration loop.
_SLOW_MODULES = {
    "test_spmd", "test_examples", "test_cluster", "test_frameworks",
    "test_elastic", "test_xla_global", "test_chaos_matrix",
    "test_fleet_matrix",
}
# Individual subprocess-spawning tests inside otherwise-fast modules
# (spawned workers may contend for the real chip; the fast lane stays
# in-process on the CPU mesh).
_SLOW_NAMES = {
    "test_autotune_spmd_convergence",
    "test_fit_on_parquet_np2",
    "test_fit_on_parquet_torch_np2",
    "test_fit_on_parquet_lightning_np2",
    "test_launch_two_ranks_end_to_end",
    "test_run_command_spmd_worker",
    "test_hvdrun_console_entry",
    "test_output_filename_captures_per_rank",
    "test_run_programmatic",
    "test_failed_rank_fails_job",
    "test_run_command_multi_host_topology",
    # In-process but compile-heavy (~20s each): keep the fast lane <3min.
    "test_resnet_remat_variants_run",
    "test_space_to_depth_stem_equivalent",
    "test_transformer_remat_variants_run",
    "test_keras_applications_model_on_mesh",
    "test_keras_applications_through_bridge",
    "test_fsdp_training_matches_replicated",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: launcher-spawned multi-process test (minutes); "
        "deselect with -m 'not slow'")


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = getattr(item.module, "__name__", "")
        if mod in _SLOW_MODULES or item.name.split("[")[0] in _SLOW_NAMES:
            item.add_marker(pytest.mark.slow)


def clean_spawn_env(**overrides):
    """Base env for worker subprocesses: drop pytest-process state that
    must not leak (XLA device-count flags; the keras backend another
    test module may have claimed at import), pin the CPU platform, then
    apply overrides. One helper so the next leaking variable is fixed
    in one place."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("KERAS_BACKEND", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(overrides)
    return env


@pytest.fixture(scope="session")
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    yield hvd


@pytest.fixture(scope="session")
def n_devices():
    return len(jax.devices())


@pytest.fixture
def fresh_log(monkeypatch):
    """The test writes to a program's log (``utils/compile_cache.py``)
    of its own."""
    import collections
    from horovod_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "_log", [])
    monkeypatch.setattr(compile_cache, "_steady", collections.deque(
        maxlen=compile_cache.STEADY_KEPT))
    monkeypatch.setattr(compile_cache, "_followers", [])
    monkeypatch.setattr(compile_cache, "_stages", {
        stage: compile_cache._Union() for stage in compile_cache.STAGES})
