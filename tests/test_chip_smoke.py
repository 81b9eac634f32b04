"""What can be held about the chip path without a chip: chip_smoke.py
refuses the CPU, the compile cache can be placed from outside and
otherwise stays put, and nothing second-guesses JAX_PLATFORMS."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "jax.default_backend()='cpu'" in out.stderr
    assert "JAX_PLATFORMS='cpu'" in out.stderr
    assert '"ok"' not in out.stdout


_CACHE_SCRIPT = """
import sys
sys.path.insert(0, {repo!r})
import jax
from horovod_tpu.utils import compile_cache
print(compile_cache.enable())
print(jax.config.jax_compilation_cache_dir)
"""


def _cache_dirs(cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT.format(repo=REPO)],
        capture_output=True, text=True, timeout=300, cwd=cwd,
        env=dict(base, JAX_PLATFORMS="cpu", **env))
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_compile_cache_defaults_to_one_path_in_the_checkout(tmp_path):
    expected = os.path.join(REPO, ".jax_cache")
    assert _cache_dirs(str(tmp_path)) == [expected, expected]
    assert _cache_dirs(REPO) == [expected, expected]


def test_compile_cache_leaves_an_outside_placement_alone(tmp_path):
    placed = str(tmp_path / "placed")
    assert _cache_dirs(str(tmp_path), JAX_COMPILATION_CACHE_DIR=placed) \
        == [placed, placed]


def test_basics_has_no_platform_override():
    from horovod_tpu import basics
    assert not hasattr(basics, "FORCED_PLATFORM_MARKERS")


def test_launcher_processes_may_not_share_a_tpu_host():
    import pytest
    from horovod_tpu import basics
    from horovod_tpu.exceptions import TpuHostSharedError
    with pytest.raises(TpuHostSharedError, match="JAX_PLATFORMS='tpu,cpu'"):
        basics._refuse_shared_tpu_host(2, "tpu,cpu")
    basics._refuse_shared_tpu_host(1, "tpu,cpu")   # one process per host
    basics._refuse_shared_tpu_host(2, "cpu")       # host-plane job
    basics._refuse_shared_tpu_host(2, None)        # JAX picks; not ours
