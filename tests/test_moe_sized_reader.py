"""The benchmark's reader ``moe_sized_pct`` on made-up draws: the share
of the expert layers whose last step ran on buffers sized to the draw,
by the program's own test, and nothing where the program has no such
path or the cell no expert layer."""

import os
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from benchmark.layers import Context  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402

BUILDER = "benchmark/builders/glm4_moe_lite.py"
CFG = {"builder": BUILDER, "experts_held": [0, 2]}
# 1024 pairs over 8 experts, 2 held: 512 rows (moe.sized_rows).
FITS = [150.0, 250, 104, 104, 104, 104, 104, 104]
OVER = [300.0, 400, 54, 54, 54, 54, 54, 54]


def reader():
    return harness.load_module(
        REPO, "benchmark/layer_metrics/moe_sized_pct.py")


@pytest.fixture
def draw():
    builder = harness.load_module(REPO, BUILDER)

    def put(*layers):
        builder.DRAW["aux"] = {"moe_state": {
            f"block_{i}": {"moe": {"bias": jnp.zeros((8,)),
                                   "expert_tokens": jnp.asarray(drawn)}}
            for i, drawn in enumerate(layers)}}
    yield put
    builder.DRAW.clear()


@pytest.mark.parametrize("layers,want", [
    ([FITS, FITS, FITS], 100.0), ([FITS, OVER, FITS, FITS], 75.0),
    ([OVER], 0.0)], ids=["all_fit", "one_over", "none_fits"])
def test_share_of_layers_on_sized_buffers(draw, layers, want):
    draw(*layers)
    assert reader().read(Context(cell={"cfg": CFG}, root=REPO)) == want


def test_every_expert_held_has_no_sized_path(draw):
    draw(FITS, FITS)
    cfg = dict(CFG, experts_held=[0, 8])
    assert reader().read(Context(cell={"cfg": cfg}, root=REPO)) == 0.0


def test_reads_nothing_without_the_programs_test(draw, monkeypatch):
    draw(FITS)
    monkeypatch.delattr(moe, "took_sized_path")
    assert reader().read(Context(cell={"cfg": CFG}, root=REPO)) is None


@pytest.mark.parametrize("cfg", [{}, {"builder": BUILDER}, CFG],
                         ids=["no_builder", "no_experts_held", "no_draw"])
def test_reads_nothing_without_an_expert_layers_draw(cfg):
    assert reader().read(Context(cell={"cfg": cfg}, root=REPO)) is None
