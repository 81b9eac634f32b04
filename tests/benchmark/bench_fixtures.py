"""Fixtures of the benchmark's CPU tests (imported by name, not a
``conftest.py``: the tests one directory up import theirs as the module
``conftest``, and a second one would shadow it): a temporary root that holds
the benchmark as committed (linked, never edited) plus tiny cells added
the way a later PR adds them, as new files and BENCHMARK.json entries.

Nothing here touches a device or describes a topology at import.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
DATA_DIRS = ("configs", "traffic", "layer_metrics", "builders", "references")

BROKEN_BUILDER = '''
"""A timed path broken underneath: ``{mode}``."""
import jax
from benchmark import harness

def build(cfg, traffic, mesh, hvd_jax):
    sound = harness.load_module({repo!r}, {builder!r}).build(
        cfg, traffic, mesh, hvd_jax)
    sound_step = sound.step
    if {mode!r} == "frozen":
        # A step that returns its state unchanged.
        def step(*args):
            out = sound_step(*jax.tree.map(lambda x: x + 0, args))
            return (*args[:-1], out[-1])
    else:
        # A step that leaves out half of every chip's share of the batch.
        def step(*args):
            half = jax.tree.map(
                lambda x: x.at[x.shape[0] // 2:].set(x[:x.shape[0] // 2]),
                args[-1])
            return sound_step(*args[:-1], half)
    sound.step = jax.jit(step)
    return sound
'''

# A family the benchmark has never seen, as a later PR would bring it:
# a two-layer perceptron on vectors. Its reference counts its own
# operations; it has no attention and so no ``attention_shape``.
MLP_REFERENCE = '''
"""Plain reference of a two-layer perceptron."""
import math
import jax
import jax.numpy as jnp
from benchmark.references import common


def init_params(cfg, key):
    k1, k2 = jax.random.split(key)
    d, h, c = cfg["input_size"], cfg["hidden_size"], cfg["num_classes"]
    return {"w1": jax.random.normal(k1, (d, h)) / math.sqrt(d),
            "w2": jax.random.normal(k2, (h, c)) / math.sqrt(h)}


def init_aux(cfg):
    return {}


def loss_fn(params, aux, batch, cfg, precision="float32"):
    x, labels = batch
    x = jnp.tanh(common.einsum("bd,dh->bh", x, params["w1"], precision))
    logits = common.einsum("bh,hc->bc", x, params["w2"], precision)
    return common.softmax_xent_mean(logits, labels), aux


def flops_per_row(cfg, traffic):
    # The first layer needs no gradient with respect to its input.
    return 2 * (2 * cfg["input_size"] * cfg["hidden_size"]
                + 3 * cfg["hidden_size"] * cfg["num_classes"])
'''

MLP_BUILDER = '''
"""The perceptron through the program's train step."""
import jax
import jax.numpy as jnp
import optax
from benchmark.builders import Program
from benchmark.references import common


def build(cfg, traffic, mesh, hvd_jax):
    lr = cfg["optimizer"]["learning_rate"]
    opt = hvd_jax.DistributedOptimizer(optax.sgd(lr))

    def loss_fn(params, batch):
        x, labels = batch
        logits = jnp.tanh(x @ params["w1"]) @ params["w2"]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    @jax.jit
    def sqnorms(before, after):
        return common.leaf_sqnorms(jax.tree.map(
            jnp.subtract, before, after)) / lr ** 2

    return Program(
        step=hvd_jax.make_train_step(loss_fn, opt, mesh=mesh),
        init_state=lambda params, aux: (params, jax.jit(opt.init)(params)),
        first_grad_sqnorms=lambda state, before: sqnorms(before(),
                                                         state[0]))
'''


class Root:
    """A benchmark root in a temporary directory."""

    def __init__(self, path):
        self.path = str(path)
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        base = os.path.join(self.path, "benchmark")
        os.makedirs(base)
        for entry in os.listdir(os.path.join(REPO, "benchmark")):
            src = os.path.join(REPO, "benchmark", entry)
            if entry in DATA_DIRS:
                os.makedirs(os.path.join(base, entry))
                for f in os.listdir(src):
                    os.symlink(os.path.join(src, f),
                               os.path.join(base, entry, f))
            elif entry != "__pycache__":
                os.symlink(src, os.path.join(base, entry))
        self.committed = self.snapshot()
        self.write()

    def snapshot(self):
        """Every committed file of the benchmark with its bytes."""
        out = {}
        for d, _, files in os.walk(os.path.join(REPO, "benchmark")):
            if "__pycache__" in d:
                continue
            for f in files:
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.join(d, f)] = fh.read()
        return out

    def write(self):
        with open(os.path.join(self.path, "BENCHMARK.json"), "w") as f:
            json.dump(self.bench, f)

    def add_file(self, relpath, content):
        path = os.path.join(self.path, relpath)
        assert not os.path.lexists(path), f"{relpath} exists: not an add"
        with open(path, "w") as f:
            f.write(content if isinstance(content, str)
                    else json.dumps(content))

    def read_json(self, relpath):
        with open(os.path.join(REPO, relpath)) as f:
            return json.load(f)

    def add_config(self, name, like, **changes):
        cfg = self.read_json(f"benchmark/configs/{like}.json")
        cfg.update(name=name, **changes)
        self.add_file(f"benchmark/configs/{name}.json", cfg)
        self.bench["configs"].append({
            "name": name, "source": cfg["source"], "reduced": [],
            "file": f"benchmark/configs/{name}.json", "why": "test"})
        self.write()

    def add_traffic(self, name, like, **changes):
        traffic = self.read_json(f"benchmark/traffic/{like}.json")
        traffic.update(**changes)
        self.add_file(f"benchmark/traffic/{name}.json", traffic)

    def add_cell(self, name, config, traffic, chips, like):
        """A cell that reports what the cell ``like`` reports."""
        self.bench["workloads"].append({
            "name": name, "config": config, "traffic": traffic,
            "chips": chips, "why": "test"})
        for m in self.bench["end_to_end"] + self.bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
        self.write()

    def add_tiny_lm(self, chips=1, builder=None, name="lmtiny"):
        # The limits are this tiny size's own, read on the CPU the way
        # PERF.md reads the cells' on the chip: the program's largest
        # over seeds 1-3 is 1.5e-4 / 0.0075 / 0.016, the int8 control's
        # smallest 4.9e-4 / 0.027 / 0.012.
        changes = dict(hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=4, intermediate_size=256,
                       vocab_size=512, attention_impl="einsum",
                       limits={"loss_gap": 4e-4, "grad_norm_gap": 0.015,
                               "update_norm_gap": 0.05})
        if builder:
            changes["builder"] = builder
        self.add_config(name, "lm365m", **changes)
        if not os.path.lexists(os.path.join(
                self.path, "benchmark/traffic/seq128x2.json")):
            self.add_traffic(
                "seq128x2", "seq8192x2", seq_len=128, units_per_row=128,
                fields=[{"dist": "randint", "high": "vocab_size",
                         "shape": [129], "dtype": "int32",
                         "next_token": True}])
        like = ("lm365m-seq2048-4chip" if chips > 1
                else "lm365m-seq8192-1chip")
        cell = f"{name}-{chips}chip"
        self.add_cell(cell, name, "seq128x2", chips, like)
        return cell

    def add_tiny_resnet(self):
        self.add_config("resnettiny", "resnet50", stage_sizes=[1, 1],
                        num_filters=8, num_classes=10, image_size=32)
        self.add_traffic(
            "b8", "b384", rows_per_chip=8, check_rows=8, ring=2,
            fields=[{"dist": "uniform", "high": 1.0, "shape": [32, 32, 3],
                     "dtype": "bfloat16"},
                    {"dist": "randint", "high": "num_classes",
                     "shape": [], "dtype": "int32"}])
        self.add_cell("resnettiny-1chip", "resnettiny", "b8", 1,
                      "resnet50-b384-1chip")
        return "resnettiny-1chip"

    def add_mlp_family(self):
        """A configuration of a new family with its builder, reference
        and traffic, and a cell: new files and entries only."""
        self.add_file("benchmark/references/mlp.py", MLP_REFERENCE)
        self.add_file("benchmark/builders/mlp.py", MLP_BUILDER)
        self.add_file("benchmark/configs/mlptiny.json", {
            "name": "mlptiny", "source": "test",
            "builder": "benchmark/builders/mlp.py",
            "reference": "benchmark/references/mlp.py",
            "input_size": 32, "hidden_size": 64, "num_classes": 10,
            "optimizer": {"name": "sgd", "learning_rate": 0.1},
            "control_precision": "int8",
            "limits": {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                       "update_norm_gap": 1e-3}})
        self.bench["configs"].append({
            "name": "mlptiny", "source": "test", "reduced": [],
            "file": "benchmark/configs/mlptiny.json", "why": "test"})
        self.add_file("benchmark/traffic/vec32x16.json", {
            "why": "test", "rows_per_chip": 16, "row_unit": "images",
            "units_per_row": 1, "ring": 2, "check_steps": 3,
            "check_rows": 16,
            "fields": [{"dist": "uniform", "high": 1.0, "shape": [32],
                        "dtype": "float32"},
                       {"dist": "randint", "high": "num_classes",
                        "shape": [], "dtype": "int32"}]})
        self.add_cell("mlptiny-1chip", "mlptiny", "vec32x16", 1,
                      "resnet50-b384-1chip")
        return "mlptiny-1chip"

    def add_broken_builder(self, mode, sound):
        relpath = f"benchmark/builders/broken_{mode}.py"
        self.add_file(relpath, BROKEN_BUILDER.format(
            mode=mode, repo=REPO, builder=sound))
        return relpath


@pytest.fixture
def bench_root(tmp_path):
    return Root(tmp_path / "root")


@pytest.fixture
def cpu_peak(monkeypatch):
    """The table of peaks has no CPU, so that no CPU run can print a
    device metric. The tests that drive a whole run add a made-up one,
    here and nowhere else."""
    from benchmark import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
