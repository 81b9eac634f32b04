"""The one general generator of training traffic.

A traffic mix is a data file, ``traffic/<name>.json``:

    rows_per_chip   rows of the batch each chip takes per step
    ring            0: a fresh batch every step, made on the host from
                    the seed and the step's number and placed one step
                    ahead; N > 0: N seeded batches made on the device
                    once and used in turn
    fields          what a row holds, in the order the loss function
                    takes them: {"dist": "randint" | "uniform", "shape",
                    "dtype", "high" (a number, or a key of the
                    configuration)}; "next_token": true makes a field of
                    shape [n] into the pair (row[:-1], row[1:])
    check_steps     first steps the reference follows (3)
    check_rows      rows per chip the reference takes at a time

Every step of every seed has the same shapes, so the seed changes the
values and never the work.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def load(path):
    with open(path) as f:
        traffic = json.load(f)
    for key in ("rows_per_chip", "ring", "fields"):
        if key not in traffic:
            raise ValueError(f"{os.path.basename(path)}: no {key!r}")
    return traffic


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _high(field, cfg):
    high = field.get("high", 1)
    return cfg[high] if isinstance(high, str) else high


class Feed:
    """Batches for step 0, 1, 2, ... of one run, placed on ``mesh`` with
    the rows split along its first axis."""

    def __init__(self, traffic, cfg, mesh, seed):
        self.traffic, self.cfg, self.seed = traffic, cfg, seed
        self.chips = mesh.devices.size
        self.rows = traffic["rows_per_chip"] * self.chips
        self.sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        self.ring = None
        if traffic["ring"]:
            make = jax.jit(self._make_on_device, static_argnums=1,
                           out_shardings=self.sharding)
            keys = jax.random.split(seed_key(seed), traffic["ring"])
            self.ring = [make(k, self.rows) for k in keys]

    def _make_on_device(self, key, rows):
        out = []
        for i, field in enumerate(self.traffic["fields"]):
            k = jax.random.fold_in(key, i)
            shape = (rows, *field["shape"])
            if field["dist"] == "randint":
                x = jax.random.randint(k, shape, 0, _high(field, self.cfg),
                                       jnp.dtype(field["dtype"]))
            else:
                x = jax.random.uniform(k, shape, jnp.float32, 0.0,
                                       _high(field, self.cfg))
                x = x.astype(field["dtype"])
            out.extend(_split(x, field))
        return tuple(out)

    def _make_on_host(self, step):
        rng = np.random.default_rng([self.seed, step])
        out = []
        for field in self.traffic["fields"]:
            shape = (self.rows, *field["shape"])
            if field["dist"] == "randint":
                x = rng.integers(0, _high(field, self.cfg), shape,
                                 dtype=np.dtype(field["dtype"]))
            else:
                x = rng.uniform(0.0, _high(field, self.cfg), shape)
                x = x.astype(jnp.dtype(field["dtype"]))
            out.extend(_split(x, field))
        return tuple(out)

    def global_batch(self, step):
        """Step ``step``'s whole batch, not yet placed (host arrays) or
        as it lies on the device (ring)."""
        if self.ring is not None:
            return self.ring[step % len(self.ring)]
        return self._make_on_host(step)

    def batch(self, step):
        """Step ``step``'s batch, placed."""
        if self.ring is not None:
            return self.ring[step % len(self.ring)]
        return tuple(jax.device_put(x, self.sharding)
                     for x in self._make_on_host(step))

    def reference_blocks(self, step, mesh=None):
        """Step ``step``'s batch as [blocks, rows, ...] for the reference:
        each block holds ``check_rows`` rows of every chip's share, so
        that a mean over blocks of means over rows is the step's mean."""
        per_chip = self.traffic["rows_per_chip"]
        take = self.traffic.get("check_rows", per_chip)
        blocks = per_chip // take
        out = []
        for x in self.global_batch(step):
            x = jnp.asarray(x) if self.ring is not None else x
            x = x.reshape(self.chips, blocks, take, *x.shape[1:])
            x = x.swapaxes(0, 1).reshape(blocks, self.chips * take,
                                         *x.shape[3:])
            if mesh is not None:
                x = jax.device_put(x, NamedSharding(
                    mesh, P(None, mesh.axis_names[0])))
            out.append(x)
        return tuple(out)


def _split(x, field):
    if field.get("next_token"):
        return [x[:, :-1], x[:, 1:]]
    return [x]
