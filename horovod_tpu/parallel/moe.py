"""Expert parallelism: top-k gated mixture-of-experts with all_to_all
dispatch.

Experts shard over the data axes (the standard mapping: the dispatch
all_to_all rides the same wires the gradient allreduce uses, and dp ranks
already hold distinct tokens). Dispatch/combine use the dense one-hot
formulation — (tokens, experts, capacity) einsums — which XLA lowers to MXU
matmuls, avoiding gather/scatter (slow on TPU). Over-capacity tokens are
dropped (their combine weight is zero), standard Switch/GShard semantics.

Two entry points:
- ``moe_apply``: functional, callable inside shard_map with a named 'ep'
  axis (manual collectives), or with axis_name=None under plain jit where
  GSPMD partitions the expert dimension via the sharding rules
  (parallel/sharding.py: moe/w_in over ('dp','fsdp')).
- ``MoELayer``: flax module for the model zoo (GSPMD route).
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax



def _top_k_dispatch(gate_logits, k, capacity):
    """Build dispatch/combine tensors from gate logits.

    Returns (dispatch (T,E,C) bool-ish float, combine (T,E,C) float,
    aux_loss scalar).
    """
    t, e = gate_logits.shape
    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    masked = gate_logits.astype(jnp.float32)
    # Tokens already routed in earlier slots occupy expert capacity first.
    fill = jnp.zeros((e,), jnp.float32)
    density_sum = jnp.zeros((e,), jnp.float32)
    for _ in range(k):
        choice = jnp.argmax(masked, axis=-1)                  # (T,)
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # (T,E)
        density_sum = density_sum + onehot.mean(axis=0)
        # Position of each token within its chosen expert's buffer.
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) + fill[None, :]
        pos = jnp.sum(pos * onehot, axis=-1)                   # (T,)
        keep = pos < capacity
        pos = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # (T,C)
        d = onehot[:, :, None] * slot[:, None, :]
        d = d * keep[:, None, None]
        dispatch = dispatch + d
        prob = jnp.sum(gates * onehot, axis=-1)                # (T,)
        combine = combine + d * prob[:, None, None]
        fill = fill + jnp.sum(onehot * keep[:, None], axis=0)
        masked = jnp.where(onehot > 0, -1e30, masked)

    # Renormalize the kept top-k probabilities.
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.where(denom == 0, 1.0, denom)
    # GShard load-balancing auxiliary loss.
    density = density_sum / k
    mean_gate = gates.mean(axis=0)
    aux = e * jnp.sum(density * mean_gate)
    return dispatch, combine, aux


def moe_apply(x, w_gate, w_in, w_out, *, axis_name=None, k=2,
              capacity_factor=1.25, activation=jax.nn.gelu):
    """Apply the MoE FFN to tokens.

    Args:
      x: (tokens, d_model) local tokens.
      w_gate: (d_model, n_experts_global).
      w_in: (experts_local, d_model, d_ff) — local experts when ``axis_name``
        is set, all experts otherwise.
      w_out: (experts_local, d_ff, d_model).
      axis_name: 'ep' mesh axis for expert parallelism (inside shard_map);
        None = single-program (GSPMD or single device).
    Returns (y (tokens, d_model), aux_loss scalar).
    """
    tokens, d = x.shape
    e_global = w_gate.shape[1]
    n = lax.axis_size(axis_name) if axis_name is not None else 1
    e_local = w_in.shape[0]
    if e_local * n != e_global:
        raise ValueError(
            f"w_in holds {e_local} experts x {n} ranks != gate's {e_global}")
    capacity = int(np.ceil(k * tokens * capacity_factor / e_global))
    capacity = max(capacity, 1)

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        w_gate.astype(jnp.float32))
    dispatch, combine, aux = _top_k_dispatch(logits, k, capacity)

    expert_in = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                           dispatch).astype(x.dtype)      # (E, C, d)
    if axis_name is not None:
        # Exchange: each rank keeps its local experts' buffers from every
        # rank: (E, C, d) -> (E_local, n*C, d).
        expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                                   concat_axis=1, tiled=True)
    h = jnp.einsum("ecd,edf->ecf", expert_in, w_in.astype(expert_in.dtype))
    h = activation(h)
    out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(h.dtype))
    if axis_name is not None:
        # (E_local, n*C, d) -> (E, C, d): route results back to the ranks
        # whose tokens they are.
        out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                             tiled=True)
    y = jnp.einsum("ecd,tec->td", out.astype(jnp.float32), combine)
    if axis_name is not None:
        # Load statistics are per-rank; average the aux loss across ranks.
        aux = lax.pmean(aux, axis_name)
    return y.astype(x.dtype), aux


class MoELayer(nn.Module):
    """Flax MoE FFN block (GSPMD route; param names match
    parallel/sharding.py rules under the 'moe' scope)."""

    n_experts: int
    d_ff: int
    k: int = 2
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        # x: (batch, seq, d); flatten tokens for dispatch.
        b, s, d = x.shape
        w_gate = self.param("w_gate", nn.initializers.lecun_normal(),
                            (d, self.n_experts))
        w_in = self.param("w_in", nn.initializers.lecun_normal(),
                          (self.n_experts, d, self.d_ff))
        w_out = self.param("w_out", nn.initializers.lecun_normal(),
                           (self.n_experts, self.d_ff, d))
        y, aux = moe_apply(x.reshape(b * s, d), w_gate, w_in, w_out,
                           k=self.k, capacity_factor=self.capacity_factor)
        self.sow("losses", "moe_aux_loss", aux)
        return y.reshape(b, s, d).astype(self.dtype)
