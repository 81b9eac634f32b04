"""Mean number of keys a query selected in the window's last step, over
the sparse-attention layers: ``selected_keys`` of the non-trained state
the step returned, which the program counts from the mask itself,
fetched after the window (program counter). ``sum_t min(t + 1, topk) /
T``, 1,920.06 at 16,384 positions and ``topk`` 2048: any other reading
is a selection at fault. None where the state has no such leaf."""

import jax

from benchmark import harness


def read(ctx):
    cfg = ctx["cell"]["cfg"]
    if "builder" not in cfg:
        return None
    builder = harness.load_module(ctx["root"], cfg["builder"])
    aux = getattr(builder, "DRAW", {}).get("aux")
    if aux is None:
        return None
    counts = [float(jax.device_get(leaf))
              for path, leaf in jax.tree_util.tree_leaves_with_path(aux)
              if getattr(path[-1], "key", None) == "selected_keys"]
    return sum(counts) / len(counts) if counts else None
