"""(token, choice) pairs of the last step that went to experts held on
this chip, summed over the expert layers (the MTP module's among them):
``expert_tokens`` of the non-trained state the window's last step
returned, fetched after the window (program counter).

``flops_per_row``, and so ``mfu`` and ``moe_experts_roofline``, count
the routed experts by expectation: tokens x ``num_experts_per_tok`` x
held / published a layer, 20,480 pairs a step in
``glm47flash-seq4096-1chip``. The step computes the real draw, which
the router's training moves. A change that shifts this number moves
tokens/s and ``mfu`` with no change in code speed; read it beside
them."""

import jax

from benchmark import harness


def read(ctx):
    cfg = ctx["cell"]["cfg"]
    if "builder" not in cfg or "experts_held" not in cfg:
        return None
    builder = harness.load_module(ctx["root"], cfg["builder"])
    aux = getattr(builder, "DRAW", {}).get("aux")
    if aux is None:
        return None
    first, end = cfg["experts_held"]
    drawn = [jax.device_get(leaf)[first:end].sum()
             for path, leaf in jax.tree_util.tree_leaves_with_path(aux)
             if getattr(path[-1], "key", None) == "expert_tokens"]
    return float(sum(drawn)) if drawn else None
