"""How unevenly the window's last step drew the experts held on this
chip: the most-drawn held expert's tokens over the held experts' mean,
in the expert layer where that is largest (``expert_tokens`` of the
non-trained state that step returned, as ``moe_held_pairs`` reads it;
program counter). 1 is an even draw. The grouped product's groups are
the held experts' draws, so this is how uneven the groups were that
``moe_experts_roofline`` timed. None where the builder keeps no draw."""

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
    held = [jax.device_get(leaf)[first:end]
            for path, leaf in jax.tree_util.tree_leaves_with_path(aux)
            if getattr(path[-1], "key", None) == "expert_tokens"]
    worst = [float(drawn.max() / drawn.mean()) for drawn in held
             if drawn.sum() > 0]
    return max(worst) if worst else None
