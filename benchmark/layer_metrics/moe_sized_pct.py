"""Share of the expert layers (the MTP module's among them) that ran the
window's last step on buffers sized to the draw and not on a row for
every (token, choice) pair: ``expert_tokens`` of the non-trained state
that step returned, as ``moe_held_pairs`` reads it, put to the
program's own test (``parallel/moe.py: took_sized_path``, the one
``moe_apply`` makes on the device; program counter). None where the
program has no such path."""

import jax

from benchmark import harness


def read(ctx):
    cfg = ctx["cell"]["cfg"]
    if "builder" not in cfg or "experts_held" not in cfg:
        return None
    from horovod_tpu.parallel import moe
    took = getattr(moe, "took_sized_path", None)
    builder = harness.load_module(ctx["root"], cfg["builder"])
    aux = getattr(builder, "DRAW", {}).get("aux")
    if took is None or aux is None:
        return None
    first, end = cfg["experts_held"]
    sized = [took(jax.device_get(leaf), first, end)
             for path, leaf in jax.tree_util.tree_leaves_with_path(aux)
             if getattr(path[-1], "key", None) == "expert_tokens"]
    return 100.0 * sum(sized) / len(sized) if sized else None
