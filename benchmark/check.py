"""The comparison that decides ``correct``.

Both sides, the program and the plain reference, start from the same
seeded weights and follow the same first steps of the cell's own
traffic. Each yields a ``Trail``: the loss of every step, the norm of
every leaf of the first gradient, and the norm of every leaf of the
parameters' change after the steps. Norms are compared by the worst
leaf: the gap between the two norms (not the norm of the difference),
against the reference's norm of that leaf or of the median leaf,
whichever is larger, since some gradients are all but zero.
"""

import dataclasses
import math
import statistics


@dataclasses.dataclass
class Trail:
    losses: list            # one per step followed
    grad_norms: list        # one per parameter leaf, first step
    update_norms: list      # one per parameter leaf, after the last step
    leaf_names: list


def _worst_leaf(ours, ref, names):
    floor = statistics.median(ref)
    worst, where = 0.0, ""
    for a, b, name in zip(ours, ref, names):
        if not (math.isfinite(a) and math.isfinite(b)):
            return math.inf, name
        gap = abs(a - b) / max(b, floor)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def compare(program, reference, limits, extra=None):
    """Returns (correct, rows): one row per number compared, as
    {"name", "value", "limit", "ok", "where"}. ``extra`` adds exact
    counts such as replicas that differ across chips (limit 0)."""
    steps = min(len(program.losses), len(reference.losses))
    loss_gap, where = 0.0, ""
    for k in range(steps):
        a, b = program.losses[k], reference.losses[k]
        gap = abs(a - b) / abs(b) if math.isfinite(a) else math.inf
        if gap > loss_gap:
            loss_gap, where = gap, f"step {k}"
    numbers = [("loss_gap", loss_gap, where)]
    numbers.append(("grad_norm_gap",) + _worst_leaf(
        program.grad_norms, reference.grad_norms, reference.leaf_names))
    numbers.append(("update_norm_gap",) + _worst_leaf(
        program.update_norms, reference.update_norms,
        reference.leaf_names))
    rows = [{"name": n, "value": v, "limit": limits[n],
             "ok": bool(v <= limits[n]), "where": w}
            for n, v, w in numbers]
    for name, value in (extra or {}).items():
        rows.append({"name": name, "value": value, "limit": 0,
                     "ok": value == 0, "where": ""})
    return all(r["ok"] for r in rows), rows
