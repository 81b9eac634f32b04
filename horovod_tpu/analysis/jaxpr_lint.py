"""Layer 1: static collective-correctness analysis of jaxprs.

The reference framework catches cross-rank divergence at *runtime*: the
controller sees which named tensors every rank submitted and stalls — or
warns — when they disagree (reference: horovod/common/controller.cc:73
ComputeResponseList + stall_inspector.cc). On TPU the collectives are
compiled into one XLA program, so the same divergence becomes a silent
deadlock at trace time. This module walks a closed jaxpr instead and
flags the three compile-time-detectable shapes:

- **HVD101** — a collective (``psum``, ``all_gather``, ``ppermute``, …)
  whose axis name is bound by no enclosing ``shard_map``/``pmap`` mesh
  and was not declared by the caller (``axis_sizes``).
- **HVD102** — a collective nested inside ``cond``/``while`` whose
  predicate data-flows from ``axis_index`` (the in-graph rank): ranks
  disagree on whether/how often the collective runs, and since every
  XLA collective instruction carries its own channel id, branch-local
  collectives never pair across replicas — the SPMD deadlock shape.
- **HVD103** — ``cond`` branches under a rank-dependent predicate whose
  collective sequences disagree in op/axis/shape/dtype: even when every
  rank *does* enter a collective, the pairs exchange mismatched buffers.

Everything here is trace-level only: no device computation is run and
nothing is compiled. JAX imports stay inside functions so importing the
linter (e.g. from the CLI) costs nothing.
"""

from .diagnostics import Diagnostic, dedupe

# Cross-replica collective primitives (jax.lax.parallel + psum_scatter).
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmin", "pmax", "ppermute", "pshuffle", "pgather",
    "all_gather", "all_to_all", "psum_scatter", "reduce_scatter",
    "psum_invariant",
})
# Primitives whose output is the replica id: the taint sources for the
# rank-dependent control-flow analysis.
RANK_PRIMS = frozenset({"axis_index"})

_DOC_HINT = "see docs/lint.md"


def _source_of(eqn):
    """(file, line) of an eqn's user frame, best effort."""
    try:
        from jax._src import source_info_util
        summary = source_info_util.summarize(eqn.source_info)
        # "path/to/file.py:123 (fn_name)"
        loc = summary.split(" ")[0]
        file, _, line = loc.rpartition(":")
        return file or loc, int(line or 0)
    except Exception:  # noqa: BLE001 - diagnostics must never crash
        return "<jaxpr>", 0


def _as_jaxpr(obj):
    """Normalize Jaxpr | ClosedJaxpr | None to a Jaxpr (or None)."""
    if obj is None:
        return None
    return getattr(obj, "jaxpr", obj)


def _sub_jaxprs(params):
    """Every jaxpr nested in an eqn's params (lists/tuples included)."""
    out = []

    def scan(v):
        if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
            sub = _as_jaxpr(v)
            if sub is not None and hasattr(sub, "eqns"):
                out.append(sub)
        elif isinstance(v, (list, tuple)):
            for item in v:
                scan(item)

    for v in params.values():
        scan(v)
    return out


def _eqn_axis_names(eqn):
    """String axis names a collective eqn operates over (positional int
    axes from vmap are not mesh axes and are skipped)."""
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name")
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _collectives_in(jaxpr, _cache=None):
    """Ordered (prim, axes, shapes, dtypes, file, line) for every
    collective in the jaxpr, recursing into sub-jaxprs."""
    if _cache is None:
        _cache = {}
    key = id(jaxpr)
    if key in _cache:
        return _cache[key]
    found = []
    _cache[key] = found  # break cycles
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            shapes = tuple(tuple(getattr(v.aval, "shape", ()))
                           for v in eqn.invars if hasattr(v, "aval"))
            dtypes = tuple(str(getattr(v.aval, "dtype", ""))
                           for v in eqn.invars if hasattr(v, "aval"))
            file, line = _source_of(eqn)
            found.append((name, _eqn_axis_names(eqn), shapes, dtypes,
                          file, line))
        for sub in _sub_jaxprs(eqn.params):
            found.extend(_collectives_in(sub, _cache))
    return found


class _Walker:
    """Taint-propagating jaxpr walker.

    ``walk`` returns the taint (rank-dependence) of the jaxpr's outvars
    given its invars' taint; diagnostics accumulate on ``self.diags``
    (dedupe at the end — ``while``-body fixpoint iteration revisits
    eqns)."""

    def __init__(self, diags):
        self.diags = diags

    @staticmethod
    def _taint(env, v):
        # Literals have no .count/.aval identity to track — never tainted.
        return env.get(id(v), False) if hasattr(v, "aval") else False

    def walk(self, jaxpr, bound, taint_in):
        env = {}
        for v, t in zip(jaxpr.invars, taint_in):
            env[id(v)] = bool(t)
        for v in jaxpr.constvars:
            env[id(v)] = False
        for eqn in jaxpr.eqns:
            self._eqn(eqn, bound, env)
        return [self._taint(env, v) for v in jaxpr.outvars]

    # -- per-eqn dispatch --------------------------------------------------
    def _eqn(self, eqn, bound, env):
        prim = eqn.primitive.name
        in_taint = any(self._taint(env, v) for v in eqn.invars)
        out_taint = in_taint or prim in RANK_PRIMS

        if prim in COLLECTIVE_PRIMS:
            self._check_axes(eqn, bound)
        elif prim == "shard_map":
            out_taint = self._shard_map(eqn, bound, env, in_taint)
        elif prim in ("pmap", "xla_pmap"):
            out_taint = self._pmap(eqn, bound, env, in_taint)
        elif prim == "cond":
            out_taint = self._cond(eqn, bound, env, in_taint)
        elif prim == "while":
            out_taint = self._while(eqn, bound, env, in_taint)
        else:
            subs = _sub_jaxprs(eqn.params)
            if subs:
                out_taint = self._generic_call(eqn, bound, env, subs,
                                               in_taint)
        for v in eqn.outvars:
            env[id(v)] = bool(out_taint)

    def _check_axes(self, eqn, bound):
        for axis in _eqn_axis_names(eqn):
            if axis not in bound:
                file, line = _source_of(eqn)
                bound_desc = (", ".join(sorted(bound))
                              if bound else "<none>")
                self.diags.append(Diagnostic.make(
                    "HVD101",
                    f"collective `{eqn.primitive.name}` uses axis "
                    f"{axis!r} which is not bound by any enclosing "
                    f"shard_map/pmap mesh (bound axes: {bound_desc})",
                    file=file, line=line,
                    hint="bind the axis with shard_map over a mesh that "
                         f"names {axis!r}, or declare it via "
                         "axis_sizes= if an outer caller binds it; "
                         + _DOC_HINT))

    def _fit(self, taints, invars, in_taint):
        """Map caller-side taints onto a sub-jaxpr's invars; when arity
        does not line up (consts got hoisted), fall back to the
        conservative any-input taint."""
        if len(taints) == len(invars):
            return taints
        return [in_taint] * len(invars)

    def _shard_map(self, eqn, bound, env, in_taint):
        inner = _as_jaxpr(eqn.params.get("jaxpr"))
        mesh = eqn.params.get("mesh")
        names = tuple(getattr(mesh, "axis_names", ()) or ())
        if inner is None:
            return in_taint
        taints = [self._taint(env, v) for v in eqn.invars]
        outs = self.walk(inner, bound | set(names),
                         self._fit(taints, inner.invars, in_taint))
        return any(outs) or in_taint

    def _pmap(self, eqn, bound, env, in_taint):
        inner = _as_jaxpr(eqn.params.get("call_jaxpr"))
        axis = eqn.params.get("axis_name")
        names = {axis} if isinstance(axis, str) else set()
        if inner is None:
            return in_taint
        taints = [self._taint(env, v) for v in eqn.invars]
        outs = self.walk(inner, bound | names,
                         self._fit(taints, inner.invars, in_taint))
        return any(outs) or in_taint

    def _cond(self, eqn, bound, env, in_taint):
        branches = [_as_jaxpr(b) for b in eqn.params.get("branches", ())]
        pred_tainted = self._taint(env, eqn.invars[0])
        op_taints = [self._taint(env, v) for v in eqn.invars[1:]]
        out_taint = in_taint
        branch_colls = []
        for br in branches:
            if br is None:
                branch_colls.append([])
                continue
            outs = self.walk(br, bound,
                             self._fit(op_taints, br.invars, in_taint))
            out_taint = out_taint or any(outs)
            branch_colls.append(_collectives_in(br))

        if pred_tainted and any(branch_colls):
            file, line = _source_of(eqn)
            prims = sorted({c[0] for colls in branch_colls for c in colls})
            self.diags.append(Diagnostic.make(
                "HVD102",
                "cond predicate depends on axis_index (the replica id) "
                "and a branch contains collective(s) "
                f"{', '.join(prims)}: ranks will disagree on which "
                "collective program point runs, and branch-local XLA "
                "collectives never pair across replicas — this deadlocks "
                "or corrupts the exchange",
                file=file, line=line,
                hint="hoist the collective out of the cond (compute both "
                     "sides, select with jnp.where), or make the "
                     "predicate replica-invariant; " + _DOC_HINT))
            # Dtype/shape pairing check is only meaningful when ranks
            # actually take different branches, i.e. the pred is
            # rank-dependent and >1 branch exchanges data.
            with_colls = [c for c in branch_colls if c]
            if len(with_colls) >= 2:
                sigs = {tuple((p, a, s, d) for p, a, s, d, _, _ in colls)
                        for colls in with_colls}
                if len(sigs) > 1:
                    self.diags.append(Diagnostic.make(
                        "HVD103",
                        "collectives in the branches of this "
                        "rank-dependent cond disagree on "
                        "op/axis/shape/dtype — ranks taking different "
                        "branches would exchange mismatched buffers",
                        file=file, line=line,
                        hint="give every branch an identical collective "
                             "signature, or restructure without "
                             "rank-dependent branching; " + _DOC_HINT))
        return out_taint

    def _while(self, eqn, bound, env, in_taint):
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        cond_j = _as_jaxpr(eqn.params.get("cond_jaxpr"))
        body_j = _as_jaxpr(eqn.params.get("body_jaxpr"))
        taints = [self._taint(env, v) for v in eqn.invars]
        cond_consts = taints[:cn]
        body_consts = taints[cn:cn + bn]
        carry = taints[cn + bn:]
        pred_tainted = in_taint
        if cond_j is not None and body_j is not None:
            # Fixpoint over the carry: the body can taint a carried value
            # (e.g. accumulate axis_index) that feeds the next trip's
            # predicate. Converges in <= len(carry)+1 rounds; cap small.
            for _ in range(4):
                pred = self.walk(
                    cond_j, bound,
                    self._fit(cond_consts + carry, cond_j.invars,
                              in_taint))
                pred_tainted = any(pred)
                body_out = self.walk(
                    body_j, bound,
                    self._fit(body_consts + carry, body_j.invars,
                              in_taint))
                body_out = self._fit(body_out, carry, any(body_out))
                new_carry = [a or b for a, b in zip(carry, body_out)]
                if new_carry == carry:
                    break
                carry = new_carry
        body_colls = _collectives_in(body_j) if body_j is not None else []
        if pred_tainted and body_colls:
            file, line = _source_of(eqn)
            prims = sorted({c[0] for c in body_colls})
            self.diags.append(Diagnostic.make(
                "HVD102",
                "while-loop trip count depends on axis_index (the "
                "replica id) and the body contains collective(s) "
                f"{', '.join(prims)}: ranks run the collective a "
                "different number of times and the program deadlocks",
                file=file, line=line,
                hint="make the trip count replica-invariant (e.g. psum/"
                     "pmax the bound first), or mask the extra "
                     "iterations instead of skipping them; " + _DOC_HINT))
        return in_taint or any(carry) or pred_tainted

    def _generic_call(self, eqn, bound, env, subs, in_taint):
        # pjit / closed_call / scan / remat / custom_* — axes pass
        # through unchanged; map taint 1:1 when arity matches.
        taints = [self._taint(env, v) for v in eqn.invars]
        out = in_taint
        for sub in subs:
            outs = self.walk(sub, bound,
                             self._fit(taints, sub.invars, in_taint))
            out = out or any(outs)
        return out


def check_jaxpr(jaxpr, axis_sizes=None, bound_axes=None):
    """Analyze a (closed) jaxpr; returns a list of :class:`Diagnostic`.

    ``bound_axes`` (or the keys of ``axis_sizes``) are axis names the
    caller promises an enclosing mesh binds — collectives over them are
    legal even with no shard_map in this jaxpr.
    """
    bound = set(bound_axes or ())
    bound |= set(axis_sizes or ())
    inner = _as_jaxpr(jaxpr)
    diags = []
    walker = _Walker(diags)
    walker.walk(inner, frozenset(bound), [False] * len(inner.invars))
    return dedupe(diags)


def check_fn(fn, *args, axis_sizes=None, **kwargs):
    """Trace ``fn(*args, **kwargs)`` and analyze the resulting jaxpr.

    ``axis_sizes`` maps externally-bound axis names to sizes — the axes
    an enclosing ``shard_map`` (or the runtime's replica mesh) will bind
    around ``fn``. Tracing runs under an extended axis env so bare
    collectives over those axes trace cleanly; an axis bound nowhere at
    all surfaces as an HVD101 diagnostic instead of a NameError.

    Accepts concrete arrays or ``jax.ShapeDtypeStruct`` args; nothing is
    compiled or executed on devices.
    """
    import jax

    axis_sizes = dict(axis_sizes or {})
    try:
        closed = jax.make_jaxpr(
            fn, axis_env=list(axis_sizes.items()))(*args, **kwargs)
    except NameError as exc:
        # "unbound axis name: X" — the trace itself proves HVD101.
        return [Diagnostic.make(
            "HVD101",
            f"tracing failed with {exc}: the function performs a "
            "collective over an axis bound by no enclosing shard_map/"
            "pmap and not declared via axis_sizes=",
            hint="pass axis_sizes={'<axis>': <size>} if an outer mesh "
                 "binds it, or wrap the function in shard_map; "
                 + _DOC_HINT)]
    return check_jaxpr(closed, axis_sizes=axis_sizes)
