"""One run of one cell: set-up, the first steps that ``correct`` rests
on, warm-up, the measured window, the reference, the result line.

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric is found by its name in ``BENCHMARK.json`` and read
from a file of its own, so a later PR adds files and entries and edits
nothing here.
"""

import contextlib
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time

DATA = "benchmark"          # paths[0]: configs/, traffic/, layer_metrics/
TRACE_SECONDS = 6.0         # a traced window is cut to this
TRACE_DIR = "chiprun_out/bench_trace"   # in .gitignore; a run keeps its last
WARMUP_STEPS = 2


class Refused(SystemExit):
    """The run cannot be made: non-zero exit, no result line."""

    def __init__(self, why):
        super().__init__(f"benchmark: {why}")


def load_module(root, relpath):
    path = os.path.realpath(os.path.join(root, relpath))
    name = "bench_file_" + "".join(c if c.isalnum() else "_" for c in path)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_cell(root, workload):
    """The cell as data: its BENCHMARK.json entry, its configuration and
    traffic files, and the names of the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; BENCHMARK.json has "
                      f"{sorted(cells)}")
    cell = dict(cells[workload])
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        cell["cfg"] = json.load(f)
    from benchmark import traffic
    cell["traffic_params"] = traffic.load(os.path.join(
        root, DATA, "traffic", cell["traffic"] + ".json"))

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    cell["end_to_end"] = mine(bench["end_to_end"])
    cell["per_layer"] = mine(bench["per_layer"])
    return cell


def find_devices(chips, need_tpu=True):
    import jax
    devices = jax.devices()
    if need_tpu and any(d.platform != "tpu" for d in devices):
        raise Refused(f"needs a TPU; JAX reports "
                      f"{sorted({d.platform for d in devices})} "
                      f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX reports "
                      f"{len(devices)}")
    return devices


class Spans:
    """Host spans of the loop, on the host clock and (when a trace is
    being taken) in the profiler's trace under the same names."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def __call__(self, name):
        import jax
        from benchmark.trace_reduce import HOST_PREFIX
        with jax.profiler.TraceAnnotation(HOST_PREFIX + name):
            t = time.perf_counter()
            try:
                yield
            finally:
                self.seconds.setdefault(name, []).append(
                    time.perf_counter() - t)


def drive(compiled, state, feed, first_step, spans, seconds=None,
          steps=None):
    """The training loop, closed, one step of look-ahead: step k+1 is
    dispatched before the host waits for step k's loss. Returns the new
    state and what the window saw."""
    import jax
    seen = {"done": [], "losses": []}
    with spans("window"):
        batch = feed.batch(first_step)
        seen["start"] = time.perf_counter()
        out = compiled(*state, batch)
        state, waiting = out[:-1], out[-1]
        seen["attempted"] = 1
        while True:
            more = (time.perf_counter() - seen["start"] < seconds
                    if steps is None else seen["attempted"] < steps)
            if more:
                with spans("next_batch"):
                    batch = feed.batch(first_step + seen["attempted"])
                with spans("dispatch"):
                    out = compiled(*state, batch)
                state, ahead = out[:-1], out[-1]
                seen["attempted"] += 1
            with spans("wait_loss"):
                waiting.block_until_ready()
            seen["done"].append(time.perf_counter())
            seen["losses"].append(waiting)
            if not more:
                break
            waiting = ahead
        jax.block_until_ready(state[0])
        seen["end"] = time.perf_counter()
    seen["losses"] = [float(x) for x in jax.device_get(seen["losses"])]
    seen["failed"] = sum(not math.isfinite(x) for x in seen["losses"])
    return state, seen


def percentile(values, q):
    """The q-th percentile by linear interpolation between order
    statistics."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def end_to_end(cell, reference, seen, setup_s, device_kind):
    """The end-to-end metrics of a window, from the host clock. The
    rate and ``mfu`` are taken over all the steps completed and all the
    seconds of the window; ``reference.flops_per_row`` is the
    configuration's own count of required operations."""
    from benchmark import peaks
    traffic, chips = cell["traffic_params"], cell["chips"]
    window = seen["end"] - seen["start"]
    completed = len(seen["done"])
    intervals = [b - a for a, b in zip(seen["done"], seen["done"][1:])]
    rows_per_s = completed * traffic["rows_per_chip"] / window  # a chip
    values = {
        traffic["row_unit"] + "_per_s_per_chip":
            rows_per_s * traffic["units_per_row"],
        "mfu": 100.0 * rows_per_s * reference.flops_per_row(
            cell["cfg"], traffic) / peaks.peak(device_kind,
                                               "bf16_flops_per_s"),
        "step_ms_p90": 1e3 * percentile(intervals, 90),
        "setup_s": setup_s,
    }
    return values, {"step_ms_median": 1e3 * statistics.median(intervals),
                    "intervals": len(intervals), "window_s": window}


def hbm_bytes(compiled):
    """Bytes one device holds for the step, by XLA's own account
    (``memory_stats()`` on this runtime leaves out temporaries)."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def replicas_differ(params, mesh):
    """Number of parameter leaves whose copies on the chips are not
    bit-identical."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    axis = mesh.axis_names[0]

    def spread(tree):
        # Every chip holds what it believes is the replicated leaf;
        # compare the chips' copies through the mesh.
        return jnp.stack([
            jnp.any(jax.lax.pmax(x, axis) != jax.lax.pmin(x, axis))
            for x in jax.tree.leaves(tree)]).astype(jnp.int32)

    fn = jax.jit(jax.shard_map(spread, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False))
    return int(fn(params).sum())


class Session:
    """What a process sets up once for a cell: devices, mesh, the
    reference, the program and its compiled step."""

    def __init__(self, root, workload, on_chip=True):
        self.cell = load_cell(root, workload)
        self.cfg = self.cell["cfg"]
        self.traffic = self.cell["traffic_params"]
        self.chips = self.cell["chips"]
        self.devices = find_devices(self.chips, need_tpu=on_chip)

        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        self.cache_dir = None
        if on_chip:
            from horovod_tpu.utils import compile_cache
            self.cache_dir = compile_cache.enable()
            # The benchmark's own small programs (weights, batches,
            # norms) are cached too: a second run compiles nothing.
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
        self.compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.compiles.append(name)
            if name.endswith("backend_compile_duration") else None)

        self.spans = Spans()
        import horovod_tpu as hvd
        import horovod_tpu.jax as hvd_jax
        self.hvd = hvd
        with self.spans("init"):
            hvd.init()
            self.mesh = (hvd.mesh() if len(self.devices) == self.chips
                         else Mesh(np.array(self.devices[:self.chips]),
                                   ("hvd",)))
        self.reference = load_module(root, self.cfg["reference"])
        builder = load_module(root, self.cfg["builder"])
        cfg, reference = self.cfg, self.reference
        self.init = jax.jit(lambda key: reference.init_params(cfg, key),
                            out_shardings=NamedSharding(self.mesh, P()))
        self.program = builder.build(cfg, self.traffic, self.mesh, hvd_jax)
        self.compiled = None
        self.reference_steps = {}       # by precision, traced once

    def make_params(self, seed):
        from benchmark.traffic import seed_key
        return self.init(seed_key(seed))

    def make_aux(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(self.reference.init_aux(self.cfg),
                              NamedSharding(self.mesh, P()))

    def feed(self, seed):
        from benchmark.traffic import Feed
        return Feed(self.traffic, self.cfg, self.mesh, seed)

    def first_steps(self, seed, feed):
        """Seeded state through the first steps, by the window's own
        call and feed: the program's trail, and the state to go on
        from. Compiles the step on first use."""
        from benchmark import check, follow
        state = self.program.init_state(self.make_params(seed),
                                        self.make_aux())
        if self.compiled is None:
            with self.spans("compile"):
                self.compiled = self.program.step.lower(
                    *state, feed.batch(0)).compile()
        losses, grad_norms = [], None
        for k in range(self.steps):
            out = self.compiled(*state, feed.batch(k))
            state, loss = out[:-1], out[-1]
            losses.append(float(loss))
            if k == 0:
                grad_norms = [math.sqrt(x) for x in
                              self.program.first_grad_sqnorms(
                                  state, lambda: self.make_params(seed)
                              ).tolist()]
        change = follow.change_norms(state[0], self.make_params(seed))
        return state, check.Trail(losses, grad_norms, change, [])

    @property
    def steps(self):
        return self.traffic.get("check_steps", 3)

    def follow(self, seed, feed, precision="float32"):
        from benchmark import follow
        if precision not in self.reference_steps:
            self.reference_steps[precision] = follow.make_step(
                self.reference, self.cfg, precision, self.mesh)
        return follow.follow(self.reference, self.cfg, feed,
                             lambda: self.make_params(seed), self.steps,
                             self.reference_steps[precision], self.mesh)


def run(root, workload, seed, seconds, trace, t0, on_chip=True, say=print):
    """One run. Returns the result object (the last line). ``on_chip``
    False is for the CPU tests only: it skips the look for a TPU and
    leaves JAX's compilation cache alone."""
    import jax
    from benchmark import check
    session = Session(root, workload, on_chip)
    cell, spans, chips = session.cell, session.spans, session.chips
    devices, steps = session.devices, session.steps
    kind = devices[0].device_kind
    feed = session.feed(seed)
    state, ours = session.first_steps(seed, feed)
    compiled = session.compiled
    state, _ = drive(compiled, state, feed, steps, spans,
                     steps=WARMUP_STEPS)

    trace_dir = os.path.join(root, TRACE_DIR, workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        seconds = min(seconds, TRACE_SECONDS)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the loop's own spans do
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    before = len(session.compiles)
    setup_s = time.perf_counter() - t0
    state, seen = drive(compiled, state, feed, steps + WARMUP_STEPS, spans,
                        seconds=seconds)
    compiled_inside = len(session.compiles) - before
    if trace:
        jax.profiler.stop_trace()
    if compiled_inside:
        raise Refused(f"{compiled_inside} compilation(s) inside the "
                      f"measured window")

    extra = {}
    if chips > 1:
        extra["replicas_differ"] = replicas_differ(state[0], session.mesh)
    memory = hbm_bytes(compiled)
    context = {"cell": cell, "spans": spans.seconds, "seen": seen,
               "memory_bytes": memory, "root": root,
               "trace_dir": trace_dir if trace else None,
               "device_kind": kind, "reference": session.reference}
    if trace:
        context["hlo"] = compiled.as_text()
    # The reference runs once the program's state is freed, so that it
    # fits and the memory reading stays the program's.
    del state, compiled
    session.compiled = None
    t_ref = time.perf_counter()
    theirs = session.follow(seed, feed)
    reference_s = time.perf_counter() - t_ref
    correct, rows = check.compare(ours, theirs, session.cfg["limits"],
                                  extra)
    for row in rows:
        say(f"compared {row['name']}: {row['value']:.6g} (limit "
            f"{row['limit']:.6g}) {'ok' if row['ok'] else 'NOT OK'} "
            f"{row['where']}")
    correct = correct and seen["failed"] == 0

    values, notes = end_to_end(cell, session.reference, seen, setup_s, kind)
    say(f"window {notes['window_s']:.3f} s, {len(seen['done'])} steps "
        f"completed of {seen['attempted']} dispatched; step interval "
        f"median {notes['step_ms_median']:.3f} ms over "
        f"{notes['intervals']} intervals; setup {setup_s:.2f} s: init "
        f"{sum(spans.seconds['init']):.2f} s, lower+compile "
        f"{sum(spans.seconds['compile']):.2f} s, reference "
        f"{reference_s:.2f} s (not in setup_s); cache {session.cache_dir}")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": bool(correct), "attempted": seen["attempted"],
              "failed": seen["failed"], "device": device}
    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    if trace:
        from benchmark import layers
        context["end_to_end"] = values
        metrics, device_times, breakdown = layers.read_all(context)
        device.update(device_times)
        result["breakdown"] = breakdown
    else:
        metrics = {m["name"]: values[m["name"]] for m in cell["end_to_end"]}
    result["metrics"] = {name: {"value": value, "unit": units[name]}
                         for name, value in metrics.items()}
    return result
