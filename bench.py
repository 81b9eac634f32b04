"""Headline benchmarks: ResNet-50 img/s + transformer-LM samples/s.

Prints one JSON line per metric; the LAST line is the headline
(ResNet-50, kept metric-compatible with round 1). The no-flag run needs a
TPU and exits non-zero without one. See docs/PERF.md for the measured
batch sweeps and the MFU ceiling analysis.

Baseline derivation: the reference publishes one absolute throughput —
ResNet-101 at 1656.82 total img/s on 16 Pascal P100s (reference:
docs/benchmarks.rst:35-46), i.e. ~103.6 img/s per accelerator.
``vs_baseline`` for ResNet is our per-chip img/s over that per-GPU figure.
The reference publishes NO absolute transformer number, so the transformer
line reports model FLOPs utilization (MFU vs the chip's bf16 peak) as
``vs_baseline`` — the honest scale-free anchor.
"""

import json
import sys
import timeit

BASELINE_PER_ACCEL = 1656.82 / 16.0
# Per-chip bf16 peak FLOP/s by the device_kind JAX reports (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s).
BF16_PEAK_BY_DEVICE_KIND = {"TPU v5 lite": 197e12}


def _bf16_peak():
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in BF16_PEAK_BY_DEVICE_KIND:
        raise RuntimeError(
            f"no bf16 peak on record for device_kind {kind!r}; MFU is "
            f"computed against {sorted(BF16_PEAK_BY_DEVICE_KIND)} only")
    return BF16_PEAK_BY_DEVICE_KIND[kind]


def _bench_resnet(hvd, hvd_jax):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models import ResNet50

    n = hvd.size()
    # Batch 384 is the measured throughput peak on v5e (docs/PERF.md:
    # 64->1482, 128->1977, 256->2149, 320->2166, 384->2252, 448->2213,
    # 512->1102 img/s).
    per_replica = 384
    image = 224
    global_batch = n * per_replica

    model = ResNet50(num_classes=1000)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, image, image, 3)))
    params = variables["params"]
    aux = {k: v for k, v in variables.items() if k != "params"}
    # No initial broadcast needed: every rank initializes from the
    # SAME PRNGKey(0), so parameters are bit-identical by construction.
    # hvd-lint: disable=HVD202
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1))

    def loss_fn(p, aux_state, batch):
        x, y = batch
        logits, updates = model.apply({"params": p, **aux_state}, x,
                                      mutable=list(aux_state.keys()))
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, updates

    step = hvd_jax.make_train_step(loss_fn, opt, has_aux=True)
    opt_state = opt.init(params)

    rng = np.random.RandomState(0)
    # bf16 device-resident input: no per-step host transfer, no fp32
    # upcast on the wire.
    data = jnp.asarray(rng.uniform(size=(global_batch, image, image, 3)),
                       dtype=jnp.bfloat16)
    target = jnp.asarray(rng.randint(0, 1000, size=(global_batch,)))
    state = [params, aux, opt_state]

    chain = 5

    def run_block():
        loss = None
        for _ in range(chain):
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], (data, target))
        jax.block_until_ready(loss)

    warmup = 2
    iters = 4
    timeit.timeit(run_block, number=warmup)
    t = timeit.timeit(run_block, number=iters)
    per_chip = global_batch * chain * iters / t / n
    return {
        "metric": "resnet50_train_img_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(per_chip / BASELINE_PER_ACCEL, 3),
    }


def _bench_transformer(hvd, hvd_jax, on_tpu, seq_tpu=512, batch_tpu=24,
                       metric=None, compression=None, overlap=None,
                       zero=None):
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models import TransformerLM, TransformerConfig

    n = hvd.size()
    seq = seq_tpu if on_tpu else 64
    batch = (batch_tpu if on_tpu else 2) * n
    # BERT-large dimensions as a causal decoder LM (the reference's BERT
    # target, BASELINE.md): 365M params. The pallas flash kernel (causal
    # block-skip + 1024-tiles + unpadded d=64) beats XLA's fused einsum
    # attention at seq 512 (88.1 vs 71.6 samples/s): skipping
    # above-diagonal tiles halves attention FLOPs, big tiles amortize the
    # online-softmax bookkeeping, and the freed O(s^2) logits memory
    # admits batch 24 without remat (docs/PERF.md round-3 sweep).
    if on_tpu:
        cfg = TransformerConfig(vocab_size=30522, hidden=1024, layers=24,
                                heads=16, max_len=seq, causal=True,
                                use_rope=True, attention_impl="flash")
    else:
        cfg = TransformerConfig(vocab_size=1024, hidden=128, layers=2,
                                heads=4, max_len=seq, causal=True,
                                use_rope=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, seq), jnp.int32))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    # --compression sweep: the gradient collectives inside the train
    # step run the block-quantized EQuARX pipeline (docs/compression.md)
    # — this is the direct attack on the gradient-bytes half of the
    # transformer gap (ROADMAP items 1 + 5).
    comp = (getattr(hvd.Compression, compression)
            if compression else None)
    # --overlap sweep: the bucketed comm/compute overlap path
    # (HVDTPU_OVERLAP, docs/performance.md) is baked into the train step
    # at optimizer construction, so flip the knob before building it.
    if overlap is not None:
        os.environ["HVDTPU_OVERLAP"] = "1" if overlap else "0"
    # --zero sweep: the ZeRO-1 sharded weight update (HVDTPU_ZERO,
    # docs/performance.md "ZeRO-1") — the A/B records per-replica
    # optimizer-state bytes next to throughput.
    opt = hvd_jax.DistributedOptimizer(
        optax.adamw(1e-4),
        **({"compression": comp} if comp is not None else {}),
        **({"zero": bool(zero)} if zero is not None else {}))

    def loss_fn(p, b):
        x, y = b
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    step = hvd_jax.make_train_step(loss_fn, opt)
    opt_state = opt.init(params)
    opt_state_bytes = None
    if zero is not None:
        # Per-replica optimizer-state footprint: the A/B's second
        # axis. Sharded mode reads the runtime's measure (what the
        # hvd_zero_state_bytes gauge reports); replicated sums the
        # whole state tree every chip holds.
        if zero:
            opt_state_bytes = opt._zero_rt.state_bytes(opt_state)
        else:
            opt_state_bytes = sum(
                int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(opt_state)
                if hasattr(x, "dtype"))
    rng = np.random.RandomState(0)
    data = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(batch, seq)))
    target = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(batch, seq)))
    state = [params, opt_state]

    chain = 5 if on_tpu else 1

    def run_block():
        loss = None
        for _ in range(chain):
            state[0], state[1], loss = step(state[0], state[1],
                                            (data, target))
        float(loss)

    warmup = 2 if on_tpu else 1
    iters = 4 if on_tpu else 2
    timeit.timeit(run_block, number=warmup)
    t = timeit.timeit(run_block, number=iters)
    per_chip = batch * chain * iters / t / n
    tok_s = per_chip * seq
    out = {
        "metric": metric or ("transformer_lm_365m_seq512_train_samples"
                             "_per_sec_per_chip"),
        "value": round(per_chip, 2),
        "unit": "samples/s/chip",
    }
    if on_tpu:
        # No published reference absolute exists for transformers; report
        # MFU against the chip's bf16 peak instead (module docstring).
        # 6N per token (fwd+bwd matmuls) + attention's 12*L*s*h
        # quadratic term.
        flops_per_tok = 6 * n_params + 12 * cfg.layers * seq * cfg.hidden
        out["vs_baseline"] = round(
            tok_s * flops_per_tok / _bf16_peak(), 3)
    if compression:
        # Wire-format accounting for the gradient collectives: the
        # in-jit pipeline cannot touch host counters, so the ratio is
        # computed from the codec's wire layout (payload + per-block
        # scales) against the fp32 gradient bytes — BENCH_r* records
        # the gradient-bytes delta next to the samples/s delta.
        from horovod_tpu.compression import codecs as _codecs
        from horovod_tpu.utils import envparse as _envparse
        block = _envparse.get_int(_envparse.COMPRESSION_BLOCK,
                                  _codecs.DEFAULT_BLOCK)
        grad_bytes = n_params * 4
        wire_bytes = _codecs.CODECS[compression].wire_bytes(
            n_params, block, 4)
        out["compression"] = compression
        out["compression_ratio"] = round(wire_bytes / grad_bytes, 4)
        out["grad_bytes_saved_per_step"] = int(grad_bytes - wire_bytes)
    if zero is not None:
        out["zero"] = int(bool(zero))
        out["opt_state_bytes_per_replica"] = int(opt_state_bytes)
        if zero:
            out["zero_buckets"] = len(opt._zero_rt.plan.buckets)
    if overlap is not None:
        from horovod_tpu.ops import bucketing as _bucketing
        from horovod_tpu.utils import envparse as _envparse
        out["overlap"] = int(bool(overlap))
        bucket_bytes = _envparse.get_int(
            _envparse.BUCKET_BYTES, _bucketing.DEFAULT_BUCKET_BYTES)
        out["bucket_bytes"] = bucket_bytes
        if overlap:
            out["buckets"] = len(_bucketing.plan_buckets(
                jax.tree.leaves(params), bucket_bytes))
    return out


def _bench_trace_lane(hvd, on_tpu):
    """--trace: A/B the eager gradient-reduction plane with the
    cross-rank trace plane off vs on (docs/tracing.md), on the
    transformer-LM stand-in's gradient set. Tracing instruments the
    coordinator submit/complete path, so the honest workload is the
    eager plane: one named allreduce per gradient leaf per step — the
    shard then carries a real multi-step, multi-collective schedule
    the analyzer summarizes (critical path, stragglers, comm
    breakdown). Returns (rows, analyzer_summary, overhead_frac).

    The <3% overhead budget is asserted by the caller against
    best-of-3 timings: buffered JSONL writes per collective must stay
    in the noise next to the collective itself."""
    import os
    import shutil
    import tempfile
    import time as _time

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerLM, TransformerConfig
    from horovod_tpu.ops import collectives as hvd_collectives
    from horovod_tpu.tracing import analyze as trace_analyze
    from horovod_tpu.tracing import merge as trace_merge

    n = hvd.size()
    seq = 64
    # Gradient leaves must be realistically sized: the budget is a
    # claim about training workloads, where a collective moves MBs and
    # the tracer's fixed ~10 us/collective is noise — not about
    # KB-scale toys where any fixed cost looks huge. hidden=512 puts
    # the stand-in's leaves at 1-4 MB (the 365M target's are larger).
    cfg = TransformerConfig(vocab_size=1024, hidden=512, layers=2,
                            heads=8, max_len=seq, causal=True,
                            use_rope=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, seq), jnp.int32))
    # Stacked per-virtual-rank gradient stand-ins (the eager plane's
    # input contract): one device array per leaf, reused every step.
    grads = [jnp.stack([jnp.asarray(leaf)] * n)
             for leaf in jax.tree.leaves(params)]
    steps, repeats = 10, 5

    def run_steps():
        for _ in range(steps):
            handles = [
                hvd_collectives.allreduce_async(
                    g, name=f"grad.{i}", op=hvd.Sum)
                for i, g in enumerate(grads)]
            for h in handles:
                hvd.synchronize(h)

    def measure():
        """Fresh runtime under the current knobs; best-of-N step
        rate."""
        hvd.shutdown()
        hvd.init()
        run_steps()  # warmup: compile + caches
        best = float("inf")
        for _ in range(repeats):
            t0 = _time.perf_counter()
            run_steps()
            best = min(best, _time.perf_counter() - t0)
        return best

    saved = {k: os.environ.get(k)
             for k in ("HVDTPU_TRACE", "HVDTPU_TRACE_DIR")}
    trace_dir = tempfile.mkdtemp(prefix="hvd_bench_trace_")
    try:
        os.environ["HVDTPU_TRACE"] = "0"
        t_off = measure()
        os.environ["HVDTPU_TRACE"] = "1"
        os.environ["HVDTPU_TRACE_DIR"] = trace_dir
        t_on = measure()
        # Close the shard (shutdown flushes + pushes) before analyzing,
        # then restore a fresh runtime under the caller's knobs.
        hvd.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        hvd.init()

        overhead = t_on / t_off - 1.0
        leaves = len(grads)
        rows = [
            {"metric": "transformer_lm_grad_eager_allreduce_steps"
                       "_per_sec_trace_off",
             "value": round(steps / t_off, 2), "unit": "steps/s",
             "leaves_per_step": leaves},
            {"metric": "transformer_lm_grad_eager_allreduce_steps"
                       "_per_sec_trace_on",
             "value": round(steps / t_on, 2), "unit": "steps/s",
             "overhead_frac": round(overhead, 4)},
        ]
        shards = trace_merge.load_paths(
            [trace_dir], kinds=(trace_merge.SHARD_PREFIX,))
        report = trace_analyze.analyze(shards)
        trace_analyze.publish_metrics(report)
        crit = [{"step": st["step"],
                 "duration_ms": round((st["duration_s"] or 0) * 1e3, 3),
                 "critical_comm_ms": round(
                     st["critical_comm_s"] * 1e3, 3),
                 "gating": st["gating_collective"]}
                for st in report["steps"]]
        summary = {
            "collectives": report["collectives"],
            "steps": crit,
            "stragglers": {str(r): v for r, v in
                           report["stragglers"].items()},
            "overlap_fraction": {
                str(r): c.get("overlap_fraction")
                for r, c in report["comm"].items()},
        }
        return rows, summary, overhead
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _bench_autotune(hvd, on_tpu):
    """--autotune lane (ISSUE 12; docs/autotune.md): A/B the trace-driven
    online tuner on the transformer-LM eager gradient plane —
    (a) the default config, (b) the config the online sweep converges
    on, (c) a warm-started second run applying the persisted winner
    before the first scored window. Returns (rows, summary) with the
    sweep history from the cache entry. The workload is the trace
    lane's: one named allreduce per gradient leaf per step, which gives
    the flight ring the repeated name x occurrence structure the
    steps/sec score source keys on."""
    import os
    import tempfile
    import time as _time

    import jax
    import jax.numpy as jnp

    from horovod_tpu import basics
    from horovod_tpu.autotune import store as tune_store
    from horovod_tpu.models import TransformerLM, TransformerConfig
    from horovod_tpu.ops import collectives as hvd_collectives

    n = hvd.size()
    seq = 64
    cfg = TransformerConfig(vocab_size=1024, hidden=512, layers=2,
                            heads=8, max_len=seq, causal=True,
                            use_rope=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, seq), jnp.int32))
    grads = [jnp.stack([jnp.asarray(leaf)] * n)
             for leaf in jax.tree.leaves(params)]
    steps, repeats = 10, 5

    def run_steps():
        for _ in range(steps):
            handles = [
                hvd_collectives.allreduce_async(
                    g, name=f"grad.{i}", op=hvd.Sum)
                for i, g in enumerate(grads)]
            for h in handles:
                hvd.synchronize(h)

    def measure():
        """Best-of-N steps/sec under the CURRENT runtime + knobs."""
        run_steps()   # warmup: compile + caches
        best = float("inf")
        for _ in range(repeats):
            t0 = _time.perf_counter()
            run_steps()
            best = min(best, _time.perf_counter() - t0)
        return steps / best

    knobs = ("HVDTPU_AUTOTUNE", "HVDTPU_AUTOTUNE_CACHE",
             "HVDTPU_AUTOTUNE_SIGNATURE",
             "HVDTPU_AUTOTUNE_WARMUP_CYCLES",
             "HVDTPU_AUTOTUNE_CYCLES_PER_CANDIDATE",
             "HVDTPU_AUTOTUNE_FUSION_CANDIDATES_MIB",
             "HVDTPU_AUTOTUNE_CYCLE_CANDIDATES_MS")
    saved = {k: os.environ.get(k) for k in knobs}
    fd, cache = tempfile.mkstemp(prefix="hvd_bench_autotune_",
                                 suffix=".json")
    os.close(fd)
    os.remove(cache)   # the store treats a missing file as a first run
    try:
        # (a) default config, tuner off.
        hvd.shutdown()
        hvd.init()
        coord = basics.runtime().coordinator
        default_knobs = (coord.fusion_threshold, coord.cycle_time_s)
        default_rate = measure()

        # (b) online sweep to convergence, then the converged config's
        # rate. The grid spans deliberately bad corners (fusion off,
        # long cycles) so the sweep has something to reject; the
        # explicit signature keeps the cache key stable across runs
        # (the ring-derived default would also see init-time names).
        os.environ.update({
            "HVDTPU_AUTOTUNE": "1",
            "HVDTPU_AUTOTUNE_CACHE": cache,
            "HVDTPU_AUTOTUNE_SIGNATURE": "bench-transformer-lm-grads",
            "HVDTPU_AUTOTUNE_WARMUP_CYCLES": "5",
            "HVDTPU_AUTOTUNE_CYCLES_PER_CANDIDATE": "20",
            "HVDTPU_AUTOTUNE_FUSION_CANDIDATES_MIB": "0,4,32,128",
            "HVDTPU_AUTOTUNE_CYCLE_CANDIDATES_MS": "0.5,1.0,5.0",
        })
        hvd.shutdown()
        hvd.init()
        tuner = basics.runtime().autotuner
        assert tuner is not None, "HVDTPU_AUTOTUNE=1 must build the tuner"
        deadline = _time.monotonic() + 300
        sweep_t0 = _time.monotonic()
        sweep_steps = 0
        while tuner.enabled and _time.monotonic() < deadline:
            run_steps()
            sweep_steps += steps
        assert not tuner.enabled, "sweep did not converge in 300s"
        sweep_seconds = _time.monotonic() - sweep_t0
        converged_cfg = dict(tuner.best_config)
        score_label = tuner._score_label
        converged_rate = measure()

        # (c) warm-started second run: fresh runtime, populated cache.
        hvd.shutdown()
        hvd.init()
        tuner = basics.runtime().autotuner
        warm_rounds = 0
        while tuner.enabled and warm_rounds < 50:
            run_steps()
            warm_rounds += 1
        assert not tuner.enabled, "warm start did not engage"
        assert tuner._history == [], \
            "warm start must apply the stored winner WITHOUT sweeping"
        warm_cfg = dict(tuner.best_config)
        warm_rate = measure()

        # Paired A/B/A on the SAME runtime: fresh-runtime variance on
        # the CPU stand-in is larger than the config delta, so the
        # headline tuned-vs-default ratio flips the live knobs in place
        # (identical process, caches, allocator state — only the
        # config differs) and takes the tuned side's best of two.
        coord = basics.runtime().coordinator
        tuned_knobs = (coord.fusion_threshold, coord.cycle_time_s)
        coord.fusion_threshold, coord.cycle_time_s = default_knobs
        paired_default = measure()
        coord.fusion_threshold, coord.cycle_time_s = tuned_knobs
        paired_tuned = max(warm_rate, measure())

        (key, entry), = tune_store.load(cache).items()
        rows = [
            {"metric": "transformer_lm_grad_eager_autotune_default"
                       "_steps_per_sec",
             "value": round(default_rate, 2), "unit": "steps/s"},
            # Measured in the sweep's own process: the 90-step sweep
            # history biases this runtime, so the apples-to-apples
            # tuned-config number is the warm-started FRESH runtime
            # below (same knobs, same lifecycle as the default row).
            {"metric": "transformer_lm_grad_eager_autotune_converged"
                       "_steps_per_sec_in_process",
             "value": round(converged_rate, 2), "unit": "steps/s",
             "config": converged_cfg, "score_source": score_label,
             "sweep_scored_windows": len(entry["history"]),
             "sweep_steps": sweep_steps,
             "sweep_seconds": round(sweep_seconds, 1)},
            {"metric": "transformer_lm_grad_eager_autotune_warm_start"
                       "_steps_per_sec",
             "value": round(warm_rate, 2), "unit": "steps/s",
             "config": warm_cfg,
             "warm_config_matches_converged": warm_cfg == converged_cfg},
            {"metric": "transformer_lm_grad_eager_autotune_paired"
                       "_tuned_steps_per_sec",
             "value": round(paired_tuned, 2), "unit": "steps/s",
             "paired_default_steps_per_sec": round(paired_default, 2)},
        ]
        summary = {
            "world": n,
            "cache_key": key,
            # Same-runtime paired A/B/A (see the paired row) — the
            # comparison fresh-runtime variance can't swamp.
            "tuned_vs_default": round(paired_tuned / paired_default, 3),
            "warm_fresh_vs_default_fresh": round(
                warm_rate / default_rate, 3),
            "post_sweep_in_process_vs_default": round(
                converged_rate / default_rate, 3),
            "converged_config": converged_cfg,
            "history": entry["history"],
        }
        return rows, summary
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if os.path.exists(cache):
            os.remove(cache)
        # Fresh runtime under the caller's knobs for later lanes.
        hvd.shutdown()
        hvd.init()


def _bench_sparse(hvd, on_tpu):
    """`--sparse` lane (ISSUE 11; docs/sparse.md): a DLRM/NMT stand-in
    — one large embedding table whose gradient touches a density
    fraction of rows per step, next to a small dense MLP — swept over
    density × {gather, dense, auto} × {none, int8} on the eager
    gradient plane, with the densified pre-plane baseline
    (HVDTPU_SPARSE unset) as the reference row.

    METHODOLOGY (CPU stand-in): wire bytes are the docs/sparse.md MODEL
    bytes — dense ring ~ 2·R·W·b_v per rank, gather ~
    (n−1)·nnz·(W·b_v + b_i)(/n per rank) — because the in-process
    loopback transport has no real fabric to meter; both sides use the
    same model, so the RATIO (the pinned ≥4× number at ≤5% density) is
    transport-independent. samples/s uses a nominal batch of 256
    lookups/step. int8 applies to gathered VALUES only (indices exact);
    on the dense path the existing compression plane owns the wire, so
    dense+int8 rows record the dense model bytes unchanged."""
    import os
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu import basics
    from horovod_tpu.ops import sparse as sparse_mod

    n = hvd.size() if hvd.size() > 1 else len(jax.devices())
    rows_total, width = (32768, 32) if not on_tpu else (1 << 20, 64)
    batch, steps = 256, 6
    from horovod_tpu import compression as compression_mod

    coord = basics.runtime().coordinator
    saved_plane = coord._sparse
    saved_compression = coord._compression
    saved_env = {k: os.environ.get(k)
                 for k in ("HVDTPU_SPARSE", "HVDTPU_COMPRESSION")}
    rng = np.random.RandomState(0)
    mlp = [jnp.asarray(rng.randn(n, width, 64).astype(np.float32)),
           jnp.asarray(rng.randn(n, 64, 1).astype(np.float32))]

    def make_slices(density, seed):
        nnz = max(1, int(density * rows_total))
        out = []
        for r in range(n):
            rr = np.random.RandomState(seed * 1000 + r)
            idx = rr.choice(rows_total, size=nnz,
                            replace=False).astype(np.int32)
            out.append(sparse_mod.SparseGradient(
                idx, rr.randn(nnz, width).astype(np.float32),
                (rows_total, width)))
        return out, nnz

    def run_config(density, mode, codec):
        if mode is None:
            os.environ.pop("HVDTPU_SPARSE", None)
        else:
            os.environ["HVDTPU_SPARSE"] = mode
        if codec == "int8":
            os.environ["HVDTPU_COMPRESSION"] = "int8"
        else:
            os.environ.pop("HVDTPU_COMPRESSION", None)
        coord._sparse = sparse_mod.make_plane()
        # Rebuild the COMPRESSION plane too: it was constructed at
        # hvd.init() with the env as it was then — leaving it stale
        # would run every dense-path "int8" row uncompressed while
        # archiving codec=int8 (the sparse plane owns only the gather
        # path's row codec; on the dense path the compression plane
        # owns the wire).
        coord._compression = compression_mod.make_plane(basics.runtime())
        slices, nnz = make_slices(density, int(density * 1e4) + 7)
        tag = (f"d{density}_{mode or 'baseline'}_"
               f"{codec or 'none'}")
        before = (dict(coord._sparse.path_counts)
                  if coord._sparse else None)
        # SPMD mode takes this rank's slices; the single-controller
        # plane takes the whole per-rank list (size() counts VIRTUAL
        # ranks there too, so the mode — not the size — decides).
        arg = (slices[hvd.rank()]
               if basics.runtime().mode == basics.MODE_SPMD else slices)
        t0 = time.perf_counter()
        for s in range(steps):
            out = hvd.sparse_allreduce(arg, op=hvd.Sum,
                                       name=f"emb_table.{tag}.{s}")
            for i, g in enumerate(mlp):
                hvd.allreduce(g, op=hvd.Average,
                              name=f"mlp.{tag}.{i}.{s}")
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if coord._sparse is None:
            path = "dense"
        else:
            after = coord._sparse.path_counts
            path = ("gather" if after["gather"] > before["gather"]
                    else "dense")
        dense_bytes = sparse_mod.dense_wire_bytes((rows_total, width), 4)
        if path == "gather":
            wire = sparse_mod.gather_wire_bytes(
                nnz * n, width, 4, 4, n,
                codec=(codec if codec == "int8" else None))
        else:
            wire = dense_bytes
        return {
            "metric": f"sparse_embedding_{tag}",
            "value": round(batch * steps / dt, 2),
            "unit": "samples/s",
            "density": density,
            "mode": mode or "baseline-unset",
            "codec": codec or "none",
            "path_taken": path,
            "emb_wire_bytes_per_rank_per_step": int(wire),
            "dense_wire_bytes_per_rank_per_step": int(dense_bytes),
            "wire_reduction_vs_dense": round(dense_bytes / max(wire, 1),
                                             2),
            "nnz_rows_per_rank": int(nnz),
            "table": [rows_total, width],
            "world": n,
        }

    out_rows = []
    try:
        # The pre-plane reference: knob unset, sparse grads densify.
        out_rows.append(run_config(0.05, None, None))
        for density in (0.01, 0.05, 0.25):
            for mode in ("gather", "dense", "auto"):
                for codec in (None, "int8"):
                    out_rows.append(run_config(density, mode, codec))
    finally:
        coord._sparse = saved_plane
        coord._compression = saved_compression
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    by = {(r["density"], r["mode"], r["codec"]): r for r in out_rows}
    summary = {}
    base = by.get((0.05, "baseline-unset", "none"))
    g5 = by.get((0.05, "gather", "none"))
    q5 = by.get((0.05, "gather", "int8"))
    if base and g5:
        summary = {
            "wire_reduction_at_5pct_density": round(
                base["emb_wire_bytes_per_rank_per_step"]
                / max(g5["emb_wire_bytes_per_rank_per_step"], 1), 2),
            "wire_reduction_at_5pct_density_int8": round(
                base["emb_wire_bytes_per_rank_per_step"]
                / max(q5["emb_wire_bytes_per_rank_per_step"], 1), 2)
            if q5 else None,
            "auto_path_by_density": {
                str(d): by[(d, "auto", "none")]["path_taken"]
                for d in (0.01, 0.05, 0.25)
                if (d, "auto", "none") in by},
            "world": n,
            "methodology": ("model wire bytes (docs/sparse.md): CPU "
                            "stand-in loopback has no fabric to meter; "
                            "ratio is transport-independent"),
        }
    return out_rows, summary


def _bench_serving(hvd, on_tpu):
    """`--serving` lane (ISSUE 13; docs/serving.md): closed-loop load
    generator against the full serving stack — KV store + 2 in-process
    continuous-batching workers + router, all over real HTTP — at 3
    offered-load points. Arrivals are Poisson (exponential gaps, seeded
    RNG) over a prompt/output-length mix; every request is a raw
    client (no 429 retry), so the rejection rate IS the backpressure
    the stack sheds at that load.

    METHODOLOGY (CPU stand-in): the ToyLM decode step is padded to
    DECODE_DELAY_S to stand in for a real model's step time — latency
    and tokens/s scale with it, but the SHAPE of the curve (p99 growth
    then rejection onset as offered load crosses capacity) is the
    serving plane's own behavior: admission watermark, queue bound,
    batch recomposition. Archived to BENCH_r11.json."""
    import json as _json
    import threading
    import time
    import urllib.error
    import urllib.request

    import numpy as np

    from horovod_tpu.runner.http_server import (AUTH_HEADER,
                                                KVStoreServer,
                                                new_job_token)
    from horovod_tpu.serving.model import ToyLM
    from horovod_tpu.serving.router import Router
    from horovod_tpu.serving.worker import ServingWorker

    DECODE_DELAY_S = 0.01
    WINDOW_S = 3.0
    LOADS_RPS = (15, 45, 135)
    PROMPTS = ((2, 0.5), (6, 0.3), (12, 0.2))
    NEW_TOKENS = ((4, 0.5), (8, 0.3), (16, 0.2))

    class PacedToyLM(ToyLM):
        def decode(self, contexts):
            time.sleep(DECODE_DELAY_S)
            return super().decode(contexts)

    token = new_job_token()
    kv = KVStoreServer(job_token=token, addr="127.0.0.1")
    kv_port = kv.start()
    workers, rows = [], []
    try:
        for wid in range(2):
            w = ServingWorker(PacedToyLM(), cohort="c0", wid=wid,
                              num_pages=24, page_size=2,
                              queue_limit=8,
                              max_batch_tokens=128).start()
            port = w.serve_http(addr="127.0.0.1", token=token)
            w.register("127.0.0.1", kv_port, token,
                       advertise=f"127.0.0.1:{port}")
            workers.append(w)
        router = Router(kv=("127.0.0.1", kv_port, token))
        router.refresh_from_kv(["c0"])
        rport = router.serve_http(addr="127.0.0.1", token=token)

        def one_request(prompt_len, max_new, record):
            body = _json.dumps({"prompt": [1] * prompt_len,
                                "max_new_tokens": max_new}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{rport}/v1/generate", data=body,
                method="POST")
            req.add_header(AUTH_HEADER, token)
            t0 = time.monotonic()
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    out = _json.loads(resp.read())
                    record.append(("ok", time.monotonic() - t0,
                                   len(out["tokens"])))
            except urllib.error.HTTPError as e:
                kind = "rejected" if e.code == 429 else "error"
                record.append((kind, time.monotonic() - t0, 0))
            except Exception:  # noqa: BLE001 — counted, not raised
                record.append(("error", time.monotonic() - t0, 0))

        def pick(rng, mix):
            vals, weights = zip(*mix)
            return int(rng.choice(vals, p=np.asarray(weights)
                                  / sum(weights)))

        for load in LOADS_RPS:
            rng = np.random.RandomState(load)
            record, threads = [], []
            t_start = time.monotonic()
            t_next = t_start
            while t_next < t_start + WINDOW_S:
                gap = rng.exponential(1.0 / load)
                t_next += gap
                now = time.monotonic()
                if t_next > now:
                    time.sleep(t_next - now)
                th = threading.Thread(
                    target=one_request,
                    args=(pick(rng, PROMPTS), pick(rng, NEW_TOKENS),
                          record))
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=90)
            span = time.monotonic() - t_start
            lat = sorted(t for kind, t, _ in record if kind == "ok")
            tokens = sum(tk for kind, _, tk in record if kind == "ok")
            rejected = sum(1 for kind, _, _ in record
                           if kind == "rejected")
            errors = sum(1 for kind, _, _ in record if kind == "error")
            q = (lambda p: lat[min(len(lat) - 1,
                                   int(p * len(lat)))]) if lat \
                else (lambda p: None)
            rows.append({
                "benchmark": "serving_closed_loop",
                "offered_rps": load,
                "offered": len(record),
                "completed": len(lat),
                "rejected": rejected,
                "errors": errors,
                "rejection_rate": round(rejected / max(len(record), 1),
                                        4),
                "p50_latency_s": round(q(0.50), 4) if lat else None,
                "p99_latency_s": round(q(0.99), 4) if lat else None,
                "tokens_per_sec": round(tokens / span, 1),
                "window_s": round(span, 2),
            })
        router.stop_http()
    finally:
        for w in workers:
            w.stop()
        kv.stop()
    summary = {
        "hosts": 2,
        "decode_step_delay_s": DECODE_DELAY_S,
        "knobs": {"num_pages": 24, "page_size": 2, "queue_limit": 8,
                  "max_batch_tokens": 128},
        "loads_rps": list(LOADS_RPS),
        "rejection_onset": next(
            (r["offered_rps"] for r in rows if r["rejected"]), None),
        "zero_error_requests": all(r["errors"] == 0 for r in rows),
    }
    return rows, summary


def _bench_migration(hvd, on_tpu):
    """`--serving` companion lane (ISSUE 19; docs/serving.md "Live
    migration"): migrate-vs-recompute A/B at long contexts. Two
    identical 2-worker rigs; 8 long streams (32-token prompt, 48 new
    tokens) are posted straight at worker 0, interrupted mid-decode by
    a drain. The MIGRATE arm hands its live KV pages to the peer
    (verified page transfer, zero re-prefill); the RECOMPUTE arm
    (``migrate=False``) must finish every stream locally before the
    chip comes free. Measured per arm: chip-release latency (drain ->
    worker-0 idle — the number fleet arbitration waits on) and
    drain-completion time (drain -> every client has its tokens),
    plus the re-prefill count, which the migrate arm must hold at 0.
    Archived to BENCH_r15.json."""
    import json as _json
    import threading
    import time
    import urllib.request

    from horovod_tpu.runner.http_server import (AUTH_HEADER,
                                                KVStoreServer,
                                                new_job_token)
    from horovod_tpu.serving.model import ToyLM
    from horovod_tpu.serving.worker import ServingWorker

    DECODE_DELAY_S = 0.01
    STREAMS = 8
    PROMPT_TOKENS = 32
    NEW_TOKENS = 48
    INTERRUPT_S = 0.2

    class PacedToyLM(ToyLM):
        def decode(self, contexts):
            time.sleep(DECODE_DELAY_S)
            return super().decode(contexts)

    oracle = ToyLM()

    def one_arm(migrate):
        token = new_job_token()
        kv = KVStoreServer(job_token=token, addr="127.0.0.1")
        kv_port = kv.start()
        workers, ports = [], []
        try:
            for wid in range(2):
                w = ServingWorker(PacedToyLM(), cohort="c0", wid=wid,
                                  migrate=migrate).start()
                port = w.serve_http(addr="127.0.0.1", token=token)
                w.register("127.0.0.1", kv_port, token,
                           advertise=f"127.0.0.1:{port}")
                workers.append(w)
                ports.append(port)

            def one_request(i, record):
                prompt = [(i % 7) + 1] * PROMPT_TOKENS
                body = _json.dumps(
                    {"prompt": prompt,
                     "max_new_tokens": NEW_TOKENS}).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{ports[0]}/v1/generate",
                    data=body, method="POST")
                req.add_header(AUTH_HEADER, token)
                with urllib.request.urlopen(req, timeout=120) as resp:
                    out = _json.loads(resp.read())
                record[i] = (out["tokens"] ==
                             oracle.reference_completion(
                                 prompt, NEW_TOKENS))

            record = [None] * STREAMS
            threads = []
            for i in range(STREAMS):
                th = threading.Thread(target=one_request,
                                      args=(i, record))
                th.start()
                threads.append(th)
            time.sleep(INTERRUPT_S)  # streams provably mid-decode
            t0 = time.monotonic()
            drain = urllib.request.Request(
                f"http://127.0.0.1:{ports[0]}/v1/serving/drain",
                data=b"{}", method="POST")
            drain.add_header(AUTH_HEADER, token)
            urllib.request.urlopen(drain, timeout=10).read()
            while not workers[0].scheduler.idle():
                time.sleep(0.002)
            chip_release_s = time.monotonic() - t0
            for th in threads:
                th.join(timeout=120)
            completion_s = time.monotonic() - t0
            s0 = workers[0].scheduler.stats()
            s1 = workers[1].scheduler.stats()
            return {
                "benchmark": "serving_migration_ab",
                "arm": "migrate" if migrate else "recompute",
                "streams": STREAMS,
                "prompt_tokens": PROMPT_TOKENS,
                "new_tokens": NEW_TOKENS,
                "decode_step_delay_s": DECODE_DELAY_S,
                "chip_release_s": round(chip_release_s, 4),
                "drain_completion_s": round(completion_s, 4),
                "migrated_out": s0["migrated_out"],
                "migrate_failed": s0["migrate_failed"],
                "migrated_in_peer": s1["migrated_in"],
                "re_prefills": s0["preemptions"] + s1["preemptions"],
                "token_exact": all(record),
            }
        finally:
            for w in workers:
                w.stop()
            kv.stop()

    rows = [one_arm(migrate=True), one_arm(migrate=False)]
    mig, rec = rows
    summary = {
        "chip_release_speedup": round(
            rec["chip_release_s"] / max(mig["chip_release_s"], 1e-9),
            2),
        "zero_re_prefill_on_migrate": (mig["migrated_out"] >= 1
                                       and mig["re_prefills"] == 0),
        "token_exact_both_arms": (mig["token_exact"]
                                  and rec["token_exact"]),
    }
    return rows, summary


def _bench_fleet(hvd, on_tpu):
    """`--fleet` lane (docs/fault_tolerance.md "Fleet arbitration"):
    replay a scripted traffic-spike profile against the two-plane rig
    — a simulated training loop (deterministic cohort-size-invariant
    updates, one "commit" per step) sharing a slot budget with a real
    serving stack (continuous-batching workers + router) under the
    fleet arbiter — and measure what the arbitration costs each plane:
    recovery time from spike onset to lease completion, training steps
    lost (MUST be 0: the trajectory is compared step-for-step against
    an uninterrupted reference), and accepted requests lost (MUST be
    0: rejections are backpressure, errors are loss).

    METHODOLOGY (CPU stand-in): decode steps padded to DECODE_DELAY_S,
    training steps to TRAIN_STEP_S, exactly like the serving lane; the
    numbers scale with the padding but the arbitration path — breach
    detection, lease state machine, preempt-at-commit-boundary, scale
    -out — is the production code. The process-level version (real
    SIGTERM/exit-83 workers) is pinned by tests/test_fleet_matrix.py;
    this lane is the measurable replay. Archived to BENCH_r14.json."""
    import json as _json
    import threading
    import time

    import numpy as np

    from horovod_tpu.fleet.arbiter import FleetArbiter
    from horovod_tpu.fleet.ledger import LeaseLedger, MemoryBackend
    from horovod_tpu.fleet.policy import FleetPolicy
    from horovod_tpu.serving.model import ToyLM
    from horovod_tpu.serving.router import InProcClient, Router
    from horovod_tpu.serving.worker import ServingWorker

    DECODE_DELAY_S = 0.01
    TRAIN_STEP_S = 0.05
    STEPS = 120
    SLO_P99 = 0.2
    # Scripted profile: (seconds, offered requests per second). The
    # spike is sized past one worker's capacity (~50 req/s at this
    # decode padding and page budget) so the SLO genuinely breaches.
    PROFILE = ((1.5, 4), (3.0, 120), (2.5, 4))
    DIM, LR = 8, 0.1

    class PacedToyLM(ToyLM):
        def decode(self, contexts):
            time.sleep(DECODE_DELAY_S)
            return super().decode(contexts)

    def reference_trajectory():
        params = np.zeros(DIM, np.float32)
        losses = []
        for step in range(STEPS):
            g = params * np.float32(0.3) + np.sin(
                0.5 * step + np.arange(DIM)).astype(np.float32)
            params = params - np.float32(LR) * g
            losses.append(float(np.sum(params ** 2)))
        return losses

    class SimTrainer(threading.Thread):
        """The training plane: deterministic updates, one commit per
        step, cohort size applied at the commit boundary — the same
        contract the elastic driver gives real workers (preemption
        lands between steps, never inside one)."""

        def __init__(self, slots):
            super().__init__(daemon=True)
            self.slots = slots          # applied at the next boundary
            self.size_log = []
            self.losses = []
            self.params = np.zeros(DIM, np.float32)
            self.step = 0

        def run(self):
            while self.step < STEPS:
                size = self.slots      # commit-boundary snapshot
                g = self.params * np.float32(0.3) + np.sin(
                    0.5 * self.step + np.arange(DIM)).astype(
                        np.float32)
                # Cohort average of identical per-rank gradients ==
                # the gradient itself at any size: the invariance the
                # real allreduce provides.
                self.params = self.params - np.float32(LR) * g
                self.losses.append(float(np.sum(self.params ** 2)))
                self.size_log.append(size)
                self.step += 1
                time.sleep(TRAIN_STEP_S)

    class SimActuators:
        def __init__(self, trainer, plane):
            self.trainer = trainer
            self.plane = plane

        def pick_train_victims(self, old, new):
            return [f"sim:{i}" for i in range(new, old)]

        def pick_serve_victims(self, old, new):
            return [f"sim:{i}" for i in range(new, old)]

        def set_train_slots(self, n):
            self.trainer.slots = n

        def set_serve_slots(self, n):
            self.plane.set_slots(n)

        def drain(self, wid):
            pass

    class SimProbes:
        def __init__(self, trainer, plane):
            self.trainer = trainer
            self.plane = plane

        def train_size(self):
            return self.trainer.slots

        def train_victims_gone(self, victims):
            return True

        def serve_size(self):
            return len(self.plane.workers)

        def serve_drained(self, victims):
            return True

        def cohort_stats(self):
            return {f"serve.{w.wid}": w.stats()
                    for w in self.plane.workers}

    class ServePlane:
        def __init__(self):
            self.workers = []
            self.router = Router(members={"serve": []})

        def set_slots(self, n):
            while len(self.workers) < n:
                w = ServingWorker(
                    PacedToyLM(), cohort="serve",
                    wid=len(self.workers), num_pages=24, page_size=2,
                    queue_limit=32, max_batch_tokens=64).start()
                self.workers.append(w)
            self.router.members["serve"] = [InProcClient(w)
                                            for w in self.workers]

        def stop(self):
            for w in self.workers:
                w.stop()

    oracle = ToyLM()
    plane = ServePlane()
    plane.set_slots(1)
    trainer = SimTrainer(slots=2)
    arbiter = FleetArbiter(
        LeaseLedger(MemoryBackend()), SimActuators(trainer, plane),
        SimProbes(trainer, plane),
        policy=FleetPolicy(min_train_slots=1, min_serve_slots=1,
                           window=2, cooldown_s=600.0,
                           ebb_idle_s=600.0, scale_up_depth=8,
                           slo_p99=SLO_P99),
        train_slots=2, serve_slots=1, drain_timeout=10.0,
        tick_s=0.2)

    phase_records = [[] for _ in PROFILE]
    request_threads = []

    def one_request(i, record):
        prompt = [2, 3 + i % 5]
        t0 = time.monotonic()
        status, body = plane.router.generate(
            {"prompt": prompt, "max_new_tokens": 8})
        dt = time.monotonic() - t0
        if status == 200:
            good = body["tokens"] == oracle.reference_completion(
                prompt, 8)
            record.append(("ok" if good else "corrupt", dt))
        elif status in (429, 503):
            record.append(("rejected", dt))
        else:
            record.append(("error", dt))

    rows = []
    try:
        trainer.start()
        arbiter.start()
        spike_t0 = None
        reqno = 0
        for phase, (dur, rps) in enumerate(PROFILE):
            if phase == 1:
                spike_t0 = time.monotonic()
            t_end = time.monotonic() + dur
            while time.monotonic() < t_end:
                th = threading.Thread(
                    target=one_request,
                    args=(reqno, phase_records[phase]))
                th.start()
                request_threads.append(th)
                reqno += 1
                time.sleep(1.0 / rps)
        for th in request_threads:
            th.join(timeout=60)
        # Recovery time: spike onset -> lease complete.
        deadline = time.monotonic() + 30
        while arbiter.ledger.active() is not None \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        recovery_s = None
        if arbiter.split.get("leased", 0) > 0 and spike_t0 is not None:
            recovery_s = time.monotonic() - spike_t0
        arbiter.stop()
        trainer.join(timeout=STEPS * TRAIN_STEP_S + 30)
    finally:
        plane.stop()

    reference = reference_trajectory()
    lost_steps = STEPS - len(trainer.losses)
    trajectory_equal = trainer.losses == reference
    for phase, (dur, rps) in enumerate(PROFILE):
        rec = phase_records[phase]
        lat = sorted(t for kind, t in rec if kind == "ok")
        q = (lambda p: round(lat[min(len(lat) - 1,
                                     int(p * len(lat)))], 4)) \
            if lat else (lambda p: None)
        rows.append({
            "benchmark": "fleet_spike_replay",
            "phase": ("warmup", "spike", "after")[phase],
            "offered_rps": rps,
            "offered": len(rec),
            "completed": len(lat),
            "rejected": sum(1 for k, _ in rec if k == "rejected"),
            "errors": sum(1 for k, _ in rec
                          if k in ("error", "corrupt")),
            "p50_latency_s": q(0.50),
            "p99_latency_s": q(0.99),
        })
    summary = {
        "profile_s_rps": [list(p) for p in PROFILE],
        "slo_p99_s": SLO_P99,
        "train_steps": STEPS,
        "split_after": arbiter.split,
        "transfer_completed": arbiter.split.get("leased", 0) > 0,
        "recovery_time_s": (round(recovery_s, 2)
                            if recovery_s is not None else None),
        "lost_steps": lost_steps,
        "trajectory_equal_to_reference": trajectory_equal,
        "train_sizes_seen": sorted(set(trainer.size_log)),
        "accepted_request_loss": sum(r["errors"] for r in rows),
    }
    return rows, summary


def _simulate_worker():
    """--simulate-worker: one measured eager run at the world size the
    parent pinned via XLA_FLAGS, with the trace plane on so the shard
    carries calibratable sub→fin spans (+ payload bytes). Prints one
    JSON line: {"n", "step_s", "leaves", "step_bytes"}. Knobs via env
    (BENCH_SIM_STEPS/BENCH_SIM_REPEATS) so the tier-1 test can run a
    fast geometry."""
    import math
    import os
    import time as _time

    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.models import TransformerLM, TransformerConfig
    from horovod_tpu.ops import collectives as hvd_collectives

    hvd.init()
    n = hvd.size()
    seq = 64
    cfg = TransformerConfig(vocab_size=1024, hidden=512, layers=2,
                            heads=8, max_len=seq, causal=True,
                            use_rope=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, seq), jnp.int32))
    grads = [jnp.stack([jnp.asarray(leaf)] * n)
             for leaf in jax.tree.leaves(params)]
    step_bytes = sum(int(math.prod(g.shape)) * g.dtype.itemsize
                     for g in grads)
    steps = int(os.environ.get("BENCH_SIM_STEPS", "10"))
    repeats = int(os.environ.get("BENCH_SIM_REPEATS", "3"))

    def one_step():
        handles = [
            hvd_collectives.allreduce_async(
                g, name=f"grad.{i}", op=hvd.Sum)
            for i, g in enumerate(grads)]
        for h in handles:
            hvd.synchronize(h)

    for _ in range(steps):
        one_step()  # warmup: compile + caches
    # Median single-step time — the same statistic the calibration
    # takes per run group (eager CPU step times are noisy; means and
    # minima diverge from it by 2x under load).
    times = []
    for _ in range(steps * repeats):
        t0 = _time.perf_counter()
        one_step()
        times.append(_time.perf_counter() - t0)
    times.sort()
    mid = len(times) // 2
    step_s = (times[mid] if len(times) % 2
              else (times[mid - 1] + times[mid]) / 2.0)
    hvd.shutdown()  # flush + close the shard before the parent reads it
    print(json.dumps({"n": n, "step_s": step_s,
                      "leaves": len(grads),
                      "step_bytes": step_bytes}), flush=True)


def _bench_simulate_lane():
    """--simulate: measured n=2/4/8 eager runs (each in a subprocess
    with its own host-device count and a fresh trace dir) calibrate
    the α–β cost model, which then predicts step-time/comm-fraction
    curves at n∈{8,64,256,1024}. Archived to BENCH_r12.json together
    with the predicted-vs-measured residual at the measured
    geometries — the honesty check that makes the extrapolated
    numbers worth printing (docs/performance.md "Predicted
    scaling")."""
    import os
    import shutil
    import subprocess
    import tempfile
    from types import SimpleNamespace

    from horovod_tpu.analysis import costmodel
    from horovod_tpu.tracing import merge as trace_merge

    worlds = (2, 4, 8)
    root = tempfile.mkdtemp(prefix="hvd_bench_sim_")
    measured = []
    try:
        for n in worlds:
            d = os.path.join(root, f"n{n}")
            os.makedirs(d, exist_ok=True)
            env = dict(os.environ)
            flags = [f for f in env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count"
                     not in f]
            flags.append(
                f"--xla_force_host_platform_device_count={n}")
            env["XLA_FLAGS"] = " ".join(flags)
            env["JAX_PLATFORMS"] = "cpu"
            env["HVDTPU_TRACE"] = "1"
            env["HVDTPU_TRACE_DIR"] = d
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--simulate-worker"],
                env=env, capture_output=True, text=True, timeout=900)
            if out.returncode != 0:
                raise RuntimeError(
                    f"simulate worker n={n} failed: "
                    f"{out.stderr.strip()[-500:]}")
            row = json.loads(out.stdout.strip().splitlines()[-1])
            measured.append(row)

        table = costmodel.fit_shards(trace_merge.load_paths(
            [os.path.join(root, f"n{n}") for n in worlds],
            kinds=(trace_merge.SHARD_PREFIX,)))

        leaves = measured[-1]["leaves"]
        events = [SimpleNamespace(kind="allreduce_async")] * leaves
        residuals = []
        for row in measured:
            pred = costmodel.predict_step(
                events, row["n"], table,
                step_bytes=row["step_bytes"])
            residuals.append({
                "n": row["n"],
                "measured_step_ms": round(row["step_s"] * 1e3, 3),
                "predicted_step_ms": round(pred["step_s"] * 1e3, 3),
                "residual": round(
                    abs(pred["step_s"] - row["step_s"])
                    / row["step_s"], 4),
            })

        # Extrapolated curves at a REAL multi-host geometry: constant
        # per-rank payload (the per-leaf gradient set), unlike the
        # measured single-controller runs whose stacked arrays grow
        # with n — the residual table above is fit on what was
        # actually measured.
        per_rank_bytes = int(measured[0]["step_bytes"]
                             / measured[0]["n"])
        curves = []
        for n in (8, 64, 256, 1024):
            pred = costmodel.predict_step(events, n, table,
                                          step_bytes=per_rank_bytes)
            curves.append({
                "n": n,
                "predicted_step_ms": round(pred["step_s"] * 1e3, 3),
                "predicted_comm_ms": round(pred["comm_s"] * 1e3, 3),
                "comm_fraction": round(pred["comm_fraction"], 4),
            })
        doc = {
            "cmd": "python bench.py --simulate",
            "table": {
                "source": table["source"],
                "kinds": table["kinds"],
                "compute_s": table["compute_s"],
                "fixed_s": table.get("fixed_s", 0.0),
                "serial_fraction": table["serial_fraction"],
                "worlds": table["worlds"],
                "spans": table["spans"],
            },
            "payload_bytes_per_rank_step": per_rank_bytes,
            "residuals": residuals,
            "predicted_scaling": curves,
        }
        return doc
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _bench_reshard():
    """--reshard: the redistribution planner lane (ISSUE 17,
    docs/resharding.md). Times planner-emitted programs against the
    naive gather-all baseline (every destination rank stages every
    source shard — the pre-planner shape of an elastic reshard) across
    the three canonical transitions: ZeRO 4→2, ZeRO 2→4, and
    dense→2D (replicated tree onto a dp × tp composed layout).
    Archives BENCH_r13.json with bytes moved, wall time, peak staging
    bytes vs the shard + 2×bucket budget, and the α–β cost model's
    predicted-vs-measured ratio per program."""
    import time

    import numpy as np

    import jax

    from horovod_tpu import resharding
    from horovod_tpu.ops.zero import plan_zero

    rng = np.random.RandomState(0)
    # Transformer-block-ish leaves, ~2.6 MB total, shapes chosen so
    # the tensor dims divide tp=2 but the flat sizes stay pad-heavy.
    meta = [((256, 512), "float32"), ((512,), "float32"),
            ((512, 256), "float32"), ((1024, 64), "float32"),
            ((37,), "float32")]
    leaves = [rng.randn(*s).astype(d) for s, d in meta]
    structs = [jax.ShapeDtypeStruct(s, d) for s, d in meta]
    bucket = 256 * 1024  # small enough to force multi-step windows

    def zero_spec(n, axis="z"):
        return resharding.zero_flat_spec(
            plan_zero(structs, n), axis=axis)

    # dense -> 2D: a replicated tree onto dp=2 x tp=2 — tensor stages
    # mirror parallel.sharding's column/row rules, ZeRO legs over dp.
    tp_layouts = [resharding.Sharded("tp", 1),
                  resharding.Sharded("tp", 0),
                  resharding.Sharded("tp", 0),
                  resharding.Replicated(),
                  resharding.Replicated()]
    tp_structs = []
    for (shape, dtype), lay in zip(meta, tp_layouts):
        shape = list(shape)
        if isinstance(lay, resharding.Sharded):
            shape[lay.dim] //= 2
        tp_structs.append(jax.ShapeDtypeStruct(tuple(shape), dtype))
    twod_spec = resharding.Spec(
        {"dp": 2, "tp": 2}, tp_layouts,
        zero=resharding.ZeroFlat("dp", plan_zero(tp_structs, 2)))

    transitions = [
        ("zero_4_to_2", zero_spec(4), zero_spec(2)),
        ("zero_2_to_4", zero_spec(2), zero_spec(4)),
        ("dense_to_2d",
         resharding.replicated_spec(len(meta), {"m": 4}), twod_spec),
    ]
    rows = []
    for tag, src, dst in transitions:
        t0 = time.perf_counter()
        program = resharding.plan_redistribution(
            src, dst, meta, bucket_bytes=bucket)
        plan_s = time.perf_counter() - t0
        assert program.prove() == [], f"{tag}: program not proven clean"
        bufs = {r: resharding.buffers_of_tree(src, meta, leaves, r)
                for r in range(src.world)}
        ledger = resharding.MemoryLedger()
        t0 = time.perf_counter()
        _, report = resharding.execute_host(
            program, resharding.reader_for_buffers(bufs),
            ledger=ledger)
        wall_s = time.perf_counter() - t0

        # Naive baseline: every dst rank stages EVERY source shard
        # before slicing its part — the full replica per rank.
        t0 = time.perf_counter()
        naive_bytes = 0
        naive_peak = 0
        for _ in range(dst.world):
            staged = [np.array(v) for b in bufs.values()
                      for v in b.values()]
            nb = sum(v.nbytes for v in staged)
            naive_bytes += nb
            naive_peak = max(naive_peak, nb)
            del staged
        naive_s = time.perf_counter() - t0

        shard = max(
            sum(n * np.dtype(d).itemsize
                for n, d in spec.local_buffers(meta, r).values())
            for spec in (src, dst) for r in range(spec.world))
        budget = shard + 2 * bucket
        assert report["peak_bytes"] <= budget, (
            f"{tag}: peak {report['peak_bytes']} exceeds "
            f"shard + 2 x bucket = {budget}")
        rows.append({
            "metric": f"reshard_{tag}",
            "strategy": program.strategy,
            "steps": len(program.steps),
            "plan_seconds": round(plan_s, 6),
            "wall_seconds": round(wall_s, 6),
            "naive_wall_seconds": round(naive_s, 6),
            "wire_bytes": program.bytes_moved(),
            "naive_bytes": naive_bytes,
            "bytes_saved_vs_naive":
                naive_bytes - program.bytes_moved(),
            "peak_bytes": report["peak_bytes"],
            "naive_peak_bytes": naive_peak,
            "peak_budget_bytes": budget,
            "peak_within_budget": report["peak_bytes"] <= budget,
            "predicted_seconds": round(program.predicted_s, 9),
            "predicted_over_measured":
                round(program.predicted_s / max(wall_s, 1e-9), 4),
        })
    total_wire = sum(r["wire_bytes"] for r in rows)
    total_naive = sum(r["naive_bytes"] for r in rows)
    summary = {
        "transitions": len(rows),
        "total_wire_bytes": total_wire,
        "total_naive_bytes": total_naive,
        "wire_fraction_of_naive": round(
            total_wire / max(total_naive, 1), 4),
        "all_peaks_within_budget": all(
            r["peak_within_budget"] for r in rows),
        "all_programs_proven": True,
    }
    return {"cmd": "python bench.py --reshard", "rows": rows,
            "summary": summary}


LANE_FLAGS = ("--compression", "--overlap", "--zero", "--reshard",
              "--sparse", "--serving", "--fleet", "--autotune", "--trace",
              "--simulate")


def main():
    if "--simulate-worker" in sys.argv:
        _simulate_worker()
        return
    import os

    import jax

    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.utils import compile_cache
    compile_cache.enable()

    # The flag-selected lanes are CPU stand-ins until the benchmark of
    # cells replaces them (ROADMAP A1); the no-flag run measures the
    # chip and nothing else.
    lanes = [flag for flag in LANE_FLAGS if flag in sys.argv]
    on_tpu = jax.default_backend() == "tpu"
    if not lanes and not on_tpu:
        sys.exit(f"bench.py: the no-flag run needs a TPU; "
                 f"jax.default_backend()={jax.default_backend()!r}, "
                 f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")

    # Metrics ride along with every bench run: the archived snapshot
    # (fusion efficiency, per-collective bytes/latency) is the measured
    # substrate future perf PRs cite next to the BENCH json. Any prefix
    # spelling of the knob (HOROVOD_TPU_METRICS=0 included) wins over
    # this default.
    from horovod_tpu.utils import envparse
    if envparse.get_env(envparse.METRICS) is None:
        os.environ["HVDTPU_METRICS"] = "1"
    hvd.init()

    def emit(fn, *args, **kwargs):
        """Run one benchmark and print its line; a line that cannot be
        produced fails the run."""
        print(json.dumps(fn(*args, **kwargs)), flush=True)

    if on_tpu:
        emit(_bench_transformer, hvd, hvd_jax, on_tpu)
    # --compression: sweep the transformer line across codecs so
    # BENCH_r* records the gradient-bytes delta (the `none` point is
    # the headline transformer line just emitted). int8 always; fp8
    # when the jax build carries it.
    if "--compression" in sys.argv:
        from horovod_tpu.compression import codecs as _codecs
        sweep = ["int8"] + (["fp8"] if _codecs.fp8_supported() else [])
        for codec in sweep:
            emit(_bench_transformer, hvd, hvd_jax, on_tpu,
                 compression=codec,
                 metric=f"transformer_lm_365m_seq512_compression_"
                        f"{codec}_train_samples_per_sec_per_chip")
    # --overlap: A/B the bucketed comm/compute overlap path (overlap
    # on/off × compression none/int8) on the transformer line and
    # archive the four rows to BENCH_r06.json (docs/performance.md).
    if "--overlap" in sys.argv:
        # The sweep mutates the overlap knobs per row; snapshot them so
        # the lines AFTER the sweep (seq2048, keras, resnet headline)
        # run under the caller's configuration, not the last row's.
        _saved_knobs = {k: os.environ.get(k)
                        for k in ("HVDTPU_OVERLAP", "HVDTPU_BUCKET_BYTES")}
        # The off-TPU stand-in config has ~2 MB of gradients — at the
        # 16 MiB default everything lands in one bucket and the A/B
        # degenerates. Scale the bucket down so the sweep exercises a
        # real multi-bucket schedule (a user-set knob always wins).
        if not on_tpu and envparse.get_env(envparse.BUCKET_BYTES) is None:
            os.environ["HVDTPU_BUCKET_BYTES"] = str(256 * 1024)
        rows = []
        for ov in (0, 1):
            for codec in (None, "int8"):
                tag = (f"overlap_{'on' if ov else 'off'}_comp_"
                       f"{codec or 'none'}")
                try:
                    row = _bench_transformer(
                        hvd, hvd_jax, on_tpu, overlap=ov,
                        compression=codec,
                        metric=f"transformer_lm_365m_seq512_{tag}"
                               "_train_samples_per_sec_per_chip")
                except Exception as e:  # noqa: BLE001 — best-effort row
                    print(f"# bench: overlap row {tag} failed: {e!r}",
                          file=sys.stderr, flush=True)
                    continue
                print(json.dumps(row), flush=True)
                rows.append(row)
        try:
            with open("BENCH_r06.json", "w") as f:
                json.dump({"cmd": "python bench.py --overlap",
                           "rows": rows}, f, indent=1)
            print("# bench: overlap A/B archived to BENCH_r06.json",
                  file=sys.stderr, flush=True)
        except Exception as e:  # noqa: BLE001 — evidence is best-effort
            print(f"# bench: BENCH_r06.json write failed: {e}",
                  file=sys.stderr, flush=True)
        for k, v in _saved_knobs.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    # --zero: A/B the replicated vs ZeRO-1 sharded weight update on the
    # transformer-LM stand-in (throughput + per-replica optimizer-state
    # bytes) and archive BENCH_r08.json (docs/performance.md "ZeRO-1").
    if "--zero" in sys.argv:
        rows = []
        for z in (0, 1):
            for codec in ((None,) if z == 0 else (None, "int8")):
                tag = (f"zero_{'on' if z else 'off'}"
                       + (f"_comp_{codec}" if codec else ""))
                try:
                    row = _bench_transformer(
                        hvd, hvd_jax, on_tpu, zero=z, compression=codec,
                        metric=f"transformer_lm_365m_seq512_{tag}"
                               "_train_samples_per_sec_per_chip")
                except Exception as e:  # noqa: BLE001 — best-effort row
                    print(f"# bench: zero row {tag} failed: {e!r}",
                          file=sys.stderr, flush=True)
                    continue
                print(json.dumps(row), flush=True)
                rows.append(row)
        try:
            n = hvd.size() if hvd.size() > 1 else len(jax.devices())
            by_zero = {r["zero"]: r for r in rows
                       if "compression" not in r}
            summary = {}
            if 0 in by_zero and 1 in by_zero:
                summary = {
                    "replicated_state_bytes":
                        by_zero[0]["opt_state_bytes_per_replica"],
                    "sharded_state_bytes":
                        by_zero[1]["opt_state_bytes_per_replica"],
                    "state_fraction": round(
                        by_zero[1]["opt_state_bytes_per_replica"]
                        / max(by_zero[0]["opt_state_bytes_per_replica"],
                              1), 4),
                    "world_size": n,
                }
            with open("BENCH_r08.json", "w") as f:
                json.dump({"cmd": "python bench.py --zero",
                           "rows": rows, "summary": summary}, f,
                          indent=1)
            print("# bench: zero A/B archived to BENCH_r08.json",
                  file=sys.stderr, flush=True)
        except Exception as e:  # noqa: BLE001 — evidence is best-effort
            print(f"# bench: BENCH_r08.json write failed: {e}",
                  file=sys.stderr, flush=True)
    # --reshard: planner-emitted redistribution programs vs the naive
    # gather-all baseline (4→2, 2→4, dense→2D), peak staging vs the
    # shard + 2×bucket budget, predicted-vs-measured ratio per program.
    # Archives BENCH_r13.json (docs/resharding.md "Bench").
    if "--reshard" in sys.argv:
        try:
            doc = _bench_reshard()
            for row in doc["rows"]:
                print(json.dumps(row), flush=True)
            with open("BENCH_r13.json", "w") as f:
                json.dump(doc, f, indent=1)
            print("# bench: reshard lane archived to BENCH_r13.json",
                  file=sys.stderr, flush=True)
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 — best-effort lane
            print(f"# bench: reshard lane failed: {e!r}",
                  file=sys.stderr, flush=True)
    # --sparse: the sparse/embedding gradient plane lane (ISSUE 11,
    # docs/sparse.md): density × path × codec sweep on a DLRM/NMT
    # stand-in, archived as BENCH_r09.json with wire bytes next to
    # samples/s against the densified baseline.
    if "--sparse" in sys.argv:
        try:
            rows, summary = _bench_sparse(hvd, on_tpu)
            for row in rows:
                print(json.dumps(row), flush=True)
            with open("BENCH_r09.json", "w") as f:
                json.dump({"cmd": "python bench.py --sparse",
                           "rows": rows, "summary": summary}, f,
                          indent=1)
            print("# bench: sparse sweep archived to BENCH_r09.json",
                  file=sys.stderr, flush=True)
            red = summary.get("wire_reduction_at_5pct_density", 0)
            assert red >= 4.0, (
                f"embedding wire reduction {red}x at 5% density is "
                "under the 4x acceptance bar (BENCH_r09.json has the "
                "sweep)")
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 — best-effort lane
            print(f"# bench: sparse lane failed: {e!r}",
                  file=sys.stderr, flush=True)
    # --serving: closed-loop load generator over the serving plane
    # (router + 2 continuous-batching workers over real HTTP) at 3
    # offered loads; p50/p99 latency, tokens/s and rejection rate
    # archived as BENCH_r11.json (ISSUE 13, docs/serving.md).
    if "--serving" in sys.argv:
        try:
            rows, summary = _bench_serving(hvd, on_tpu)
            for row in rows:
                print(json.dumps(row), flush=True)
            with open("BENCH_r11.json", "w") as f:
                json.dump({"cmd": "python bench.py --serving",
                           "rows": rows, "summary": summary}, f,
                          indent=1)
            print("# bench: serving load sweep archived to "
                  "BENCH_r11.json", file=sys.stderr, flush=True)
            assert summary["zero_error_requests"], (
                "serving lane saw transport/5xx errors — backpressure "
                "must reject with 429, never fail accepted requests "
                "(BENCH_r11.json has the sweep)")
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 — best-effort lane
            print(f"# bench: serving lane failed: {e!r}",
                  file=sys.stderr, flush=True)
        # Companion A/B: live migration vs recompute at long contexts
        # (ISSUE 19, docs/serving.md "Live migration"). Chip-release
        # and drain-completion latency per arm, archived separately so
        # BENCH_r11.json keeps its stable load-sweep schema.
        try:
            rows, summary = _bench_migration(hvd, on_tpu)
            for row in rows:
                print(json.dumps(row), flush=True)
            with open("BENCH_r15.json", "w") as f:
                json.dump({"cmd": "python bench.py --serving",
                           "rows": rows, "summary": summary}, f,
                          indent=1)
            print("# bench: migrate-vs-recompute A/B archived to "
                  "BENCH_r15.json", file=sys.stderr, flush=True)
            assert summary["token_exact_both_arms"], (
                "migration A/B diverged from the oracle tokens "
                "(BENCH_r15.json has both arms)")
            assert summary["zero_re_prefill_on_migrate"], (
                "migrate arm re-prefilled or never migrated — drain "
                "fell back to recompute (BENCH_r15.json)")
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 — best-effort lane
            print(f"# bench: migration A/B failed: {e!r}",
                  file=sys.stderr, flush=True)
    # --fleet: scripted traffic-spike replay through the chip-budget
    # arbiter (training sim + real serving stack under one slot
    # budget); recovery time, lost steps (must be 0) and accepted
    # -request loss (must be 0) archived as BENCH_r14.json
    # (docs/fault_tolerance.md "Fleet arbitration").
    if "--fleet" in sys.argv:
        try:
            rows, summary = _bench_fleet(hvd, on_tpu)
            for row in rows:
                print(json.dumps(row), flush=True)
            with open("BENCH_r14.json", "w") as f:
                json.dump({"cmd": "python bench.py --fleet",
                           "rows": rows, "summary": summary}, f,
                          indent=1)
            print("# bench: fleet spike replay archived to "
                  "BENCH_r14.json", file=sys.stderr, flush=True)
            assert summary["transfer_completed"], (
                "fleet lane spike never completed a lease transfer — "
                "no arbitration was measured (BENCH_r14.json)")
            assert summary["lost_steps"] == 0, (
                "fleet lane lost training steps across the transfer "
                "(BENCH_r14.json has the replay)")
            assert summary["trajectory_equal_to_reference"], (
                "fleet lane training trajectory diverged from the "
                "uninterrupted reference (BENCH_r14.json)")
            assert summary["accepted_request_loss"] == 0, (
                "fleet lane lost accepted serving requests — "
                "rejection is backpressure, an error is loss "
                "(BENCH_r14.json has the replay)")
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 — best-effort lane
            print(f"# bench: fleet lane failed: {e!r}",
                  file=sys.stderr, flush=True)
    # --autotune: default vs converged vs warm-started A/B of the
    # trace-driven online tuner (ISSUE 12, docs/autotune.md), archived
    # with the sweep history as BENCH_r10.json.
    if "--autotune" in sys.argv:
        try:
            rows, summary = _bench_autotune(hvd, on_tpu)
            for row in rows:
                print(json.dumps(row), flush=True)
            with open("BENCH_r10.json", "w") as f:
                json.dump({"cmd": "python bench.py --autotune",
                           "rows": rows, "summary": summary}, f,
                          indent=1)
            print("# bench: autotune A/B archived to BENCH_r10.json",
                  file=sys.stderr, flush=True)
            ratio = summary.get("tuned_vs_default", 0.0)
            if ratio < 1.0:
                print(f"# bench: converged config at {ratio}x the "
                      "default — CPU stand-in noise; BENCH_r10.json "
                      "has the sweep history", file=sys.stderr,
                      flush=True)
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 — best-effort lane
            print(f"# bench: autotune lane failed: {e!r}",
                  file=sys.stderr, flush=True)
    # --trace: smoke the cross-rank trace plane on the transformer-LM
    # gradient set (eager plane), archive the analyzer summary to
    # BENCH_r07.json and hold tracing-on to the <3% overhead budget
    # (docs/tracing.md).
    if "--trace" in sys.argv:
        try:
            rows, summary, overhead = _bench_trace_lane(hvd, on_tpu)
            for row in rows:
                print(json.dumps(row), flush=True)
            with open("BENCH_r07.json", "w") as f:
                json.dump({"cmd": "python bench.py --trace",
                           "rows": rows, "analyzer": summary}, f,
                          indent=1)
            print("# bench: trace A/B + analyzer summary archived to "
                  "BENCH_r07.json", file=sys.stderr, flush=True)
            assert overhead < 0.03, (
                f"tracing-on overhead {overhead:.1%} exceeds the 3% "
                "budget (BENCH_r07.json has the A/B)")
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 — best-effort lane
            print(f"# bench: trace lane failed: {e!r}",
                  file=sys.stderr, flush=True)
    # --simulate: calibrate the α–β cost model on measured n=2/4/8
    # eager runs, archive predicted scaling curves at n∈{8,64,256,1024}
    # plus the predicted-vs-measured residual table as BENCH_r12.json
    # (ISSUE 16, docs/performance.md "Predicted scaling").
    if "--simulate" in sys.argv:
        try:
            doc = _bench_simulate_lane()
            for row in doc["residuals"]:
                print(json.dumps({"metric": "costmodel_residual",
                                  **row}), flush=True)
            with open("BENCH_r12.json", "w") as f:
                json.dump(doc, f, indent=1)
            print("# bench: predicted scaling curves + residuals "
                  "archived to BENCH_r12.json", file=sys.stderr,
                  flush=True)
            worst = max(r["residual"] for r in doc["residuals"])
            assert worst <= 0.25, (
                f"cost-model residual {worst:.1%} exceeds the 25% "
                "acceptance bar (BENCH_r12.json has the table)")
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 — best-effort lane
            print(f"# bench: simulate lane failed: {e!r}",
                  file=sys.stderr, flush=True)
    # Long-context line: seq 2048 is where the einsum path cannot run at
    # all (27G logits > 15.75G HBM) and the flash kernel carries it.
    # TPU-only: off-TPU the small stand-in config would rerun the same
    # seq-64 workload under a mislabeled seq-2048 metric name.
    if on_tpu:
        # Batch 6 measured fastest at the 1024-token tiles (r3 sweep:
        # b4 17.04, b6 17.53, b8 15.95 samples/s — docs/PERF.md).
        emit(_bench_transformer, hvd, hvd_jax, on_tpu, seq_tpu=2048,
             batch_tpu=6,
             metric="transformer_lm_365m_seq2048_flash_train_samples"
                    "_per_sec_per_chip")
        # Headline last (the driver records the final line); metric name
        # kept compatible with round 1 for cross-round comparison.
        emit(_bench_resnet, hvd, hvd_jax)
    _dump_metrics_snapshot(hvd)


def _dump_metrics_snapshot(hvd):
    """Archive the run's telemetry next to the BENCH json (file, not
    stdout: the driver records the final stdout line as the headline).
    Inspect or compare runs with `hvd-metrics dump/diff`. Never allowed
    to fail the bench."""
    try:
        from horovod_tpu import telemetry
        from horovod_tpu.utils import envparse
        path = envparse.get_str(envparse.METRICS_SNAPSHOT,
                                "BENCH_metrics.json")
        with open(path, "w") as f:
            f.write(telemetry.render_json(hvd.metrics_snapshot(),
                                          indent=1))
        print(f"# bench: metrics snapshot written to {path}",
              file=sys.stderr, flush=True)
    except Exception as e:  # noqa: BLE001 — evidence is best-effort
        print(f"# bench: metrics snapshot failed: {e}",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
