"""KV-cache live migration (docs/serving.md "Live migration").

The tier-1 acceptance contract (ISSUE 19):

- export/import round-trips a sequence's KV pages bit-exactly, in
  table order, with per-page sha256 digests verified BEFORE any page
  is allocated (corrupt payload => DigestMismatch, pool untouched);
- placement is all-or-nothing against the target watermark
  (NoHeadroom leaves the free count exactly as it was) and fenced by
  elastic version (a stale record answers 409 ``version_fenced``);
- a pool-exhausted scheduler migrates its preemption victim to a peer
  with headroom and the stream completes there token-exact with ZERO
  recompute (target preemptions stay 0);
- every failure leg falls back loudly to the recompute status quo —
  identical final tokens either way;
- drain moves every live sequence out (``migrate_all_out``), and the
  429 Retry-After hint carries deterministic per-request jitter.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from horovod_tpu import chaos
from horovod_tpu.runner.http_server import (AUTH_HEADER, KVStoreServer,
                                            new_job_token)
from horovod_tpu.serving import metrics as smetrics
from horovod_tpu.serving import migration
from horovod_tpu.serving.kv_cache import (DigestMismatch,
                                          GeometryMismatch, NoHeadroom,
                                          PagePool, PageTable)
from horovod_tpu.serving.model import ToyLM
from horovod_tpu.serving.router import Router, retry_after_jitter
from horovod_tpu.serving.scheduler import Request, Scheduler
from horovod_tpu.serving.worker import ServingWorker
from horovod_tpu.utils import envparse


# ==========================================================================
# PagePool export/import: verified, ordered, all-or-nothing
# ==========================================================================

def _filled_table(pool, n_tokens, seed=7):
    rng = np.random.default_rng(seed)
    table = PageTable(pool)
    table.append(rng.standard_normal(
        (n_tokens, pool.kv_dim)).astype(np.float32))
    return table


def test_export_import_roundtrip_bit_exact():
    src = PagePool(8, 4, kv_dim=3, watermark=1)
    # 10 tokens over 4-slot pages: 3 pages, the last only 2/4 used —
    # the partial-page case must round-trip too.
    table = _filled_table(src, 10)
    rec = src.export_sequence(table)
    assert rec["num_tokens"] == 10
    assert len(rec["pages"]) == 3
    dst = PagePool(8, 4, kv_dim=3, watermark=1)
    free_before = dst.free_pages
    imported = dst.import_sequence(rec)
    assert dst.free_pages == free_before - 3
    np.testing.assert_array_equal(imported.gather(), table.gather())
    # Release accounting survives the trip.
    imported.release()
    assert dst.free_pages == free_before


def test_export_is_in_table_order_not_page_id_order():
    pool = PagePool(8, 2, kv_dim=2, watermark=1)
    decoy = pool.alloc(3)          # force non-contiguous page ids
    table = _filled_table(pool, 5)
    pool.free(decoy)
    rec = pool.export_sequence(table)
    dst = PagePool(8, 2, kv_dim=2, watermark=1)
    np.testing.assert_array_equal(
        dst.import_sequence(rec).gather(), table.gather())


def test_corrupt_payload_rejected_pool_unchanged():
    src = PagePool(8, 4, kv_dim=3, watermark=1)
    rec = src.export_sequence(_filled_table(src, 10))
    assert migration._corrupt_payload(rec["pages"])
    dst = PagePool(8, 4, kv_dim=3, watermark=1)
    free_before = dst.free_pages
    with pytest.raises(DigestMismatch):
        dst.import_sequence(rec)
    assert dst.free_pages == free_before, \
        "a refused import must leave the pool untouched"


def test_import_refused_below_watermark_all_or_nothing():
    src = PagePool(8, 4, kv_dim=3, watermark=1)
    rec = src.export_sequence(_filled_table(src, 10))  # needs 3 pages
    dst = PagePool(4, 4, kv_dim=3, watermark=2)        # 4-3 < 2
    free_before = dst.free_pages
    with pytest.raises(NoHeadroom):
        dst.import_sequence(rec)
    assert dst.free_pages == free_before


def test_import_geometry_mismatches_are_loud():
    src = PagePool(8, 4, kv_dim=3, watermark=1)
    rec = src.export_sequence(_filled_table(src, 10))
    with pytest.raises(GeometryMismatch):
        PagePool(8, 2, kv_dim=3, watermark=1).import_sequence(rec)
    with pytest.raises(GeometryMismatch):
        PagePool(8, 4, kv_dim=5, watermark=1).import_sequence(rec)
    # Page count vs token count disagreement.
    short = dict(rec, pages=rec["pages"][:-1])
    with pytest.raises(GeometryMismatch):
        PagePool(8, 4, kv_dim=3, watermark=1).import_sequence(short)


# ==========================================================================
# Wire helpers: chunking, jitter, staging
# ==========================================================================

def test_chunk_pages_bounds_and_preserves_order():
    pages = [{"payload": "x" * 300, "digest": str(i)}
             for i in range(7)]
    chunks = migration.chunk_pages(pages, max_bytes=1000)
    assert len(chunks) > 1
    assert [pg["digest"] for c in chunks for pg in c] \
        == [str(i) for i in range(7)]
    # A cold (pageless) record still gets its commit chunk.
    assert migration.chunk_pages([], max_bytes=1000) == [[]]
    # One oversized page still ships alone (the target 413s loudly).
    assert len(migration.chunk_pages(
        [{"payload": "y" * 5000}], max_bytes=1000)) == 1


def test_retry_after_jitter_deterministic_and_spread():
    vals = {rid: retry_after_jitter(rid) for rid in
            (f"req-{i}" for i in range(64))}
    for rid, v in vals.items():
        assert v == retry_after_jitter(rid), "must be deterministic"
        assert 0.5 <= v <= 1.5, v
    assert len(set(vals.values())) > 16, \
        "jitter must de-herd: many distinct values across request ids"
    assert retry_after_jitter("a", base=0.1) != \
        retry_after_jitter("b", base=0.1) or \
        retry_after_jitter("a") != retry_after_jitter("b")


def test_inbound_staging_reassembles_out_of_order():
    st = migration.InboundStaging(max_staged=2, ttl_s=30.0)
    mk = lambda c, total, commit: {
        "mid": "m1", "chunk": c, "total": total,
        "pages": [{"payload": f"p{c}"}],
        **({"meta": {"id": "s"}, "commit": True} if commit else {})}
    assert st.offer(mk(1, 3, True)) is None     # commit arrives early
    assert st.offer(mk(2, 3, False)) is None
    rec = st.offer(mk(0, 3, False))
    assert rec is not None and rec["id"] == "s"
    assert [p["payload"] for p in rec["pages"]] == ["p0", "p1", "p2"]
    assert st.depth() == 0


def test_inbound_staging_bounded_and_validating():
    st = migration.InboundStaging(max_staged=1, ttl_s=30.0)
    assert st.offer({"mid": "a", "chunk": 0, "total": 2,
                     "pages": []}) is None
    with pytest.raises(migration.StagingFull):
        st.offer({"mid": "b", "chunk": 0, "total": 2, "pages": []})
    with pytest.raises(ValueError):
        st.offer({"mid": "a", "chunk": 5, "total": 2, "pages": []})


def test_migrate_knobs_registered_with_documented_defaults():
    assert envparse.KNOBS["SERVING_MIGRATE_RETRIES"]["default"] == "3"
    assert envparse.KNOBS["SERVING_MIGRATE_DEADLINE"]["default"] == "5"
    assert envparse.KNOBS["SERVING_MIGRATE_MAX_BYTES"]["default"] \
        == "4194304"
    cfg = migration.knobs()
    assert cfg == {"retries": 3, "deadline": 5.0,
                   "max_bytes": 4194304}


# ==========================================================================
# Scheduler: migrate-before-preempt, drain hand-off, cold records
# ==========================================================================

class _LocalMigrator:
    """In-proc Migrator stand-in: imports straight into a target
    scheduler (no HTTP) so the scheduler-side policy is testable
    alone."""

    def __init__(self, target):
        self.target = target
        self.moved = {}      # source id -> target SequenceResult

    def migrate_seq(self, record):
        try:
            rid, result = self.target.import_remote(record)
        except Exception:
            return None
        self.moved[record["id"]] = result
        return {"url": "inproc", "wid": 1, "id": rid, "cohort": "c0"}


def _drive(scheduler, results, max_steps=500):
    for _ in range(max_steps):
        scheduler.step()
        if all(r.done.is_set() for r in results):
            return
    raise AssertionError(f"not done after {max_steps} steps: "
                         f"{scheduler.stats()}")


def test_scheduler_migrates_instead_of_preempting():
    m = ToyLM()
    # Source pool sized so decode growth must evict someone (the
    # no-migration twin of this setup is
    # test_scheduler_preemption_resumes_exactly).
    src = Scheduler(m, max_batch_tokens=32, queue_limit=8,
                    num_pages=6, page_size=2, watermark=1)
    dst = Scheduler(m, max_batch_tokens=32, queue_limit=8,
                    num_pages=64, page_size=2)
    src.migrator = _LocalMigrator(dst)
    reqs = [([i + 1, 2], 5) for i in range(4)]
    results = [src.submit(Request(f"q{i}", p, n))
               for i, (p, n) in enumerate(reqs)]
    for _ in range(500):
        src.step()
        dst.step()
        if all(r.done.is_set() for r in results):
            break
    if src.migrator.moved:
        _drive(dst, list(src.migrator.moved.values()))
    assert src.migrated_out >= 1, "pool was sized to force migration"
    assert src.preemptions == 0, \
        "migration must replace recompute-preemption entirely here"
    for (p, n), r in zip(reqs, results):
        ref = m.reference_completion(p, n)
        summary = r.summary
        if summary["state"] == "migrated":
            # The stream finished on the target, token-exact, with
            # zero re-prefill there.
            tgt = src.migrator.moved[summary["id"]]
            assert tgt.tokens(timeout=5) == ref
            assert summary["migrations"] == 1
        else:
            assert r.tokens(timeout=5) == ref
    assert dst.preemptions == 0
    assert dst.migrated_in == src.migrated_out


def test_migrate_all_out_moves_hot_and_cold_sequences():
    m = ToyLM()
    src = Scheduler(m, max_batch_tokens=32, queue_limit=8,
                    num_pages=16, page_size=2)
    dst = Scheduler(m, max_batch_tokens=32, queue_limit=8,
                    num_pages=64, page_size=2)
    reqs = [([9, i + 1], 8) for i in range(3)]
    results = [src.submit(Request(f"d{i}", p, n))
               for i, (p, n) in enumerate(reqs)]
    for _ in range(3):
        src.step()               # everyone admitted and decoding
    # Hand-preempt one so a COLD (pageless) record is in the mix.
    with src._lock:
        src._preempt_lru(exclude_id=None)
    src.migrator = _LocalMigrator(dst)
    moved = src.migrate_all_out()
    assert moved == 3, "drain must move running AND preempted"
    assert src.idle()
    for (p, n), r in zip(reqs, results):
        assert r.summary["state"] == "migrated"
        tgt = src.migrator.moved[r.summary["id"]]
        _drive(dst, [tgt])
        assert tgt.tokens(timeout=5) == m.reference_completion(p, n)
    # The cold record re-entered through recompute admission: exactly
    # one target prefill was a resume (preempts carried over).
    assert dst.migrated_in == 3


def test_migration_failure_falls_back_to_recompute():
    m = ToyLM()

    class _RefusingMigrator:
        def migrate_seq(self, record):
            return None          # every peer said no

    src = Scheduler(m, max_batch_tokens=32, queue_limit=8,
                    num_pages=6, page_size=2, watermark=1)
    src.migrator = _RefusingMigrator()
    reqs = [([i + 1, 2], 5) for i in range(4)]
    results = [src.submit(Request(f"f{i}", p, n))
               for i, (p, n) in enumerate(reqs)]
    _drive(src, results)
    assert src.preemptions > 0, "fallback must engage recompute"
    assert src.migrate_failed > 0
    for (p, n), r in zip(reqs, results):
        assert r.tokens(timeout=5) == m.reference_completion(p, n), \
            "graceful degradation: identical final tokens"


# ==========================================================================
# Worker HTTP surface: route, fences, refusals
# ==========================================================================

def _post(port, path, payload, token=""):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(), method="POST")
    if token:
        req.add_header(AUTH_HEADER, token)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        body = e.read()
        return e.code, (json.loads(body) if body else {})


def _export_from(model, prompt, n_steps, version="0", **pool_kw):
    """A hot wire record: run a real scheduler a few steps and export
    its (only) running sequence."""
    s = Scheduler(model, max_batch_tokens=64, queue_limit=4, **pool_kw)
    s.elastic_version = version
    s.submit(Request("src", prompt, 8))
    for _ in range(n_steps):
        s.step()
    seq = next(iter(s._running.values()))
    return s._export_record(seq)


def test_http_migrate_in_token_gate_and_commit():
    token = new_job_token()
    m = ToyLM()
    w = ServingWorker(m, cohort="c0", wid=1, num_pages=64,
                      page_size=4).start()
    try:
        port = w.serve_http(addr="127.0.0.1", token=token)
        rec = _export_from(m, [4, 2], 3, num_pages=64, page_size=4)
        body = {"mid": "m-gate", "chunk": 0, "total": 1,
                "pages": rec["pages"],
                "meta": {k: v for k, v in rec.items() if k != "pages"},
                "commit": True}
        status, _ = _post(port, migration.MIGRATE_PATH, body)
        assert status == 403, "migrate_in must be token-gated"
        status, out = _post(port, migration.MIGRATE_PATH, body,
                            token=token)
        assert status == 200 and out["state"] == "imported"
        # The import resumed decode, no prefill: the stream finishes
        # with the oracle tokens and zero preemptions/recompute.
        st2, final = _post(port, "/v1/generate",
                           {"attach": out["id"]}, token=token)
        assert st2 == 200
        assert final["tokens"] == m.reference_completion([4, 2], 8)
        assert w.scheduler.preemptions == 0
        assert w.scheduler.migrated_in == 1
    finally:
        w.stop()


def test_http_migrate_in_version_fence_and_digest_refusal():
    token = new_job_token()
    m = ToyLM()
    w = ServingWorker(m, cohort="c0", wid=1, num_pages=64,
                      page_size=4)   # loop not needed for refusals
    try:
        port = w.serve_http(addr="127.0.0.1", token=token)
        fenced = _export_from(m, [4, 2], 3, version="9",
                              num_pages=64, page_size=4)
        body = {"mid": "m-fence", "chunk": 0, "total": 1,
                "pages": fenced["pages"],
                "meta": {k: v for k, v in fenced.items()
                         if k != "pages"},
                "commit": True}
        status, out = _post(port, migration.MIGRATE_PATH, body,
                            token=token)
        assert (status, out["error"]) == (409, "version_fenced")
        assert out["record_version"] == "9"

        rec = _export_from(m, [4, 2], 3, num_pages=64, page_size=4)
        migration._corrupt_payload(rec["pages"])
        free_before = w.scheduler.pool.free_pages
        body = {"mid": "m-bad", "chunk": 0, "total": 1,
                "pages": rec["pages"],
                "meta": {k: v for k, v in rec.items() if k != "pages"},
                "commit": True}
        status, out = _post(port, migration.MIGRATE_PATH, body,
                            token=token)
        assert (status, out["error"]) == (422, "digest_mismatch")
        assert w.scheduler.pool.free_pages == free_before
        assert w.scheduler.migrated_in == 0

        # A draining target refuses structurally (the source tries the
        # next peer).
        w.scheduler.drain()
        status, out = _post(port, migration.MIGRATE_PATH, body,
                            token=token)
        assert (status, out["error"]) == (409, "draining")
    finally:
        w.stop()


def test_migrate_out_chunked_transfer_and_retry(monkeypatch):
    """A multi-chunk transfer against a real worker, with the first
    chunk POST failing once (chaos transport error) — the per-chunk
    retry absorbs it and the commit still lands."""
    token = new_job_token()
    m = ToyLM()
    target = ServingWorker(m, cohort="c0", wid=1, num_pages=64,
                           page_size=2).start()
    monkeypatch.setenv("HVDTPU_CHAOS", "migrate_out:fail:n=1")
    chaos.reset()
    try:
        port = target.serve_http(addr="127.0.0.1", token=token)
        rec = _export_from(m, [4, 2, 7], 4, num_pages=64, page_size=2)
        assert len(rec["pages"]) >= 2
        body = migration.migrate_out(
            f"http://127.0.0.1:{port}", rec, token=token,
            retries=3, deadline=5.0,
            max_bytes=len(rec["pages"][0]["payload"]) + 256)
        assert body["state"] == "imported"
        assert target.scheduler.migrated_in == 1
        res = None
        with target._attached_lock:
            res = target._attached[body["id"]]
        assert res.tokens(timeout=10) \
            == m.reference_completion([4, 2, 7], 8)
        assert target.scheduler.preemptions == 0
    finally:
        monkeypatch.delenv("HVDTPU_CHAOS")
        chaos.reset()
        target.stop()


def test_migrate_in_corrupt_chaos_falls_back_to_recompute(monkeypatch):
    """Chaos matrix row (b), fast form: the payload is corrupted in
    flight (migrate_out:corrupt), the target digest-rejects it, and
    the source falls back to plain recompute-preemption — identical
    final tokens, loud counters."""
    token = new_job_token()
    m = ToyLM()
    target = ServingWorker(m, cohort="c0", wid=1, num_pages=64,
                           page_size=2).start()
    monkeypatch.setenv("HVDTPU_CHAOS", "migrate_out:corrupt")
    chaos.reset()
    try:
        port = target.serve_http(addr="127.0.0.1", token=token)
        src = Scheduler(m, max_batch_tokens=32, queue_limit=8,
                        num_pages=6, page_size=2, watermark=1)
        src.migrator = migration.Migrator(
            "c0", 0, token=token,
            peers=[(1, f"http://127.0.0.1:{port}")])
        reqs = [([i + 1, 2], 5) for i in range(4)]
        results = [src.submit(Request(f"c{i}", p, n))
                   for i, (p, n) in enumerate(reqs)]
        _drive(src, results)
        assert src.migrated_out == 0, "corrupt transfers must not land"
        assert src.migrate_failed > 0 and src.preemptions > 0
        assert target.scheduler.migrated_in == 0
        for (p, n), r in zip(reqs, results):
            assert r.tokens(timeout=5) == m.reference_completion(p, n)
    finally:
        monkeypatch.delenv("HVDTPU_CHAOS")
        chaos.reset()
        target.stop()


# ==========================================================================
# End to end: two HTTP workers + router, zero-recompute preemption
# ==========================================================================

class _SlowLM(ToyLM):
    """Per-decode-step delay: streams provably overlap, so pool
    pressure (and drains landing mid-decode) are deterministic."""

    def __init__(self, delay_s=0.003, **kw):
        super().__init__(**kw)
        self._delay_s = delay_s

    def decode(self, contexts):
        time.sleep(self._delay_s)
        return super().decode(contexts)


def test_e2e_migration_zero_recompute_preemption():
    """The tentpole acceptance, in-proc: worker 0's pool is tiny, so
    under concurrent streams it must shed a sequence; with migration
    wired the victim's KV moves to worker 1 and every stream completes
    token-exact with ZERO recompute anywhere — preemption cost became
    a page transfer. The router follows the handoff transparently."""
    token = new_job_token()
    kv = KVStoreServer(job_token=token, addr="127.0.0.1")
    kv_port = kv.start()
    m = ToyLM()
    # 8 pages @ watermark 2: one 17-token stream needs 5 of the 6
    # usable pages, so two overlapping streams MUST shed one.
    w0 = ServingWorker(_SlowLM(), cohort="c0", wid=0, num_pages=8,
                       page_size=4, watermark=2,
                       max_batch_tokens=64).start()
    w1 = ServingWorker(_SlowLM(), cohort="c0", wid=1, num_pages=128,
                       page_size=4, max_batch_tokens=64).start()
    try:
        ports = [w.serve_http(addr="127.0.0.1", token=token)
                 for w in (w0, w1)]
        for w, port in zip((w0, w1), ports):
            w.register("127.0.0.1", kv_port, token,
                       advertise=f"127.0.0.1:{port}")
        router = Router(kv=("127.0.0.1", kv_port, token))
        assert router.refresh_from_kv(["c0"]) == {"c0": 2}

        specs = [([i + 1, 3, 5], 14) for i in range(6)]
        out = [None] * 6

        def gen(i, p, n):
            out[i] = router.generate(
                {"id": f"e2e-{i}", "prompt": p, "max_new_tokens": n})

        threads = [threading.Thread(target=gen, args=(i, p, n))
                   for i, (p, n) in enumerate(specs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        for i, (p, n) in enumerate(specs):
            status, body = out[i]
            assert status == 200, (i, out[i])
            assert body["tokens"] == m.reference_completion(p, n), i
        assert w0.scheduler.migrated_out >= 1, \
            "the tiny pool never forced a migration"
        assert w1.scheduler.migrated_in == w0.scheduler.migrated_out
        assert w0.scheduler.preemptions == 0
        assert w1.scheduler.preemptions == 0
        assert router.handoffs >= 1
        assert router.rerouted == 0, \
            "migration handoff is not a reroute (no replay happened)"
    finally:
        w0.stop()
        w1.stop()
        kv.stop()


def test_e2e_drain_via_migration_and_direct_client_transparency():
    """Drain moves live sequences to the peer; a DIRECT client (no
    router) keeps its original connection and the source worker
    proxies the continuation — same tokens, no client-visible
    migration."""
    token = new_job_token()
    kv = KVStoreServer(job_token=token, addr="127.0.0.1")
    kv_port = kv.start()
    m = ToyLM()
    w0 = ServingWorker(ToyLM(), cohort="c0", wid=0,
                       num_pages=64, page_size=4)
    # The drain has to land on a decoding sequence. The hand-off runs
    # on its own thread and needs the scheduler's lock, which the loop
    # thread holds through every decode and takes again at once, so a
    # delay in the model only made a window to race for (and under
    # load to lose). Park w0's loop between steps instead, outside the
    # lock, from the stream's first token until the hand-off has moved
    # it: what is waited on is events, and the timeouts only bound a
    # failure.
    stepped, resume = threading.Event(), threading.Event()
    real_step = w0.scheduler.step

    def held_step():
        composition = real_step()
        if composition:
            stepped.set()
            resume.wait(timeout=60)
        return composition

    w0.scheduler.step = held_step
    w0.start()
    w1 = ServingWorker(m, cohort="c0", wid=1, num_pages=128,
                       page_size=4).start()
    try:
        ports = [w.serve_http(addr="127.0.0.1", token=token)
                 for w in (w0, w1)]
        for w, port in zip((w0, w1), ports):
            w.register("127.0.0.1", kv_port, token,
                       advertise=f"127.0.0.1:{port}")
        out = {}

        def gen():
            out["r"] = _post(ports[0], "/v1/generate",
                             {"id": "direct", "prompt": [2, 6],
                              "max_new_tokens": 20}, token=token)

        t = threading.Thread(target=gen)
        t.start()
        assert stepped.wait(timeout=60), "the stream never reached decode"
        status, body = _post(ports[0], "/v1/serving/drain", {},
                             token=token)
        assert status == 200 and body["draining"]
        deadline = time.monotonic() + 60
        while (w0.scheduler.migrated_out < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        resume.set()
        t.join(timeout=60)
        status, body = out["r"]
        assert status == 200, out["r"]
        assert body["tokens"] == m.reference_completion([2, 6], 20)
        assert body["id"] == "direct"
        # The continuation genuinely ran on the peer.
        assert w0.scheduler.migrated_out >= 1
        assert w1.scheduler.migrated_in >= 1
        assert body["worker"] == "c0.1"
    finally:
        resume.set()
        w0.stop()
        w1.stop()
        kv.stop()


def test_migrator_no_peer_is_loud_and_metered():
    smetrics.migrations_total("no_peer")  # family resolves (NULL ok)
    mig = migration.Migrator("c0", 0, peers=[])
    assert mig.migrate_seq({"id": "x", "pages": []}) is None
