"""hvd-lint: jaxpr analyzer, AST linter, CLI, auto-naming, and the
runtime submission-order guard / stall warning.

Every lint rule has at least one positive and one negative case; the
clean-sweep tests pin `hvd-lint` to zero findings over examples/ and
horovod_tpu/models/ so the shipped code stays lint-clean.
"""

import json
import os
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from conftest import clean_spawn_env
from horovod_tpu import analysis
from horovod_tpu.analysis import (ast_lint, baseline as baseline_mod,
                                  explain as explain_mod,
                                  sarif as sarif_mod, schedule,
                                  simulate)
from horovod_tpu.analysis.diagnostics import Diagnostic
from horovod_tpu.analysis.order_guard import SubmissionOrderGuard
from horovod_tpu.exceptions import (CollectiveLintError,
                                    SubmissionOrderError)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
AXES = {"hvd": 8}


def rules_of(diags):
    return sorted(d.rule for d in diags)


# ==========================================================================
# Layer 1: jaxpr analyzer
# ==========================================================================
class TestJaxprRules:
    def test_unbound_axis_at_trace_time(self):
        diags = analysis.check_fn(lambda x: lax.psum(x, "tp"),
                                  jnp.ones(4), axis_sizes=AXES)
        assert rules_of(diags) == ["HVD101"]

    def test_unbound_axis_structural(self):
        closed = jax.make_jaxpr(lambda x: lax.psum(x, "tp"),
                                axis_env=[("hvd", 8), ("tp", 2)])(1.0)
        assert rules_of(analysis.check_jaxpr(
            closed, bound_axes={"hvd"})) == ["HVD101"]
        # negative: the axis IS declared bound
        assert analysis.check_jaxpr(closed,
                                    bound_axes={"hvd", "tp"}) == []

    def test_shard_map_binds_its_axis(self):
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()), ("hvd",))
        fn = jax.shard_map(lambda x: lax.psum(x, "hvd"), mesh=mesh,
                           in_specs=P("hvd"), out_specs=P())
        assert analysis.check_fn(fn, jnp.ones(8)) == []

    def test_declared_axis_is_clean(self):
        assert analysis.check_fn(lambda x: lax.pmean(x, "hvd"),
                                 jnp.ones(4), axis_sizes=AXES) == []

    def test_rank_dependent_cond(self):
        def fn(x):
            pred = lax.axis_index("hvd") == 0
            return lax.cond(pred, lambda y: lax.psum(y, "hvd"),
                            lambda y: y, x)
        diags = analysis.check_fn(fn, jnp.float32(1.0), axis_sizes=AXES)
        assert rules_of(diags) == ["HVD102"]
        assert diags[0].line > 0  # carries a real source location

    def test_data_dependent_cond_is_clean(self):
        def fn(x):
            return lax.cond(x.sum() > 0, lambda y: lax.psum(y, "hvd"),
                            lambda y: -y, x)
        assert analysis.check_fn(fn, jnp.ones(4), axis_sizes=AXES) == []

    def test_rank_dependent_while(self):
        def fn(x):
            i = lax.axis_index("hvd")
            return lax.while_loop(
                lambda c: c[0] < i,
                lambda c: (c[0] + 1, lax.psum(c[1], "hvd")),
                (0, x))
        diags = analysis.check_fn(fn, jnp.float32(1.0), axis_sizes=AXES)
        assert "HVD102" in rules_of(diags)

    def test_invariant_while_is_clean(self):
        def fn(x):
            return lax.while_loop(
                lambda c: c[0] < 3,
                lambda c: (c[0] + 1, lax.psum(c[1], "hvd")),
                (0, x))
        assert analysis.check_fn(fn, jnp.float32(1.0),
                                 axis_sizes=AXES) == []

    def test_mismatched_branch_collectives(self):
        def fn(x):
            pred = lax.axis_index("hvd") == 0
            return lax.cond(
                pred,
                lambda y: lax.psum(y, "hvd"),
                lambda y: lax.psum(y.astype(jnp.bfloat16),
                                   "hvd").astype(jnp.float32), x)
        diags = analysis.check_fn(fn, jnp.ones(4), axis_sizes=AXES)
        assert "HVD103" in rules_of(diags)

    def test_matching_branch_collectives_no_103(self):
        def fn(x):
            pred = lax.axis_index("hvd") == 0
            return lax.cond(pred,
                            lambda y: lax.psum(y * 2, "hvd"),
                            lambda y: lax.psum(y + 1, "hvd"), x)
        diags = analysis.check_fn(fn, jnp.ones(4), axis_sizes=AXES)
        assert "HVD103" not in rules_of(diags)  # 102 still fires
        assert "HVD102" in rules_of(diags)

    def test_collective_through_jit_is_seen(self):
        fn = jax.jit(lambda x: lax.psum(x, "tp"))
        diags = analysis.check_fn(fn, jnp.ones(4), axis_sizes=AXES)
        assert rules_of(diags) == ["HVD101"]

    def test_clean_function(self):
        assert analysis.check_fn(jax.jit(lambda x: x * 2),
                                 jnp.ones(3)) == []

    def test_enforce_raises_on_errors(self):
        diags = analysis.check_fn(lambda x: lax.psum(x, "tp"),
                                  jnp.ones(4), axis_sizes=AXES)
        with pytest.raises(CollectiveLintError) as err:
            analysis.enforce(diags, True, what="test")
        assert "HVD101" in str(err.value)
        # warn mode never raises
        analysis.enforce(diags, "warn", what="test")
        analysis.enforce(diags, False, what="test")


# ==========================================================================
# Layer 2: AST linter (fixture corpus)
# ==========================================================================
class TestAstRules:
    def lint(self, name):
        return ast_lint.lint_file(os.path.join(FIXTURES, name))

    def test_rank_guard_fixture(self):
        diags = self.lint("bad_rank_guard.py")
        assert rules_of(diags) == ["HVD201", "HVD201"]

    def test_missing_broadcast_fixture(self):
        assert rules_of(self.lint("bad_missing_broadcast.py")) == \
            ["HVD202"]

    def test_auto_name_fixture(self):
        assert rules_of(self.lint("bad_auto_name.py")) == \
            ["HVD203", "HVD203"]

    def test_clean_fixture(self):
        assert self.lint("good_clean.py") == []

    def test_suppression_comments(self):
        assert self.lint("good_suppressed.py") == []

    def test_per_tensor_allreduce_fixture(self):
        assert rules_of(self.lint("bad_per_tensor_allreduce.py")) == \
            ["HVD206", "HVD206", "HVD206"]

    def test_zero_combo_fixture(self):
        assert rules_of(self.lint("bad_zero_combo.py")) == \
            ["HVD208", "HVD208", "HVD208"]

    def test_zero_plain_is_clean(self):
        src = ("import horovod_tpu.jax as hvd_jax\n"
               "opt = hvd_jax.DistributedOptimizer(inner, zero=True)\n")
        assert ast_lint.lint_source(src) == []

    def test_adasum_without_zero_is_clean(self):
        src = ("import horovod_tpu.jax as hvd_jax\n"
               "opt = hvd_jax.DistributedAdasumOptimizer(inner)\n")
        assert ast_lint.lint_source(src) == []

    def test_zero_env_then_adasum_flagged(self):
        src = ("import os\n"
               "import horovod_tpu.jax as hvd_jax\n"
               "os.environ['HVDTPU_ZERO'] = '1'\n"
               "opt = hvd_jax.DistributedAdasumOptimizer(inner)\n")
        assert rules_of(ast_lint.lint_source(src)) == ["HVD208"]

    def test_explicit_zero_false_overrides_env_knob(self):
        # zero=False opts this optimizer out at runtime even under
        # HVDTPU_ZERO=1 (__init__ honors the explicit arg) — no finding.
        src = ("import os\n"
               "import horovod_tpu.jax as hvd_jax\n"
               "os.environ['HVDTPU_ZERO'] = '1'\n"
               "opt = hvd_jax.DistributedOptimizer(inner, zero=False,\n"
               "                                   op=hvd.Adasum)\n")
        assert ast_lint.lint_source(src) == []

    def test_zero_combo_suppressible(self):
        src = ("import horovod_tpu.jax as hvd_jax\n"
               "opt = hvd_jax.DistributedOptimizer(inner, zero=True, "
               "op=hvd.Adasum)  # hvd-lint: disable=HVD208\n")
        assert ast_lint.lint_source(src) == []

    def test_index_codec_fixture(self):
        diags = self.lint("bad_index_codec.py")
        assert rules_of(diags) == ["HVD209", "HVD209", "HVD209"]
        assert [d.line for d in diags] == [11, 15, 18]
        msgs = " ".join(d.message for d in diags)
        assert "index tensor" in msgs

    def test_index_codec_values_half_is_clean(self):
        # The values half of a sparse gradient is exactly what a wire
        # codec is for — never an HVD209 finding.
        src = ("import horovod_tpu as hvd\n"
               "g = grad()\n"
               "hvd.allreduce(g.values, "
               "compression=hvd.Compression.int8)\n")
        assert ast_lint.lint_source(src) == []

    def test_index_codec_int_dtype_stays_hvd205(self):
        # An index tensor with a VISIBLE int dtype is HVD205's finding;
        # the rules dedup — never both on one call.
        src = ("import jax.numpy as jnp\n"
               "import horovod_tpu as hvd\n"
               "idx = jnp.zeros((4,), dtype=jnp.int32)\n"
               "hvd.allreduce(idx.argsort(), "
               "compression=hvd.Compression.int8)\n")
        assert rules_of(ast_lint.lint_source(src)) == ["HVD205"]

    def test_index_codec_suppressible(self):
        src = ("import horovod_tpu as hvd\n"
               "hvd.allreduce(g.indices, "
               "compression=hvd.Compression.int8)"
               "  # hvd-lint: disable=HVD209\n")
        assert ast_lint.lint_source(src) == []

    def test_unbounded_queue_fixture(self):
        diags = self.lint("bad_unbounded_queue.py")
        assert rules_of(diags) == ["HVD210", "HVD210", "HVD210"]
        assert [d.line for d in diags] == [13, 25, 31]
        msgs = " ".join(d.message for d in diags)
        assert "queue.Queue" in msgs and "append" in msgs

    def test_bounded_buffers_in_serving_context_are_clean(self):
        src = ("import collections\n"
               "import queue\n"
               "class RequestScheduler:\n"
               "    def __init__(self, limit):\n"
               "        self.pending = queue.Queue(maxsize=limit)\n"
               "        self.admit = queue.Queue(limit)\n"
               "        self.recent = collections.deque(maxlen=64)\n")
        assert ast_lint.lint_source(src) == []

    def test_unbounded_queue_outside_serving_context_is_clean(self):
        # The same spellings in plain data-plumbing code are idiomatic;
        # only serving scheduler/router/handler context is held to the
        # backpressure contract.
        src = ("import queue\n"
               "class TilePipeline:\n"
               "    def __init__(self):\n"
               "        self.stages = queue.Queue()\n"
               "        self.pending = []\n"
               "    def push(self, t):\n"
               "        self.pending.append(t)\n")
        assert ast_lint.lint_source(src) == []

    def test_serving_file_path_is_context(self):
        # Under a serving/ path every unbounded queue is in scope, even
        # without a telling class name.
        src = ("import queue\n"
               "class Pump:\n"
               "    def __init__(self):\n"
               "        self.inbox = queue.Queue()\n")
        diags = ast_lint.lint_source(
            src, filename="horovod_tpu/serving/pump.py")
        assert rules_of(diags) == ["HVD210"]

    def test_simple_queue_always_flagged_in_context(self):
        src = ("from queue import SimpleQueue\n"
               "def handle_submit(req):\n"
               "    box = SimpleQueue()\n"
               "    box.put(req)\n")
        assert rules_of(ast_lint.lint_source(src)) == ["HVD210"]

    def test_unbounded_queue_suppressible(self):
        src = ("import queue\n"
               "class RequestRouter:\n"
               "    def __init__(self):\n"
               "        self.audit_queue = queue.Queue()"
               "  # hvd-lint: disable=HVD210\n")
        assert ast_lint.lint_source(src) == []

    def test_hvd210_in_catalog(self):
        from horovod_tpu.analysis.diagnostics import RULES, WARNING
        severity, title = RULES["HVD210"]
        assert severity == WARNING
        assert "backpressure" in title

    # -- HVD211: hand-rolled resharding -----------------------------------
    def test_hand_resharding_fixture(self):
        assert rules_of(self.lint("bad_hand_resharding.py")) == \
            ["HVD211", "HVD211", "HVD211"]

    def test_hand_resharding_direct_chain(self):
        src = ("import jax\n"
               "import numpy as np\n"
               "def move(tree, sharding):\n"
               "    full = jax.device_get(tree)\n"
               "    return jax.device_put(full.reshape(4, -1),\n"
               "                          sharding)\n")
        assert rules_of(ast_lint.lint_source(src)) == ["HVD211"]

    def test_device_get_alone_is_clean(self):
        # Checkpoint writers / telemetry reads never device_put back.
        src = ("import jax\n"
               "import numpy as np\n"
               "def snapshot(tree, path):\n"
               "    np.save(path, jax.device_get(tree))\n")
        assert ast_lint.lint_source(src) == []

    def test_device_put_of_fresh_data_is_clean(self):
        src = ("import jax\n"
               "import numpy as np\n"
               "def seed(shape, sharding):\n"
               "    return jax.device_put(np.zeros(shape), sharding)\n")
        assert ast_lint.lint_source(src) == []

    def test_resharding_package_is_exempt(self):
        src = ("import jax\n"
               "def window(buf, sharding):\n"
               "    host = jax.device_get(buf)\n"
               "    return jax.device_put(host, sharding)\n")
        diags = ast_lint.lint_source(
            src, filename="horovod_tpu/resharding/execute.py")
        assert diags == []

    def test_hand_resharding_suppressible(self):
        src = ("import jax\n"
               "def move(x, sharding):\n"
               "    v = jax.device_get(x)\n"
               "    return jax.device_put(v, sharding)"
               "  # hvd-lint: disable=HVD211\n")
        assert ast_lint.lint_source(src) == []

    def test_hvd211_in_catalog(self):
        from horovod_tpu.analysis.diagnostics import RULES, WARNING
        severity, title = RULES["HVD211"]
        assert severity == WARNING
        assert "resharding" in title

    # -- HVD212: hand-rolled worker lifecycle ------------------------------
    def test_worker_lifecycle_fixture(self):
        diags = self.lint("bad_worker_lifecycle.py")
        assert rules_of(diags) == ["HVD212", "HVD212", "HVD212"]
        assert [d.line for d in diags] == [14, 19, 23]

    def test_direct_slotprocess_spawn_flagged(self):
        src = ("from horovod_tpu.runner.spawn import SlotProcess\n"
               "def launch(env):\n"
               "    return SlotProcess(['python', 'w.py'], env=env)\n")
        assert rules_of(ast_lint.lint_source(src)) == ["HVD212"]

    def test_terminate_on_driver_workers_flagged(self):
        src = ("import horovod_tpu\n"
               "def stop(driver, wid):\n"
               "    driver.workers[wid].proc.terminate()\n")
        assert rules_of(ast_lint.lint_source(src)) == ["HVD212"]

    def test_plain_subprocess_is_clean(self):
        src = ("import subprocess\n"
               "def run(cmd):\n"
               "    p = subprocess.Popen(cmd)\n"
               "    p.terminate()\n")
        assert ast_lint.lint_source(src) == []

    def test_lifecycle_owners_are_exempt(self):
        # The driver and the fleet actuator ARE the legal mutation
        # surface — the rule must stay silent inside them.
        src = ("from horovod_tpu.runner.spawn import SlotProcess\n"
               "def respawn(env):\n"
               "    return SlotProcess(['python', 'w.py'], env=env)\n")
        for owner in ("horovod_tpu/runner/elastic_driver.py",
                      "horovod_tpu/fleet/actuators.py"):
            assert ast_lint.lint_source(src, filename=owner) == []

    def test_worker_lifecycle_suppressible(self):
        src = ("from horovod_tpu.runner.spawn import SlotProcess\n"
               "p = SlotProcess(['python', 'w.py'], env={})"
               "  # hvd-lint: disable=HVD212\n")
        assert ast_lint.lint_source(src) == []

    def test_hvd212_in_catalog(self):
        from horovod_tpu.analysis.diagnostics import RULES, WARNING
        severity, title = RULES["HVD212"]
        assert severity == WARNING
        assert "spawn/terminate" in title

    # -- HVD213: silently swallowed transport errors -----------------------
    def test_silent_degradation_fixture(self):
        diags = self.lint("bad_silent_degradation.py")
        assert rules_of(diags) == ["HVD213", "HVD213", "HVD213"]
        assert [d.line for d in diags] == [22, 44, 51]

    def test_swallow_in_serving_file_flagged(self):
        src = ("def fetch(client):\n"
               "    try:\n"
               "        return client.stats()\n"
               "    except OSError:\n"
               "        return None\n")
        diags = ast_lint.lint_source(
            src, filename="horovod_tpu/serving/router.py")
        assert rules_of(diags) == ["HVD213"]

    def test_logged_handler_is_clean(self):
        src = ("class StreamRouter:\n"
               "    def fetch(self, client):\n"
               "        try:\n"
               "            return client.stats()\n"
               "        except OSError as e:\n"
               "            self._log.warning('scrape failed: %s', e)\n"
               "            return None\n")
        assert ast_lint.lint_source(src) == []

    def test_metric_bump_is_clean(self):
        src = ("class FleetArbiter:\n"
               "    def probe(self, peer):\n"
               "        try:\n"
               "            return peer.ping()\n"
               "        except ConnectionError:\n"
               "            self._m_failed.inc()\n"
               "            return None\n")
        assert ast_lint.lint_source(src) == []

    def test_non_transport_exception_is_clean(self):
        src = ("def handle_parse(raw):\n"
               "    try:\n"
               "        return int(raw)\n"
               "    except ValueError:\n"
               "        return 0\n")
        assert ast_lint.lint_source(src) == []

    def test_http_error_translation_is_clean(self):
        # HTTPError means the peer ANSWERED — translating its status
        # into a return value is protocol handling, not a swallow.
        src = ("import urllib.error\n"
               "class WorkerRouter:\n"
               "    def req(self, client):\n"
               "        try:\n"
               "            return client.call()\n"
               "        except urllib.error.HTTPError as e:\n"
               "            return e.code, {}\n")
        assert ast_lint.lint_source(src) == []

    def test_outside_serving_context_is_clean(self):
        # Same swallow, but no serving/fleet context anywhere: not a
        # finding (the rule scopes to the degradation contract).
        src = ("def read_config(path):\n"
               "    try:\n"
               "        return open(path).read()\n"
               "    except OSError:\n"
               "        return ''\n")
        assert ast_lint.lint_source(src) == []

    def test_silent_degradation_suppressible(self):
        src = ("class PeerScheduler:\n"
               "    def probe(self, peer):\n"
               "        try:\n"
               "            return peer.ping()\n"
               "        except OSError:"
               "  # hvd-lint: disable=HVD213\n"
               "            return None\n")
        assert ast_lint.lint_source(src) == []

    def test_serving_and_fleet_sweep_is_hvd213_clean(self):
        # The shipped serving/fleet planes hold themselves to the
        # loud-fallback contract the rule enforces.
        import glob
        hits = []
        for pkg in ("serving", "fleet"):
            pat = os.path.join(REPO, "horovod_tpu", pkg, "*.py")
            for path in sorted(glob.glob(pat)):
                hits += [d for d in ast_lint.lint_file(path)
                         if d.rule == "HVD213"]
        assert hits == [], [(d.file, d.line) for d in hits]

    def test_hvd213_in_catalog(self):
        from horovod_tpu.analysis.diagnostics import RULES, WARNING
        severity, title = RULES["HVD213"]
        assert severity == WARNING
        assert "transport" in title

    def test_deferred_reraise_retry_ladder_is_clean(self):
        # Regression (false positive): a retry ladder that stores the
        # exception and re-raises it after the loop DOES observe the
        # error — the raise is just deferred past the last attempt.
        src = ("class KvClient:\n"
               "    def call(self, req):\n"
               "        last = None\n"
               "        for _ in range(3):\n"
               "            try:\n"
               "                return self._send(req)\n"
               "            except OSError as e:\n"
               "                last = e\n"
               "        raise last\n")
        assert ast_lint.lint_source(
            src, filename="horovod_tpu/serving/client.py") == []

    def test_deferred_reraise_via_alias_chain_and_cause(self):
        # The stored name may be re-aliased, and the eventual raise may
        # wrap it as __cause__ — still observed.
        src = ("class KvClient:\n"
               "    def call(self, req):\n"
               "        last = None\n"
               "        for _ in range(3):\n"
               "            try:\n"
               "                return self._send(req)\n"
               "            except ConnectionError as exc:\n"
               "                failure = exc\n"
               "                last = failure\n"
               "        raise TimeoutError('kv retries exhausted')"
               " from last\n")
        assert ast_lint.lint_source(
            src, filename="horovod_tpu/serving/client.py") == []

    def test_stored_but_never_reraised_is_still_flagged(self):
        # Storing the exception without ever raising it is the silent
        # swallow the rule exists for.
        src = ("class KvClient:\n"
               "    def call(self, req):\n"
               "        last = None\n"
               "        for _ in range(3):\n"
               "            try:\n"
               "                return self._send(req)\n"
               "            except OSError as e:\n"
               "                last = e\n"
               "        return None\n")
        diags = ast_lint.lint_source(
            src, filename="horovod_tpu/serving/client.py")
        assert rules_of(diags) == ["HVD213"]

    def test_loop_invariant_allreduce_is_clean(self):
        # One metric per epoch is not the per-tensor-reduction shape.
        src = ("import horovod_tpu as hvd\n"
               "hvd.init()\n"
               "for epoch in range(5):\n"
               "    loss = hvd.allreduce(metric, name='loss')\n")
        assert ast_lint.lint_source(src) == []

    def test_per_batch_metric_through_call_is_clean(self):
        # The canonical per-batch metric reduction: the value reaches
        # the loop variable only through a function call, so it is new
        # per-iteration data — not bucketable, not a finding.
        src = ("import horovod_tpu as hvd\n"
               "hvd.init()\n"
               "for batch in loader:\n"
               "    loss = hvd.allreduce(train_step(model, batch),\n"
               "                         name='loss')\n")
        assert ast_lint.lint_source(src) == []

    def test_grouped_allreduce_in_loop_is_clean(self):
        # grouped_* IS the bucketed API; chunked grouped calls are fine.
        src = ("import horovod_tpu as hvd\n"
               "hvd.init()\n"
               "for chunk in chunks:\n"
               "    outs = hvd.grouped_allreduce(chunk)\n")
        assert ast_lint.lint_source(src) == []

    def test_per_tensor_allreduce_suppressible(self):
        src = ("import horovod_tpu as hvd\n"
               "hvd.init()\n"
               "for g in grads:\n"
               "    hvd.allreduce(g)  # hvd-lint: disable=HVD206\n")
        assert ast_lint.lint_source(src) == []

    def test_rank_guarded_logging_is_clean(self):
        src = ("import horovod_tpu as hvd\n"
               "hvd.init()\n"
               "if hvd.rank() == 0:\n"
               "    print('hello from rank 0')\n")
        assert ast_lint.lint_source(src) == []

    def test_elastic_state_satisfies_broadcast(self):
        src = ("import horovod_tpu.torch as hvd\n"
               "from horovod_tpu import elastic\n"
               "hvd.init()\n"
               "opt = hvd.DistributedOptimizer(opt)\n")
        assert ast_lint.lint_source(src) == []

    def test_keras_callback_satisfies_broadcast(self):
        src = ("import horovod_tpu.keras as hvd\n"
               "hvd.init()\n"
               "opt = hvd.DistributedOptimizer(opt)\n"
               "cbs = [hvd.callbacks.BroadcastGlobalVariablesCallback(0)]\n")
        assert ast_lint.lint_source(src) == []

    def test_lax_collective_under_rank_guard(self):
        src = ("import horovod_tpu as hvd\n"
               "from jax import lax\n"
               "def step(x):\n"
               "    if hvd.rank() == 0:\n"
               "        x = lax.psum(x, 'hvd')\n"
               "    return x\n")
        assert rules_of(ast_lint.lint_source(src)) == ["HVD201"]

    def test_fixed_name_broadcast_helpers_exempt_from_203(self):
        """broadcast_object & co. use fixed internal names (functions.py)
        — never call-order dependent, so no HVD203 for them even under
        rank-dependent branching."""
        src = ("import horovod_tpu as hvd\n"
               "hvd.init()\n"
               "if hvd.rank() == 0:\n"
               "    hvd.broadcast_object(cfg)\n"
               "else:\n"
               "    cfg = hvd.broadcast_object(None)\n")
        assert ast_lint.lint_source(src) == []

    def test_unrelated_broadcast_name_is_not_horovod(self):
        src = ("class Bus:\n"
               "    def emit(self):\n"
               "        broadcast(self)\n")
        assert ast_lint.lint_source(src) == []

    def test_syntax_error_reported(self):
        assert rules_of(ast_lint.lint_source("def broken(:\n")) == \
            ["HVD001"]

    def test_file_level_suppression(self):
        src = ("# hvd-lint: disable-file=HVD201\n"
               "import horovod_tpu as hvd\n"
               "if hvd.rank() == 0:\n"
               "    hvd.barrier()\n")
        assert ast_lint.lint_source(src) == []


# ==========================================================================
# HVD704/705: control-plane protocol-order rules (the model checker's
# static companions — hvd-model proves the ordering matters, these
# catch the shape at the AST)
# ==========================================================================
class TestProtocolOrderRules:
    def lint(self, name):
        return ast_lint.lint_file(os.path.join(FIXTURES, name))

    def test_fixture_positives_and_lines(self):
        diags = self.lint("bad_protocol_misuse.py")
        assert [(d.rule, d.line) for d in diags] == [
            ("HVD704", 18), ("HVD705", 29)]

    def test_actuation_before_ledger_message(self):
        diags = [d for d in self.lint("bad_protocol_misuse.py")
                 if d.rule == "HVD704"]
        assert "set_serve_slots" in diags[0].message
        assert "ledger" in diags[0].message.lower()

    def test_correct_order_and_fenced_put_are_clean(self):
        # The negatives in the same fixture: ledger-first ordering and
        # the term= kwarg each silence their rule (asserted via the
        # exact positive list above), plus the suppression comment.
        diags = self.lint("bad_protocol_misuse.py")
        flagged_lines = {d.line for d in diags}
        assert 23 not in flagged_lines   # advance_correctly
        assert 33 not in flagged_lines   # publish_correctly
        assert 37 not in flagged_lines   # hvd-lint: disable=HVD705

    def test_outside_protocol_context_is_clean(self):
        # Same shapes in a class whose name/path has no arbiter/ledger
        # /journal/lease context: not a finding.
        src = ("class BatchWriter:\n"
               "    def flush(self, rows):\n"
               "        self.sink.put('scope', 'key', rows)\n")
        assert ast_lint.lint_source(src) == []

    def test_shipped_control_plane_is_clean(self):
        import glob
        hits = []
        for pkg in ("fleet", "runner", "serving"):
            pat = os.path.join(REPO, "horovod_tpu", pkg, "*.py")
            for path in sorted(glob.glob(pat)):
                hits += [d for d in ast_lint.lint_file(path)
                         if d.rule in ("HVD704", "HVD705")]
        assert hits == [], [(d.file, d.line) for d in hits]

    def test_rules_in_catalog(self):
        from horovod_tpu.analysis.diagnostics import RULES, WARNING
        for rule in ("HVD704", "HVD705"):
            severity, _ = RULES[rule]
            assert severity == WARNING


# ==========================================================================
# HVD307: metric registry <-> docs/metrics.md cross-check
# ==========================================================================
class TestMetricDocs:
    METRICS_MD = os.path.join(REPO, "docs", "metrics.md")

    def test_shipped_docs_match_registrations(self):
        diags = ast_lint.check_metric_docs(self.METRICS_MD)
        assert diags == [], "\n".join(d.format() for d in diags)

    def test_detects_drift_both_ways_and_kind_mismatch(self, tmp_path):
        sources = [
            os.path.join(REPO, "horovod_tpu", "serving", "metrics.py"),
            os.path.join(REPO, "horovod_tpu", "fleet", "metrics.py")]
        registered = {
            name: rec
            for name, rec in
            ast_lint._registered_metrics(sources).items()
            if name.startswith(("hvd_serving_", "hvd_fleet_"))}
        assert registered, "metric scrape found nothing — broken"
        doc = tmp_path / "metrics.md"
        rows = []
        skipped = None
        for name in sorted(registered):
            kind = registered[name][0]
            if skipped is None:
                skipped = name          # registered, undocumented
                continue
            if name.endswith("_total") and kind == "counter":
                kind = "gauge"          # kind mismatch
            rows.append(f"| `{name}` | {kind} | — | x |")
        rows.append("| `hvd_serving_imaginary_total` | counter | — |"
                    " x |")                # documented, unregistered
        doc.write_text("\n".join(rows) + "\n")
        diags = ast_lint.check_metric_docs(str(doc))
        assert all(d.rule == "HVD307" for d in diags)
        msgs = " ".join(d.message for d in diags)
        assert skipped in msgs
        assert "hvd_serving_imaginary_total" in msgs
        assert "counter" in msgs and "gauge" in msgs

    def test_registration_findings_anchor_at_source(self, tmp_path):
        doc = tmp_path / "metrics.md"
        doc.write_text("")          # everything is undocumented
        diags = ast_lint.check_metric_docs(str(doc))
        assert diags
        anchored = [d for d in diags if d.file.endswith("metrics.py")]
        assert anchored and all(d.line > 0 for d in anchored)

    def test_hvd307_in_catalog(self):
        from horovod_tpu.analysis.diagnostics import ERROR, RULES
        severity, title = RULES["HVD307"]
        assert severity == ERROR
        assert "metric" in title


def test_clean_sweep_examples_and_models():
    """Acceptance: zero findings over examples/, horovod_tpu/models/,
    and the telemetry + chaos subsystems."""
    diags = ast_lint.lint_paths([os.path.join(REPO, "examples"),
                                 os.path.join(REPO, "horovod_tpu",
                                              "models"),
                                 os.path.join(REPO, "horovod_tpu",
                                              "telemetry"),
                                 os.path.join(REPO, "horovod_tpu",
                                              "chaos")])
    assert diags == [], "\n".join(d.format() for d in diags)


# ==========================================================================
# Layer 2.5: interprocedural schedule verifier (hvd-lint verify, HVD4xx)
# ==========================================================================
class TestScheduleRules:
    def verify(self, name):
        return schedule.verify_paths([os.path.join(FIXTURES, name)])

    def test_tainted_schedule_fixture(self):
        diags = self.verify("bad_tainted_schedule.py")
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD401", 20), ("HVD401", 24), ("HVD401", 34)]
        assert all(os.path.basename(d.file)
                   == "bad_tainted_schedule.py" for d in diags)

    def test_divergent_loop_fixture(self):
        diags = self.verify("bad_divergent_loop.py")
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD402", 15), ("HVD402", 23), ("HVD402", 31)]

    def test_cross_set_interleave_fixture(self):
        diags = self.verify("bad_cross_set_interleave.py")
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD404", 19), ("HVD404", 30), ("HVD404", 38)]

    def test_skipped_collective_fixture(self):
        diags = self.verify("bad_skipped_collective.py")
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD403", 15), ("HVD403", 22), ("HVD403", 29)]

    def test_adasum_bucketed_fixture(self):
        diags = self.verify("bad_adasum_bucketed.py")
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD405", 18), ("HVD405", 23), ("HVD405", 31)]

    def test_clean_fixture_silent_on_both_layers(self):
        path = os.path.join(FIXTURES, "good_verify_clean.py")
        assert schedule.verify_paths([path]) == []
        assert ast_lint.lint_file(path) == []

    def test_interprocedural_chain_named_in_message(self):
        src = ("import horovod_tpu as hvd\n"
               "def sync(x):\n"
               "    return hvd.allreduce(x, name='s')\n"
               "def main(x):\n"
               "    if hvd.rank() == 0:\n"
               "        sync(x)\n")
        diags = schedule.verify_source(src, "chain.py")
        assert rules_of(diags) == ["HVD401"]
        assert diags[0].line == 3          # the collective, not the call
        assert "called from main" in diags[0].message

    def test_direct_one_hop_guard_stays_hvd201(self):
        """The exact single-hop shape stays HVD201's finding: verify
        adds no duplicate HVD401 on top of it."""
        src = ("import horovod_tpu as hvd\n"
               "def main(x):\n"
               "    if hvd.rank() == 0:\n"
               "        hvd.allreduce(x, name='m')\n")
        assert schedule.verify_source(src, "direct.py") == []
        assert rules_of(ast_lint.lint_source(src)) == ["HVD201"]

    def test_collective_result_launders_taint(self):
        src = ("import horovod_tpu as hvd\n"
               "def main(x, n):\n"
               "    steps = hvd.allreduce(n, op=hvd.Min, name='n')\n"
               "    if steps > 0:\n"
               "        hvd.allreduce(x, name='m')\n")
        assert schedule.verify_source(src, "launder.py") == []

    def test_tuple_unpack_taints_elementwise(self):
        src = ("import horovod_tpu as hvd\n"
               "def main(x):\n"
               "    rank, size = hvd.rank(), hvd.size()\n"
               "    if size > 1:\n"
               "        hvd.allreduce(x, name='m')\n")
        assert schedule.verify_source(src, "tuple.py") == []

    def test_enumerate_counter_is_replica_invariant(self):
        """A rank-sharded iterable is one HVD402 for the loop — NOT a
        cascade of HVD401 for every step-guarded collective inside
        (enumerate counters run 0,1,2,... on every rank)."""
        src = ("import horovod_tpu as hvd\n"
               "def main(dataset, params):\n"
               "    shard = dataset.shard(hvd.size(), hvd.rank())\n"
               "    for step, b in enumerate(shard):\n"
               "        hvd.allreduce(b, name='grad')\n"
               "        if step == 0:\n"
               "            hvd.broadcast_parameters(params,"
               " root_rank=0)\n")
        assert rules_of(schedule.verify_source(src, "enum.py")) == \
            ["HVD402"]

    def test_sibling_module_import_resolves(self, tmp_path):
        (tmp_path / "helpers.py").write_text(
            "import horovod_tpu as hvd\n"
            "def sync(x):\n"
            "    return hvd.allreduce(x, name='h')\n")
        train = tmp_path / "train.py"
        train.write_text(
            "import horovod_tpu as hvd\n"
            "from helpers import sync\n"
            "def main(x):\n"
            "    if hvd.rank() == 0:\n"
            "        sync(x)\n")
        diags = schedule.verify_paths([str(train)])
        assert rules_of(diags) == ["HVD401"]
        assert os.path.basename(diags[0].file) == "helpers.py"

    def test_extract_schedule(self):
        src = ("import horovod_tpu as hvd\n"
               "def step(x, ps):\n"
               "    if hvd.rank() == 0:\n"
               "        hvd.allreduce(x, name='a', process_set=ps)\n"
               "    hvd.allgather(x, name='b')\n")
        events = schedule.extract_schedule(src, "sched.py")
        assert [(e["kind"], e["name"], e["process_set"])
                for e in events] == \
            [("allreduce", "a", "ps"), ("allgather", "b", "global")]
        assert events[0]["context"] == ["if rank-tainted@3"]
        assert events[1]["context"] == []

    def test_syntax_error_reported(self):
        assert rules_of(schedule.verify_source("def broken(:\n")) == \
            ["HVD001"]


# ==========================================================================
# SARIF 2.1.0 emitter
# ==========================================================================

# Structural subset of the OASIS SARIF 2.1.0 schema: the required
# properties plus the constraints on every field hvd-lint emits. The
# full 330 KB schema is not vendored; this subset rejects exactly the
# malformations a consumer (GitHub code scanning, VS Code) would.
_SARIF_21_SCHEMA = {
    "type": "object",
    "required": ["version", "runs"],
    "properties": {
        "version": {"const": "2.1.0"},
        "$schema": {"type": "string"},
        "runs": {"type": "array", "minItems": 1, "items": {
            "type": "object", "required": ["tool"],
            "properties": {
                "tool": {
                    "type": "object", "required": ["driver"],
                    "properties": {"driver": {
                        "type": "object", "required": ["name"],
                        "properties": {
                            "name": {"type": "string"},
                            "version": {"type": "string"},
                            "informationUri": {"type": "string"},
                            "rules": {"type": "array", "items": {
                                "type": "object", "required": ["id"],
                                "properties": {
                                    "id": {"type": "string"},
                                    "shortDescription": {
                                        "type": "object",
                                        "required": ["text"]},
                                    "defaultConfiguration": {
                                        "type": "object",
                                        "properties": {"level": {
                                            "enum": ["none", "note",
                                                     "warning",
                                                     "error"]}}},
                                }}},
                        }}},
                },
                "results": {"type": "array", "items": {
                    "type": "object", "required": ["message"],
                    "properties": {
                        "ruleId": {"type": "string"},
                        "ruleIndex": {"type": "integer",
                                      "minimum": 0},
                        "level": {"enum": ["none", "note", "warning",
                                           "error"]},
                        "message": {"type": "object",
                                    "required": ["text"]},
                        "locations": {"type": "array", "items": {
                            "type": "object",
                            "properties": {"physicalLocation": {
                                "type": "object",
                                "properties": {
                                    "artifactLocation": {
                                        "type": "object",
                                        "properties": {"uri": {
                                            "type": "string"}}},
                                    "region": {
                                        "type": "object",
                                        "properties": {"startLine": {
                                            "type": "integer",
                                            "minimum": 1}}},
                                }}}}},
                        "partialFingerprints": {"type": "object"},
                        "codeFlows": {"type": "array", "items": {
                            "type": "object",
                            "required": ["threadFlows"],
                            "properties": {
                                "message": {"type": "object",
                                            "required": ["text"]},
                                "threadFlows": {
                                    "type": "array", "minItems": 1,
                                    "items": {
                                        "type": "object",
                                        "required": ["locations"],
                                        "properties": {
                                            "id": {"type": "string"},
                                            "locations": {
                                                "type": "array",
                                                "minItems": 1,
                                                "items": {
                                                    "type": "object",
                                                    "required": [
                                                        "location"],
                                                }},
                                        }}},
                            }}},
                        "suppressions": {"type": "array", "items": {
                            "type": "object", "required": ["kind"],
                            "properties": {"kind": {
                                "enum": ["inSource", "external"]}}}},
                    }}},
            }}},
    },
}


class TestSarifOutput:
    def test_golden_file(self):
        """Pin the exact emitted document (key layout, fingerprints,
        suppression shape) against the checked-in golden."""
        d1 = Diagnostic.make(
            "HVD401", "collective `allreduce` runs only on ranks that "
            "take a rank-dependent path", file="golden/train.py",
            line=12,
            hint="hoist the collective out of the rank-dependent path")
        d2 = Diagnostic.make(
            "HVD304", "raw os.environ read of 'HVDTPU_DEMO' bypasses "
            "utils/envparse.py", file="golden/train.py", line=40)
        doc = sarif_mod.to_sarif([d1], suppressed=[d2])
        doc["runs"][0]["tool"]["driver"]["version"] = "GOLDEN"
        with open(os.path.join(FIXTURES, "golden_lint.sarif")) as f:
            golden = json.load(f)
        assert doc == golden

    def test_corpus_sarif_validates_against_schema(self):
        import jsonschema
        proc = _run_cli("verify", FIXTURES, "--format", "sarif",
                        "--fail-on", "never")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        jsonschema.validate(doc, _SARIF_21_SCHEMA)
        run = doc["runs"][0]
        rules = [r["id"] for r in run["tool"]["driver"]["rules"]]
        assert {"HVD401", "HVD402", "HVD403", "HVD404",
                "HVD405"} <= set(rules)
        for result in run["results"]:
            # ruleIndex must actually point at its rule
            assert rules[result["ruleIndex"]] == result["ruleId"]
            assert "hvdLintKey/v1" in result["partialFingerprints"]

    def test_sim_golden_file(self):
        """Pin the exact SARIF document for a proven HVD501 finding —
        counterexample trace as codeFlows, one threadFlow per symbolic
        rank — against the checked-in golden."""
        src = ("import horovod_tpu as hvd\n"
               "def exchange(x):\n"
               "    if hvd.rank() == 0:\n"
               "        hvd.allreduce(x, name='alpha')\n"
               "    else:\n"
               "        hvd.allreduce(x, name='beta')\n")
        diags = simulate.simulate_source(src, "golden/train.py")
        assert rules_of(diags) == ["HVD501"]
        doc = sarif_mod.to_sarif(diags)
        doc["runs"][0]["tool"]["driver"]["version"] = "GOLDEN"
        with open(os.path.join(FIXTURES, "golden_sim.sarif")) as f:
            golden = json.load(f)
        assert doc == golden

    def test_sim_corpus_codeflows_validate_against_schema(self):
        import jsonschema
        proc = _run_cli("verify",
                        os.path.join(FIXTURES, "bad_sim_deadlock.py"),
                        os.path.join(FIXTURES, "bad_sim_mismatch.py"),
                        "--format", "sarif", "--fail-on", "never")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        jsonschema.validate(doc, _SARIF_21_SCHEMA)
        results = doc["runs"][0]["results"]
        proven = [r for r in results
                  if r["ruleId"] in ("HVD501", "HVD502")]
        assert len(proven) == 7
        for r in proven:
            flows = r["codeFlows"]
            thread_flows = flows[0]["threadFlows"]
            # one threadFlow per symbolic rank, each with locations
            assert len(thread_flows) >= 2
            ids = {tf["id"] for tf in thread_flows}
            assert any(i.startswith("rank") for i in ids)
        # the HVD503 approximation carries no counterexample
        for r in results:
            if r["ruleId"] == "HVD503":
                assert "codeFlows" not in r

    def test_suppressed_results_are_marked_not_dropped(self):
        d = Diagnostic.make("HVD402", "divergent loop",
                            file="x.py", line=3)
        doc = sarif_mod.to_sarif([], suppressed=[d])
        results = doc["runs"][0]["results"]
        assert len(results) == 1
        assert results[0]["suppressions"][0]["kind"] == "external"
        # a NEW finding carries no suppressions key at all
        doc = sarif_mod.to_sarif([d])
        assert "suppressions" not in doc["runs"][0]["results"][0]

    # -- the unified writer + artifact validator ---------------------------
    def _diag(self, rule="HVD401", file="x.py", line=3):
        return Diagnostic.make(rule, "msg", file=file, line=line)

    def test_write_sarif_tool_param_reaches_driver_name(self, capsys):
        sarif_mod.write_sarif(None, [self._diag("HVD701")],
                              tool="hvd-model")
        doc = json.loads(capsys.readouterr().out)
        assert doc["runs"][0]["tool"]["driver"]["name"] == "hvd-model"
        assert doc["version"] == "2.1.0"

    def test_write_sarif_file_and_stdout_encode_identically(
            self, tmp_path, capsys):
        """One canonical encoding for every CI artifact: same bytes to
        a file as to stdout."""
        path = str(tmp_path / "out.sarif")
        sarif_mod.write_sarif(path, [self._diag()])
        sarif_mod.write_sarif("-", [self._diag()])
        assert capsys.readouterr().out == open(path).read()

    def test_validate_passes_a_sound_artifact(self):
        doc = sarif_mod.to_sarif([self._diag("HVD401"),
                                  self._diag("HVD402")])
        assert sarif_mod.validate(
            doc, require_rules=["HVD401", "HVD402"],
            require_families=["HVD4"],
            forbid_locations=["clean_code"]) == []

    def test_validate_names_every_problem(self):
        doc = sarif_mod.to_sarif([self._diag("HVD401",
                                             file="bad_sim_x.py")])
        problems = sarif_mod.validate(
            doc, require_rules=["HVD999"], require_families=["HVD5"],
            require_flows=[("HVD401", 2)],
            forbid_locations=["bad_sim"])
        text = " ".join(problems)
        assert "HVD999" in text          # missing rule
        assert "HVD5*" in text           # missing family
        assert "threadFlows" in text     # flowless result
        assert "forbidden location" in text

    def test_validate_expect_none_ignores_suppressed(self):
        doc = sarif_mod.to_sarif([], suppressed=[self._diag()])
        assert sarif_mod.validate(doc, expect_none=True) == []
        doc = sarif_mod.to_sarif([self._diag()])
        problems = sarif_mod.validate(doc, expect_none=True)
        assert problems and "expected a clean artifact" in problems[0]

    def test_validator_cli_exit_codes(self, tmp_path, capsys):
        path = str(tmp_path / "a.sarif")
        sarif_mod.write_sarif(path, [self._diag("HVD401")])
        assert sarif_mod.main([path, "--require-rule", "HVD401"]) == 0
        out = capsys.readouterr().out
        assert "ok (1 result(s), tool hvd-lint)" in out
        assert sarif_mod.main([path, "--require-rule", "HVD999"]) == 1
        assert "HVD999" in capsys.readouterr().err
        assert sarif_mod.main(
            [str(tmp_path / "missing.sarif")]) == 2
        capsys.readouterr()


# ==========================================================================
# Baseline workflow (--write-baseline / --baseline)
# ==========================================================================
class TestBaseline:
    def _fixture_diags(self):
        return schedule.verify_paths(
            [os.path.join(FIXTURES, "bad_divergent_loop.py")])

    def test_round_trip_write_then_clean(self, tmp_path):
        diags = self._fixture_diags()
        assert diags
        path = str(tmp_path / "base.json")
        baseline_mod.write_baseline(diags, path)
        doc = baseline_mod.load_baseline(path)
        new, suppressed = baseline_mod.filter_new(diags, doc)
        assert new == [] and len(suppressed) == len(diags)

    def test_new_finding_fails_after_baseline(self, tmp_path):
        diags = self._fixture_diags()
        path = str(tmp_path / "base.json")
        baseline_mod.write_baseline(diags, path)
        doc = baseline_mod.load_baseline(path)
        injected = Diagnostic.make("HVD401", "fresh regression",
                                   file="new_code.py", line=7)
        new, suppressed = baseline_mod.filter_new(
            diags + [injected], doc)
        assert new == [injected]
        assert len(suppressed) == len(diags)

    def test_keys_survive_line_shifts(self, tmp_path):
        """Baseline keys are content-addressed: prepending lines moves
        every finding's line number but resurfaces nothing."""
        src = open(os.path.join(FIXTURES,
                                "bad_divergent_loop.py")).read()
        target = tmp_path / "shifty.py"
        target.write_text(src)
        before = schedule.verify_paths([str(target)])
        path = str(tmp_path / "base.json")
        baseline_mod.write_baseline(before, path)
        target.write_text("# a\n# b\n# c\n" + src)
        after = schedule.verify_paths([str(target)])
        assert [d.line for d in after] == \
            [d.line + 3 for d in before]
        new, suppressed = baseline_mod.filter_new(
            after, baseline_mod.load_baseline(path))
        assert new == [] and len(suppressed) == len(after)

    def test_editing_flagged_line_resurfaces(self, tmp_path):
        src = ("import horovod_tpu as hvd\n"
               "def f(x):\n"
               "    for i in range(hvd.rank() + 1):\n"
               "        hvd.allgather(x, name='g')\n")
        target = tmp_path / "edit.py"
        target.write_text(src)
        diags = schedule.verify_paths([str(target)])
        assert rules_of(diags) == ["HVD402"]
        path = str(tmp_path / "base.json")
        baseline_mod.write_baseline(diags, path)
        # touching the flagged line invalidates its content hash
        target.write_text(src.replace("hvd.rank() + 1",
                                      "hvd.rank() + 2"))
        diags = schedule.verify_paths([str(target)])
        new, suppressed = baseline_mod.filter_new(
            diags, baseline_mod.load_baseline(path))
        assert rules_of(new) == ["HVD402"] and suppressed == []

    def test_corrupt_baseline_rejected(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text('{"not": "a baseline"}')
        with pytest.raises(ValueError):
            baseline_mod.load_baseline(str(path))
        path.write_text('{"version": 99, "findings": {}}')
        with pytest.raises(ValueError):
            baseline_mod.load_baseline(str(path))

    def test_cli_round_trip(self, tmp_path):
        """write -> re-run clean -> inject finding -> fails: the full
        no-flag-day workflow through the CLI."""
        fixture = os.path.join(FIXTURES, "bad_divergent_loop.py")
        base = str(tmp_path / "lint-baseline.json")
        proc = _run_cli("verify", fixture, "--write-baseline", base)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "baseline recorded" in proc.stdout
        proc = _run_cli("verify", fixture, "--baseline", base)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "baseline-suppressed" in proc.stdout
        extra = tmp_path / "regression.py"
        extra.write_text(
            "import horovod_tpu as hvd\n"
            "def f(x):\n"
            "    gate = hvd.rank() == 0\n"
            "    if gate:\n"
            "        hvd.allreduce(x, name='r')\n")
        proc = _run_cli("verify", fixture, str(extra),
                        "--baseline", base)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        # the injected rank-gated collective is a PROVEN deadlock now:
        # HVD501 supersedes the heuristic HVD401 on the same event
        assert "HVD501" in proc.stdout
        assert "regression.py" in proc.stdout

    def test_env_knob_default_baseline(self, tmp_path):
        """HVDTPU_LINT_BASELINE supplies the default --baseline."""
        fixture = os.path.join(FIXTURES, "bad_divergent_loop.py")
        base = str(tmp_path / "env-base.json")
        proc = _run_cli("verify", fixture, "--write-baseline", base)
        assert proc.returncode == 0
        env = clean_spawn_env(
            PYTHONPATH=REPO + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
            HVDTPU_LINT_BASELINE=base)
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.analysis.cli",
             "verify", fixture],
            env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "baseline-suppressed" in proc.stdout

    def test_explicit_missing_baseline_is_an_error(self):
        proc = _run_cli("verify", os.path.join(FIXTURES,
                                               "good_clean.py"),
                        "--baseline", "/nonexistent/base.json")
        assert proc.returncode == 2
        assert "cannot read baseline" in proc.stderr


def test_ci_lint_script(tmp_path):
    """Tier-1 gate: scripts/ci_lint.sh — self-analysis + dogfood sweep
    + fixture-corpus canary emitting a valid lint.sarif artifact."""
    out = str(tmp_path / "lint.sarif")
    env = clean_spawn_env(
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        LINT_SARIF_OUT=out)
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "ci_lint.sh")],
        env=env, capture_output=True, text=True, timeout=500)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "all gates green" in proc.stdout
    doc = json.load(open(out))
    assert doc["version"] == "2.1.0"
    rules = {r["ruleId"] for r in doc["runs"][0]["results"]}
    assert {"HVD401", "HVD402", "HVD403", "HVD404", "HVD405",
            "HVD501", "HVD502", "HVD503"} <= rules
    # per-leg analysis wall time is part of the gate output
    assert "leg wall time" in proc.stdout


# ==========================================================================
# CLI (console entry point behavior via python -m)
# ==========================================================================
def _run_cli(*args):
    env = clean_spawn_env(
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis.cli", *args],
        env=env, capture_output=True, text=True, timeout=120)


def test_cli_detects_fixture_corpus():
    proc = _run_cli(FIXTURES, "--format", "json", "--fail-on", "warning")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    findings = json.loads(proc.stdout)
    found = {d["rule"] for d in findings}
    assert {"HVD201", "HVD202", "HVD203"} <= found
    files = {os.path.basename(d["file"]) for d in findings}
    assert "good_clean.py" not in files
    assert "good_suppressed.py" not in files


def test_cli_clean_sweep_and_rule_listing():
    """The shipped examples and models lint clean through the CLI (the
    CI usage documented in docs/lint.md), and --list-rules works."""
    proc = _run_cli(os.path.join(REPO, "examples"),
                    os.path.join(REPO, "horovod_tpu", "models"),
                    os.path.join(REPO, "horovod_tpu", "telemetry"),
                    os.path.join(REPO, "horovod_tpu", "chaos"),
                    "--fail-on", "warning")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
    listing = _run_cli("--list-rules")
    assert listing.returncode == 0
    assert "HVD201" in listing.stdout


# ==========================================================================
# Symbolic N-rank schedule simulator (analysis/simulate.py, HVD5xx)
# ==========================================================================
class TestSimulator:
    def test_deadlock_fixture(self):
        """Pinned positives: 4 proven deadlocks over 3 shapes, plus
        the bounded-exploration HVD503; negatives + the HVD501
        suppression case stay silent."""
        diags = simulate.simulate_paths(
            [os.path.join(FIXTURES, "bad_sim_deadlock.py")])
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD501", 21), ("HVD501", 29), ("HVD501", 31),
             ("HVD501", 39), ("HVD503", 68)]

    def test_mismatch_fixture(self):
        diags = simulate.simulate_paths(
            [os.path.join(FIXTURES, "bad_sim_mismatch.py")])
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD502", 19), ("HVD502", 26), ("HVD502", 33)]

    def test_every_proven_finding_carries_a_counterexample(self):
        """Acceptance pin: every HVD501/502 positive ships a trace
        with a pinned file:line event list for EACH symbolic rank."""
        diags = simulate.simulate_paths(
            [os.path.join(FIXTURES, "bad_sim_deadlock.py"),
             os.path.join(FIXTURES, "bad_sim_mismatch.py")])
        proven = [d for d in diags if d.rule in ("HVD501", "HVD502")]
        assert len(proven) == 7
        for d in proven:
            trace = d.trace
            assert trace and len(trace["ranks"]) >= 2, d.format()
            for entry in trace["ranks"]:
                if entry["end"] != "exhausted":
                    assert entry["events"], (d.rule, entry)
                for ev in entry["events"]:
                    assert ev["file"].endswith(".py")
                    assert ev["line"] >= 1
            assert trace["forks"], d.format()

    def test_clean_fixture_zero_hvd5xx(self):
        """Acceptance: the balanced/laundered/member-guarded shapes
        stay silent on the simulator too."""
        path = os.path.join(FIXTURES, "good_verify_clean.py")
        assert simulate.verify_and_simulate_paths([path]) == []

    def test_proven_supersedes_401_on_same_event(self):
        """Ownership contract (mirrors 201-vs-401): the proven finding
        owns the event; no double report."""
        src = ("import horovod_tpu as hvd\n"
               "def main(x):\n"
               "    is_root = hvd.rank() == 0\n"
               "    if is_root:\n"
               "        hvd.allreduce(x, name='a')\n")
        diags = simulate.verify_and_simulate_source(src, "own401.py")
        assert rules_of(diags) == ["HVD501"]

    def test_proven_supersedes_402_on_same_loop(self):
        src = ("import horovod_tpu as hvd\n"
               "def main(x):\n"
               "    for _ in range(hvd.rank() + 1):\n"
               "        x = hvd.allgather(x, name='r')\n"
               "    return x\n")
        diags = simulate.verify_and_simulate_source(src, "own402.py")
        assert rules_of(diags) == ["HVD501"]

    def test_unprovable_shapes_keep_the_heuristic(self):
        """The tainted-argument-steers-callee-guard shape is a
        documented simulator approximation: HVD401 stays the owner,
        and the data-dependent convergence while stays HVD402 (no
        HVD503 double report on either)."""
        diags = simulate.verify_and_simulate_paths(
            [os.path.join(FIXTURES, "bad_tainted_schedule.py")])
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD501", 20), ("HVD501", 24), ("HVD401", 34)]
        diags = simulate.verify_and_simulate_paths(
            [os.path.join(FIXTURES, "bad_divergent_loop.py")])
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD501", 16), ("HVD501", 24), ("HVD402", 31)]

    def test_hvd403_keeps_the_exit_501_names_the_collective(self):
        """HVD403 (the exit line) and HVD501 (the skipped collective)
        are complementary locations, both reported."""
        diags = simulate.verify_and_simulate_paths(
            [os.path.join(FIXTURES, "bad_skipped_collective.py")])
        assert [(d.rule, d.line) for d in diags] == \
            [("HVD403", 15), ("HVD501", 16), ("HVD403", 22),
             ("HVD501", 23), ("HVD403", 29), ("HVD501", 30)]

    def test_suppressed_heuristic_carries_over_to_proven(self):
        """A `# hvd-lint: disable=HVD402` on the divergent loop waives
        the proven HVD501 for the same fork too — the human already
        reviewed that exact divergence."""
        src = ("import horovod_tpu as hvd\n"
               "def main(x):\n"
               "    # padded upstream\n"
               "    # hvd-lint: disable=HVD402\n"
               "    for _ in range(hvd.rank() + 1):\n"
               "        x = hvd.allgather(x, name='p')\n"
               "    return x\n")
        assert simulate.verify_and_simulate_source(src, "sup.py") == []

    def test_balanced_incompatible_arms_proven(self):
        """The headline precision gain: balanced branches (HVD401
        exempt) with incompatible slots are a PROVEN deadlock."""
        src = ("import horovod_tpu as hvd\n"
               "def main(x):\n"
               "    if hvd.rank() == 0:\n"
               "        hvd.allreduce(x, name='alpha')\n"
               "    else:\n"
               "        hvd.allreduce(x, name='beta')\n")
        diags = simulate.verify_and_simulate_source(src, "bal.py")
        assert rules_of(diags) == ["HVD501"]
        assert "alpha" in diags[0].message
        assert "beta" in diags[0].message

    def test_three_way_fork_found_by_n3_cohort(self):
        """Both inner divergences of an elif chain are proven (the
        n=3 cohort is what reaches the deepest arm)."""
        diags = simulate.simulate_paths(
            [os.path.join(FIXTURES, "bad_sim_deadlock.py")])
        lines = [d.line for d in diags if d.rule == "HVD501"]
        assert 29 in lines and 31 in lines

    def test_trace_format_golden(self):
        """Satellite pin: the HVD501 counterexample text format is
        golden — tooling parses it."""
        src = ("import horovod_tpu as hvd\n"
               "def exchange(x):\n"
               "    if hvd.rank() == 0:\n"
               "        hvd.allreduce(x, name='alpha')\n"
               "    else:\n"
               "        hvd.allreduce(x, name='beta')\n")
        diags = simulate.simulate_source(src, "golden/train.py")
        assert rules_of(diags) == ["HVD501"]
        assert simulate.render_trace(diags[0]) == (
            "    counterexample (cohort: any n >= 2)\n"
            "      rank r:\n"
            "        1. allreduce(name='alpha')  golden/train.py:4"
            "  [blocked]\n"
            "      rank rest:\n"
            "        1. allreduce(name='beta')  golden/train.py:6"
            "  [blocked]\n"
            "      forks:\n"
            "        - golden/train.py:3: condition tests "
            "rank()/membership directly — arms differ per rank")

    def test_exhausted_rank_in_trace(self):
        src = ("import horovod_tpu as hvd\n"
               "def main(x):\n"
               "    skip = hvd.rank() > 0\n"
               "    if not skip:\n"
               "        hvd.barrier()\n")
        diags = simulate.simulate_source(src, "exh.py")
        assert rules_of(diags) == ["HVD501"]
        ends = {e["rank"]: e["end"]
                for e in diags[0].trace["ranks"]}
        assert "exhausted" in ends.values()
        assert "blocked" in ends.values()

    def test_fstring_names_never_proven(self):
        diags = simulate.verify_and_simulate_paths(
            [os.path.join(FIXTURES, "bad_sim_mismatch.py")])
        # the fstring_names_are_unprovable negative contributes nothing
        assert all(d.line < 50 for d in diags
                   if d.rule.startswith("HVD5")), \
            [(d.rule, d.line) for d in diags]

    def test_dogfood_sweeps_stay_clean(self):
        """Acceptance: no new false positives at fail-on-warning —
        the package itself, examples/ and the serving plane
        produce zero HVD5xx findings."""
        pkg = os.path.join(REPO, "horovod_tpu")
        diags = simulate.verify_and_simulate_paths(
            [os.path.join(pkg, "serving"), os.path.join(pkg, "spark"),
             os.path.join(REPO, "examples")])
        hvd5 = [d for d in diags if d.rule.startswith("HVD5")]
        assert hvd5 == [], "\n".join(d.format() for d in hvd5)

    def test_parse_cache_shared_across_layers(self, tmp_path):
        """Satellite pin: one parse per file per invocation — the AST
        layer and the verifier corpus reuse the same tree object."""
        path = tmp_path / "cached.py"
        path.write_text("import horovod_tpu as hvd\n"
                        "def f(x):\n"
                        "    return hvd.allreduce(x, name='c')\n")
        src1, tree1 = ast_lint.parse_cached(str(path))
        src2, tree2 = ast_lint.parse_cached(str(path))
        assert tree1 is tree2
        verifier = schedule.Verifier()
        verifier.add_path(str(path))
        mod = verifier.corpus.modules[os.path.abspath(str(path))]
        assert mod.tree is tree1
        # an edit invalidates the cache entry
        time.sleep(0.01)
        path.write_text("import horovod_tpu as hvd\n")
        os.utime(str(path))
        _, tree3 = ast_lint.parse_cached(str(path))
        assert tree3 is not tree1

    def test_cli_reports_wall_time(self, tmp_path):
        path = tmp_path / "t.py"
        path.write_text("x = 1\n")
        proc = _run_cli(str(path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        import re as _re
        assert _re.search(r"in \d+\.\d\ds", proc.stdout), proc.stdout

    def test_rules_in_catalog_and_cli_listing(self):
        for rule in ("HVD501", "HVD502", "HVD503"):
            assert rule in analysis.RULES
        listing = _run_cli("--list-rules")
        assert "HVD501" in listing.stdout
        assert "HVD503" in listing.stdout


# ==========================================================================
# hvd-lint explain (analysis/explain.py): postmortem → source line
# ==========================================================================
class TestExplain:
    BUNDLE = os.path.join(FIXTURES, "postmortem_bundle")
    PROGRAM = os.path.join(FIXTURES, "sim_explain_program.py")

    def test_golden_bundle_roundtrip(self):
        """Satellite pin: the golden bundle (generated from the
        chaos-matrix stall row's output shape) names the
        never-submitted op AND its source line."""
        report = explain_mod.explain_bundle(self.BUNDLE,
                                            [self.PROGRAM])
        assert report["ranks"] == [0, 1]
        assert report["reason"] == "collective_abort"
        div = report["divergence"]
        assert div["type"] == "missing_submission"
        assert div["rule"] == "HVD501"
        assert div["name"] == "step3" and div["occurrence"] == 1
        assert div["submitted_by"] == [0]
        assert div["involved_ranks"] == [1]
        # the f-string pattern `step{...}` maps back to the call site
        assert len(div["sources"]) == 1
        site = div["sources"][0]
        assert site["file"].endswith("sim_explain_program.py")
        assert site["line"] == 17
        assert site["kind"] == "allreduce"

    def test_render_report_text(self):
        report = explain_mod.explain_bundle(self.BUNDLE,
                                            [self.PROGRAM])
        text = explain_mod.render_report(report)
        assert "first divergent slot: `step3` occurrence 1" in text
        assert "NEVER submitted by rank(s) [1]" in text
        assert "diagnosis: HVD501" in text
        assert "sim_explain_program.py:17" in text

    def test_without_program_still_names_the_slot(self):
        report = explain_mod.explain_bundle(self.BUNDLE)
        assert report["divergence"]["name"] == "step3"
        assert report["divergence"]["sources"] == []
        text = explain_mod.render_report(report)
        assert "--program" in text

    def test_field_mismatch_bundle(self, tmp_path):
        (tmp_path / "postmortem.r0.p1.v0.jsonl").write_text(
            '{"e":"meta","t":1.0,"kind":"postmortem","rank":0,'
            '"size":2,"ver":0,"off":0.0,"reason":"mismatch"}\n'
            '{"e":"sub","t":1.1,"n":"g","k":"allreduce","o":1}\n')
        (tmp_path / "postmortem.r1.p2.v0.jsonl").write_text(
            '{"e":"meta","t":1.0,"kind":"postmortem","rank":1,'
            '"size":2,"ver":0,"off":0.0,"reason":"mismatch"}\n'
            '{"e":"sub","t":1.1,"n":"g","k":"allgather","o":1}\n')
        report = explain_mod.explain_bundle(str(tmp_path))
        div = report["divergence"]
        assert div["type"] == "field_mismatch"
        assert div["rule"] == "HVD502"
        assert div["kinds"] == ["allgather", "allreduce"]

    def test_runtime_stall_is_hvd503(self, tmp_path):
        """All ranks submitted compatibly, nothing finished: a runtime
        stall, not a schedule divergence."""
        for rank in (0, 1):
            (tmp_path / f"postmortem.r{rank}.p{rank}.v0.jsonl"
             ).write_text(
                '{"e":"meta","t":1.0,"kind":"postmortem",'
                f'"rank":{rank},'
                '"size":2,"ver":0,"off":0.0,"reason":"stall"}\n'
                '{"e":"sub","t":1.1,"n":"s","k":"allreduce","o":1}\n')
        report = explain_mod.explain_bundle(str(tmp_path))
        div = report["divergence"]
        assert div["type"] == "never_finished"
        assert div["rule"] == "HVD503"

    def test_clean_bundle_reports_no_divergence(self, tmp_path):
        for rank in (0, 1):
            (tmp_path / f"postmortem.r{rank}.p{rank}.v0.jsonl"
             ).write_text(
                '{"e":"meta","t":1.0,"kind":"postmortem",'
                f'"rank":{rank},'
                '"size":2,"ver":0,"off":0.0,"reason":"external"}\n'
                '{"e":"sub","t":1.1,"n":"s","k":"allreduce","o":1}\n'
                '{"e":"fin","t":1.2,"n":"s","o":1}\n')
        report = explain_mod.explain_bundle(str(tmp_path))
        assert report["divergence"] is None
        assert "no divergent slot" in \
            explain_mod.render_report(report)

    def test_newest_elastic_version_wins(self, tmp_path):
        """Two aborts in one directory: explain analyzes the newest
        cohort's bundle (bundle_by_rank contract)."""
        for ver, name in ((0, "old"), (2, "new")):
            for rank in (0, 1):
                events = (
                    f'{{"e":"sub","t":1.1,"n":"{name}",'
                    '"k":"allreduce","o":1}\n')
                if rank == 0 or ver == 0:
                    pass
                (tmp_path / f"postmortem.r{rank}.p{rank}.v{ver}.jsonl"
                 ).write_text(
                    '{"e":"meta","t":1.0,"kind":"postmortem",'
                    f'"rank":{rank},"size":2,"ver":{ver},"off":0.0,'
                    '"reason":"collective_abort"}\n'
                    + (events if rank == 0 else ""))
        report = explain_mod.explain_bundle(str(tmp_path))
        assert report["version"] == 2
        assert report["divergence"]["name"] == "new"

    def test_ring_evicted_sub_with_surviving_fin_not_hvd501(
            self, tmp_path):
        """A rank whose `sub` fell off the bounded flight ring but
        whose `fin` survived DID submit that slot: the completion
        proves it. The window artifact must not shadow the genuinely
        never-submitted slot."""
        (tmp_path / "postmortem.r0.p1.v0.jsonl").write_text(
            '{"e":"meta","t":1.0,"kind":"postmortem","rank":0,'
            '"size":2,"ver":0,"off":0.0,"reason":"collective_abort"}\n'
            '{"e":"sub","t":1.0,"n":"w","k":"allreduce","o":1}\n'
            '{"e":"fin","t":1.1,"n":"w","o":1}\n'
            '{"e":"sub","t":1.5,"n":"step3","k":"allreduce","o":1}\n')
        # rank 1: the older `sub` for `w` was evicted, its fin kept;
        # `step3` genuinely never submitted
        (tmp_path / "postmortem.r1.p2.v0.jsonl").write_text(
            '{"e":"meta","t":1.0,"kind":"postmortem","rank":1,'
            '"size":2,"ver":0,"off":0.0,"reason":"collective_abort"}\n'
            '{"e":"fin","t":1.1,"n":"w","o":1}\n')
        report = explain_mod.explain_bundle(str(tmp_path))
        div = report["divergence"]
        assert div["name"] == "step3", report
        assert div["type"] == "missing_submission"
        assert div["involved_ranks"] == [1]

    def test_missing_program_path_fails_loudly(self, tmp_path):
        """A typo'd --program must not silently degrade to 'no source
        mapping' with exit 0 — even when the bundle itself has no
        divergence (the early no-divergence return must not skip the
        path check)."""
        with pytest.raises(explain_mod.ExplainError,
                           match="program path not found"):
            explain_mod.explain_bundle(
                self.BUNDLE, [str(tmp_path / "no_such_train.py")])
        proc = _run_cli("explain", self.BUNDLE,
                        "--program", str(tmp_path / "nope.py"))
        assert proc.returncode == 2
        assert "program path not found" in proc.stderr
        # clean bundle + bad program: still rc 2
        for rank in (0, 1):
            (tmp_path / f"postmortem.r{rank}.p{rank}.v0.jsonl"
             ).write_text(
                '{"e":"meta","t":1.0,"kind":"postmortem",'
                f'"rank":{rank},'
                '"size":2,"ver":0,"off":0.0,"reason":"external"}\n'
                '{"e":"sub","t":1.1,"n":"s","k":"allreduce","o":1}\n'
                '{"e":"fin","t":1.2,"n":"s","o":1}\n')
        proc = _run_cli("explain", str(tmp_path),
                        "--program", str(tmp_path / "nope.py"))
        assert proc.returncode == 2
        assert "program path not found" in proc.stderr

    def test_empty_dir_raises(self, tmp_path):
        with pytest.raises(explain_mod.ExplainError):
            explain_mod.explain_bundle(str(tmp_path))

    def test_cli_explain_text_and_json(self):
        proc = _run_cli("explain", self.BUNDLE,
                        "--program", self.PROGRAM)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "first divergent slot: `step3`" in proc.stdout
        assert "sim_explain_program.py:17" in proc.stdout
        proc = _run_cli("explain", self.BUNDLE, "--format", "json")
        assert proc.returncode == 0
        doc = json.loads(proc.stdout)
        assert doc["divergence"]["name"] == "step3"

    def test_cli_explain_missing_bundle_exits_2(self, tmp_path):
        proc = _run_cli("explain", str(tmp_path / "nope"))
        assert proc.returncode == 2
        proc = _run_cli("explain", str(tmp_path))
        assert proc.returncode == 2
        assert "no postmortem shards" in proc.stderr


# ==========================================================================
# Deterministic auto-naming (ops/collectives.py)
# ==========================================================================
class TestAutoNames:
    def test_per_site_counter_and_determinism(self):
        from horovod_tpu.ops import collectives as C

        def site_a():
            return C._auto_name("allreduce")

        def site_b():
            return C._auto_name("allreduce")

        C.reset_auto_name_counters()
        first = [site_a(), site_a(), site_b()]
        # Same site twice -> same stem, bumped counter; different site ->
        # different stem.
        assert first[0].endswith("#1") and first[1].endswith("#2")
        assert first[0].rsplit("#", 1)[0] == first[1].rsplit("#", 1)[0]
        assert first[2].rsplit("#", 1)[0] != first[0].rsplit("#", 1)[0]
        assert "site_a" in first[0] and "site_b" in first[2]
        # A second process running the same program (simulated by a
        # counter reset) generates the identical stream — the property
        # that keeps auto names rank-invariant.
        C.reset_auto_name_counters()
        assert [site_a(), site_a(), site_b()] == first

    def test_interleaving_does_not_shift_names(self):
        from horovod_tpu.ops import collectives as C

        def site_a():
            return C._auto_name("allreduce")

        def site_b():
            return C._auto_name("allgather")

        C.reset_auto_name_counters()
        rank0 = [site_a(), site_b(), site_a()]
        C.reset_auto_name_counters()
        # "rank 1" interleaves the sites differently (an extra rank-local
        # call order change); per-site names still match pairwise.
        rank1 = [site_b(), site_a(), site_a()]
        assert sorted(rank0) == sorted(rank1)

    def test_legacy_env_knob(self, monkeypatch):
        from horovod_tpu.ops import collectives as C
        monkeypatch.setenv("HOROVOD_TPU_LEGACY_AUTO_NAMES", "1")
        C.reset_auto_name_counters()
        try:
            name = C._auto_name("allreduce")
            assert name == "allreduce.noname.1"
        finally:
            monkeypatch.delenv("HOROVOD_TPU_LEGACY_AUTO_NAMES")
            C.reset_auto_name_counters()


# ==========================================================================
# Layer 3: submission-order guard
# ==========================================================================
class TestOrderGuard:
    def test_identical_streams_pass(self):
        guards = [SubmissionOrderGuard(rank=r) for r in range(2)]
        for g in guards:
            for i in range(200):
                g.record(f"grad.{i % 7}", "allreduce")
        idx = SubmissionOrderGuard.compare_payloads(
            [g.sync_payload() for g in guards])
        assert idx is not None and idx >= 1

    def test_divergent_order_is_caught(self):
        """Acceptance: an intentionally rank-divergent submission order
        (same multiset of names, different order) raises."""
        g0, g1 = SubmissionOrderGuard(rank=0), SubmissionOrderGuard(rank=1)
        names = [f"t{i}" for i in range(64)]
        for n in names:
            g0.record(n)
        for n in reversed(names):
            g1.record(n)
        with pytest.raises(SubmissionOrderError) as err:
            SubmissionOrderGuard.compare_payloads(
                [g0.sync_payload(), g1.sync_payload()])
        assert "hvd-lint" in str(err.value)

    def test_skewed_counts_compare_at_common_checkpoint(self):
        """A rank that is merely AHEAD (same prefix) must not be flagged
        — comparison is count-aligned, not instantaneous."""
        g0, g1 = SubmissionOrderGuard(rank=0), SubmissionOrderGuard(rank=1)
        for i in range(64):
            g0.record(f"t{i}")
            g1.record(f"t{i}")
        for i in range(64, 100):  # rank 1 ran ahead within checkpoint 2
            g1.record(f"t{i}")
        idx = SubmissionOrderGuard.compare_payloads(
            [g0.sync_payload(), g1.sync_payload()])
        assert idx == 1

    def test_no_common_checkpoint_yet(self):
        g0, g1 = SubmissionOrderGuard(rank=0), SubmissionOrderGuard(rank=1)
        g0.record("a")  # below checkpoint_every: nothing to compare
        assert SubmissionOrderGuard.compare_payloads(
            [g0.sync_payload(), g1.sync_payload()]) is None

    def test_verify_reshapes_gathered_rows(self):
        g = SubmissionOrderGuard(rank=0)
        for i in range(70):
            g.record(f"t{i}")
        stacked = np.stack([g.sync_payload(), g.sync_payload()])
        assert g.verify(stacked.reshape(-1), num_ranks=2) == 1

    def test_record_and_dump(self, tmp_path):
        g = SubmissionOrderGuard(rank=3, record=True)
        g.record("alpha", "allreduce", callsite="train.py:10 (main)")
        g.record("beta", "allgather")
        path = g.dump(str(tmp_path / "order.{rank}.json"))
        data = json.loads(open(path).read())
        assert path.endswith("order.3.json")
        assert data["count"] == 2
        assert [e["name"] for e in data["sequence"]] == ["alpha", "beta"]
        assert data["sequence"][0]["site"] == "train.py:10 (main)"

    def test_mixed_checkpoint_every_is_a_config_error(self):
        """Differing checkpoint_every across ranks makes checkpoint
        indices incomparable — a configuration error, not a silent None
        and not a false divergence."""
        g0 = SubmissionOrderGuard(rank=0, checkpoint_every=32)
        g1 = SubmissionOrderGuard(rank=1, checkpoint_every=64)
        for i in range(64):
            g0.record(f"t{i}")
            g1.record(f"t{i}")
        with pytest.raises(ValueError) as err:
            SubmissionOrderGuard.compare_payloads(
                [g0.sync_payload(), g1.sync_payload()])
        assert "checkpoint_every" in str(err.value)
        assert "[32, 64]" in str(err.value)

    def test_common_checkpoint_slid_out_of_window(self):
        """Extreme skew: the laggard's newest checkpoint has already
        slid out of the leader's bounded window — no comparison this
        round (None), never a false divergence."""
        g0 = SubmissionOrderGuard(rank=0, checkpoint_every=4, window=2)
        g1 = SubmissionOrderGuard(rank=1, checkpoint_every=4, window=2)
        for i in range(4):      # laggard: only checkpoint index 1
            g0.record(f"t{i}")
        for i in range(40):     # leader's window holds indices 9, 10
            g1.record(f"t{i}")
        assert SubmissionOrderGuard.compare_payloads(
            [g0.sync_payload(), g1.sync_payload()]) is None

    def test_divergence_names_rank_groups_and_window(self):
        """The error partitions ranks by digest (so the odd rank out is
        identifiable in a 3-rank cohort) and bounds the offending
        submission window."""
        g0, g1, g2 = (SubmissionOrderGuard(rank=r) for r in range(3))
        for i in range(64):
            g0.record(f"t{i}")
            g2.record(f"t{i}")
        for i in reversed(range(64)):
            g1.record(f"t{i}")
        with pytest.raises(SubmissionOrderError) as err:
            SubmissionOrderGuard.compare_payloads(
                [g.sync_payload() for g in (g0, g1, g2)])
        msg = str(err.value)
        assert "ranks [0, 2]" in msg and "ranks [1]" in msg
        assert "first 64 submissions" in msg

    def test_record_cap_sets_truncated(self, tmp_path):
        """The fixture recorder is bounded: past max_record the hash
        keeps running (comparison stays exact) but the sequence stops
        growing and the dump says so."""
        g = SubmissionOrderGuard(rank=0, record=True, max_record=3)
        for i in range(5):
            g.record(f"t{i}")
        assert g.truncated
        data = json.loads(open(g.dump(
            str(tmp_path / "order.json"))).read())
        assert data["truncated"] is True
        assert data["count"] == 5
        assert len(data["sequence"]) == 3

    def test_digest_is_order_sensitive_and_count_tagged(self):
        g0, g1 = SubmissionOrderGuard(rank=0), SubmissionOrderGuard(rank=1)
        for n in ("a", "b"):
            g0.record(n)
        for n in ("b", "a"):
            g1.record(n)
        assert g0.digest() != g1.digest()   # same multiset, diff order
        g2 = SubmissionOrderGuard(rank=2)
        for n in ("a", "b"):
            g2.record(n)
        assert g0.digest() == g2.digest()
        g2.record("c")
        assert g0.digest() != g2.digest()   # count rides the digest


# ==========================================================================
# Coordinator integration: stall warning, duplicate-name call-sites,
# ORDER_CHECK wiring, disabled-by-default hot path
# ==========================================================================
class _LogRecorder:
    def __init__(self):
        self.messages = []

    def warning(self, fmt, *args):
        self.messages.append(fmt % args if args else fmt)

    error = info = debug = warning


def _stub_runtime():
    return types.SimpleNamespace(
        topology=types.SimpleNamespace(rank=0, size=1),
        mode="single", backend=None, timeline=None, autotuner=None)


class TestCoordinatorGuards:
    def test_order_guard_disabled_by_default(self, hvd):
        import horovod_tpu.basics as basics
        coord = basics.runtime().coordinator
        assert coord._order_guard is None

    def test_disabled_hot_path_skips_callsite_capture(self, hvd,
                                                      monkeypatch):
        """With ORDER_CHECK off, submit() must not walk the stack (the
        no-new-work-when-disabled guarantee)."""
        import horovod_tpu.coordinator as coord_mod

        def bomb():
            raise AssertionError("callsite captured on disabled hot path")

        monkeypatch.setattr(coord_mod, "format_user_frame", bomb)
        out = hvd.allreduce(jnp.ones(len(jax.devices())), op=hvd.Sum,
                            name="lint.hotpath.check")
        assert np.isfinite(np.asarray(out)).all()

    def test_duplicate_name_error_mentions_sites_and_rule(self, hvd,
                                                          n_devices):
        import horovod_tpu.basics as basics
        from horovod_tpu.exceptions import DuplicateNameError
        coord = basics.runtime().coordinator
        saved = coord.cycle_time_s
        coord.cycle_time_s = 1.0  # hold the cycle open
        try:
            x = jnp.ones((n_devices, 2))
            h1 = hvd.allreduce_async(x, op=hvd.Sum, name="lint.dup")
            with pytest.raises(DuplicateNameError) as err:
                hvd.allreduce_async(x, op=hvd.Sum, name="lint.dup")
        finally:
            coord.cycle_time_s = saved
        hvd.synchronize(h1)
        msg = str(err.value)
        assert "HVD203" in msg
        assert "duplicate submitted at" in msg
        assert "test_lint.py" in msg  # the raise-time call-site

    def test_stall_warning_is_one_summary_line(self):
        """N stalled ops produce ONE summary (count + oldest op + age +
        call-site), not N lines; an unchanged stalled set within the
        threshold stays quiet on later scans."""
        from horovod_tpu.coordinator import Coordinator
        coord = Coordinator(_stub_runtime())
        log = _LogRecorder()
        coord._log = log
        now = time.monotonic()
        coord._pending_names[(0, "stuck.grad")] = [
            now - 2 * coord.stall_warn_s, "train.py:42 (main)"]
        coord._pending_names[(0, "stuck.bias")] = [
            now - 1.5 * coord.stall_warn_s, None]
        coord._last_stall_scan = now - coord._stall_scan_period - 1
        coord._check_stalls(now=now)
        assert len(log.messages) == 1
        msg = log.messages[0]
        assert "2 tensor(s)" in msg
        assert "stuck.grad" in msg       # the oldest op is named
        assert "stuck.bias" not in msg   # the rest are only counted
        assert "train.py:42" in msg
        assert "hvd-lint" in msg
        # same stalled set, within the refresh period: quiet
        coord._last_stall_scan = now - coord._stall_scan_period - 1
        coord._check_stalls(now=now)
        assert len(log.messages) == 1
        # a NEW op crossing the threshold re-triggers the summary
        coord._pending_names[(0, "stuck.new")] = [
            now - 3 * coord.stall_warn_s, None]
        coord._last_stall_scan = now - coord._stall_scan_period - 1
        coord._check_stalls(now=now)
        assert len(log.messages) == 2
        assert "3 tensor(s)" in log.messages[1]

    def test_stall_knob_spellings(self, monkeypatch):
        from horovod_tpu.coordinator import Coordinator
        monkeypatch.setenv("HOROVOD_TPU_STALL_CHECK_TIME", "7.5")
        assert Coordinator(_stub_runtime()).stall_warn_s == 7.5
        monkeypatch.delenv("HOROVOD_TPU_STALL_CHECK_TIME")
        monkeypatch.setenv("HVDTPU_STALL_CHECK_TIME_SECONDS", "9")
        assert Coordinator(_stub_runtime()).stall_warn_s == 9.0
        monkeypatch.setenv("HVDTPU_STALL_CHECK_DISABLE", "1")
        assert Coordinator(_stub_runtime()).stall_warn_s == 0.0

    def test_order_check_records_submissions(self, tmp_path):
        """HOROVOD_TPU_ORDER_CHECK=1 end to end in a fresh process:
        submissions are recorded in order and dumped on shutdown."""
        record = str(tmp_path / "order.json")
        script = (
            "import horovod_tpu as hvd, jax.numpy as jnp\n"
            "hvd.init()\n"
            "import horovod_tpu.basics as basics\n"
            "coord = basics.runtime().coordinator\n"
            "assert coord._order_guard is not None\n"
            "import jax\n"
            "n = len(jax.devices())\n"
            "for i in range(3):\n"
            "    hvd.allreduce(jnp.ones((n, 2)), name=f'g.{i}')\n"
            "hvd.allreduce(jnp.ones((n, 2)))\n"
            "assert coord._order_guard.count == 4\n"
            "hvd.shutdown()\n"
            "print('ORDER-OK')\n")
        env = clean_spawn_env(
            PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                          ""),
            HOROVOD_TPU_ORDER_CHECK="1",
            HOROVOD_TPU_ORDER_CHECK_RECORD=record)
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=180)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "ORDER-OK" in proc.stdout
        data = json.loads(open(record).read())
        names = [e["name"] for e in data["sequence"]]
        assert names[:3] == ["g.0", "g.1", "g.2"]
        assert names[3].startswith("allreduce.auto.")  # deterministic stem
        assert all(e["site"] for e in data["sequence"])


# ==========================================================================
# verify= wiring in the compile bridges
# ==========================================================================
class TestVerifyFlag:
    def test_bridges_expose_verify(self):
        import inspect
        from horovod_tpu.torch.compile import tpu_compile as torch_compile
        from horovod_tpu.tensorflow.compile import (tpu_compile as
                                                    tf_compile)
        assert "verify" in inspect.signature(torch_compile).parameters
        assert "verify" in inspect.signature(tf_compile).parameters

    def test_verify_traceable_clean_and_bad(self):
        assert analysis.verify_traceable(
            lambda x: x * 2, (jnp.ones(3),), axis_sizes=AXES) == []
        with pytest.raises(CollectiveLintError):
            analysis.verify_traceable(
                lambda x: lax.psum(x, "tp"), (jnp.ones(3),),
                axis_sizes=AXES)

    def test_torch_bridge_verify_runs_clean(self, hvd):
        torch = pytest.importorskip("torch")
        from horovod_tpu.torch import tpu_compile

        class Net(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.fc = torch.nn.Linear(4, 3)

            def forward(self, x):
                return torch.tanh(self.fc(x))

        compiled = tpu_compile(Net().eval(), verify=True)
        out = compiled(x=torch.ones(2, 4))
        assert np.asarray(out).shape == (2, 3)
