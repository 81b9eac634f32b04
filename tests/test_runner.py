"""Launcher tests: host parsing, slot assignment, KV rendezvous, and
end-to-end hvdrun launches (the analog of the reference's
test/single/test_run.py unit tests + running parallel suites under the
launcher, .buildkite/gen-pipeline.sh:231)."""

import os
import subprocess
import sys

import pytest

from horovod_tpu.runner import hosts as hosts_mod
from horovod_tpu.runner import http_client
from horovod_tpu.runner.http_server import KVStoreServer, RendezvousServer

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "spmd_worker.py")


# -- hosts / assignments ---------------------------------------------------

def test_parse_hosts():
    hs = hosts_mod.parse_hosts("a:2,b:4,c")
    assert [(h.hostname, h.slots) for h in hs] == [("a", 2), ("b", 4),
                                                   ("c", 1)]
    with pytest.raises(ValueError):
        hosts_mod.parse_hosts("a:2,a:3")
    with pytest.raises(ValueError):
        hosts_mod.parse_hosts("")


def test_parse_hostfile(tmp_path):
    p = tmp_path / "hostfile"
    p.write_text("# comment\nhost1 slots=2\nhost2:3\nhost3\n\n")
    hs = hosts_mod.parse_hostfile(str(p))
    assert [(h.hostname, h.slots) for h in hs] == [
        ("host1", 2), ("host2", 3), ("host3", 1)]


def test_host_assignments_single_host():
    slots = hosts_mod.get_host_assignments(
        hosts_mod.parse_hosts("localhost:4"), 3)
    assert [s.rank for s in slots] == [0, 1, 2]
    assert all(s.size == 3 for s in slots)
    assert [s.local_rank for s in slots] == [0, 1, 2]
    assert all(s.local_size == 3 for s in slots)
    assert all(s.cross_rank == 0 and s.cross_size == 1 for s in slots)


def test_host_assignments_multi_host():
    slots = hosts_mod.get_host_assignments(
        hosts_mod.parse_hosts("a:2,b:2,c:1"), 5)
    assert [(s.hostname, s.rank, s.local_rank) for s in slots] == [
        ("a", 0, 0), ("a", 1, 1), ("b", 2, 0), ("b", 3, 1), ("c", 4, 0)]
    # local_rank 0 exists on a,b,c; local_rank 1 only on a,b.
    assert [(s.cross_rank, s.cross_size) for s in slots] == [
        (0, 3), (0, 2), (1, 3), (1, 2), (2, 3)]


def test_host_assignments_overflow():
    with pytest.raises(ValueError):
        hosts_mod.get_host_assignments(hosts_mod.parse_hosts("a:1"), 2)


# -- KV store --------------------------------------------------------------

def test_kvstore_roundtrip():
    server = KVStoreServer()
    port = server.start()
    try:
        assert http_client.get_kv("127.0.0.1", port, "s", "k") is None
        http_client.put_kv("127.0.0.1", port, "s", "k", "hello")
        assert http_client.get_kv("127.0.0.1", port, "s", "k") == b"hello"
        http_client.delete_kv("127.0.0.1", port, "s", "k")
        assert http_client.get_kv("127.0.0.1", port, "s", "k") is None
        http_client.put_kv("127.0.0.1", port, "s", "a", "1")
        http_client.put_kv("127.0.0.1", port, "s", "b", "2")
        http_client.delete_kv("127.0.0.1", port, "s", "_all")
        assert http_client.get_kv("127.0.0.1", port, "s", "a") is None
    finally:
        server.stop()


def test_kvstore_auth():
    server = KVStoreServer(job_token="sekrit")
    port = server.start()
    try:
        # Auth rejections are fatal (never retried) and name the op,
        # scope and key — the explicit HTTPError mapping.
        with pytest.raises(http_client.KVFatalError) as ei:
            http_client.put_kv("127.0.0.1", port, "s", "k", "v",
                               token="wrong")
        assert ei.value.code == 403
        assert "put s/k" in str(ei.value)
        http_client.put_kv("127.0.0.1", port, "s", "k", "v", token="sekrit")
        assert http_client.get_kv("127.0.0.1", port, "s", "k",
                                  token="sekrit") == b"v"
    finally:
        server.stop()


def test_rendezvous_publishes_slots():
    slots = hosts_mod.get_host_assignments(
        hosts_mod.parse_hosts("localhost:2"), 2)
    server = RendezvousServer()
    port = server.start()
    try:
        server.publish_assignments(slots)
        line = http_client.get_kv("127.0.0.1", port, "slots", "1")
        assert line == b"localhost,1,2,1,2,0,1"
        assert http_client.get_kv("127.0.0.1", port, "slots",
                                  "size") == b"2"
    finally:
        server.stop()


# -- end-to-end launches ---------------------------------------------------

def _worker_env():
    # Workers must not inherit the test session's 8-device virtual flags.
    # PYTHONPATH carries the repo and tests dir so pickled test functions
    # resolve in the worker interpreter.
    pythonpath = os.pathsep.join(
        [REPO, HERE, os.environ.get("PYTHONPATH", "")])
    return {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
            "PYTHONPATH": pythonpath}


def test_run_command_spmd_worker():
    """The full SPMD suite launched through the runner: peers come from
    rendezvous, not HVDTPU_PEERS."""
    from horovod_tpu.runner import run_command
    rc = run_command([sys.executable, WORKER], num_proc=2,
                     env=_worker_env())
    assert rc == 0


def test_hvdrun_console_entry():
    """`python -m horovod_tpu.runner.launch -np 2 python -c ...` — the
    declared console script must import and run a trivial job."""
    from conftest import clean_spawn_env
    env = clean_spawn_env(
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    script = ("import horovod_tpu as hvd, jax.numpy as jnp, numpy as np; "
              "hvd.init(); "
              "out = hvd.allreduce(jnp.ones(4) * (hvd.rank() + 1), "
              "op=hvd.Sum, name='t'); "
              "np.testing.assert_allclose(np.asarray(out), 3.0); "
              "print('LAUNCHED-OK', hvd.rank())")
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         sys.executable, "-c", script],
        env=env, capture_output=True, timeout=180)
    out = proc.stdout.decode()
    assert proc.returncode == 0, out + proc.stderr.decode()
    assert "LAUNCHED-OK 0" in out
    assert "LAUNCHED-OK 1" in out


def test_output_filename_captures_per_rank(tmp_path):
    """--output-filename mirrors each rank's streams into
    rank.N/stdout|stderr (reference: gloo_run.py:157 MultiFile capture)."""
    from conftest import clean_spawn_env
    env = clean_spawn_env(
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out_dir = str(tmp_path / "logs")
    script = ("import horovod_tpu as hvd, sys; hvd.init(); "
              "print('CAPTURED', hvd.rank()); "
              "print('ERRSIDE', hvd.rank(), file=sys.stderr)")
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--output-filename", out_dir,
         sys.executable, "-c", script],
        env=env, capture_output=True, timeout=180)
    assert proc.returncode == 0, proc.stdout.decode() + \
        proc.stderr.decode()
    for rank in (0, 1):
        stdout = open(os.path.join(out_dir, f"rank.{rank}",
                                   "stdout")).read()
        stderr = open(os.path.join(out_dir, f"rank.{rank}",
                                   "stderr")).read()
        assert f"CAPTURED {rank}" in stdout
        assert f"ERRSIDE {rank}" in stderr
    # Console still shows the prefixed stream.
    assert "CAPTURED 0" in proc.stdout.decode()


def test_config_file_fills_defaults(tmp_path):
    """--config-file YAML fills unset flags; explicit CLI flags win;
    unknown keys error (reference: launch.py:513 + config_parser)."""
    from horovod_tpu.runner.launch import parse_args

    cfg = tmp_path / "hvd.yaml"
    cfg.write_text("num-proc: 4\nstart_timeout: 33\n"
                   "fusion-threshold-mb: 16\nautotune: true\n")
    args = parse_args(["--config-file", str(cfg), "echo", "hi"])
    assert args.num_proc == 4
    assert args.start_timeout == 33
    assert args.fusion_threshold_mb == 16
    assert args.autotune is True

    # CLI wins over the file — including a flag passed AT its default
    # value (-np 1 equals the parser default but was explicit).
    args = parse_args(["-np", "2", "--config-file", str(cfg),
                       "echo", "hi"])
    assert args.num_proc == 2
    args = parse_args(["-np", "1", "--config-file", str(cfg),
                       "echo", "hi"])
    assert args.num_proc == 1

    # Config values go through the flag's argparse type.
    typed = tmp_path / "typed.yaml"
    typed.write_text('num-proc: "4"\n')
    args = parse_args(["--config-file", str(typed), "echo", "hi"])
    assert args.num_proc == 4 and isinstance(args.num_proc, int)

    bad = tmp_path / "bad.yaml"
    bad.write_text("not-a-flag: 1\n")
    import pytest as _pytest
    with _pytest.raises(SystemExit):
        parse_args(["--config-file", str(bad), "echo", "hi"])
    untyped = tmp_path / "untyped.yaml"
    untyped.write_text("num-proc: not-a-number\n")
    with _pytest.raises(SystemExit):
        parse_args(["--config-file", str(untyped), "echo", "hi"])

    # Boolean flags parse strictly: a quoted "false" must not enable.
    boolcfg = tmp_path / "bool.yaml"
    boolcfg.write_text('autotune: "false"\nverbose: "on"\n')
    args = parse_args(["--config-file", str(boolcfg), "echo", "hi"])
    assert args.autotune is False and args.verbose is True
    badbool = tmp_path / "badbool.yaml"
    badbool.write_text("autotune: maybe\n")
    with _pytest.raises(SystemExit):
        parse_args(["--config-file", str(badbool), "echo", "hi"])

    # Null values and parser-internal dests fail fast...
    nullcfg = tmp_path / "null.yaml"
    nullcfg.write_text("num-proc:\n")
    with _pytest.raises(SystemExit):
        parse_args(["--config-file", str(nullcfg), "echo", "hi"])
    # ...unless the same key was given explicitly on the CLI, which wins
    # over a malformed config value.
    args = parse_args(["-np", "4", "--config-file", str(nullcfg),
                       "echo", "hi"])
    assert args.num_proc == 4
    helpcfg = tmp_path / "help.yaml"
    helpcfg.write_text("help: true\n")
    with _pytest.raises(SystemExit):
        parse_args(["--config-file", str(helpcfg), "echo", "hi"])


def test_run_programmatic():
    """horovod_tpu.runner.run(): pickled function, per-rank results."""
    from horovod_tpu.runner import run
    results = run(_prog_fn, num_proc=2, env=_worker_env())
    assert results == [[0, 2, 10.0], [1, 2, 10.0]]


def _prog_fn():
    import horovod_tpu as hvd
    import jax.numpy as jnp
    hvd.init()
    out = hvd.allreduce(jnp.full((4,), float(hvd.rank() + 1)), op=hvd.Sum,
                        name="p")
    return [hvd.rank(), hvd.size(), float(out[0]) + 7.0]


def test_failed_rank_fails_job():
    from horovod_tpu.runner import run_command
    rc = run_command(
        [sys.executable, "-c",
         "import os, sys; sys.exit(3 if os.environ['HVDTPU_RANK'] == '1' "
         "else 0)"],
        num_proc=2, env=_worker_env())
    assert rc == 3


def test_run_command_multi_host_topology():
    """Two distinct 'hosts' (localhost + 127.0.0.1, both local) at one
    slot each: the launcher's GLOBAL/LOCAL/CROSS slot math must surface in
    worker topology queries end to end."""
    from horovod_tpu.runner import run_command
    script = ("import horovod_tpu as hvd, jax.numpy as jnp, numpy as np; "
              "hvd.init(); "
              "assert hvd.size() == 2 and hvd.local_size() == 1, "
              "(hvd.size(), hvd.local_size()); "
              "assert hvd.cross_size() == 2, hvd.cross_size(); "
              "assert hvd.cross_rank() == hvd.rank(), "
              "(hvd.cross_rank(), hvd.rank()); "
              "out = hvd.allreduce(jnp.ones(2), op=hvd.Sum, name='m'); "
              "np.testing.assert_allclose(np.asarray(out), 2.0); "
              "print('MULTIHOST-OK', hvd.rank())")
    rc = run_command([sys.executable, "-c", script], num_proc=2,
                     hosts="localhost:1,127.0.0.1:1", env=_worker_env())
    assert rc == 0


def test_new_launcher_flags():
    """Round-4 flag additions mapped from the reference's horovodrun
    surface: --version, --timeline-mark-cycles, ssh options,
    --hierarchical-threshold-mb, --network-interface."""
    from horovod_tpu.runner.launch import parse_args, _knob_env, \
        _iface_addr

    args = parse_args(["--timeline-mark-cycles",
                       "--hierarchical-threshold-mb", "2",
                       "--ssh-port", "2222",
                       "--ssh-identity-file", "/tmp/key",
                       "echo", "hi"])
    env = _knob_env(args)
    assert env["HVDTPU_TIMELINE_MARK_CYCLES"] == "1"
    assert env["HVDTPU_HIERARCHICAL_THRESHOLD"] == str(2 * 1024 * 1024)
    assert args.ssh_port == 2222
    assert args.ssh_identity_file == "/tmp/key"

    # --version parses without a command.
    args = parse_args(["--version"])
    assert args.version

    # Loopback interface resolves; a bogus one fails loud.
    assert _iface_addr(None) is None
    assert _iface_addr("lo") == "127.0.0.1"
    import pytest as _pytest
    with _pytest.raises(SystemExit, match="no-such-iface"):
        _iface_addr("no-such-iface")


def test_version_prints_and_exits(capsys):
    from horovod_tpu.runner.launch import run_commandline
    import horovod_tpu
    rc = run_commandline(["--version"])
    assert rc == 0
    assert horovod_tpu.__version__ in capsys.readouterr().out


def test_launcher_builds_native_core_before_spawning(monkeypatch):
    """From a clean tree N workers would each run make in csrc/ at once
    (the loader's lock is per process): the launcher parent builds."""
    import pytest
    from horovod_tpu import native
    from horovod_tpu.runner import launch
    order = []
    monkeypatch.setattr(native, "ensure_built",
                        lambda: order.append("build"))
    monkeypatch.setattr(launch, "launch_job",
                        lambda settings, command: order.append("spawn") or 0)
    with pytest.raises(SystemExit) as exit_info:
        launch.run_commandline(["-np", "2", "true"])
    assert exit_info.value.code == 0
    assert order == ["build", "spawn"]


def test_timeline_mark_cycles_emits_markers(tmp_path):
    """start_timeline(mark_cycles=True) drops CYCLE_START instants when
    host-plane cycles move tensors (previously a dead parameter)."""
    import json
    import jax
    import numpy as np
    import horovod_tpu as hvd
    hvd.init()
    trace = tmp_path / "tl.json"
    hvd.start_timeline(str(trace), mark_cycles=True)
    # Single-mode inputs are stacked: leading axis = virtual ranks.
    hvd.allreduce(np.zeros((len(jax.devices()), 2), np.float32),
                  op=hvd.Sum, name="tlmc")
    hvd.stop_timeline()
    events = json.loads(trace.read_text())
    assert any(e.get("name") == "CYCLE_START" for e in events), events
