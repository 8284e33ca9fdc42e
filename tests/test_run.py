"""Launcher unit tests + a real multi-process run() integration test.

Parity model: `test/test_run.py` (arg→env mapping :68-80, config YAML, host
parsing, command construction — unit, mocked) and `test/test_interactiverun.py`
(run() func API across 2 real processes)."""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from horovod_tpu.run import config_parser, hosts, rendezvous
from horovod_tpu.run.launcher import build_parser, make_rank_envs


def test_parse_hosts():
    hs = hosts.parse_hosts("h1:4, h2:2,h3")
    assert [(h.hostname, h.slots) for h in hs] == [("h1", 4), ("h2", 2),
                                                   ("h3", 1)]


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hostfile"
    f.write_text("h1 slots=4\nh2:2  # comment\n\n")
    hs = hosts.parse_hostfile(str(f))
    assert [(h.hostname, h.slots) for h in hs] == [("h1", 4), ("h2", 2)]


def test_allocate_local_cross():
    ranks = hosts.allocate(hosts.parse_hosts("h1:2,h2:2"), 4)
    assert [(r.rank, r.hostname, r.local_rank, r.cross_rank)
            for r in ranks] == [
        (0, "h1", 0, 0), (1, "h1", 1, 0), (2, "h2", 0, 1), (3, "h2", 1, 1)]
    assert all(r.local_size == 2 and r.cross_size == 2 for r in ranks)


def test_allocate_uneven_cross_sets():
    ranks = hosts.allocate(hosts.parse_hosts("h1:3,h2:1"), 4)
    # local_rank 0 exists on both hosts; local ranks 1,2 only on h1
    r3 = ranks[3]
    assert r3.hostname == "h2" and r3.local_rank == 0 and r3.cross_size == 2
    assert ranks[1].cross_size == 1  # local_rank 1 only on h1


def test_allocate_overflow_raises():
    with pytest.raises(ValueError, match="exceeds"):
        hosts.allocate(hosts.parse_hosts("h1:2"), 4)


def test_args_to_env_mapping():
    args = build_parser().parse_args(
        ["-np", "2", "--fusion-threshold-mb", "32", "--cycle-time-ms", "3.5",
         "--timeline-filename", "/tmp/tl.json", "--autotune", "--",
         "python", "x.py"])
    env = config_parser.env_from_config(None, args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "3.5"
    assert env["HOROVOD_TIMELINE"] == "/tmp/tl.json"
    assert env["HOROVOD_AUTOTUNE"] == "1"


def test_config_yaml(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(textwrap.dedent("""
        fusion-threshold-mb: 16
        cycle-time-ms: 2.0
        timeline:
            filename: /tmp/t2.json
            mark-cycles: true
        autotune:
            enabled: true
    """))
    env = config_parser.env_from_config(str(cfg))
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(16 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "2.0"
    assert env["HOROVOD_TIMELINE"] == "/tmp/t2.json"
    assert env["HOROVOD_TIMELINE_MARK_CYCLES"] == "1"
    assert env["HOROVOD_AUTOTUNE"] == "1"


def test_make_rank_envs():
    ranks = hosts.allocate(hosts.parse_hosts("localhost:2"), 2)
    envs = make_rank_envs(ranks, "127.0.0.1:1234", "127.0.0.1:9",
                          "sec", {"HOROVOD_CYCLE_TIME": "5"})
    assert envs[0]["HVD_PROCESS_ID"] == "0"
    assert envs[1]["HVD_PROCESS_ID"] == "1"
    assert envs[0]["HVD_NUM_PROCS"] == "2"
    assert envs[0]["HVD_COORDINATOR_ADDR"] == "127.0.0.1:1234"
    assert envs[1]["HOROVOD_CYCLE_TIME"] == "5"


def test_kv_store_roundtrip():
    secret = rendezvous.make_secret()
    srv = rendezvous.KVStoreServer(secret).start()
    try:
        c = rendezvous.KVStoreClient(f"127.0.0.1:{srv.port}", secret)
        c.put("scope", "key", b"value")
        assert c.get("scope", "key") == b"value"
        assert c.get("scope", "missing") is None
        # bad secret rejected
        bad = rendezvous.KVStoreClient(f"127.0.0.1:{srv.port}", "wrong")
        with pytest.raises(Exception):
            bad.put("scope", "key2", b"x")
    finally:
        srv.stop()


def _worker_allreduce():
    import numpy as np

    import horovod_tpu as hvd

    out = hvd.allreduce(np.full((4,), float(hvd.rank() + 1), np.float32),
                        name="mp", op=hvd.Sum)
    return (hvd.rank(), hvd.size(), [float(x) for x in np.asarray(out)])


@pytest.mark.integration
def test_run_func_two_processes():
    """Real 2-process launch: jax.distributed rendezvous + cross-process
    allreduce through the multiprocess engine (test_interactiverun parity)."""
    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    env = {
        # each worker: CPU platform, own pair of virtual devices
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        # workers must be able to import this test module to unpickle fn
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
    }
    results = run(_worker_allreduce, np=2, env=env, start_timeout=120)
    assert results[0][:2] == (0, 2)
    assert results[1][:2] == (1, 2)
    assert results[0][2] == [3.0, 3.0, 3.0, 3.0]
    assert results[1][2] == [3.0, 3.0, 3.0, 3.0]


_WORKER_PREAMBLE = """
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    sys.path.insert(0, %r)
    import horovod_tpu as hvd
    hvd.init()
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_hvdrun(tmp_path, body, np_ranks=2):
    """Launch a 2-rank hvdrun job whose per-rank script is the shared CPU
    preamble + ``body``; returns the CompletedProcess."""
    script = tmp_path / "job.py"
    script.write_text(textwrap.dedent(_WORKER_PREAMBLE)
                      + textwrap.dedent(body))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    return subprocess.run(
        [sys.executable, os.path.join(repo, "bin", "hvdrun"),
         "-np", str(np_ranks), "--", sys.executable, str(script)],
        capture_output=True, text=True, timeout=180, env=env)


def test_check_build_report():
    """--check-build prints the capability report without needing -np
    (`run/run.py:289-332` parity)."""
    from horovod_tpu.run import launcher

    out = launcher.check_build()
    assert "Available Frameworks" in out
    assert "[X] JAX / flax" in out
    assert "Available Controllers" in out
    assert "Available Tensor Operations" in out
    assert launcher.run_commandline(["--check-build"]) == 0
    # flags in the USER command must not be hijacked (the report flag only
    # applies before the command remainder)
    assert launcher.run_commandline(
        ["-np", "0", "--", "python", "x.py", "--check-build"]) == 2


@pytest.mark.integration
def test_hvdrun_tf_graph_mode(tmp_path):
    """Graph-mode (tf.function) collectives across REAL processes: a
    compiled train step with DistributedGradientTape under the coordinated
    control plane — the deployment shape the in-process rig can't fully
    represent (one rank per process, own TF runtime each)."""
    pytest.importorskip("tensorflow")
    r = _run_hvdrun(tmp_path, """
        import tensorflow as tf
        import horovod_tpu.tensorflow as hvd_tf

        w = tf.Variable([1.0, 2.0])

        @tf.function
        def step(x):
            with tf.GradientTape() as tape:
                loss = tf.reduce_sum(w * x)
            dtape = hvd_tf.DistributedGradientTape(tape)
            g = dtape.gradient(loss, [w])[0]
            w.assign_sub(0.1 * g)
            return g

        g = step(tf.fill((2,), float(hvd.rank() + 1)))
        # average of per-rank dy (=rank+1) over 2 ranks = 1.5
        print("GRAD", [round(float(v), 3) for v in g.numpy()])
        print("W", [round(float(v), 3) for v in w.numpy()])
    """)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("GRAD [1.5, 1.5]") == 2, r.stdout
    assert r.stdout.count("W [0.85, 1.85]") == 2, r.stdout


@pytest.mark.integration
def test_hvdrun_cli_smoke(tmp_path):
    """hvdrun CLI end-to-end on 2 local ranks."""
    r = _run_hvdrun(tmp_path, """
        out = hvd.allreduce(np.ones((2,), np.float32), name="cli",
                            op=hvd.Sum)
        print("RANK", hvd.rank(), "OUT", float(np.asarray(out)[0]))
    """)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OUT 2.0" in r.stdout
    assert "[0]<stdout>" in r.stdout and "[1]<stdout>" in r.stdout


@pytest.mark.integration
def test_rank_death_kills_job_not_hangs(tmp_path):
    """A rank dying mid-stream must terminate the whole job with a nonzero
    exit (first-failure kill, `gloo_run.py:253-259`) — the survivor, stuck
    in negotiation with a dead peer, must NOT hang past the kill."""
    t0 = time.monotonic()
    r = _run_hvdrun(tmp_path, """
        hvd.allreduce(np.ones(2), name="ok")      # both ranks complete one
        if hvd.rank() == 1:
            os._exit(3)                           # die mid-job, no goodbye
        hvd.allreduce(np.ones(2), name="never")   # peer is dead: would hang
        print("SURVIVOR FINISHED")                # must not be reached
    """)
    assert r.returncode != 0
    assert "SURVIVOR FINISHED" not in r.stdout
    assert time.monotonic() - t0 < 150  # killed, not timed out


def test_round4_flag_env_mapping():
    """Flag parity sweep (reference `run/run.py:395-616` mapped through
    `config_parser.py:140-180`, test style `test/test_run.py:68-80`):
    autotune sub-knobs, hierarchical collectives, stall-check disable."""
    args = build_parser().parse_args(
        ["-np", "2",
         "--autotune", "--autotune-warmup-samples", "2",
         "--autotune-steps-per-sample", "3",
         "--autotune-bayes-opt-max-samples", "7",
         "--autotune-gaussian-process-noise", "0.9",
         "--hierarchical-allreduce", "--no-hierarchical-allgather",
         "--no-stall-check", "--", "python", "x.py"])
    env = config_parser.env_from_config(None, args)
    assert env["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"] == "2"
    assert env["HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"] == "3"
    assert env["HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"] == "7"
    assert env["HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"] == "0.9"
    assert env["HOROVOD_HIERARCHICAL_ALLREDUCE"] == "1"
    assert env["HOROVOD_HIERARCHICAL_ALLGATHER"] == "0"
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "1"


def test_tristate_flags_absent_by_default():
    """Unset tri-state flags must NOT export env — the workers' own env or
    defaults stay in force (reference leaves unset args out of the env)."""
    args = build_parser().parse_args(["-np", "2", "--", "python", "x.py"])
    env = config_parser.env_from_config(None, args)
    for var in ("HOROVOD_HIERARCHICAL_ALLREDUCE",
                "HOROVOD_HIERARCHICAL_ALLGATHER",
                "HOROVOD_STALL_CHECK_DISABLE"):
        assert var not in env, var


def test_config_yaml_round4_sections(tmp_path):
    """YAML sections mirror the reference layout: params.hierarchical-*,
    autotune.{warmup,steps,bayes,noise}, stall-check.{enabled,times}
    (`run/common/util/config_parser.py:60-92`)."""
    import textwrap as tw

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(tw.dedent("""
        params:
            hierarchical-allreduce: true
            hierarchical-allgather: false
        autotune:
            enabled: true
            warmup-samples: 4
            steps-per-sample: 5
            bayes-opt-max-samples: 6
            gaussian-process-noise: 0.25
        stall-check:
            enabled: false
            warning-time-seconds: 30
            shutdown-time-seconds: 90
    """))
    env = config_parser.env_from_config(str(cfg))
    assert env["HOROVOD_HIERARCHICAL_ALLREDUCE"] == "1"
    assert env["HOROVOD_HIERARCHICAL_ALLGATHER"] == "0"
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"] == "4"
    assert env["HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"] == "5"
    assert env["HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"] == "6"
    assert env["HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"] == "0.25"
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "1"
    assert env["HOROVOD_STALL_CHECK_TIME_SECONDS"] == "30"
    assert env["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] == "90"
    # CLI flag overrides the config file (reference override_args behavior)
    args = build_parser().parse_args(
        ["-np", "2", "--stall-check", "--", "python", "x.py"])
    env = config_parser.env_from_config(str(cfg), args)
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "0"


def test_flag_audit_aliases_and_log_flags():
    """Alias and negative-pair parity from the audit
    (`docs/design.md` launcher flag audit): -p, -hostfile,
    --network-interface, --no-autotune, --no-timeline-mark-cycles,
    --[no-]log-hide-timestamp, reference stall flag spellings."""
    args = build_parser().parse_args(
        ["-np", "2", "-p", "2222", "-hostfile", "/tmp/hf",
         "--network-interface", "eth0,eth1",
         "--no-autotune", "--no-timeline-mark-cycles",
         "--log-hide-timestamp",
         "--stall-check-warning-time-seconds", "45",
         "--stall-check-shutdown-time-seconds", "120",
         "--", "python", "x.py"])
    assert args.ssh_port == 2222
    assert args.hostfile == "/tmp/hf"
    assert args.nics == "eth0,eth1"
    env = config_parser.env_from_config(None, args)
    assert env["HOROVOD_AUTOTUNE"] == "0"
    assert env["HOROVOD_TIMELINE_MARK_CYCLES"] == "0"
    assert env["HOROVOD_LOG_HIDE_TIME"] == "1"
    assert env["HOROVOD_STALL_CHECK_TIME_SECONDS"] == "45.0"
    assert env["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] == "120.0"
    # unset tri-states stay absent
    args2 = build_parser().parse_args(["-np", "2", "--", "python", "x.py"])
    env2 = config_parser.env_from_config(None, args2)
    for var in ("HOROVOD_AUTOTUNE", "HOROVOD_TIMELINE_MARK_CYCLES",
                "HOROVOD_LOG_HIDE_TIME"):
        assert var not in env2, var


def test_config_yaml_logging_section(tmp_path):
    import textwrap as tw

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(tw.dedent("""
        logging:
            level: DEBUG
            hide-timestamp: true
    """))
    env = config_parser.env_from_config(str(cfg))
    assert env["HOROVOD_LOG_LEVEL"] == "DEBUG"
    assert env["HOROVOD_LOG_HIDE_TIME"] == "1"
