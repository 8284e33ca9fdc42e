#!/usr/bin/env python
"""Pod-day readiness smoke: the exact multi-host command lines documented
in docs/running.md ("Pod day" section) must stay valid with zero edits.

For every ``hvdrun ...`` line in that section this checks, without
launching anything:

  * the hvdrun flags parse against the REAL launcher parser;
  * the target script exists and its own argparser accepts the
    documented arguments (--help-level validation in a subprocess with a
    stubbed-out run, for scripts with argparse; compile-check otherwise).

Run by ci/run_tests.sh; also runnable directly: python ci/pod_smoke.py
"""

import os
import re
import shlex
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DOC = os.path.join(REPO, "docs", "running.md")
ELASTIC_DOC = os.path.join(REPO, "docs", "elastic.md")


def pod_day_commands():
    text = open(DOC).read()
    m = re.search(r"## Pod day.*?```bash\n(.*?)```", text, re.S)
    assert m, "docs/running.md lost its Pod day section"
    cmds = [ln.strip() for ln in m.group(1).splitlines()
            if ln.strip().startswith("hvdrun ")]
    assert len(cmds) >= 4, f"expected >=4 pod-day commands, found {cmds}"
    return cmds


def elastic_commands():
    """The documented elastic launch lines (docs/elastic.md) get the same
    no-rot guarantee: --min-np/--max-np/--host-discovery-script/
    --blacklist-cooldown must keep parsing against the real launcher."""
    text = open(ELASTIC_DOC).read()
    cmds = [ln.strip()
            for m in re.finditer(r"```bash\n(.*?)```", text, re.S)
            for ln in m.group(1).splitlines()
            if ln.strip().startswith("hvdrun ")]
    assert len(cmds) >= 2, f"expected >=2 elastic commands, found {cmds}"
    return cmds


def check_command(cmd: str) -> None:
    from horovod_tpu.run.launcher import build_parser

    argv = shlex.split(cmd)[1:]
    args = build_parser().parse_args(argv)  # SystemExit on a rotten flag
    rest = args.command
    assert rest and rest[0] == "python", f"{cmd!r}: remainder {rest}"
    script = rest[1]
    script_path = os.path.join(REPO, script)
    assert os.path.exists(script_path), f"{cmd!r}: {script} missing"
    script_args = rest[2:]
    if script_args:
        # the script's own argparser must accept the documented args:
        # append --help AFTER them — argparse validates the names/choices/
        # types of everything it consumed before the help action fires, so
        # an unknown or ill-typed documented flag exits 2 while a valid
        # line exits 0. (Known limit: --help short-circuits required-arg
        # presence checks; none of the documented scripts have required
        # args today.)
        code = (
            "import sys, runpy\n"
            f"sys.argv = [{script!r}] + {script_args!r} + ['--help']\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "try:\n"
            f"    runpy.run_path({script_path!r}, run_name='__main__')\n"
            "except SystemExit as e:\n"
            "    raise SystemExit(0 if e.code in (0, None) else e.code)\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, (
            f"{cmd!r}: script argparse rejected the documented args:\n"
            f"{r.stderr[-2000:]}")
    else:
        # no args: a syntax/compile check is the zero-cost validation
        import py_compile

        py_compile.compile(script_path, doraise=True)


def check_metrics_endpoint() -> None:
    """Live /metrics smoke (docs/metrics.md): a 2-thread local cluster with
    HOROVOD_METRICS_PORT=0 scrapes its own endpoint via urllib and prints the
    text; this parent fails on empty or Prometheus-unparsable output."""
    code = (
        "import os, sys, urllib.request\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "os.environ['HOROVOD_METRICS_PORT'] = '0'\n"
        "import numpy as np\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu import testing\n"
        "from horovod_tpu.metrics import server_port\n"
        "def fn():\n"
        "    for i in range(3):\n"
        "        hvd.allreduce(np.ones((8,), np.float32), name='g',"
        " op=hvd.Sum)\n"
        "    return True\n"
        "assert all(testing.run_cluster(fn, np=2))\n"
        "port = server_port()\n"
        "assert port, 'metrics endpoint did not start'\n"
        "body = urllib.request.urlopen(\n"
        "    f'http://127.0.0.1:{port}/metrics', timeout=10).read()\n"
        "hvd.shutdown()\n"
        "sys.stdout.write(body.decode())\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"metrics smoke job failed:\n{r.stderr[-2000:]}")
    from horovod_tpu.metrics import parse_prometheus

    assert r.stdout.strip(), "metrics endpoint served empty output"
    samples = parse_prometheus(r.stdout)  # ValueError on unparsable text
    for want in ("hvd_allreduce_latency_seconds_count",
                 "hvd_wire_bytes_total",
                 "hvd_response_cache_hits_total",
                 "hvd_elastic_epoch"):
        assert want in samples, f"/metrics output missing {want}"
    print(f"ok: /metrics endpoint served {len(samples)} sample families")


def check_chaos_reconnect() -> None:
    """Fault-tolerance smoke (docs/fault-tolerance.md): a real 2-process job
    with a connection drop injected mid-step (HOROVOD_FAULT_SPEC) must
    complete normally AND its /metrics endpoint must show a nonzero
    ``hvd_control_reconnects_total`` — proof the drop was recovered by
    reconnect+replay, not by luck."""
    code = (
        "import sys, time, urllib.request\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "from horovod_tpu.run.api import run\n"
        "def fn():\n"
        "    import time, urllib.request\n"
        "    import numpy as np\n"
        "    import horovod_tpu as hvd\n"
        "    from horovod_tpu.metrics import server_port\n"
        "    hvd.init()\n"
        "    r = hvd.rank()\n"
        "    for i in range(6):\n"
        "        out = hvd.allreduce(np.ones((8,), np.float32),"
        " name=f'c{i}', op=hvd.Sum)\n"
        "        assert np.allclose(np.asarray(out), 2.0)\n"
        "    time.sleep(1.0)  # a few metrics-ship intervals: rank 1's\n"
        "    # reconnect count must reach the rank-0 aggregator\n"
        "    body = ''\n"
        "    if r == 0:\n"
        "        port = server_port()\n"
        "        assert port, 'metrics endpoint did not start'\n"
        "        body = urllib.request.urlopen(\n"
        "            f'http://127.0.0.1:{port}/metrics',"
        " timeout=10).read().decode()\n"
        "    hvd.shutdown()\n"
        "    return (r, body)\n"
        "env = {\n"
        "    'JAX_PLATFORMS': 'cpu',\n"
        "    'HVD_ELASTIC': '1',\n"
        "    'HOROVOD_FAULT_SPEC': 'conn_drop@tick:3#1',\n"
        "    'HOROVOD_METRICS_PORT': '0',\n"
        "    'HOROVOD_METRICS_INTERVAL': '0.2',\n"
        f"    'PYTHONPATH': {REPO!r},\n"
        "}\n"
        "out = dict(run(fn, np=2, env=env, start_timeout=120))\n"
        "sys.stdout.write('===METRICS===\\n' + out[0] + '===END===\\n')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"chaos smoke job failed:\n{r.stderr[-2000:]}")
    from horovod_tpu.metrics import parse_prometheus

    m = re.search(r"===METRICS===\n(.*?)===END===", r.stdout, re.S)
    assert m, (
        "chaos smoke produced no metrics body; stdout tail:\n"
        f"{r.stdout[-2000:]}")
    samples = parse_prometheus(m.group(1))
    assert "hvd_control_reconnects_total" in samples, \
        "/metrics output missing hvd_control_reconnects_total"
    total = sum(samples["hvd_control_reconnects_total"].values())
    assert total > 0, (
        "injected connection drop produced no reconnect: "
        f"hvd_control_reconnects_total == {total}")
    print(f"ok: chaos smoke recovered {int(total)} injected connection "
          "drop(s) via reconnect+replay")


def check_nan_skip() -> None:
    """Data-plane integrity smoke (docs/fault-tolerance.md): training with
    `nan@grad` injected under HOROVOD_GRAD_GUARD=skip must still converge,
    with a nonzero ``hvd_steps_skipped_total`` — proof the poisoned step
    was dropped in lockstep on every rank rather than reduced into the
    weights."""
    code = (
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "os.environ['HOROVOD_GRAD_GUARD'] = 'skip'\n"
        "os.environ['HOROVOD_FAULT_SPEC'] = 'nan@grad:2#1'\n"
        "import numpy as np\n"
        "import jax, optax\n"
        "import jax.numpy as jnp\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu import testing\n"
        "from horovod_tpu.metrics import instruments\n"
        "def fn():\n"
        "    params = {'w': jnp.zeros((4,))}\n"
        "    target = jnp.asarray([1.0, -2.0, 3.0, 0.5])\n"
        "    tx = hvd.DistributedOptimizer(optax.sgd(0.3))\n"
        "    opt = tx.init(params)\n"
        "    loss_fn = lambda p: jnp.mean((p['w'] - target) ** 2)\n"
        "    grad_fn = jax.jit(jax.value_and_grad(loss_fn))\n"
        "    first = None\n"
        "    for _ in range(25):\n"
        "        loss, grads = grad_fn(params)\n"
        "        first = loss if first is None else first\n"
        "        updates, opt = tx.update(grads, opt, params)\n"
        "        params = optax.apply_updates(params, updates)\n"
        "    return float(first), float(loss_fn(params)),"
        " np.asarray(params['w'])\n"
        "res = testing.run_cluster(fn, np=2)\n"
        "skipped = instruments.steps_skipped().value\n"
        "assert skipped > 0, 'injected NaN produced no skipped step'\n"
        "np.testing.assert_array_equal(res[0][2], res[1][2])\n"
        "for first, final, _ in res:\n"
        "    assert final < first * 0.05, (first, final)\n"
        "print(f'skipped={int(skipped)} loss {res[0][0]:.3f} ->"
        " {res[0][1]:.5f}')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"nan-injection smoke job failed:\n{r.stderr[-2000:]}")
    print(f"ok: nan-injection smoke converged through a skipped step "
          f"({r.stdout.strip().splitlines()[-1]})")


def check_trace_capture() -> None:
    """Distributed-tracing smoke (docs/tracing.md): a real 2-process
    training job with HOROVOD_TRACE set must leave ONE merged strictly-valid
    Chrome trace on rank 0, and ``bin/hvdprof`` must parse it with a nonzero
    wire span count — proof both ranks' spans crossed the control plane and
    survived the merge."""
    import json
    import tempfile

    trace = os.path.join(tempfile.mkdtemp(prefix="hvd_trace_smoke_"),
                         "trace.json")
    code = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from horovod_tpu.run.api import run\n"
        "def fn():\n"
        "    import jax, optax\n"
        "    import jax.numpy as jnp\n"
        "    import horovod_tpu as hvd\n"
        "    hvd.init()\n"
        "    params = {'w': jnp.zeros((64,))}\n"
        "    tx = hvd.DistributedOptimizer(optax.sgd(0.1))\n"
        "    opt = tx.init(params)\n"
        "    loss_fn = lambda p: jnp.mean(p['w'] ** 2)\n"
        "    grad_fn = jax.jit(jax.grad(loss_fn))\n"
        "    for _ in range(4):\n"
        "        grads = grad_fn(params)\n"
        "        updates, opt = tx.update(grads, opt, params)\n"
        "        params = optax.apply_updates(params, updates)\n"
        "    hvd.shutdown()\n"
        "    return True\n"
        "env = {\n"
        "    'JAX_PLATFORMS': 'cpu',\n"
        # host-wire data plane: the only cross-process eager path on CPU
        "    'HVD_ELASTIC': '1',\n"
        f"    'HOROVOD_TRACE': {trace!r},\n"
        "    'HOROVOD_TRACE_INTERVAL': '0.2',\n"
        f"    'PYTHONPATH': {REPO!r},\n"
        "}\n"
        "assert all(run(fn, np=2, env=env, start_timeout=120))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"trace-capture smoke job failed:\n{r.stderr[-2000:]}")
    assert os.path.exists(trace), f"no merged trace at {trace}"
    hvdprof = os.path.join(REPO, "bin", "hvdprof")
    v = subprocess.run([sys.executable, hvdprof, "validate", trace],
                       capture_output=True, text=True, timeout=60)
    assert v.returncode == 0, (
        f"hvdprof validate rejected the merged trace:\n{v.stderr[-2000:]}"
        f"\n{v.stdout[-2000:]}")
    p = subprocess.run([sys.executable, hvdprof, "report", trace, "--json"],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, (
        f"hvdprof report failed:\n{p.stderr[-2000:]}")
    report = json.loads(p.stdout)
    wire = report["counts"]["wire_spans"]
    assert wire > 0, f"merged trace has no wire spans: {report['counts']}"
    ranks = sorted(int(k) for k in report["ranks"])
    assert ranks == [0, 1], f"expected spans from both ranks, got {ranks}"
    print(f"ok: trace capture merged {report['counts']['events']} events "
          f"({wire} wire spans) from ranks {ranks}; hvdprof parses it")


def check_bucket_overlap() -> None:
    """Bucket-overlap smoke (docs/overlap.md): a real 2-process training
    job with HOROVOD_BUCKET_MB set must put client-built ``grad.bucket.*``
    tensors on the wire as SEPARATE responses (several distinct bucket
    names in the trace — the controller did not re-merge them), with WIRE
    spans running concurrently with the GRAD launch/drain phase spans,
    and ``bin/hvdprof`` must report the overlap %% line off the merged
    trace."""
    import json
    import tempfile

    trace = os.path.join(tempfile.mkdtemp(prefix="hvd_overlap_smoke_"),
                         "trace.json")
    code = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from horovod_tpu.run.api import run\n"
        "def fn():\n"
        "    import jax, optax\n"
        "    import jax.numpy as jnp\n"
        "    import horovod_tpu as hvd\n"
        "    hvd.init()\n"
        # 8 dense leaves of 16 KiB against a 20 KiB budget: every leaf
        # closes its own bucket -> 8 concurrent non-fusable allreduces
        "    params = {f'w{i}': jnp.zeros((4096,)) for i in range(8)}\n"
        "    tx = hvd.DistributedOptimizer(optax.sgd(0.1))\n"
        "    opt = tx.init(params)\n"
        "    loss_fn = lambda p: sum(jnp.mean(v ** 2) for v in"
        " p.values())\n"
        "    grad_fn = jax.jit(jax.grad(loss_fn))\n"
        "    for _ in range(4):\n"
        "        grads = grad_fn(params)\n"
        "        updates, opt = tx.update(grads, opt, params)\n"
        "        params = optax.apply_updates(params, updates)\n"
        "    hvd.shutdown()\n"
        "    return True\n"
        "env = {\n"
        "    'JAX_PLATFORMS': 'cpu',\n"
        # host-wire data plane: the only cross-process eager path on CPU
        "    'HVD_ELASTIC': '1',\n"
        "    'HOROVOD_BUCKET_MB': '0.02',\n"
        f"    'HOROVOD_TRACE': {trace!r},\n"
        "    'HOROVOD_TRACE_INTERVAL': '0.2',\n"
        f"    'PYTHONPATH': {REPO!r},\n"
        "}\n"
        "assert all(run(fn, np=2, env=env, start_timeout=120))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"bucket-overlap smoke job failed:\n{r.stderr[-2000:]}")
    assert os.path.exists(trace), f"no merged trace at {trace}"
    from horovod_tpu.tracing.analyzer import intersect_us, load_events

    events = [e for e in load_events(trace) if e.get("ph") == "X"]
    buckets = {e["args"]["tensor"] for e in events
               if (e.get("args") or {}).get("tensor", "").startswith(
                   "grad.bucket.")}
    assert len(buckets) >= 2, (
        f"expected several client-built buckets on the wire, saw {buckets}")
    overlap = 0
    for rank in (0, 1):
        wire = [(e["ts"], e["dur"]) for e in events
                if e.get("pid") == rank and e.get("name") == "WIRE"]
        grad = [(e["ts"], e["dur"]) for e in events
                if e.get("pid") == rank
                and e.get("name") in ("GRAD_LAUNCH", "GRAD_DRAIN")]
        assert wire, f"rank {rank} left no WIRE spans"
        assert grad, f"rank {rank} left no GRAD phase spans"
        overlap += intersect_us(wire, grad)
    assert overlap > 0, (
        "no WIRE span ran concurrently with a GRAD phase span — bucket "
        "overlap produced zero wire/backward concurrency")
    hvdprof = os.path.join(REPO, "bin", "hvdprof")
    p = subprocess.run([sys.executable, hvdprof, "report", trace, "--json"],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, f"hvdprof report failed:\n{p.stderr[-2000:]}"
    report = json.loads(p.stdout)
    assert "overlap_pct" in report["overall"], (
        f"hvdprof report lost the overlap %: {report['overall']}")
    print(f"ok: bucket overlap — {len(buckets)} buckets on the wire, "
          f"{overlap} us of WIRE concurrent with GRAD phases, hvdprof "
          f"overall overlap {report['overall']['overlap_pct']:.1f}%")


def check_blackbox_doctor() -> None:
    """Postmortem smoke (docs/observability.md): a real 2-process job with
    rank 1 wedged at its first collective (``hang@collective``) under an
    enforced 3 s HOROVOD_COLLECTIVE_TIMEOUT must die leaving a blackbox
    dump from BOTH ranks, and ``bin/hvddoctor`` on the bundle must name
    the collective deadlock, the stalled tensor, and the missing rank."""
    import tempfile

    bbdir = tempfile.mkdtemp(prefix="hvd_blackbox_smoke_")
    code = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from horovod_tpu.run.api import run\n"
        "def fn():\n"
        "    import numpy as np\n"
        "    import horovod_tpu as hvd\n"
        "    hvd.init()\n"
        "    hvd.allreduce(np.ones((8,), np.float32), name='bb_probe',"
        " op=hvd.Sum)\n"
        "    hvd.shutdown()\n"
        "    return True\n"
        "env = {\n"
        "    'JAX_PLATFORMS': 'cpu',\n"
        # wedge rank 1 for 30s at its 1st enqueued collective; the 3s
        # watchdog fails rank 0 long before, and the launcher's
        # first-failure SIGTERM triggers rank 1's signal-path dump
        "    'HOROVOD_FAULT_SPEC': 'hang@collective:30:1#1',\n"
        "    'HOROVOD_COLLECTIVE_TIMEOUT': '3',\n"
        "    'HOROVOD_BLACKBOX': '1',\n"
        f"    'HOROVOD_BLACKBOX_DIR': {bbdir!r},\n"
        f"    'PYTHONPATH': {REPO!r},\n"
        "}\n"
        "try:\n"
        "    run(fn, np=2, env=env, start_timeout=120)\n"
        "except RuntimeError as exc:\n"
        "    print('===DIED===', str(exc).splitlines()[-1])\n"
        "else:\n"
        "    raise SystemExit('job survived a wedged rank + 3s watchdog')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"blackbox smoke job failed:\n{r.stderr[-2000:]}\n{r.stdout[-2000:]}")
    assert "===DIED===" in r.stdout, (
        f"wedged job did not die as expected:\n{r.stdout[-2000:]}")
    for rank in (0, 1):
        path = os.path.join(bbdir, f"rank_{rank}.json")
        assert os.path.exists(path), (
            f"no blackbox dump from rank {rank}; dir has "
            f"{sorted(os.listdir(bbdir))}")
    hvddoctor = os.path.join(REPO, "bin", "hvddoctor")
    d = subprocess.run([sys.executable, hvddoctor, bbdir],
                       capture_output=True, text=True, timeout=60)
    assert d.returncode == 0, (
        f"hvddoctor rejected the bundle:\n{d.stderr[-2000:]}")
    out = d.stdout
    assert "collective deadlock" in out, f"no deadlock diagnosis:\n{out}"
    assert "bb_probe" in out, f"diagnosis does not name the tensor:\n{out}"
    assert "[1]" in out, f"diagnosis does not name the missing rank:\n{out}"
    print("ok: blackbox smoke — both ranks dumped; hvddoctor named the "
          "deadlock, tensor 'bb_probe', missing rank [1]")


def _failover_smoke_fn():
    """3-rank elastic job with the warm standby on; rank 0 — the
    coordinator — dies abruptly mid-training. Survivors must finish all 10
    steps on the promoted standby and return a parameter digest."""
    import hashlib
    import os

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import blackbox

    hvd.init()
    state = hvd.elastic.ElasticState(w=np.array([4.0], np.float32), step=0)

    @hvd.elastic.run_fn
    def train(state):
        while state.step < 10:
            if hvd.rank() == 0 and state.step == 4:
                os._exit(29)  # no BYE, no cleanup: the coordinator is gone
            g = np.float32(hvd.rank() + 1) * (np.asarray(state.w) - 1.0)
            avg = hvd.allreduce(g, name=f"grad{state.step}",
                                op=hvd.Average)
            state.w = np.asarray(state.w) - np.float32(0.1) * \
                np.asarray(avg, np.float32)
            state.step += 1
            state.commit()
        return hashlib.sha256(
            np.asarray(state.w, np.float32).tobytes()).hexdigest()

    digest = train(state)
    # the blackbox normally only speaks on abnormal exit; force the dump
    # so hvddoctor can diagnose the failover this survivor lived through
    blackbox.dump("failover smoke postmortem", force=True)
    return digest


def check_coordinator_failover() -> None:
    """Survivable-control-plane smoke (docs/control-plane.md): SIGKILL the
    rank-0 coordinator mid-step with HOROVOD_STANDBY_COORD on. Training
    must resume on the promoted standby, the survivors' parameter digests
    must be bit-identical, and ``bin/hvddoctor`` over the blackbox bundle
    must name the coordinator failover."""
    import pickle
    import tempfile
    import time

    import cloudpickle

    from horovod_tpu.run import rendezvous

    bbdir = tempfile.mkdtemp(prefix="hvd_failover_smoke_")
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_failover_smoke_fn, (), {})))

    procs = []
    try:
        for r in range(3):
            env = dict(os.environ)
            env.update({
                "HVD_NUM_PROCS": "3",
                "HVD_PROCESS_ID": str(r),
                "HVD_KV_ADDR": addr,
                "HVD_SECRET": secret,
                "HVD_ELASTIC": "1",
                "HOROVOD_STANDBY_COORD": "1",
                # failover doesn't wait on the grace (promotion declares
                # rank 0 lost explicitly); a tight value only risks a
                # loaded host spuriously losing a live survivor
                "HOROVOD_RECONNECT_GRACE": "15",
                "HOROVOD_BLACKBOX": "1",
                "HOROVOD_BLACKBOX_DIR": bbdir,
                "JAX_PLATFORMS": "cpu",
                # the smoke fn unpickles by reference to this module
                "PYTHONPATH": os.pathsep.join(
                    [REPO, os.path.dirname(os.path.abspath(__file__))]),
            })
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))

        deadline = time.time() + 180
        blobs = {}
        while time.time() < deadline and len(blobs) < 2:
            for r in (1, 2):
                if r not in blobs:
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
            if len(blobs) < 2 and all(p.poll() is not None for p in procs):
                time.sleep(1.0)
                for r in (1, 2):
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
                break
            time.sleep(0.25)
        assert len(blobs) == 2, (
            "survivors produced no result after the coordinator kill; "
            f"got ranks {sorted(blobs)}, exit codes "
            f"{[p.poll() for p in procs]}")
        digests = {}
        for r, blob in blobs.items():
            ok, payload = pickle.loads(blob)
            assert ok, f"rank {r} raised:\n{payload}"
            digests[r] = payload
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()

    assert procs[0].wait(timeout=10) == 29, \
        "rank 0 did not die with its marker code"
    assert digests[1] == digests[2], (
        "survivors' parameters diverged across the failover: "
        f"{digests}")

    for rank in (1, 2):
        path = os.path.join(bbdir, f"rank_{rank}.json")
        assert os.path.exists(path), (
            f"no blackbox dump from survivor rank {rank}; dir has "
            f"{sorted(os.listdir(bbdir))}")
    hvddoctor = os.path.join(REPO, "bin", "hvddoctor")
    d = subprocess.run([sys.executable, hvddoctor, bbdir],
                       capture_output=True, text=True, timeout=60)
    assert d.returncode == 0, (
        f"hvddoctor rejected the bundle:\n{d.stderr[-2000:]}")
    assert "coordinator failover" in d.stdout, (
        f"hvddoctor did not diagnose the failover:\n{d.stdout[-3000:]}")
    print("ok: coordinator failover smoke — rank 0 killed mid-step, "
          "survivors resumed on the promoted standby with bit-identical "
          f"parameters (sha256 {digests[1][:12]}…); hvddoctor named the "
          "coordinator failover")


def _split_brain_smoke_fn():
    """2-rank elastic job for the split-brain drill (docs/fault-tolerance.md):
    the lease plane is on and a ``partition@net`` cut isolates rank 0 (with
    the coordinator) from rank 1 (with the standby) mid-training. Rank 0
    must self-fence before the TTL expires, rank 1's standby must take over
    by acquiring the lease, and after the heal the deposed primary's FENCED
    answer must be rejected by the promoted side's fence guard. The
    gradient is identical on every rank, so averaging over any member set
    is bit-exact and the survivor's final parameters are closed-form."""
    import os
    import time

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import blackbox
    from horovod_tpu.metrics import instruments

    hvd.init()
    rank = hvd.rank()
    state = hvd.elastic.ElasticState(w=np.array([4.0], np.float32), step=0)

    @hvd.elastic.run_fn
    def train(state):
        while state.step < 12:
            time.sleep(0.7)  # pace the run so the cut lands mid-training
            w = np.asarray(state.w, np.float32)
            g = (w - np.float32(1.0)).astype(np.float32)
            avg = hvd.allreduce(g, name=f"grad{state.step}", op=hvd.Average)
            state.w = (w - np.float32(0.1)
                       * np.asarray(avg, np.float32)).astype(np.float32)
            state.step += 1
            state.commit()
        return np.asarray(state.w, np.float32)

    try:
        w = train(state)
        fenced_seen = 0
        deadline = time.monotonic() + 25
        while time.monotonic() < deadline:
            fenced_seen = int(instruments.frames_fenced().value)
            if fenced_seen:
                break
            time.sleep(0.25)
        blackbox.dump("split-brain smoke postmortem", force=True)
        return ("done", int(state.step), w.tobytes().hex(), fenced_seen)
    except Exception as exc:  # the fenced side of the cut lands here
        if rank == 0:
            # stay alive past the heal so the fenced server can answer the
            # promoted standby's redial with its FENCED frame
            time.sleep(12.0)
        blackbox.dump("split-brain smoke postmortem", force=True)
        return ("fenced", repr(exc), int(state.step))


def check_split_brain() -> None:
    """Partition-tolerance smoke (docs/fault-tolerance.md): cut a 2-process
    lease-enabled job in half mid-training. The old coordinator must
    self-fence before the lease TTL, the standby must promote by acquiring
    the lease, the survivor must finish with the closed-form parameters,
    and the merged blackbox history must satisfy the jepsen-lite checker:
    single-writer leadership, exactly-once step application, and at least
    one fenced-frame rejection — while ``bin/hvddoctor`` stays clean of
    the split_brain signature."""
    import json
    import pickle
    import tempfile
    import time

    import cloudpickle
    import numpy as np

    from horovod_tpu.faultinject import jepsen
    from horovod_tpu.run import rendezvous

    bbdir = tempfile.mkdtemp(prefix="hvd_splitbrain_smoke_")
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_split_brain_smoke_fn, (), {})))

    procs = []
    results = {}
    try:
        for r in range(2):
            env = dict(os.environ)
            env.update({
                "HVD_NUM_PROCS": "2",
                "HVD_PROCESS_ID": str(r),
                "HVD_KV_ADDR": addr,
                "HVD_SECRET": secret,
                "HVD_ELASTIC": "1",
                "HOROVOD_STANDBY_COORD": "1",
                "HOROVOD_LEASE_TTL": "1.2",
                "HOROVOD_LEASE_RENEW": "0.25",
                "HOROVOD_RECONNECT_GRACE": "20",
                "HOROVOD_BLACKBOX": "1",
                "HOROVOD_BLACKBOX_DIR": bbdir,
                # cut ranks {0} | {1} 8s in, heal 6s later
                "HOROVOD_FAULT_SPEC": "partition@net:0|1:6:8",
                "JAX_PLATFORMS": "cpu",
                # the smoke fn unpickles by reference to this module
                "PYTHONPATH": os.pathsep.join(
                    [REPO, os.path.dirname(os.path.abspath(__file__))]),
            })
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))

        deadline = time.time() + 180
        while time.time() < deadline and len(results) < 2:
            for r in range(2):
                if r not in results:
                    blob = client.get("result", str(r))
                    if blob is not None:
                        ok, payload = pickle.loads(blob)
                        assert ok, f"rank {r} harness raised:\n{payload}"
                        results[r] = payload
            time.sleep(0.25)
        assert len(results) == 2, (
            "the partitioned job did not finish; got ranks "
            f"{sorted(results)}, exit codes {[p.poll() for p in procs]}")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()

    assert results[0][0] == "fenced", (
        f"rank 0 was cut from the KV and must self-fence: {results[0]}")
    outcome, steps, w_hex, fenced_seen = results[1]
    assert outcome == "done" and steps == 12, (
        f"the survivor did not finish all 12 steps: {results[1]}")
    assert fenced_seen > 0, (
        "no fenced-frame rejection observed on the promoted side "
        "(hvd_frames_fenced_total stayed 0)")
    # identical gradients make the survivor's parameters closed-form:
    # replay the same float32 recurrence locally
    w = np.array([4.0], np.float32)
    for _ in range(12):
        g = (w - np.float32(1.0)).astype(np.float32)
        w = (w - np.float32(0.1) * g).astype(np.float32)
    assert w_hex == w.tobytes().hex(), (
        f"survivor parameters diverged: {w_hex} != {w.tobytes().hex()}")

    bundle = {}
    for rank in (0, 1):
        path = os.path.join(bbdir, f"rank_{rank}.json")
        assert os.path.exists(path), (
            f"no blackbox dump from rank {rank}; dir has "
            f"{sorted(os.listdir(bbdir))}")
        with open(path) as f:
            bundle[rank] = json.load(f)
    verdict = jepsen.check_history(bundle)
    assert verdict["single_writer"], (
        f"leadership overlapped: {verdict['violations']}")
    assert verdict["exactly_once"], (
        f"steps were double-applied: {verdict['violations']}")
    assert verdict["fenced_frames"] > 0, (
        "the merged history records no fenced-frame rejection")

    hvddoctor = os.path.join(REPO, "bin", "hvddoctor")
    d = subprocess.run([sys.executable, hvddoctor, bbdir],
                       capture_output=True, text=True, timeout=60)
    assert d.returncode == 0, (
        f"hvddoctor rejected the bundle:\n{d.stderr[-2000:]}")
    assert "split_brain" not in d.stdout, (
        "hvddoctor diagnosed a split brain on a fenced (clean) history:\n"
        f"{d.stdout[-3000:]}")
    print("ok: split-brain smoke — partition isolated the coordinator, it "
          "self-fenced before the lease TTL, the standby promoted by "
          "acquiring the lease, the deposed primary's post-heal frame was "
          f"rejected ({fenced_seen} fenced), and the jepsen-lite checker "
          "proved single-writer leadership with exactly-once steps")


def _straggler_smoke_fn():
    """2-rank elastic job for the straggler smoke: every rank times its
    steps past a warmup window (long enough for the policy to exclude the
    injected straggler), so rank 0's timed mean reflects the adapted
    steady state. Returns (rank, mean_timed_step_s, partial_rounds)."""
    import os
    import time

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.metrics import instruments
    from horovod_tpu.run import rendezvous

    hvd.init()
    r = hvd.rank()
    warmup, timed = 8, 12
    x = np.ones((1 << 14,), np.float32) * (r + 1)
    times = []
    for step in range(warmup + timed):
        t0 = time.monotonic()
        try:
            hvd.allreduce(x, name="s%d" % step, op=hvd.Average)
        except hvd.WorkerLostError:
            # escalation variant: the victim was promoted away and this
            # round absorbed the epoch bump (elastic.run_fn's job in a
            # real training loop). The events we came for are recorded.
            if not os.environ.get("HVD_SMOKE_DUMP"):
                raise
            break
        if step >= warmup:
            times.append(time.monotonic() - t0)
    partial = float(instruments.partial_collectives().value)
    if os.environ.get("HVD_SMOKE_DUMP"):
        # escalation variant: the victim was promoted away mid-run; force
        # the dump so hvddoctor can read the exclusion/escalation events
        from horovod_tpu import blackbox

        blackbox.dump("straggler smoke postmortem", force=True)
    else:
        # rank 0 hosts the coordinator: hold it until the (possibly
        # excluded, trailing) peer drains its solo rounds, or its last
        # steps die with ShutdownError
        kv = rendezvous.KVStoreClient(os.environ["HVD_KV_ADDR"],
                                      os.environ["HVD_SECRET"])
        kv.put("sdone", str(r), b"1")
        if r == 0:
            deadline = time.time() + 60
            while time.time() < deadline and \
                    kv.get("sdone", "1") is None:
                time.sleep(0.2)
    hvd.shutdown()
    return (r, sum(times) / len(times) if times else 0.0, partial)


def _run_straggler_smoke_job(extra_env, want_ranks):
    """Launch _straggler_smoke_fn on 2 task.py processes; return
    {rank: payload} for the ranks in want_ranks (others may die —
    the escalation variant removes the victim on purpose)."""
    import pickle
    import time

    import cloudpickle

    from horovod_tpu.run import rendezvous

    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_straggler_smoke_fn, (), {})))
    procs = []
    try:
        for r in range(2):
            env = dict(os.environ)
            env.update({
                "HVD_NUM_PROCS": "2",
                "HVD_PROCESS_ID": str(r),
                "HVD_KV_ADDR": addr,
                "HVD_SECRET": secret,
                "HVD_ELASTIC": "1",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.pathsep.join(
                    [REPO, os.path.dirname(os.path.abspath(__file__))]),
            })
            env.update(extra_env)
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        deadline = time.time() + 180
        blobs = {}
        while time.time() < deadline and len(blobs) < len(want_ranks):
            for r in want_ranks:
                if r not in blobs:
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
            time.sleep(0.25)
        assert len(blobs) == len(want_ranks), (
            f"straggler smoke ranks {sorted(want_ranks)} produced no "
            f"result (got {sorted(blobs)}); exit codes "
            f"{[p.poll() for p in procs]}")
        out = {}
        for r, blob in blobs.items():
            ok, payload = pickle.loads(blob)
            assert ok, f"rank {r} raised:\n{payload}"
            out[r] = payload
        return out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()


def check_straggler_adaptive() -> None:
    """Straggler-adaptive smoke (docs/fault-tolerance.md): a 2-process run
    with rank 1 injected 300 ms slow per step must (a) keep rank 0's
    steady-state step time within 1.5x the fault-free baseline — the
    policy excluded the victim instead of waiting on it — with partial
    rounds actually counted, and (b) under a tight MAX_SKIP, escalate the
    victim to rank_lost and leave a blackbox bundle from which
    ``bin/hvddoctor`` names the chronic straggler."""
    import tempfile

    base = _run_straggler_smoke_job({}, want_ranks=(0, 1))
    chaos = _run_straggler_smoke_job({
        "HOROVOD_FAULT_SPEC": "slow@rank:300#1",
        "HOROVOD_STRAGGLER_DEADLINE": "3x",
        "HOROVOD_STRAGGLER_PATIENCE": "2",
        "HOROVOD_STRAGGLER_MAX_SKIP": "10000",
    }, want_ranks=(0, 1))
    base_step = base[0][1]
    chaos_step = chaos[0][1]
    # 1.5x the acceptance budget, plus a 50 ms absolute floor so two
    # near-zero means on a loaded CI host can't produce a spurious ratio;
    # an un-excluded victim costs >=300 ms/step, far past either bound
    assert chaos_step <= max(1.5 * base_step, base_step + 0.05), (
        f"step time did not track the healthy rank: baseline "
        f"{base_step * 1e3:.1f} ms vs chaos {chaos_step * 1e3:.1f} ms")
    assert chaos[0][2] > 0, (
        "no partial rounds counted — the straggler was never excluded")

    bbdir = tempfile.mkdtemp(prefix="hvd_straggler_smoke_")
    _run_straggler_smoke_job({
        "HOROVOD_FAULT_SPEC": "slow@rank:300#1",
        "HOROVOD_STRAGGLER_DEADLINE": "3x",
        "HOROVOD_STRAGGLER_PATIENCE": "1",
        "HOROVOD_STRAGGLER_MAX_SKIP": "2",
        "HVD_SMOKE_DUMP": "1",
        "HOROVOD_BLACKBOX": "1",
        "HOROVOD_BLACKBOX_DIR": bbdir,
    }, want_ranks=(0,))
    hvddoctor = os.path.join(REPO, "bin", "hvddoctor")
    d = subprocess.run([sys.executable, hvddoctor, bbdir],
                       capture_output=True, text=True, timeout=60)
    assert d.returncode == 0, (
        f"hvddoctor rejected the bundle:\n{d.stderr[-2000:]}")
    assert "chronic straggler" in d.stdout, (
        f"hvddoctor did not name the chronic straggler:\n"
        f"{d.stdout[-3000:]}")
    assert "rank 1" in d.stdout, (
        f"diagnosis does not name the victim rank:\n{d.stdout[-3000:]}")
    print(f"ok: straggler smoke — victim excluded (baseline "
          f"{base_step * 1e3:.1f} ms, chaos {chaos_step * 1e3:.1f} ms, "
          f"{chaos[0][2]:.0f} partial rounds); escalation variant left a "
          "bundle and hvddoctor named the chronic straggler")


def check_adaptive_wire() -> None:
    """Adaptive mixed-bitwidth wire smoke (docs/compression.md): a 2-process
    job under HOROVOD_COMPRESSION=adaptive must (a) converge the bitwidth
    selector to the same decision on both ranks, (b) drop wire bytes below
    int8's once the 4-bit grid engages, and (c) keep parameters bit-identical
    across ranks under the ConsistencyAuditor — proof the negotiated
    per-bucket grid compiled the same program everywhere."""
    code = (
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "import jax, optax\n"
        "import jax.numpy as jnp\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu import testing\n"
        "from horovod_tpu.ops import adaptive as ad\n"
        "from horovod_tpu.ops import compression as comp\n"
        "from horovod_tpu.runtime.executor import Executor\n"
        "def fn():\n"
        "    from horovod_tpu import basics\n"
        "    comp.AdaptiveCompressor.reset(); ad.reset()\n"
        "    n = 4096\n"
        "    params = {'w': jnp.zeros((n,))}\n"
        "    target = jnp.asarray(np.random.RandomState(0).randn(n)"
        ".astype(np.float32))\n"
        "    tx = hvd.DistributedOptimizer(optax.sgd(0.3),\n"
        "        compression=comp.AdaptiveCompressor, error_feedback=True)\n"
        "    opt = tx.init(params)\n"
        "    loss_fn = lambda p: jnp.sum((p['w'] - target) ** 2)\n"
        "    grad_fn = jax.jit(jax.value_and_grad(loss_fn))\n"
        "    modes, wire_bytes, first = [], [], None\n"
        "    for _ in range(2 * ad.interval() + 2):\n"
        "        loss, grads = grad_fn(params)\n"
        "        first = loss if first is None else first\n"
        "        updates, opt = tx.update(grads, opt, params)\n"
        "        params = optax.apply_updates(params, updates)\n"
        "        ex = basics._engine()._executor\n"
        "        modes.append(ex.last_wire_mode)\n"
        "        wire_bytes.append(ex.last_wire_bytes)\n"
        "    aud = hvd.ConsistencyAuditor(interval=1, policy='abort')\n"
        "    params = aud.audit(params)\n"
        "    return (modes, wire_bytes, float(first),"
        " float(loss_fn(params)), np.asarray(params['w']))\n"
        "res = testing.run_cluster(fn, np=2)\n"
        "(ma, ba, fa, la, wa), (mb, bb, fb, lb, wb) = res\n"
        "assert ma == mb, ('selector diverged across ranks', ma, mb)\n"
        "assert ma[0] == 'int8' and ma[-1] == 'int4', ma\n"
        "i8 = Executor.quantized_wire_layout(4096, 2, bits=8)['wire_bytes']\n"
        "assert min(ba) <= 0.6 * i8, (min(ba), i8)\n"
        "np.testing.assert_array_equal(wa, wb)\n"
        "assert la < fa * 0.2, (fa, la)\n"
        "print(f'modes {ma[0]}->{ma[-1]} bytes {max(ba)}->{min(ba)}"
        " loss {fa:.1f}->{la:.4f}')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"adaptive-wire smoke job failed:\n{r.stderr[-2000:]}")
    print(f"ok: adaptive-wire smoke — selector converged, bytes dropped "
          f"vs int8, parameters rank-consistent "
          f"({r.stdout.strip().splitlines()[-1]})")


def check_gspmd_quantized() -> None:
    """Quantized GSPMD-wire smoke (docs/gspmd.md): training on the 8-device
    virtual mesh with HOROVOD_GSPMD_WIRE=int8 in the ENVIRONMENT (the knob,
    not the API argument) must engage the quantized ring inside the
    compiled step, converge the loss, and put <=60% of the bf16 run's
    bytes on the wire per the hvd_wire_bytes_total instrument — the
    EQuARX-style acceptance from ROADMAP item 1."""
    code = (
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "import jax, optax\n"
        "import jax.numpy as jnp\n"
        "from jax.sharding import Mesh\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu import spmd\n"
        "from horovod_tpu.basics import MESH_AXIS\n"
        "from horovod_tpu.metrics import instruments\n"
        "from horovod_tpu.ops import compression as comp\n"
        "hvd.init()\n"
        "n = len(jax.devices())\n"
        "assert n == 8, n\n"
        "mesh = Mesh(np.asarray(jax.devices()), (MESH_AXIS,))\n"
        "d = 16384  # per-rank chunk 2048 = 8 whole blocks: no pad skew\n"
        "rng = np.random.RandomState(0)\n"
        "x = rng.randn(2 * n, d).astype(np.float32) / np.sqrt(d)\n"
        "y = x @ rng.randn(d).astype(np.float32)\n"
        "params = {'w': jnp.zeros((d,), jnp.float32)}\n"
        "loss_fn = lambda p, b: jnp.mean((b[0] @ p['w'] - b[1]) ** 2)\n"
        "tx = optax.adam(0.1)\n"
        "step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False)\n"
        "assert hasattr(step, 'jitted'), \\\n"
        "    'HOROVOD_GSPMD_WIRE=int8 did not engage the quantized step'\n"
        "p = spmd.replicate(params, mesh)\n"
        "o = spmd.quantized_opt_state(tx, params, mesh)\n"
        "data = spmd.shard_batch((jnp.asarray(x), jnp.asarray(y)), mesh)\n"
        "c = instruments.wire_bytes().labels(compression='gspmd-int8')\n"
        "b0, steps, losses = c.value, 40, []\n"
        "for _ in range(steps):\n"
        "    p, o, loss = step(p, o, data)\n"
        "    losses.append(float(loss))\n"
        "assert np.isfinite(losses).all(), losses\n"
        "assert losses[-1] < 0.2 * losses[0], losses\n"
        "wire = (c.value - b0) / steps\n"
        "bf16 = comp.gspmd_wire_footprint(d, 'bf16', n)\n"
        "assert wire > 0, 'quantized ring put no bytes on the instrument'\n"
        "assert wire <= 0.6 * bf16, (wire, bf16)\n"
        "print(f'loss {losses[0]:.3f}->{losses[-1]:.4f}; wire "
        "{int(wire)} B/step <= 60% of bf16 {int(bf16)} B')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               HOROVOD_GSPMD_WIRE="int8",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"quantized GSPMD smoke job failed:\n{r.stderr[-2000:]}")
    print(f"ok: quantized GSPMD smoke — env knob engaged the int8 ring, "
          f"converged, bytes under the bf16 bar "
          f"({r.stdout.strip().splitlines()[-1]})")


def check_algo_hierarchical() -> None:
    """Hierarchical collective smoke (docs/gspmd.md algorithm zoo): on a
    simulated 2-host x 4-chip factorization (HOROVOD_MESH_HOSTS=2 over the
    8-device virtual mesh) the two-level schedule must agree with the flat
    ring — bit-identical across ranks, within float tolerance of the
    ring's result (the schedules reduce in different orders, so last-ulp
    equality is the per-rank invariant, not the cross-algorithm one) —
    while crossing host boundaries with strictly fewer bytes per the
    gspmd_cross_host_footprint catalog."""
    code = (
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "import jax\n"
        "from jax.sharding import PartitionSpec as P\n"
        "from horovod_tpu import spmd\n"
        "from horovod_tpu.basics import Average, MESH_AXIS\n"
        "from horovod_tpu.ops import compression as comp\n"
        "n = len(jax.devices())\n"
        "assert n == 8, n\n"
        "assert spmd.mesh_hosts(n) == 2  # the env factorization: 2x4\n"
        "mesh = jax.make_mesh((n,), (MESH_AXIS,))\n"
        "d = 16384\n"
        "rng = np.random.RandomState(0)\n"
        "data = rng.randn(n, d).astype(np.float32)\n"
        "def run(fn, wire):\n"
        "    body = lambda r: fn(r[0], Average, MESH_AXIS, wire)[None]\n"
        "    sm = spmd._shard_map(body, mesh, in_specs=P(MESH_AXIS),\n"
        "                         out_specs=P(MESH_AXIS))\n"
        "    return np.asarray(jax.jit(sm)(data))\n"
        "for wire, tol in (('off', 1e-5), ('int8', 0.05)):\n"
        "    ring = run(spmd.quantized_allreduce, wire)\n"
        "    hier = run(spmd.quantized_allreduce_hier, wire)\n"
        "    for p in range(1, n):  # replicated params rest on this\n"
        "        assert (hier[p] == hier[0]).all(), (wire, p)\n"
        "    assert np.abs(hier[0] - ring[0]).max() < tol, wire\n"
        "block = comp.block_size()\n"
        "xring = comp.gspmd_cross_host_footprint(d, 'int8', n, 2, block,\n"
        "                                        'ring')\n"
        "xhier = comp.gspmd_cross_host_footprint(d, 'int8', n, 2, block,\n"
        "                                        'hier')\n"
        "assert 0 < xhier < xring, (xhier, xring)\n"
        "print(f'hier == ring on 2x4, cross-host {xhier} B < ring "
        "{xring} B ({100.0 * xhier / xring:.0f}%)')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               HOROVOD_MESH_HOSTS="2",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"hierarchical-algorithm smoke job failed:\n{r.stderr[-2000:]}")
    print(f"ok: hierarchical collective smoke — 2x4 factorization matched "
          f"the flat ring with fewer cross-host bytes "
          f"({r.stdout.strip().splitlines()[-1]})")


def check_moe_quantized() -> None:
    """Quantized MoE dispatch smoke (docs/moe.md): capacity-factor Switch
    dispatch on a dp=2 x ep=4 virtual mesh with HOROVOD_MOE_WIRE=int8 in
    the ENVIRONMENT (the knob, not the API argument) must route the
    token exchange through the quantized all_to_all, converge the loss,
    keep the per-step dispatch bytes <=60% of a bf16 exchange per the
    hvd_wire_bytes_total{compression="moe-int8"} instrument, and keep
    the drop rate bounded at the stock CF=1.25."""
    code = (
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "import jax, optax\n"
        "import jax.numpy as jnp\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu.metrics import instruments\n"
        "from horovod_tpu.ops import compression as comp\n"
        "from horovod_tpu.parallel import expert as epar\n"
        "hvd.init()\n"
        "assert len(jax.devices()) == 8\n"
        "E, D, N, CF = 8, 64, 1024, 1.25\n"
        "mesh = epar.make_dp_ep_mesh(2, 4)\n"
        "params = epar.init_moe_params(jax.random.PRNGKey(0), D, E,"
        " hidden_mult=2)\n"
        "rng = np.random.RandomState(0)\n"
        "xb = jnp.asarray(rng.randn(N, D).astype(np.float32))\n"
        "yb = xb @ jnp.asarray(0.1 * rng.randn(D, D).astype(np.float32))\n"
        "def loss_fn(p, batch, moe):\n"
        "    x, y = batch\n"
        "    out, aux = moe(p, x)\n"
        "    return jnp.mean((out - y) ** 2) + 0.01 * aux\n"
        "tx = optax.adam(1e-2)\n"
        "step = epar.make_ep_train_step(loss_fn, tx, mesh,"
        " dispatch='capacity', capacity_factor=CF)\n"
        "assert hasattr(step, 'jitted'), 'capacity step not instrumented'\n"
        "p = epar.shard_params_ep(params, mesh)\n"
        "opt = epar.moe_opt_state(tx, params, mesh, N, CF)\n"
        "sh = NamedSharding(mesh, P(('dp', 'ep')))\n"
        "batch = (jax.device_put(xb, sh), jax.device_put(yb, sh))\n"
        "c = instruments.wire_bytes().labels(compression='moe-int8')\n"
        "b0, steps, losses = c.value, 30, []\n"
        "for _ in range(steps):\n"
        "    p, opt, loss, stats = step(p, opt, batch)\n"
        "    losses.append(float(loss))\n"
        "assert np.isfinite(losses).all(), losses\n"
        "assert losses[-1] < 0.5 * losses[0], losses\n"
        "wire = (c.value - b0) / steps\n"
        "cap = epar.expert_capacity(N // 8, E, CF)\n"
        "per_peer = E * cap * D // 4\n"
        "bf16 = comp.moe_wire_footprint(per_peer, 'bf16', 4)\n"
        "assert wire > 0, 'HOROVOD_MOE_WIRE=int8 put no dispatch bytes "
        "on the instrument'\n"
        "assert wire <= 0.6 * bf16, (wire, bf16)\n"
        "drop_rate = float(stats['dropped']) / N\n"
        "assert 0 <= drop_rate < 0.5, drop_rate\n"
        "assert float(stats['capacity']) == cap\n"
        "assert float(instruments.moe_capacity_factor().value) == CF\n"
        "print(f'loss {losses[0]:.3f}->{losses[-1]:.4f}; dispatch "
        "{int(wire)} B/step <= 60% of bf16 {int(bf16)} B; drop rate "
        "{drop_rate:.3f} at CF={CF}')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               HOROVOD_MOE_WIRE="int8",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"quantized MoE smoke job failed:\n{r.stderr[-2000:]}")
    print(f"ok: quantized MoE smoke — env knob engaged the int8 dispatch, "
          f"converged, bytes under the bf16 bar, drops bounded "
          f"({r.stdout.strip().splitlines()[-1]})")


def check_serving_kill() -> None:
    """Elastic serving smoke (docs/inference.md): a frontend + 2 worker
    replicas under sustained load must survive a SIGKILL of one replica —
    the dead worker's in-flight requests re-admit onto the survivor, ZERO
    requests are lost, and the frontend's /metrics endpoint keeps serving
    the hvd_serving_* catalog (including the readmitted counter) after
    the kill."""
    code = (
        "import json, os, signal, subprocess, sys, time, urllib.request\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "os.environ['HOROVOD_METRICS_PORT'] = '0'\n"
        "import numpy as np\n"
        "from horovod_tpu.metrics import server_port\n"
        "from horovod_tpu.serving import ServingClient, ServingFrontend\n"
        "fe = ServingFrontend().start()\n"
        "host, port = fe.addr\n"
        "env = dict(os.environ, JAX_PLATFORMS='cpu')\n"
        "procs = [subprocess.Popen(\n"
        "    [sys.executable, '-m', 'horovod_tpu.serving.worker',\n"
        "     '--addr', f'{host}:{port}', '--rank', str(i + 1),\n"
        "     '--max-batch', '4'],\n"
        f"    env=env, cwd={REPO!r}) for i in range(2)]\n"
        "try:\n"
        "    fe.wait_for_workers(2, timeout=120)\n"
        "    cli = ServingClient(host, port, name='smoke')\n"
        "    # warm both replicas' compile caches before the timed window\n"
        "    for f in [cli.submit([1, 2, 3], 2) for _ in range(8)]:\n"
        "        f.result(timeout=120)\n"
        "    rng = np.random.RandomState(0)\n"
        "    futs = []\n"
        "    for i in range(18):\n"
        "        futs.append(cli.submit(\n"
        "            rng.randint(1, 251, size=6).tolist(), 6))\n"
        "        if i == 6:\n"
        "            procs[0].kill()  # SIGKILL a replica mid-flight\n"
        "        time.sleep(0.02)\n"
        "    lost = 0\n"
        "    for f in futs:\n"
        "        try:\n"
        "            f.result(timeout=120)\n"
        "        except Exception as exc:\n"
        "            print(f'LOST {f.id}: {exc}', file=sys.stderr)\n"
        "            lost += 1\n"
        "    stats = fe.stats()\n"
        "    assert lost == 0, f'{lost} request(s) lost after worker kill'\n"
        "    assert stats['readmitted'] >= 1, stats\n"
        "    assert stats['completed'] >= 18, stats\n"
        "    assert len(stats['workers']) == 1, stats\n"
        "    mport = server_port()\n"
        "    assert mport, 'frontend metrics endpoint did not start'\n"
        "    body = urllib.request.urlopen(\n"
        "        f'http://127.0.0.1:{mport}/metrics', timeout=10)"
        ".read().decode()\n"
        "    print(json.dumps(stats), file=sys.stderr)\n"
        "    sys.stdout.write(body)\n"
        "finally:\n"
        "    for pr in procs:\n"
        "        if pr.poll() is None:\n"
        "            pr.terminate()\n"
        "    for pr in procs:\n"
        "        try:\n"
        "            pr.wait(timeout=10)\n"
        "        except subprocess.TimeoutExpired:\n"
        "            pr.kill()\n"
        "    fe.stop()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (
        f"serving worker-kill smoke failed:\n{r.stderr[-3000:]}")
    from horovod_tpu.metrics import parse_prometheus

    samples = parse_prometheus(r.stdout)
    for want in ("hvd_serving_requests_total",
                 "hvd_serving_request_latency_seconds_count"):
        assert any(k.startswith(want) for k in samples), (
            f"/metrics output missing {want} after the kill:\n"
            f"{sorted(samples)[:40]}")
    print("ok: serving smoke — SIGKILLed a replica under load, in-flight "
          "requests re-admitted onto the survivor, zero lost, /metrics "
          "still serving the hvd_serving_* catalog")


def check_serving_frontend_kill() -> None:
    """Survivable-serving smoke (docs/inference.md failure matrix): run
    the kill-frontend chaos drill — SIGKILL the active frontend under
    Poisson load with a warm standby attached — and then point
    ``bin/hvddoctor`` at the blackbox bundle: the doctor must NAME the
    failover via the ``serving_failover`` signature (promotion recorded,
    not misdiagnosed as a coordinator event), and must not raise
    ``split_brain`` on the fenced handover."""
    import shutil
    import tempfile

    bbdir = tempfile.mkdtemp(prefix="hvd_serving_fkill_smoke_")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               HOROVOD_BLACKBOX_DIR=bbdir)
    try:
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "benchmarks", "serving_bench.py"),
             "--chaos", "kill-frontend", "--requests", "24",
             "--qps", "12", "--max-new", "4"],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=600)
        assert r.returncode == 0, (
            f"kill-frontend drill failed (rc={r.returncode}):\n"
            f"{r.stderr[-3000:]}")
        assert "exactly_once\": true" in r.stderr.replace("'", '"'), (
            f"drill output missing a clean jepsen verdict:\n"
            f"{r.stderr[-2000:]}")

        hvddoctor = os.path.join(REPO, "bin", "hvddoctor")
        d = subprocess.run([sys.executable, hvddoctor, bbdir],
                           capture_output=True, text=True, timeout=60)
        assert d.returncode == 0, (
            f"hvddoctor rejected the bundle:\n{d.stderr[-2000:]}")
        assert "serving frontend failover" in d.stdout, (
            "hvddoctor did not name the frontend failover "
            f"(serving_failover signature):\n{d.stdout[:3000]}")
        assert "split_brain" not in d.stdout, (
            "hvddoctor misdiagnosed the fenced serving handover as a "
            f"split brain:\n{d.stdout[-3000:]}")
    finally:
        shutil.rmtree(bbdir, ignore_errors=True)
    print("ok: serving frontend-kill smoke — SIGKILLed the frontend "
          "under load, standby promoted behind the lease, jepsen verdict "
          "clean, and hvddoctor named the serving_failover")


def _ckpt_smoke_fn():
    """2-rank elastic job with async sharded checkpointing on; the
    HVD_CKPT_VICTIM process hard-kills itself at step 5 and its same-rank
    replacement must restore its rank-local shard from the buddy journal
    (O(shard), no disk) and finish the bit-identical trajectory."""
    import hashlib
    import os
    import time

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import blackbox, ckpt

    hvd.init()
    state = hvd.elastic.ElasticState(
        w=np.array([4.0], np.float32),
        opt_shard=np.array([hvd.rank() + 1.0], np.float32),
        step=0)
    state.mark_sharded("opt_shard")
    target = np.float32(1.0)

    @hvd.elastic.run_fn
    def train(state):
        ctrl = hvd.basics._engine().controller
        while state.step < 12:
            if (os.environ.get("HVD_CKPT_VICTIM") == "1"
                    and state.step == 5):
                os._exit(17)  # hard kill AFTER committing step 5
            if hvd.rank() == 0 and len(ctrl.members()) < 2:
                # hold at the commit boundary until the replacement is
                # admitted: every step must run with both members or the
                # restored shard misses updates
                time.sleep(0.1)
                state.commit()
                continue
            g = np.float32(2.0) * (np.asarray(state.w, np.float32)
                                   - target)
            avg = hvd.allreduce(g, name=f"grad{state.step}",
                                op=hvd.Average)
            state.w = (np.asarray(state.w, np.float32)
                       - np.float32(0.1) * np.asarray(avg, np.float32))
            state.opt_shard = (np.float32(0.5)
                               * np.asarray(state.opt_shard, np.float32)
                               + np.asarray(avg, np.float32))
            state.step += 1
            state.commit()
        return hashlib.sha256(
            np.asarray(state.w, np.float32).tobytes()).hexdigest()

    digest = train(state)
    mgr = ckpt.active()
    blackbox.dump("checkpoint smoke postmortem", force=True)
    return {"digest": digest,
            "restore": mgr.last_restore if mgr is not None else None,
            "shard": float(np.asarray(state.opt_shard)[0])}


def check_ckpt_kill_restore() -> None:
    """Restart-as-a-product smoke (docs/checkpoint.md): SIGKILL a worker
    mid-training with HOROVOD_CKPT_DIR on, then launch a same-rank
    replacement. The replacement must restore its shard from the buddy
    journal (source == "peer" at the victim's last commit), both
    survivors must finish with bit-identical parameters, and the blackbox
    must carry the K_CKPT snapshot/finalize/peer_restore trail."""
    import json
    import pickle
    import tempfile
    import time

    import cloudpickle

    from horovod_tpu.run import rendezvous

    ckptdir = tempfile.mkdtemp(prefix="hvd_ckpt_smoke_")
    bbdir = tempfile.mkdtemp(prefix="hvd_ckpt_smoke_bb_")
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_ckpt_smoke_fn, (), {})))

    def spawn(rank, victim):
        env = dict(os.environ)
        env.update({
            "HVD_NUM_PROCS": "2",
            "HVD_PROCESS_ID": str(rank),
            "HVD_KV_ADDR": addr,
            "HVD_SECRET": secret,
            "HVD_ELASTIC": "1",
            "HOROVOD_RECONNECT_GRACE": "2",
            "HOROVOD_CKPT_DIR": ckptdir,
            "HOROVOD_CKPT_INTERVAL": "1",
            "HVD_CKPT_VICTIM": "1" if victim else "0",
            "HOROVOD_BLACKBOX": "1",
            "HOROVOD_BLACKBOX_DIR": bbdir,
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.pathsep.join(
                [REPO, os.path.dirname(os.path.abspath(__file__))]),
        })
        env.pop("XLA_FLAGS", None)
        return subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    procs = [spawn(0, False), spawn(1, True)]
    replacement = None
    try:
        deadline = time.time() + 120
        while procs[1].poll() is None and time.time() < deadline:
            time.sleep(0.25)
        assert procs[1].poll() == 17, (
            f"victim did not die with its marker code: {procs[1].poll()}")
        # let the reconnect grace lapse so the coordinator declares the
        # rank lost before the replacement shows up as a joiner
        time.sleep(3.0)
        replacement = spawn(1, False)

        blobs = {}
        deadline = time.time() + 150
        while time.time() < deadline and len(blobs) < 2:
            for r in (0, 1):
                if r not in blobs:
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
            time.sleep(0.25)
        assert len(blobs) == 2, (
            f"job did not finish after the kill; got ranks "
            f"{sorted(blobs)}, exit codes "
            f"{[p.poll() for p in procs + [replacement]]}")
        results = {}
        for r, blob in blobs.items():
            ok, payload = pickle.loads(blob)
            assert ok, f"rank {r} raised:\n{payload}"
            results[r] = payload
    finally:
        for p in procs + ([replacement] if replacement else []):
            if p.poll() is None:
                p.kill()
        kv.stop()

    restore = results[1]["restore"]
    assert restore is not None, "replacement never restored its shard"
    assert restore["source"] == "peer", (
        f"shard came from {restore} — the O(shard) buddy path was "
        "bypassed")
    assert restore["step"] == 5, restore
    assert results[0]["digest"] == results[1]["digest"], (
        f"parameters diverged across the kill-and-restore: {results}")

    # the K_CKPT trail: rank 0 snapshotted and finalized bundles; the
    # replacement's dump carries the peer_restore record
    names = {0: set(), 1: set()}
    for rank in (0, 1):
        path = os.path.join(bbdir, f"rank_{rank}.json")
        assert os.path.exists(path), (
            f"no blackbox dump from rank {rank}; dir has "
            f"{sorted(os.listdir(bbdir))}")
        doc = json.load(open(path))
        names[rank] = {e.get("name") for e in doc.get("events", [])
                       if e.get("kind") == "checkpoint"}
    assert "snapshot" in names[0], names
    assert "finalize" in names[0], names
    assert "peer_restore" in names[1], names
    print("ok: checkpoint kill-and-restore smoke — worker killed at step "
          "5, same-rank replacement restored its shard from the buddy "
          f"journal (step {restore['step']}, {restore['nbytes']} bytes) "
          "and finished bit-identical "
          f"(sha256 {results[0]['digest'][:12]}…)")


def _goodput_chaos_fn():
    """2-rank elastic job with the goodput ledger, a deliberately
    unmeetable SLO and the anomaly watch on; the victim hard-kills itself
    at step 5 and the survivor must come out the other side with nonzero
    recovery badput, a burning SLO gauge, and an hvdtop snapshot."""
    import os
    import subprocess
    import sys
    import time

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import blackbox

    hvd.init()
    state = hvd.elastic.ElasticState(w=np.array([4.0], np.float32), step=0)

    @hvd.elastic.run_fn
    def train(state):
        ctrl = hvd.basics._engine().controller
        while state.step < 12:
            if (os.environ.get("HVD_GOODPUT_VICTIM") == "1"
                    and state.step == 5):
                os._exit(17)  # hard kill AFTER committing step 5
            if hvd.rank() == 0 and len(ctrl.members()) < 2:
                # hold at the commit boundary until the replacement is
                # admitted — this wait is exactly the wall time the
                # ledger must attribute, not lose
                time.sleep(0.1)
                state.commit()
                continue
            g = np.float32(2.0) * (np.asarray(state.w, np.float32) - 1.0)
            avg = hvd.allreduce(g, name=f"grad{state.step}",
                                op=hvd.Average)
            state.w = (np.asarray(state.w, np.float32)
                       - np.float32(0.05) * np.asarray(avg, np.float32))
            state.step += 1
            state.commit()
        return float(np.asarray(state.w)[0])

    train(state)
    # let the watch take a few more SLO samples over the settled counters
    time.sleep(1.5)
    doc = hvd.metrics()
    hvdtop = {"rc": None, "out": ""}
    if hvd.rank() == 0:
        from horovod_tpu.metrics import server_port
        port = server_port()
        if port:
            repo = os.path.dirname(os.path.dirname(
                os.path.abspath(hvd.__file__)))
            r = subprocess.run(
                [sys.executable, os.path.join(repo, "bin", "hvdtop"),
                 "--once", "--url", f"http://127.0.0.1:{port}"],
                capture_output=True, text=True, timeout=30)
            hvdtop = {"rc": r.returncode, "out": r.stdout}
    blackbox.dump("goodput chaos postmortem", force=True)

    bad = {}
    for s in (doc.get("hvd_badput_seconds_total") or {}).get("series") or []:
        c = (s.get("labels") or {}).get("cause", "?")
        bad[c] = bad.get(c, 0.0) + float(s.get("value", 0.0))
    burn = 0.0
    for s in (doc.get("hvd_slo_burn_rate") or {}).get("series") or []:
        burn = max(burn, float(s.get("value", 0.0)))
    return {"badput": bad, "burn": burn, "hvdtop": hvdtop}


def check_goodput_chaos() -> None:
    """Goodput chaos smoke (docs/goodput.md): kill a worker mid-training
    in a 2-rank elastic job running under an unmeetable HOROVOD_SLO with
    the anomaly watch on. After the same-rank replacement finishes the
    job, the survivor's ledger must show nonzero
    ``hvd_badput_seconds_total{cause="recovery"}``, the SLO burn gauge
    must be past the fire threshold, ``bin/hvdtop --once`` must render a
    parseable snapshot off the live endpoint, and ``bin/hvddoctor`` on
    the blackbox bundle must name the exhausted budget and the dominant
    badput cause."""
    import json
    import pickle
    import tempfile
    import time

    import cloudpickle

    from horovod_tpu.run import rendezvous

    bbdir = tempfile.mkdtemp(prefix="hvd_goodput_smoke_bb_")
    ckptdir = tempfile.mkdtemp(prefix="hvd_goodput_smoke_ckpt_")
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_goodput_chaos_fn, (), {})))

    def spawn(rank, victim):
        env = dict(os.environ)
        env.update({
            "HVD_NUM_PROCS": "2",
            "HVD_PROCESS_ID": str(rank),
            "HVD_KV_ADDR": addr,
            "HVD_SECRET": secret,
            "HVD_ELASTIC": "1",
            "HOROVOD_RECONNECT_GRACE": "2",
            "HOROVOD_CKPT_DIR": ckptdir,
            "HOROVOD_CKPT_INTERVAL": "1",
            "HVD_GOODPUT_VICTIM": "1" if victim else "0",
            # the smoke's SLO is unmeetable by construction (this tiny
            # job is ~all communication), so the burn gauge must be hot
            # at dump time and the doctor must have something to name
            "HOROVOD_SLO": "goodput>=0.99",
            "HOROVOD_ANOMALY_WATCH": "1",
            "HOROVOD_ANOMALY_INTERVAL": "0.5",
            "HOROVOD_METRICS_INTERVAL": "0.5",
            "HOROVOD_METRICS_PORT": "0" if rank == 0 else "",
            "HOROVOD_BLACKBOX": "1",
            "HOROVOD_BLACKBOX_DIR": bbdir,
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.pathsep.join(
                [REPO, os.path.dirname(os.path.abspath(__file__))]),
        })
        env.pop("XLA_FLAGS", None)
        if not env["HOROVOD_METRICS_PORT"]:
            env.pop("HOROVOD_METRICS_PORT")
        return subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    procs = [spawn(0, False), spawn(1, True)]
    replacement = None
    try:
        deadline = time.time() + 120
        while procs[1].poll() is None and time.time() < deadline:
            time.sleep(0.25)
        assert procs[1].poll() == 17, (
            f"victim did not die with its marker code: {procs[1].poll()}")
        time.sleep(3.0)  # let the reconnect grace declare the rank lost
        replacement = spawn(1, False)

        blobs = {}
        deadline = time.time() + 150
        while time.time() < deadline and len(blobs) < 2:
            for r in (0, 1):
                if r not in blobs:
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
            time.sleep(0.25)
        assert len(blobs) == 2, (
            f"job did not finish after the kill; got ranks "
            f"{sorted(blobs)}, exit codes "
            f"{[p.poll() for p in procs + [replacement]]}")
        results = {}
        for r, blob in blobs.items():
            ok, payload = pickle.loads(blob)
            assert ok, f"rank {r} raised:\n{payload}"
            results[r] = payload
    finally:
        for p in procs + ([replacement] if replacement else []):
            if p.poll() is None:
                p.kill()
        kv.stop()

    # every second the kill cost must be on the books as recovery badput
    bad = results[0]["badput"]
    assert bad.get("recovery", 0.0) > 0.0, (
        f"no recovery badput attributed after the kill: {bad}")
    assert results[0]["burn"] >= 2.0, (
        f"SLO burn gauge never crossed the fire threshold: {results[0]}")

    top = results[0]["hvdtop"]
    assert top["rc"] == 0, f"hvdtop --once failed: {top}"
    assert top["out"].startswith("hvdtop — up="), top["out"][:200]
    assert "fleet goodput" in top["out"], top["out"][:400]
    assert "recovery" in top["out"], (
        f"hvdtop badput stack is missing the recovery cause:\n"
        f"{top['out'][:600]}")

    # hvddoctor on the bundle: the budget_exhausted detector must name
    # the exhausted SLO and the dominant badput cause with its ranks
    for rank in (0, 1):
        path = os.path.join(bbdir, f"rank_{rank}.json")
        assert os.path.exists(path), (
            f"no blackbox dump from rank {rank}; dir has "
            f"{sorted(os.listdir(bbdir))}")
    doc = json.load(open(os.path.join(bbdir, "rank_0.json")))
    assert doc.get("metrics"), "rank 0 dump carries no metrics snapshot"
    hvddoctor = os.path.join(REPO, "bin", "hvddoctor")
    d = subprocess.run([sys.executable, hvddoctor, bbdir],
                       capture_output=True, text=True, timeout=60)
    assert d.returncode == 0, (
        f"hvddoctor rejected the bundle:\n{d.stderr[-2000:]}")
    out = d.stdout
    assert "error budget burning" in out, (
        f"doctor did not flag the exhausted budget:\n{out}")
    assert "dominated by" in out, (
        f"doctor did not name the dominant badput cause:\n{out}")
    print("ok: goodput chaos smoke — worker killed at step 5; survivor "
          f"attributed {bad.get('recovery', 0.0):.2f}s of recovery "
          f"badput, SLO burn {results[0]['burn']:.0f}x fired, hvdtop "
          "--once rendered the live snapshot, and hvddoctor named the "
          "dominant badput cause")


def check_tier_rehome() -> None:
    """N-tier control-plane smoke (docs/control-plane.md): a 2-tier tree
    on simulated hosts — 4 fake ranks behind two host-tier
    sub-coordinators behind one mid-tier aggregator — loses the mid-tier
    aggregator under load. Its TierStandby must promote a stateless
    replacement under ``addr.{gen}.t2.0.f1``, the orphaned host-tier
    children must re-home there and re-ship their in-flight ledgers, and
    every rank's per-round response digest must stay identical across the
    failover (replay shards make the re-ship idempotent)."""
    import hashlib
    import socket as _socket
    import threading
    import time

    from horovod_tpu.run import rendezvous
    from horovod_tpu.runtime import wire
    from horovod_tpu.runtime.coordinator import (MSG_HELLO, MSG_LIST,
                                                 MSG_RESP, CoordState,
                                                 CoordinatorServer,
                                                 _publish_key)
    from horovod_tpu.runtime.hierarchy import SubCoordinator, TierStandby

    gen, world, rounds, kill_at = 555, 4, 6, 2
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    saved = {k: os.environ.get(k) for k in ("HVD_KV_ADDR", "HVD_SECRET")}
    os.environ["HVD_KV_ADDR"] = f"127.0.0.1:{kv.port}"
    os.environ["HVD_SECRET"] = secret
    state = CoordState(world, 0, cache_capacity=1024,
                       stall_warning_s=60.0, stall_shutdown_s=0.0)
    server = CoordinatorServer(state, secret)
    mid = standby = None
    hosts = []
    digests = [[None] * rounds for _ in range(world)]
    errors = []
    try:
        mid = SubCoordinator("127.0.0.1", server.port, secret,
                             leader_rank=0, tier=2, index=0, tiers=2)
        _publish_key(f"addr.{gen}.t2.0", f"127.0.0.1:{mid.port}", secret)
        standby = TierStandby(
            gen, 2, 0, secret,
            make_aggregator=lambda: SubCoordinator(
                "127.0.0.1", server.port, secret, leader_rank=0,
                tier=2, index=0, tiers=2),
            probe_interval=0.1, misses=2).start()
        for g in (0, 1):
            hosts.append(SubCoordinator(
                "127.0.0.1", mid.port, secret, leader_rank=2 * g,
                tier=1, index=g, tiers=2,
                up_fail_base=f"addr.{gen}.t2.0"))
        barrier = threading.Barrier(world)

        def worker(rank):
            try:
                sock = _socket.create_connection(
                    ("127.0.0.1", hosts[rank // 2].port), timeout=10)
                sock.settimeout(0.5)
                wire.send_frame(sock, secret, MSG_HELLO, 0, rank)
                stop = threading.Event()
                for i in range(rounds):
                    barrier.wait(timeout=60)
                    if rank == 0 and i == kill_at:
                        mid.stop()  # kill the mid-tier aggregator mid-run
                    m = wire.ReqMeta(f"g{i}", 0, "float32", (4,))
                    wire.send_frame(sock, secret, MSG_LIST, i, rank,
                                    wire.encode_request_list(
                                        0, [], [m], epoch=-1))
                    deadline = time.monotonic() + 60
                    while True:
                        if time.monotonic() > deadline:
                            raise TimeoutError(
                                f"rank {rank} round {i} timed out")
                        try:
                            mt, seq, _, data = wire.recv_frame(
                                sock, secret, stop)
                        except _socket.timeout:
                            continue
                        if mt == MSG_RESP and seq == i:
                            break
                    digests[rank][i] = hashlib.sha256(data).hexdigest()
                sock.close()
            except Exception as exc:  # surfaced below, thread-safe enough
                errors.append((rank, exc))

        ts = [threading.Thread(target=worker, args=(r,))
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        assert all(not t.is_alive() for t in ts), "tier smoke deadlocked"
        assert not errors, f"worker failures: {errors}"
        assert standby.promoted, (
            "tier standby never promoted a replacement aggregator")
        for i in range(rounds):
            row = {digests[r][i] for r in range(world)}
            assert len(row) == 1, (
                f"round {i} diverged across ranks: {row}")
    finally:
        for h in hosts:
            h.stop()
        if standby is not None:
            standby.stop()
        if mid is not None:
            mid.stop()
        server.stop()
        kv.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    print(f"ok: tier smoke — killed the mid-tier aggregator at round "
          f"{kill_at}, children re-homed to the promoted standby, all "
          f"{rounds} rounds bit-identical across {world} ranks")


def main():
    if len(sys.argv) > 1:
        # run only the named checks: `python ci/pod_smoke.py check_split_brain`
        # lets a CI stage (or a human) re-run one smoke without the full
        # pod-day sweep
        for name in sys.argv[1:]:
            fn = globals().get(name)
            assert name.startswith("check_") and callable(fn), (
                f"unknown smoke check {name!r}; available: "
                + ", ".join(sorted(n for n in globals()
                                   if n.startswith("check_"))))
            fn()
        return
    cmds = pod_day_commands() + elastic_commands()
    for cmd in cmds:
        check_command(cmd)
        print(f"ok: {cmd}")
    check_metrics_endpoint()
    check_chaos_reconnect()
    check_nan_skip()
    check_trace_capture()
    check_bucket_overlap()
    check_blackbox_doctor()
    check_coordinator_failover()
    check_split_brain()
    check_tier_rehome()
    check_straggler_adaptive()
    check_adaptive_wire()
    check_gspmd_quantized()
    check_algo_hierarchical()
    check_moe_quantized()
    check_serving_kill()
    check_serving_frontend_kill()
    check_ckpt_kill_restore()
    check_goodput_chaos()
    print(f"pod-day smoke: {len(cmds)} command lines + /metrics endpoint "
          "+ chaos reconnect + nan skip-step + trace capture "
          "+ bucket overlap + blackbox doctor + coordinator failover "
          "+ split-brain partition drill "
          "+ tier aggregator re-home + straggler adaptive + adaptive wire "
          "+ quantized GSPMD wire + hierarchical collective "
          "+ quantized MoE dispatch + serving worker-kill "
          "+ serving frontend-kill failover "
          "+ checkpoint kill-and-restore + goodput chaos valid")


if __name__ == "__main__":
    main()
