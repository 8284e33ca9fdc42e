"""Spark integration tests (parity: `test/test_spark.py:83-137` — happy run,
failure propagation, timeout; plus the rank-env allocation math)."""

import sys
import time

import pytest

from tests import fake_pyspark

import horovod_tpu.spark as hvd_spark  # noqa: E402
from horovod_tpu.spark.task import rank_env_from_hosts  # noqa: E402


@pytest.fixture(autouse=True)
def _fake_pyspark_installed():
    """Pin the FAKE pyspark for this module's duration only — a real
    installed pyspark (Docker CI image) must stay importable for
    tests/test_spark_real.py, and the fake must win here even then."""
    prev = sys.modules.get("pyspark")
    sys.modules["pyspark"] = fake_pyspark
    try:
        yield
    finally:
        if prev is None:
            sys.modules.pop("pyspark", None)
        else:
            sys.modules["pyspark"] = prev


def _env_probe():
    import os

    return {k: os.environ[k] for k in sorted(os.environ)
            if k.startswith("HVD_")}


def test_spark_run_happy():
    def fn(x):
        import os

        return int(os.environ["HVD_PROCESS_ID"]) * 10 + x

    res = hvd_spark.run(fn, args=(7,), num_proc=4)
    assert res == [7, 17, 27, 37]  # rank order


def test_spark_env_injection():
    res = hvd_spark.run(_env_probe, num_proc=3)
    for rank, env in enumerate(res):
        assert env["HVD_PROCESS_ID"] == str(rank)
        assert env["HVD_NUM_PROCS"] == "3"
        # threads share a hostname -> single-host split
        assert env["HVD_LOCAL_SIZE"] == "3"
        assert env["HVD_CROSS_SIZE"] == "1"
        assert env["HVD_COORDINATOR_ADDR"] == res[0]["HVD_COORDINATOR_ADDR"]
        assert ":" in env["HVD_COORDINATOR_ADDR"]


def test_spark_run_failure_propagates():
    def fn():
        import os

        if os.environ["HVD_PROCESS_ID"] == "1":
            raise ValueError("boom on rank 1")
        return True

    with pytest.raises(RuntimeError, match="rank 1"):
        hvd_spark.run(fn, num_proc=2)


def test_spark_run_startup_timeout():
    """start_timeout fires when the cluster never schedules the tasks."""
    fake_pyspark.HOLD_SCHEDULING = True
    try:
        with pytest.raises(TimeoutError, match="running after"):
            hvd_spark.run(lambda: True, num_proc=2, start_timeout=0.5)
    finally:
        fake_pyspark.HOLD_SCHEDULING = False


def test_spark_run_longer_than_start_timeout_succeeds():
    """start_timeout bounds startup only — a slow job must NOT be killed
    (regression: total-runtime cap masquerading as a start timeout)."""

    def fn():
        time.sleep(4.5)
        return "done"

    # (3 s to start two tasks: on a machine busy with the rest of the suite
    # half a second was not enough in two whole runs of three)
    assert hvd_spark.run(fn, num_proc=2, start_timeout=3.0) == ["done", "done"]


def test_spark_num_proc_defaults_to_parallelism():
    def fn():
        import os

        return int(os.environ["HVD_NUM_PROCS"])

    res = hvd_spark.run(fn)  # fake defaultParallelism = 2
    assert res == [2, 2]


def test_rank_env_multi_host_split():
    hosts = ["a", "a", "b", "b"]
    envs = [rank_env_from_hosts(r, hosts, "a:1234") for r in range(4)]
    assert [e["HVD_LOCAL_RANK"] for e in envs] == ["0", "1", "0", "1"]
    assert all(e["HVD_LOCAL_SIZE"] == "2" for e in envs)
    assert [e["HVD_CROSS_RANK"] for e in envs] == ["0", "0", "1", "1"]
    assert all(e["HVD_CROSS_SIZE"] == "2" for e in envs)
    assert all(e["HVD_COORDINATOR_ADDR"] == "a:1234" for e in envs)
