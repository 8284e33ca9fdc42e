#!/usr/bin/env python
"""Distributed training from a Spark driver.

Reference parity: `examples/keras_spark_rossmann.py` + `horovod.spark.run`
— the driver hands a training function to `horovod_tpu.spark.run`, which
launches it on barrier-mode Spark tasks (each task = one rank, env
injected through the barrier context) and returns per-rank results.

With a real cluster::

    spark-submit examples/spark_run.py

Without pyspark installed, this demo falls back to the in-process fake
used by the test suite (tasks are forked subprocesses), exercising the
identical horovod_tpu.spark code path.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def train(lr):
    """Runs inside each Spark task: one rank of a data-parallel job."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    rng = np.random.RandomState(100 + r)
    w = np.asarray(hvd.broadcast(np.zeros(4, np.float32), 0, name="w0"))
    for step in range(8):
        x = rng.randn(32, 4).astype(np.float32)
        y = x @ np.array([2.0, -1.0, 0.5, 3.0], np.float32)
        g = 2 * x.T @ (x @ w - y) / len(y)
        w = w - lr * np.asarray(hvd.allreduce(g, name=f"g{step}"))
    loss = float(np.mean((x @ w - y) ** 2))
    return {"rank": r, "size": hvd.size(), "loss": round(loss, 4),
            "w": [round(float(v), 3) for v in w]}


def main():
    try:
        import pyspark  # noqa: F401
    except ImportError:
        # demo mode: the test suite's barrier-mode fake (forked tasks)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tests"))
        import fake_pyspark
        sys.modules["pyspark"] = fake_pyspark
        print("(pyspark not installed: using the in-process fake)")

    import horovod_tpu.spark

    results = horovod_tpu.spark.run(train, args=(0.1,), num_proc=2,
                                    extra_env={"JAX_PLATFORMS": "cpu"})
    for r in results:
        print(f"rank {r['rank']}/{r['size']}  loss={r['loss']}  w={r['w']}")
    assert results[0]["w"] == results[1]["w"], "ranks diverged"
    print("all ranks converged to identical weights")


if __name__ == "__main__":
    main()
