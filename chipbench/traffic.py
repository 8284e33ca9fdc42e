"""Seeded traffic: the one general generator every cell's mix file feeds.

A traffic mix is a data file, ``chipbench/mixes/<name>.json``; this module
turns its parameters and ``--seed`` into token batches (training), request
lists (serving) and arrival times (open-loop cells, none yet). The same seed
gives the same inputs. The program under test receives only what is
generated here.

The synthetic batch is uniform token ids (``benchmarks/lm_bench.py``'s,
which PR 45 deleted) and the arrival arithmetic
``benchmarks/serving_bench.py:poisson_load``'s (a running sum of seeded
exponential gaps), listed in PERF.md for deletion. Which generator a
training cell's batches come from is its objective's to say
(``objectives/<name>.py``); next-token prediction's is
:func:`token_batches`.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    """The mix file ``mixes/<name>.json``."""
    with open(os.path.join(HERE, "mixes", f"{name}.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- training
def token_batches(seed: int, count: int, global_batch: int, seq: int,
                  vocab: int, sharding=None):
    """``count`` batches ``(tokens, targets)`` of ``[global_batch, seq]``
    int32, uniform over ``vocab`` ids, made on the device in one jitted
    call and placed with ``sharding`` (dim 0 split over the mesh)."""
    import jax
    import jax.numpy as jnp

    def make(key):
        toks = jax.random.randint(key, (count, global_batch, seq + 1), 0,
                                  vocab, dtype=jnp.int32)
        return [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(count)]

    return jax.jit(make, out_shardings=sharding)(jax.random.PRNGKey(seed))


# ----------------------------------------------------------------- serving
def _draw_lengths(rng: np.random.RandomState, spec: dict, n: int):
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    step = spec.get("round_to", 1)
    return np.clip(np.rint(raw / step) * step, spec["min"],
                   spec["max"]).astype(int)


def possible_lengths(spec: dict) -> List[int]:
    """Every length :func:`_draw_lengths` can return for ``spec``."""
    step = spec.get("round_to", 1)
    return sorted({int(np.clip(v, spec["min"], spec["max"])) for v in range(
        spec["min"] // step * step, spec["max"] + step, step)})


def request_list(seed: int, n: int, mix: dict, vocab: int) -> List[dict]:
    """``n`` requests ``{"prompt": [ids], "new": k}`` with lengths drawn
    from the mix's distributions and uniform prompt tokens."""
    rng = np.random.RandomState(seed)
    prompt_lens = _draw_lengths(rng, mix["prompt_len"], n)
    new_tokens = _draw_lengths(rng, mix["new_tokens"], n)
    return [{"prompt": rng.randint(0, vocab, int(p)).tolist(), "new": int(k)}
            for p, k in zip(prompt_lens, new_tokens)]


def arrival_times(seed: int, rate: float, n: int,
                  burst: Optional[Dict[str, float]] = None) -> np.ndarray:
    """Due times in seconds of ``n`` open-loop arrivals at ``rate`` a
    second: Poisson, or with ``burst = {"every_s", "length_s", "factor"}``
    a Poisson process whose rate is ``factor`` times higher during the
    first ``length_s`` of every ``every_s`` (the mean rate stays ``rate``).
    A request is timed from its due time, not from when it was sent."""
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0, n)
    if burst is None:
        return np.cumsum(gaps / rate)
    share = burst["length_s"] / burst["every_s"]
    low = rate / (share * burst["factor"] + (1.0 - share))
    t, out = 0.0, np.empty(n)
    for i, g in enumerate(gaps):
        in_burst = (t % burst["every_s"]) < burst["length_s"]
        t += g / (low * burst["factor"] if in_burst else low)
        out[i] = t
    return out
