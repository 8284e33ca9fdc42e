#!/usr/bin/env python
"""Uneven token routing with ragged alltoall (`alltoall(tensor, splits)`).

The classic use: each rank holds tokens destined for different peers in
UNEVEN amounts (expert routing, sample redistribution after filtering,
length-balancing for packed sequences). `splits[d]` says how many dim-0
rows this rank sends to rank d; every rank receives its peers' chunks
concatenated in source-rank order. Split metadata is negotiated through
the control plane — no rank needs to know the others' counts up front.

    JAX_PLATFORMS=cpu python examples/alltoallv_routing.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NP = 4


def worker():
    import numpy as np

    import horovod_tpu as hvd

    r, w = hvd.rank(), hvd.size()

    # every rank draws a DIFFERENT number of tokens for each destination
    # (one vectorized draw so peers can re-derive each other's splits)
    splits = np.random.RandomState(r).randint(0, 5, w).tolist()
    tokens = np.concatenate(
        [np.full((splits[d], 8), 100.0 * r + d, np.float32)
         for d in range(w)])

    routed, received = hvd.alltoall(tokens, splits=splits, name="route")
    routed = np.asarray(routed)

    # verify VALUES, not just counts: rank r receives splits_src[r] rows
    # from each src in source-rank order, stamped 100*src + r — and the
    # negotiated received_splits report exactly those per-source counts
    src_counts = [int(np.random.RandomState(src).randint(0, 5, w)[r])
                  for src in range(w)]
    np.testing.assert_array_equal(np.asarray(received), src_counts)
    expected = np.concatenate(
        [np.full((src_counts[src], 8), 100.0 * src + r, np.float32)
         for src in range(w)])
    np.testing.assert_array_equal(routed, expected)
    print(f"rank {r}: sent {splits} -> received {routed.shape[0]} tokens")
    return routed.shape[0]


def main():
    import horovod_tpu

    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    totals = horovod_tpu.run(worker, np=NP, env=env)
    # conservation: every token that left somewhere arrived somewhere
    import numpy as np
    sent = sum(int(np.random.RandomState(r).randint(0, 5, NP).sum())
               for r in range(NP))
    assert sum(totals) == sent, (totals, sent)
    print(f"token conservation holds: {sent} routed across {NP} ranks")


if __name__ == "__main__":
    main()
