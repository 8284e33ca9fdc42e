"""Operations and bytes of Mamba-2's state-space scan, from shapes: what
``ssd_roofline`` divides by (``flops.py``'s arithmetic for the scan).

"Required" is the **recurrence**, the cheapest form of the mathematics: a
program that computes the scan in its dual form (chunked matmuls over
decay matrices, ``ops/ssd.ssd_chunked``) does more operations and moves more
bytes, and shows a lower share, not the same one.
"""

from __future__ import annotations

from typing import Dict


def ssd_train_cost(batch: int, seq: int, heads: int, head_dim: int,
                   state: int, bytes_per_element: int = 2) -> Dict[str, float]:
    """Operations and HBM bytes of one layer's scan, forward and backward,
    for ``x`` ``[batch, seq, heads, head_dim]`` over an ``state``-wide state
    with one group of ``B`` and ``C``.

    Operations, a token and head: the state ``[head_dim, state]`` is decayed
    (one multiply an element), takes the outer product ``dt x B^T`` (a
    multiply-add) and is read out against ``C`` (a multiply-add): 5
    operations an element forward, and twice that backward.
    Bytes: the forward reads ``x``, ``dt`` (float32), ``B`` and ``C`` and
    writes ``y``; the backward reads them and ``dy`` and writes the four
    gradients. The state never leaves the chip."""
    tokens = batch * seq
    x = heads * head_dim * bytes_per_element      # also y, dy, dx: a token
    inputs = x + 4 * heads + 2 * state * bytes_per_element   # x, dt, B, C
    return {"flops": 15.0 * tokens * heads * head_dim * state,
            "bytes": float(tokens * ((inputs + x) + (inputs + x + inputs)))}
