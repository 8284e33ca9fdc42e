"""Operations and bytes of the gated delta rule, from shapes: what
``delta_rule_roofline`` divides by (``flops.py``'s arithmetic for the
recurrent layer of ``ops/gated_delta.py``).

"Required" is the **recurrence**, the cheapest form of the mathematics, as
``ssd_cost.py`` says of the state-space scan: a program that computes the
rule in its chunked form (a triangular solve and masked products a chunk,
a scan over chunks) does more operations and moves more bytes, and shows a
lower share, not the same one. It counts the same work whatever implements
it, so it is the yardstick a kernel is priced against.
"""

from __future__ import annotations

from typing import Dict


def gated_delta_train_cost(batch: int, seq: int, key_heads: int,
                           value_heads: int, key_dim: int, value_dim: int,
                           bytes_per_element: int = 2) -> Dict[str, float]:
    """Operations and HBM bytes of one layer's delta rule, forward and
    backward, for ``q``, ``k`` ``[batch, seq, key_heads, key_dim]`` and
    ``v`` ``[batch, seq, value_heads, value_dim]``.

    Operations, a token and value head, over its ``[key_dim, value_dim]``
    state: the decay (one multiply an element), the read ``S'^T k`` (a
    multiply-add), the rank-one write (a multiply-add) and the readout
    ``S^T q`` (a multiply-add): 7 operations an element forward, and twice
    that backward.
    Bytes: the forward reads ``q`` and ``k`` (at the key heads there are:
    handing each to several value heads is the program's cost), ``v``,
    ``g`` and ``beta`` (float32, one a value head) and writes ``o``; the
    backward reads them and ``do`` and writes the five gradients. The state
    never leaves the chip."""
    tokens = batch * seq
    qk = 2 * key_heads * key_dim * bytes_per_element       # q and k, a token
    v = value_heads * value_dim * bytes_per_element         # also o, do, dv
    inputs = qk + v + 2 * 4 * value_heads                   # + g and beta
    return {"flops": 21.0 * tokens * value_heads * key_dim * value_dim,
            "bytes": float(tokens * ((inputs + v) + (inputs + v + inputs)))}
