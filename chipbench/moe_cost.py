"""Operations and bytes of a routed feed-forward's grouped matrix products,
from shapes: what ``moe_experts_roofline`` divides by (``flops.py``'s
arithmetic for the expert layer).

"Required" is the two SwiGLU products over the rows **expected** here: a
token goes to ``top_k`` of ``experts`` and a share ``held / experts`` of its
assignments lands on this chip under a balanced router. A program that
computes every group over every row, pads each group to a capacity, or
walks its worst-case buffer does more and shows a lower share, not the same
one. The routing, the sort and the gathers move rows and multiply nothing:
they are ``moe_ms`` minus ``moe_experts_ms``, and have no roofline here.
"""

from __future__ import annotations

from typing import Dict


def expected_rows(tokens: int, top_k: int, held: int, experts: int) -> float:
    return tokens * top_k * held / experts


def moe_train_cost(rows: float, d_model: int, width: int, held: int,
                   bytes_per_element: int = 2) -> Dict[str, float]:
    """Operations and HBM bytes of one layer's grouped products, forward and
    backward, for ``rows`` routed rows over ``held`` experts of SwiGLU
    ``width``.

    Operations: ``[a, b] = x W1`` is 2 rows d 2f, ``act W2`` 2 rows f d: 6
    rows d f forward, and twice that backward (each product's two
    gradients): 18 rows d f.
    Bytes: the held experts' weights (3 d f each) are read by the forward,
    read again by the backward's products for the rows' gradients, and
    their gradients written: three passes. The rows' activations: each
    product reads its row operand and writes its result once, forward (x,
    [a, b]; act, y) and backward (dy, d act; d[a, b], dx), and the two
    weight-gradient products read their operands again (act, dy; x,
    d[a, b])."""
    d, f = d_model, width
    weights = 3 * held * 3 * d * f
    forward = (d + 2 * f) + (f + d)
    backward = (d + f) + (2 * f + d) + (f + d) + (d + 2 * f)
    return {"flops": 18.0 * rows * d * f,
            "bytes": float(bytes_per_element
                           * (weights + rows * (forward + backward)))}
