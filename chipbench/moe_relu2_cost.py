"""Operations and bytes of a routed feed-forward's grouped matrix products
where an expert is ``W2 relu(W1 u)^2`` (no gate: ``W1`` is as wide as the
expert, not twice), from shapes: ``moe_cost.py``'s arithmetic, which counts
SwiGLU experts, for that kind. The rows expected here are
``moe_cost.expected_rows``; what moves rows has no roofline here either.
"""

from __future__ import annotations

from typing import Dict


def moe_train_cost(rows: float, d_in: int, width: int, held: int,
                   bytes_per_element: int = 2) -> Dict[str, float]:
    """Operations and HBM bytes of one layer's grouped products, forward and
    backward, for ``rows`` routed rows of width ``d_in`` (the model's, or
    the latent's where the experts read one) over ``held`` experts
    ``width`` wide.

    Operations: ``a = x W1`` is 2 rows l f, ``act W2`` 2 rows f l: 4 rows l
    f forward, and twice that backward (each product's two gradients): 12
    rows l f.
    Bytes: the held experts' weights (2 l f each) are read by the forward,
    read again by the backward's products for the rows' gradients, and
    their gradients written: three passes. The rows' activations: each
    product reads its row operand and writes its result once, forward (x,
    a; act, y) and backward (dy, d act; d a, dx), and the two
    weight-gradient products read their operands again (act, dy; x, d a)."""
    l, f = d_in, width
    weights = 3 * held * 2 * l * f
    forward = (l + f) + (f + l)
    backward = (l + f) + (f + l) + (f + l) + (l + f)
    return {"flops": 12.0 * rows * l * f,
            "bytes": float(bytes_per_element
                           * (weights + rows * (forward + backward)))}
