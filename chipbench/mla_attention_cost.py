"""Operations and bytes of one layer's causal attention whose keys and
values have widths of their own, from shapes: what
``flash_attention_roofline`` divides by in a latent-attention cell
(``flops.flash_attention_train_cost``'s arithmetic with each operand at its
own width).

"Required" is the true widths: a kernel that pads its keys to whole lane
tiles, or its values to the keys' width, does more and shows a lower
share, not the same one. As there, the backward's recomputation of the
scores is not required work.
"""

from __future__ import annotations

from typing import Dict


def mla_attention_train_cost(batch: int, heads: int, seq: int, key_dim: int,
                             value_dim: int, bytes_per_element: int = 2
                             ) -> Dict[str, float]:
    """Operations and HBM bytes of one layer's causal attention, forward
    and backward, for ``[batch, seq, heads, key_dim]`` queries and keys
    and ``[batch, seq, heads, value_dim]`` values (latent attention in its
    training form: every head has keys and values of its own).

    Matmuls, each at the width it contracts or produces: forward QK^T and
    backward dQ and dK ``key_dim`` (three of 2 key_dim operations a
    score), forward PV and backward dV and dP ``value_dim`` (three of
    2 value_dim): ``6 key_dim + 6 value_dim`` a score of the causal
    triangle (half of seq^2), for every (batch, head). Bytes: the forward
    reads q, k, v and writes o; the backward reads q, k, v, o and do and
    writes dq, dk, dv: three passes each over q and over k (``key_dim``
    wide: q, q, dq; k, k, dk) and three each over v and over o
    (``value_dim`` wide: v, v, dv; o, o, do). Equal widths give
    ``flops.flash_attention_train_cost``'s numbers."""
    token = batch * heads * seq * bytes_per_element
    return {"flops": 3.0 * batch * heads * seq * seq * (key_dim + value_dim),
            "bytes": 6.0 * token * key_dim + 6.0 * token * value_dim}
