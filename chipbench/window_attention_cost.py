"""Operations and bytes of one layer's causal attention under a sliding
window, from shapes: what ``attn_window_roofline`` divides by
(``flops.flash_attention_train_cost``'s arithmetic for a band).

"Required" is the band's own scores: the query at ``i`` sees its last
``window`` positions, itself among them, so a head of ``seq`` positions has
``window * seq - window * (window - 1) / 2`` scores (the first ``window``
queries see fewer), where the causal triangle has ``seq * (seq + 1) / 2``. A
kernel that computes whole tiles the band only crosses, or masks the
triangle and skips nothing, does more and shows a lower share, not the same
one.
"""

from __future__ import annotations

from typing import Dict, Optional


def needed_scores(seq: int, window: int) -> float:
    """The (query, key) pairs of one head inside the band."""
    window = min(window, seq)
    return window * seq - window * (window - 1) / 2


def window_attention_train_cost(batch: int, heads: int, seq: int,
                                head_dim: int, window: int,
                                bytes_per_element: int = 2,
                                kv_heads: Optional[int] = None
                                ) -> Dict[str, float]:
    """Operations and HBM bytes of one layer's windowed causal attention,
    forward and backward, for ``[batch, seq, heads, head_dim]`` queries over
    ``kv_heads`` key and value heads (as many as ``heads`` unless given).

    Matmuls: forward QK^T and PV, backward dV, dP, dQ and dK: six of
    2 head_dim operations a score of the band, for every (batch, query
    head); the backward's recomputation of the scores is not required work.
    Bytes: as ``flops.flash_attention_train_cost`` counts them, six passes
    over a query-sized operand and six over a key-sized one (a band reads
    every key once at least)."""
    kv_heads = heads if kv_heads is None else kv_heads
    token = batch * seq * head_dim * bytes_per_element
    return {"flops": 12.0 * batch * heads * head_dim
            * needed_scores(seq, window),
            "bytes": 6.0 * token * heads + 6.0 * token * kv_heads}
