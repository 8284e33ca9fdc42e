"""Operations and bytes of one layer's attention under the block-diffusion
mask, from shapes: what ``flash_attention_roofline`` divides by in a cell
whose model is trained by denoising blocks
(``flops.flash_attention_train_cost``'s arithmetic for that mask).

The layer runs on ``2 seq`` rows, a sequence's noised copy and then its
clean one, in blocks of ``block`` positions. "Required" is the mask's own
scores: a clean row sees the clean rows of its block and of the blocks
before it, a noised row the clean rows of the blocks before its own and the
noised rows of its own block, so a row of block ``b`` sees ``(b + 1) block``
keys either way and a head has ``seq^2 + seq block`` scores (two triangles
and a diagonal), where a causal call over the ``2 seq`` rows would have ``2
seq^2 + seq``. A kernel that masks that triangle and skips nothing, or
computes whole tiles the mask only crosses, does more and shows a lower
share, not the same one.
"""

from __future__ import annotations

from typing import Dict, Optional


def needed_scores(seq: int, block: int) -> float:
    """The (query, key) pairs of one head the mask keeps, of ``2 seq``
    rows."""
    return float(seq) * seq + float(seq) * block


def block_diffusion_attention_train_cost(batch: int, heads: int, seq: int,
                                         head_dim: int, block: int,
                                         bytes_per_element: int = 2,
                                         kv_heads: Optional[int] = None
                                         ) -> Dict[str, float]:
    """Operations and HBM bytes of one layer's attention under the mask,
    forward and backward, for ``[batch, 2 seq, heads, head_dim]`` queries
    over ``kv_heads`` key and value heads (as many as ``heads`` unless
    given).

    Matmuls: forward QK^T and PV, backward dV, dP, dQ and dK: six of
    2 head_dim operations a kept score, for every (batch, query head); the
    backward's recomputation of the scores is not required work. Bytes: as
    ``flops.flash_attention_train_cost`` counts them, six passes over a
    query-sized operand and six over a key-sized one, each of ``2 seq``
    rows."""
    kv_heads = heads if kv_heads is None else kv_heads
    token = batch * 2 * seq * head_dim * bytes_per_element
    return {"flops": 12.0 * batch * heads * head_dim
            * needed_scores(seq, block),
            "bytes": 6.0 * token * heads + 6.0 * token * kv_heads}
