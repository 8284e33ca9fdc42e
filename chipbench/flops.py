"""Operations and bytes computed from shapes: the yardstick's arithmetic.

Everything a utilization or a roofline share divides by is computed here
from a cell's shapes, never read from the program. "Required" means what
the mathematics needs: recomputation (remat, the flash backward's second
pass over the scores) is not counted, so a program that recomputes more
shows a lower share, not the same one.
"""

from __future__ import annotations

from typing import Dict


def train_flops_per_token(n_layer: int, d: int, vocab_rows: int,
                          seq: int) -> float:
    """Forward plus backward operations one trained token requires.

    6 x (block matrices 12 L d^2 + the head's d V): a multiply-add is two
    operations, the backward pass costs twice the forward. Attention adds
    QK^T and PV, 4 s d a token and layer in the forward pass, halved
    because a causal row sees half the sequence on average, times three
    for forward plus backward: 6 L s d. The head is counted (it is 11% of
    gpt2-medium's operations at 50304 rows); recomputation is not."""
    return 6.0 * (12 * n_layer * d * d + d * vocab_rows) \
        + 6.0 * n_layer * seq * d


def flash_attention_train_cost(batch: int, heads: int, seq: int,
                               head_dim: int,
                               bytes_per_element: int = 2) -> Dict[str, float]:
    """Operations and HBM bytes of one layer's causal attention, forward
    and backward, for ``[batch, seq, heads, head_dim]`` operands.

    Matmuls: forward QK^T and PV, backward dV, dP, dQ and dK — six of
    2 s^2 head_dim each for every (batch, head), halved by causality. The
    backward's recomputation of the scores is not required work. Bytes:
    the forward reads q, k, v and writes o; the backward reads q, k, v, o
    and do and writes dq, dk, dv — twelve passes over one operand (the
    f32 row statistics, 1/head_dim of an operand, are left out)."""
    operand = batch * seq * heads * head_dim * bytes_per_element
    return {"flops": 6.0 * batch * heads * seq * seq * head_dim,
            "bytes": 12.0 * operand}


def roofline_seconds(cost: Dict[str, float], peak: dict,
                     flops_key: str = "bf16_flops_per_s") -> Dict[str, object]:
    """The least time a chip of ``peak`` could take over ``cost``, and
    which of the two limits sets it."""
    compute = cost["flops"] / peak[flops_key]
    memory = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
