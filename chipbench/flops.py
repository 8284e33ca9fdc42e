"""Operations and bytes computed from shapes: the yardstick's arithmetic.

Everything a utilization or a roofline share divides by is computed from a
cell's shapes, never read from the program: a kernel's cost and the
roofline here, an architecture's operations a token in its family
(``families/<model_type>.py``). "Required" means what
the mathematics needs: recomputation (remat, the flash backward's second
pass over the scores) is not counted, so a program that recomputes more
shows a lower share, not the same one.
"""

from __future__ import annotations

from typing import Dict, Optional


def flash_attention_train_cost(batch: int, heads: int, seq: int,
                               head_dim: int, bytes_per_element: int = 2,
                               kv_heads: Optional[int] = None
                               ) -> Dict[str, float]:
    """Operations and HBM bytes of one layer's causal attention, forward
    and backward, for ``[batch, seq, heads, head_dim]`` queries over
    ``kv_heads`` key and value heads (as many as ``heads`` unless given:
    grouped heads share theirs).

    Matmuls: forward QK^T and PV, backward dV, dP, dQ and dK — six of
    2 s^2 head_dim each for every (batch, query head), halved by causality.
    The backward's recomputation of the scores is not required work. Bytes:
    the forward reads q, k, v and writes o; the backward reads q, k, v, o
    and do and writes dq, dk, dv — six passes over a query-sized operand
    and six over a key-sized one (the f32 row statistics, 1/head_dim of an
    operand, are left out)."""
    kv_heads = heads if kv_heads is None else kv_heads
    token = batch * seq * head_dim * bytes_per_element
    return {"flops": 6.0 * batch * heads * seq * seq * head_dim,
            "bytes": 6.0 * token * heads + 6.0 * token * kv_heads}


def roofline_seconds(cost: Dict[str, float], peak: dict,
                     flops_key: str = "bf16_flops_per_s") -> Dict[str, object]:
    """The least time a chip of ``peak`` could take over ``cost``, and
    which of the two limits sets it."""
    compute = cost["flops"] / peak[flops_key]
    memory = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
