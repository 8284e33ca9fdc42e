"""Operations and bytes of Mamba-2's state-space scan with several groups
of ``B`` and ``C``, from shapes: ``ssd_cost.py``'s arithmetic, which counts
one group, for a layer that has ``groups`` of them.

"Required" is the **recurrence**, as there: a program that runs the dual
form does more and shows a lower share, not the same one.
"""

from __future__ import annotations

from typing import Dict

from . import ssd_cost


def ssd_train_cost(batch: int, seq: int, heads: int, head_dim: int,
                   state: int, groups: int,
                   bytes_per_element: int = 2) -> Dict[str, float]:
    """``ssd_cost.ssd_train_cost`` for ``groups`` groups of ``B`` and ``C``.

    Operations: the same. The groups change which ``B`` and ``C`` a head
    reads, not how much it computes: 15 an element of the state ``[head_dim,
    state]``, a token and head.
    Bytes: ``B`` and ``C`` are ``groups * state`` wide each, and cross HBM
    three times as there (read forward, read backward, their gradients
    written): the further ``groups - 1`` groups are added to its count."""
    cost = ssd_cost.ssd_train_cost(batch, seq, heads, head_dim, state,
                                   bytes_per_element)
    further = 2 * (groups - 1) * state * bytes_per_element      # a token
    return {"flops": cost["flops"],
            "bytes": cost["bytes"] + 3.0 * batch * seq * further}
