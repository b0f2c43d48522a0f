"""Bytes that a decode step's state-space recurrence has to move,
whatever implements it (beside ``benchmark/flops.py``, which an accepted
benchmark may not have edited).

One token of a Mamba-2 layer, for every slot: the float32 state
(``heads x head_dim x d_state``) read once and written once, and the
convolution's tail (``d_conv - 1`` rows of ``heads x head_dim + 2 groups
x d_state`` channels in the cache's dtype) read and written; the token's
own ``x``, ``B``, ``C``, ``dt`` and ``y`` are thousandths of that. The
step does about three operations a byte of state (a multiply-add to
decay and add, another to read): bound by memory.
"""

from __future__ import annotations


def ssm_step_bytes(slots, layers, heads, head_dim, d_state, groups, d_conv,
                   tail_itemsize=2):
    """Bytes one decode step's state-space layers move at the least: all
    ``slots`` rows (the step runs every row) of ``layers`` layers."""
    state = heads * head_dim * d_state * 4
    tail = (d_conv - 1) * (heads * head_dim + 2 * groups * d_state) \
        * tail_itemsize
    return slots * layers * 2 * (state + tail)
