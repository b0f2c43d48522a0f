"""Operations and bytes that power retention's two kernels
(``horovod_tpu/ops/pallas/power_retention.py``) need at the least,
computed from shapes (beside ``benchmark/flops.py``, which an accepted
benchmark may not have edited).

The state of a key/value head is ``S``, ``D x head_dim``, and its
normaliser ``z``, ``D``, float32, ``D = head_dim (head_dim + 1) / 2``:
what the algorithm needs, not the 0.8% more the program's padding to whole
lane tiles holds.

* A decode step (``retention_step``) reads every slot's state and
  normaliser once and writes them once; the decode program runs every
  slot, active or not. Keys, values, queries and gates of one token are
  thousandths of that and are left out. It does two operations a byte:
  bound by memory.
* A prefill's read between chunks (``retention_read``) multiplies each
  query's ``D`` features by the state: ``2 D head_dim`` operations a
  query, against ``head_dim`` numbers in and ``2 head_dim`` out a query
  and one state a call: bound by the matrix unit. The normaliser's sum
  (``2 D`` operations a query, on the vector unit) is left out.
"""

from __future__ import annotations

STATE_ITEMSIZE = 4      # float32


def state_width(head_dim):
    """``D``: the entries of the symmetric half of ``x (x) x``."""
    return head_dim * (head_dim + 1) // 2


def state_bytes(kv_heads, head_dim):
    """Bytes of ``S`` and ``z`` of one slot in one layer."""
    return kv_heads * state_width(head_dim) * (head_dim + 1) * STATE_ITEMSIZE


def retention_step_bytes(slots, layers, kv_heads, head_dim):
    """Bytes one decode step moves at the least: every slot's state in
    every layer read once and written once."""
    return 2 * slots * layers * state_bytes(kv_heads, head_dim)


def retention_read_flops(states, queries, head_dim):
    """Operations of one call: ``queries`` queries against each of
    ``states`` states."""
    return 2 * states * queries * state_width(head_dim) * head_dim


def retention_read_bytes(states, queries, head_dim, itemsize=2):
    """Bytes of one call at the least: the queries and the states in the
    operands' dtype, the normalisers and both results in float32 (the
    sums as one number a query)."""
    return states * (
        queries * head_dim * itemsize
        + state_width(head_dim) * (head_dim * itemsize + STATE_ITEMSIZE)
        + queries * (head_dim + 1) * STATE_ITEMSIZE)
