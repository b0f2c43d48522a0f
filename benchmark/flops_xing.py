"""Bytes and operations that an expert layer's semantics require,
whatever implements it (beside ``benchmark/flops.py``, which an accepted
benchmark may not have edited).

A decode step's expert layer reads, at the least, the three matrices of
every expert that some row of the step chose (an expert nobody chose
need not be touched), the shared expert's three and the router's one,
each once, in the weights' dtype; its rows' activations are thousandths
of that and are left out. It does a few operations a byte (four pairs an
expert at 64 rows): bound by memory. How many experts the steps really
hit comes from the program's counter, never from the shapes.

A decode step's latent attention reads, at the least, the latent and the
rotary key of every position its rows attend, once (keys and values are
the same bytes), in the cache's dtype; the queries and outputs of one
token a row are thousandths of that. At 32 heads it does about 110
operations a byte against the chip's 240: bound by memory. The positions
attended come from the engine's counter.
"""

from __future__ import annotations


def expert_bytes(d_model, d_ff, itemsize=2):
    """Bytes of one SiLU-gated expert of width ``d_ff``: gate, up, down."""
    return 3 * d_model * d_ff * itemsize


def moe_step_fixed_bytes(d_model, d_ff, shared, num_experts, itemsize=2):
    """Bytes every decode step reads in one expert layer whichever
    experts are chosen: the shared expert(s) and the router."""
    return (shared * expert_bytes(d_model, d_ff, itemsize)
            + d_model * num_experts * itemsize)


def moe_decode_bytes(steps, expert_steps, layers, d_model, d_ff, shared,
                     num_experts, itemsize=2):
    """Bytes ``steps`` decode steps read at the least in ``layers`` expert
    layers: ``expert_steps`` (expert, layer, step) triples in which some
    row chose the expert, plus every step's fixed part in every layer."""
    return (expert_steps * expert_bytes(d_model, d_ff, itemsize)
            + steps * layers * moe_step_fixed_bytes(
                d_model, d_ff, shared, num_experts, itemsize))


def moe_pair_flops(d_model, d_ff):
    """Operations of one (token, expert) pair: three products."""
    return 2 * 3 * d_model * d_ff


def latent_decode_bytes(positions, rank, rope_dim, itemsize=2):
    """Bytes one layer's decode attention reads at the least for
    ``positions`` attended positions (summed over rows and steps)."""
    return positions * (rank + rope_dim) * itemsize
