"""Controls for ``xing-serve-c1`` beside the float8 one: the plain
reference (``benchmark/reference_xing.py``) with one piece of its
mathematics left out, teacher forced, so that the tokens such a program
would serve can be held against the cell's limits at the cell's own
weights (``benchmark/runners/serve_xing.py`` ``reference_gaps``,
``benchmark/tools/calibrate.py``), and two planted faults that need no
forward pass of their own.

The reference itself stays plain: a fault is a changed configuration
(:data:`CONFIGURED`) or a function of the module swapped while one
forward pass is traced (:func:`forward`), never an option of it.
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark import reference_xing

# a piece of the mathematics left out: what replaces it
CONFIGURED = {"one_sinkhorn_iteration": {"sinkhorn_iters": 1},
              "router_scaling_dropped": {"routed_scaling": 1.0}}
SWAPPED = ("h_res_identity", "rotary_key_unrotated")
FAULTS = SWAPPED + tuple(CONFIGURED)


@contextlib.contextmanager
def _swapped(fault):
    sound = {name: getattr(reference_xing, name)
             for name in ("hyper_maps", "_rope")}

    def hyper_maps(X, p, cfg):
        import jax.numpy as jnp

        pre, post, res = sound["hyper_maps"](X, p, cfg)
        return pre, post, jnp.broadcast_to(
            jnp.eye(res.shape[-1], dtype=res.dtype), res.shape)

    def rope(x, positions, freq):
        # the key is the one call with a single head
        return x if x.shape[1] == 1 else sound["_rope"](x, positions, freq)

    if fault == "h_res_identity":
        reference_xing.hyper_maps = hyper_maps
    elif fault == "rotary_key_unrotated":
        reference_xing._rope = rope
    try:
        yield
    finally:
        for name, fn in sound.items():
            setattr(reference_xing, name, fn)


def forward(fault, cfg):
    """``(params, tokens, rows) -> float32 logits`` of the reference with
    ``fault`` (one of :data:`FAULTS`), jitted; a function of its own, so
    that it shares no compiled program with the sound reference."""
    import jax

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    frozen = reference_xing.frozen(dict(cfg, **CONFIGURED.get(fault, {})))

    def broken(params, tokens, rows):
        with _swapped(fault):
            return reference_xing.forward(params, tokens, frozen, "f32", rows)

    return jax.jit(broken)


def another_slots_tokens(firsts, i):
    """The tokens a slot would serve that read the latents of another
    request: what the reference puts first at the same served positions
    of the next request of the sample (``firsts``: a request's first
    tokens by served position, one array a request)."""
    return firsts[(i + 1) % len(firsts)]


def altered_token_gaps(logits, seed):
    """What one altered served token reads: at every served position of
    ``logits`` (one row a position) the gap of one token drawn from the
    vocabulary (from ``seed``) below the reference's best, as the widest
    served-token gap would read it were that token served there."""
    rng = np.random.default_rng([seed, 5])
    drawn = rng.integers(0, logits.shape[-1], logits.shape[0])
    return logits.max(axis=-1) - logits[np.arange(logits.shape[0]), drawn]
