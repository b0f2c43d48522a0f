"""Controls for ``granite-serve-chat-c1`` beside the float8 one: the
plain reference (``benchmark/reference_granite.py``) teacher forced with
one of the faults a slot engine over state-space layers can have, so
that the tokens such a program would serve can be held against the
cell's limits at the cell's own weights (``benchmark/runners/
serve_granite.py`` ``reference_gaps``, ``benchmark/tools/calibrate.py``,
``tests/test_state_space_model.py``).

The reference itself stays plain: a fault is a function of the module
swapped while one forward pass is traced (:func:`forward`), never an
option of it. The three:

* ``stale_state``: a slot that keeps its earlier occupant's state - the
  prompt is computed as it should be (a prefill reads no cache), but the
  state it leaves never reaches the slot, and the first decode step of
  every state-space layer continues from the state another request left
  there;
* ``tail_dropped``: the convolution's tail dropped at the boundary of
  prefill and decode - the first ``d_conv - 1`` decode steps see zeros
  where the prompt's last rows belong;
* ``padding_in_recurrence``: the true ``lengths`` ignored - the padding
  between the prompt's end and its prefill bucket runs through the
  recurrence and the convolution before the first decode step (the
  attention layer's cache never shows a decode step the padding, and
  does not here).
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark import reference_granite

FAULTS = ("stale_state", "tail_dropped", "padding_in_recurrence")


@contextlib.contextmanager
def _swapped(**functions):
    sound = {name: getattr(reference_granite, name) for name in functions}
    for name, fn in functions.items():
        setattr(reference_granite, name, fn)
    try:
        yield sound
    finally:
        for name, fn in sound.items():
            setattr(reference_granite, name, fn)


def final_states(cfg):
    """``(params, tokens, length) -> [state a state-space layer]``: what
    the sound recurrence leaves after ``length`` tokens of ``tokens``
    (zeros after them), jitted."""
    import jax
    import jax.numpy as jnp

    frozen = reference_granite.frozen(cfg)

    def states(params, tokens, length):
        left = []

        def recurrence(x, dt, a, b, c, d_skip):
            def one(state, xs):
                x_t, dt_t, b_t = xs
                return (jnp.exp(dt_t * a)[:, None, None] * state
                        + (dt_t[:, None] * x_t)[:, :, None]
                        * b_t[:, None, :]), None

            dt_in = jnp.where(jnp.arange(x.shape[0])[:, None] < length,
                              dt, 0.0)
            left.append(jax.lax.scan(
                one, jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32),
                (x, dt_in, b))[0])
            return sound["recurrence"](x, dt, a, b, c, d_skip)

        with _swapped(recurrence=recurrence) as sound:
            reference_granite.forward(params, tokens, frozen, "f32",
                                      jnp.zeros((1,), jnp.int32))
        return left

    return jax.jit(states)


def forward(fault, cfg):
    """``(params, tokens, rows, boundary, extra) -> float32 logits`` of
    the reference with ``fault`` (one of :data:`FAULTS`), jitted; a
    function of its own, so that it shares no compiled program with the
    sound reference. ``boundary``: the prompt's length. ``extra``:
    for ``stale_state`` the states the earlier occupant left
    (:func:`final_states`), for ``padding_in_recurrence`` the number of
    padding tokens that follow the prompt in ``tokens``
    (:func:`padded`), otherwise unused."""
    import jax
    import jax.numpy as jnp

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    frozen = reference_granite.frozen(cfg)

    def broken(params, tokens, rows, boundary, extra):
        stale = list(extra) if fault == "stale_state" else []

        def recurrence(x, dt, a, b, c, d_skip):
            left = stale.pop(0)

            def one(state, xs):
                x_t, dt_t, b_t, c_t, t = xs
                # the first decode step finds what the slot held before
                state = jnp.where(t == boundary, left, state)
                state = jnp.exp(dt_t * a)[:, None, None] * state \
                    + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
                y = jnp.einsum("hpn,hn->hp", state, c_t,
                               precision=reference_granite.HIGHEST)
                return state, y + d_skip[:, None] * x_t

            return jax.lax.scan(one, jnp.zeros_like(left), (
                x, dt, b, c, jnp.arange(x.shape[0])))[1]

        def conv(xbc, kernel, bias):
            # a row at or past the boundary sees zeros before it
            prompt = jnp.arange(xbc.shape[0])[:, None] < boundary
            return jnp.where(
                prompt, sound["conv"](xbc, kernel, bias),
                sound["conv"](jnp.where(prompt, 0.0, xbc), kernel, bias))

        def attention(q, k, v, scale):
            # keys boundary .. boundary + extra - 1 are the padding: a
            # decode step's query (past them) never sees them
            seq, heads, d = q.shape
            per = heads // k.shape[1]
            keys = jnp.arange(seq)
            hidden = (keys >= boundary) & (keys < boundary + extra)

            def one(q_b, at):
                q_b = q_b.reshape(-1, heads // per, per, d)
                s = jnp.einsum("tgrd,sgd->grts", q_b, k,
                               precision=reference_granite.HIGHEST) * scale
                seen = (keys[None, :] <= at[:, None]) & ~(
                    hidden[None, :] & (at[:, None] >= boundary + extra))
                s = jnp.where(seen[None, None], s, -jnp.inf)
                o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1),
                               v, precision=reference_granite.HIGHEST)
                return o.reshape(-1, heads, d)

            return reference_granite._blocks(
                one, [q, keys], min(reference_granite.QUERY_BLOCK, seq))

        swap = {"stale_state": dict(recurrence=recurrence),
                "tail_dropped": dict(conv=conv),
                "padding_in_recurrence": dict(attention=attention)}[fault]
        with _swapped(**swap) as sound:
            return reference_granite.forward(params, tokens, frozen, "f32",
                                             rows)

    return jax.jit(broken)


def padded(prompt, tokens, bucket, length):
    """For ``padding_in_recurrence``: the ids ``prompt + padding +
    tokens`` (padding: token 0 up to the prompt's prefill ``bucket``) in
    ``length`` places, the number of padding tokens, and for each served
    token the row that predicts it (the first comes from the prompt's
    last row, the others lie past the padding)."""
    pad = bucket - len(prompt)
    ids = np.zeros((length,), np.int32)
    full = (list(prompt) + [0] * pad + list(tokens))[:length]
    ids[:len(full)] = full
    rows = np.concatenate([[len(prompt) - 1],
                           bucket + np.arange(len(tokens) - 1)])
    return ids, pad, np.minimum(rows, length - 1).astype(np.int32)
