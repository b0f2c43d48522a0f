"""Mamba-2 state-space layers beside a position-free grouped-query layer,
a softmax router over a share of the experts on every layer, a tied head
and four fixed multipliers (granite-4.0-h-small; ``benchmark/configs/
granite-4.0-h-small.json``'s toy sizes, all ten layers) against
``benchmark/reference_granite.py``, whose recurrence runs token by
token. Logits have a standard deviation of about 1.6 here; float32
against float32 differs by the order of sums (``F32_TOL``).

The toy's chunk is 64, so that a prompt of a few hundred tokens is
several chunks and a ragged last one; the cell's geometry (128 heads of
64, a state of 128, chunks of 256) is compiled for the chip by
``tests/test_tpu_compile.py``. This file holds the mixer alone and the
router; the whole model, the engine's cache, the planted faults, spans
and scopes are ``tests/test_state_space_model.py`` (a file is what
tier-1's ``--dist loadfile`` schedules), and what every family promises
behind the engine is ``tests/test_engine_contract.py``, which this
family joined.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_granite as gref
from horovod_tpu.models import hybrid
from toy_models import granite

F32_TOL = 5e-5
HEADS, P, N, TAPS, CHUNK = 8, 32, 16, 4, 64
INNER, CHANNELS = HEADS * P, HEADS * P + 2 * N


# ------------------------------------------------------------- the mixer

def mixer(decode=False, chunk=CHUNK):
    return hybrid.StateSpace(num_heads=HEADS, head_dim=P, d_state=N,
                             d_conv=TAPS, chunk=chunk, eps=1e-5,
                             decode=decode, dtype=jnp.float32)


def mixer_params():
    """A state-space layer's parameters with the spread the cell's have
    (``benchmark/weights_granite.py``): the granite family's layer 0."""
    return granite()[1]["layer_0"]["mixer"]


def plain_mixer(m, x):
    """The reference's state-space mixer over one sequence ``x`` (seq,
    C): its own convolution and its token-by-token recurrence."""
    mm = gref._matmul("f32")
    proj = mm(x, m["in_proj"]["kernel"])
    z, xbc = proj[:, :INNER], proj[:, INNER:INNER + CHANNELS]
    dt = jax.nn.softplus(proj[:, INNER + CHANNELS:] + m["dt_bias"])
    xbc = gref.conv(xbc, m["conv_kernel"], m["conv_bias"])
    a_head = lambda t: jnp.repeat(t.reshape(-1, 1, N), HEADS, axis=1)
    y = gref.recurrence(xbc[:, :INNER].reshape(-1, HEADS, P), dt,
                        -jnp.exp(m["A_log"]), a_head(xbc[:, INNER:INNER + N]),
                        a_head(xbc[:, INNER + N:]), m["D"])
    return mm(gref._rms(y.reshape(-1, INNER) * jax.nn.silu(z),
                        m["norm"]["scale"], 1e-5), m["out_proj"]["kernel"])


def inputs(seq, seed=0, batch=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (batch, seq, 128))


# 1, 2, 3: under the convolution's reach; 63, 64, 65: round a chunk's
# edge; 203: three chunks and 11; 40 with chunk 256: shorter than one
@pytest.mark.parametrize("seq,chunk", [(1, CHUNK), (2, CHUNK), (3, CHUNK),
                                       (63, CHUNK), (64, CHUNK), (65, CHUNK),
                                       (203, CHUNK), (40, 256)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(seq, chunk):
    m = mixer_params()
    x = inputs(seq, seed=seq)
    got = mixer(chunk=chunk).apply({"params": m}, x,
                                   jnp.zeros((1,), jnp.int32))[0]
    want = plain_mixer(m, x[0])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL


def test_a_step_and_a_chunk_make_the_same_state_and_output():
    """``ssm_step`` over a sequence, one token at a time from zeros,
    against ``ssm_chunked``: outputs and the state they leave."""
    rng = np.random.default_rng(3)
    seq = 150
    x = jnp.asarray(rng.normal(size=(2, seq, HEADS, P)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(2, seq, 1, N)), jnp.float32)
            for _ in range(2))
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, (2, seq, HEADS)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, HEADS), jnp.float32)
    d_skip = jnp.asarray(rng.normal(size=HEADS), jnp.float32)
    y, last = hybrid.ssm_chunked(x, dt, a, b, c, d_skip, CHUNK, jnp.float32)
    state = jnp.zeros((2, HEADS, P, N))
    for t in range(seq):
        state, y_t = hybrid.ssm_step(state, x[:, t], dt[:, t], a, b[:, t],
                                     c[:, t], d_skip)
        assert np.abs(np.asarray(y_t - y[:, t])).max() < F32_TOL, t
    assert np.abs(np.asarray(state - last)).max() < F32_TOL


@pytest.mark.parametrize("lengths", [(1, 2), (3, 70), (64, 129), (200, 17)])
def test_padding_leaves_state_and_tail_untouched(lengths):
    """Rows padded to one length with noise: the state is the state
    after each row's true tokens and the tail its last three true rows
    before the convolution, zeros where the prompt had fewer - what the
    same rows leave unpadded. With ``lengths`` ignored the padding is
    folded into both."""
    m = mixer_params()
    seq = 208
    x = inputs(seq, seed=9, batch=2)
    apply = lambda x, n: mixer(decode=True).apply(
        {"params": m}, x, jnp.zeros((x.shape[0],), jnp.int32),
        None if n is None else jnp.asarray(n, jnp.int32),
        mutable=["cache"])[1]["cache"]
    padded = apply(x, lengths)
    for row, n in enumerate(lengths):
        alone = apply(x[row:row + 1, :n], None)
        for leaf in ("ssm_state", "conv_state"):
            assert np.abs(np.asarray(padded[leaf][row] - alone[leaf][0])
                          ).max() < F32_TOL, (leaf, n)
        tail = np.asarray(padded["conv_state"][row]).reshape(3, CHANNELS)
        assert (tail[:max(3 - n, 0)] == 0).all()
        assert np.abs(tail[max(3 - n, 0):]).min() > 0
    ignored = apply(x, None)
    for leaf in ("ssm_state", "conv_state"):
        assert np.abs(np.asarray(ignored[leaf] - padded[leaf])).max() \
            > 100 * F32_TOL, leaf   # a state's entries are hundredths


@pytest.mark.parametrize("prompt_len", [1, 2, 3, 5, 70])
def test_the_mixers_prefill_then_steps_are_its_one_forward(prompt_len):
    """The mixer alone through its cache: a padded prompt, then one
    token at a time (the convolution reading the tail the prompt left,
    zeros for a prompt under three tokens), against the reference's
    mixer over the whole sequence."""
    m = mixer_params()
    total = prompt_len + 12
    x = inputs(total, seed=prompt_len)
    want = np.asarray(plain_mixer(m, x[0]))
    padded = jnp.pad(x[:, :prompt_len], ((0, 0), (0, 80 - prompt_len),
                                         (0, 0)), constant_values=0.7)
    got, mutated = mixer(decode=True).apply(
        {"params": m}, padded, jnp.zeros((1,), jnp.int32),
        jnp.asarray([prompt_len], jnp.int32), mutable=["cache"])
    assert np.abs(np.asarray(got[0, :prompt_len]) - want[:prompt_len]
                  ).max() < F32_TOL
    cache = mutated["cache"]
    for t in range(prompt_len, total):
        got, mutated = mixer(decode=True).apply(
            {"params": m, "cache": cache}, x[:, t:t + 1],
            jnp.asarray([t], jnp.int32), mutable=["cache"])
        cache = mutated["cache"]
        assert np.abs(np.asarray(got[0, 0]) - want[t]).max() < F32_TOL, t


# ---------------------------------------------------------------- router

def test_the_softmax_router_is_its_definition():
    """The ``top_k`` largest logits chosen and a softmax over those
    alone; the sigmoid form is untouched by the new argument."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(5, 7, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 12)), jnp.float32)
    chosen, weights = hybrid.route(x, router, None, 4, 1.0,
                                   hybrid.SOFTMAX_ROUTER)
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    order = np.argsort(-logits, axis=-1)[..., :4]
    assert (np.sort(np.asarray(chosen), -1) == np.sort(order, -1)).all()
    picked = np.take_along_axis(logits, np.asarray(chosen), -1)
    want = np.exp(picked) / np.exp(picked).sum(-1, keepdims=True)
    assert np.abs(np.asarray(weights) - want).max() < 1e-5
    assert np.abs(np.asarray(weights).sum(-1) - 1).max() < 1e-5
    bias = jnp.asarray(rng.normal(size=12), jnp.float32)
    s_chosen, s_weights = hybrid.route(x, router, bias, 4, 2.5)
    g = 1 / (1 + np.exp(-logits))
    s_order = np.argsort(-(g + np.asarray(bias)), axis=-1)[..., :4]
    assert (np.sort(np.asarray(s_chosen), -1) == np.sort(s_order, -1)).all()
    s_picked = np.take_along_axis(g, np.asarray(s_chosen), -1)
    assert np.abs(np.asarray(s_weights)
                  - 2.5 * s_picked / s_picked.sum(-1, keepdims=True)
                  ).max() < 1e-5
    with pytest.raises(ValueError, match="unknown router scoring"):
        hybrid.route(x, router, None, 4, 1.0, "argmax")
