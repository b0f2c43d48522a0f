"""Power retention (``horovod_tpu/models/hybrid.py``: gated, normalised
linear attention of degree 2 over grouped heads) and the dense serving
engine over a cache of states alone, against the plain reference
(``benchmark/reference_brumby.py``: the attention form in float32, which
squares ``q . k`` and never builds a feature, a state or a normaliser) at
toy sizes with seeded weights.

Tolerances, on logits whose standard deviation is about 0.23 here:

* ``F32_TOL`` 5e-5 - program and reference both in float32 on the CPU.
  What differs is the order of float32 sums and, more than in any other
  model here, the *form*: the reference squares one 32-term dot product,
  the program sums 528 products of features whose terms cancel, so its
  weights carry an absolute error of about 1e-7 where the reference's
  carry a relative one. Measured 2e-6 to 1e-5 on logits.
* ``BF16_TOL`` 6e-2 - the program in bfloat16 (activations and matrix
  operands, the features and the state of a prefill's between-chunk
  products among them) against the float32 reference: measured 1.7e-2 to
  2.0e-2 over three token draws, so three times the sound reading. The
  float8 control (the reference's dense multiplications in float8_e4m3)
  reads 0.13 to 0.17 on the same inputs, so it fails both.
* the forms against one another (no model round them): 1e-4 on outputs
  of size ~3 where the normaliser is at least 0.1; under it the
  recurrent form's absolute error in a weight (about 1e-6 here) is no
  longer small beside the sum of the weights, and what is compared is
  the error times the normaliser, that is the numerator.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_brumby as ref
from benchmark import weights_brumby
from benchmark.runners.serve_brumby import build_model
from horovod_tpu.models import hybrid
from toy_models import (BRUMBY_CFG as CFG, brumby_reference as reference,
                        brumby_weights as weights, tokens)

F32_TOL, BF16_TOL = 5e-5, 6e-2
HEADS, GROUPS, D = 4, 2, 32
WIDTH = D * (D + 1) // 2
TURNS = D // 2 + 1          # rows of distances: the cache pads to these


@pytest.fixture(scope="module")
def served():
    return weights(), build_model(CFG)


# ----------------------------------------------------------- the mechanism

def test_features_square_the_dot_product():
    """``phi(x) . phi(y) = (x . y)^2 / d`` to float32 rounding, for every
    pair of 64 random vectors, and ``phi`` has d (d + 1) / 2 entries in
    its d/2 + 1 rows of d places: the last row's other half is zeros."""
    rng = np.random.default_rng(0)
    for d in (32, 128):
        x = jnp.asarray(rng.normal(size=(64, d)), jnp.float32)
        f = np.asarray(hybrid.power_features(x), np.float64)
        assert f.shape == (64, (d // 2 + 1) * d)
        assert (f[:, d * (d + 1) // 2:] == 0).all()
        assert (f[:, :d * (d + 1) // 2] != 0).all()
        x = np.asarray(x, np.float64)
        want = (x @ x.T) ** 2 / d
        assert np.abs(f @ f.T - want).max() < 1e-5 * want.max()


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_the_read_kernel_is_the_product_with_the_features(dtype, tol):
    """``ops/pallas/power_retention.read_state`` (interpret mode here)
    against ``phi(q)^T S`` and ``phi(q)^T z`` with the features written
    out: the kernel makes each row of distances as ``q`` times a rotation
    of ``q`` and takes the rows' weights from the state's side. In
    bfloat16 both round the features, not in the same order (the kernel
    before the weight, the written-out form after it)."""
    from horovod_tpu.ops.pallas import power_retention

    rng = np.random.default_rng(6)
    batch, rows = 3, 512
    turns = power_retention.turns(D)
    q = jnp.asarray(rng.normal(size=(batch, rows, D)), dtype)
    state = jnp.asarray(rng.normal(size=(batch, WIDTH, D)), jnp.float32)
    norm = jnp.asarray(rng.random(size=(batch, WIDTH)), jnp.float32)
    grow = ((0, 0), (0, turns * D - WIDTH))
    weights = power_retention.turn_weights(D)
    num, den = power_retention.read_state(
        q, (jnp.pad(state, grow + ((0, 0),)).reshape(batch, turns, D, D)
            * weights[..., None]).astype(dtype),
        jnp.pad(norm, grow).reshape(batch, turns, D) * weights)
    f_q = hybrid.power_features(q)[..., :WIDTH].astype(dtype).astype(
        jnp.float32)
    rounded = state.astype(dtype).astype(jnp.float32)
    want_num = np.einsum("bmn,bne->bme", f_q, rounded)
    want_den = np.einsum("bmn,bn->bm", f_q, norm)
    assert np.abs(np.asarray(num) - want_num).max() \
        < tol * np.abs(want_num).max()
    assert np.abs(np.asarray(den) - want_den).max() \
        < tol * np.abs(want_den).max()
    # 640 queries (a 128-token chunk of five heads a group) go in blocks
    # of 160; a count past a block that no 16 divides is refused
    more = jnp.concatenate([q, q[:, :128]], axis=1)
    padded = (jnp.pad(state, grow + ((0, 0),)).reshape(batch, turns, D, D)
              * weights[..., None]).astype(dtype)
    again, _ = power_retention.read_state(
        more, padded, jnp.pad(norm, grow).reshape(batch, turns, D) * weights)
    assert np.array_equal(np.asarray(again[:, :rows]), np.asarray(num))
    with pytest.raises(ValueError, match="multiple of 16"):
        power_retention.read_state(more[:, :300], padded, norm)


def gates(kind, rng, shape):
    """Log-gates: spread over (-1.5, 0); all but open (a memory of ten
    thousand tokens); all but shut (each token forgets the last)."""
    return {"random": -0.5 * np.abs(rng.normal(size=shape)),
            "near_0": -1e-4 * rng.random(shape),
            "near_-10": -10.0 + 0.1 * rng.normal(size=shape)}[kind]


def token_by_token(q, k, v, g):
    """The recurrence over (batch, seq, ...) inputs from a zero state:
    the outputs (batch, seq, heads, d), the state and the normaliser
    after every token (seq, batch, ...) and every query's normalising
    sum (batch, seq, heads, 1)."""
    out, states, norms, den = map(np.asarray, _token_by_token(q, k, v, g))
    return (np.moveaxis(out, 0, 1), states, norms,
            np.moveaxis(den, 0, 1)[..., None])


@jax.jit
def _token_by_token(q, k, v, g):
    batch = q.shape[0]

    def one(carry, xs):
        q_t, k_t, v_t, g_t = xs
        state, norm, o = hybrid.retention_step(*carry, q_t, k_t, v_t, g_t)
        f_q = hybrid.power_features(q_t).reshape(
            batch, GROUPS, -1, TURNS * D)
        den = jnp.einsum("bgrn,bgn->bgr", f_q,
                         norm.reshape(batch, GROUPS, -1)).reshape(batch, HEADS)
        return (state, norm), (o, state, norm, den)

    carry = (jnp.zeros((batch, GROUPS, TURNS, D, D), jnp.float32),
             jnp.zeros((batch, GROUPS, TURNS, D), jnp.float32))
    return jax.lax.scan(
        one, carry, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g)))[1]


@pytest.mark.parametrize("kind", ["random", "near_0", "near_-10"])
@pytest.mark.parametrize("length,chunk", [(75, 16), (64, 64), (128, 256)])
def test_the_three_forms_agree(kind, length, chunk):
    """Attention form (the reference's) against chunked against token by
    token, and the chunked form's state after each row's own length
    against the recurrence's after as many tokens."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, length, HEADS, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, length, GROUPS, D)), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(gates(kind, rng, (2, length, GROUPS)), jnp.float32)
    lengths = [length, length - 9]
    want = np.stack([np.asarray(ref.retention(q[b], k[b], v[b], g[b], 1e-6))
                     for b in range(2)])
    out, last, last_norm = hybrid.retention_chunked(
        q, k, v, g, jnp.asarray(lengths, jnp.int32), chunk, 1e-6,
        jnp.float32)
    steps, states, norms, den = token_by_token(q, k, v, g)
    for got, rows in ((steps, (length, length)), (np.asarray(out), lengths)):
        for b, n in enumerate(rows):
            err = np.abs(got[b, :n] - want[b, :n])
            small = np.broadcast_to(den[b, :n] < 0.1, err.shape)
            assert err[~small].max() < 1e-4
            assert (err * den[b, :n])[small].max(initial=0.0) < 1e-5
    # the state after each row's own length, not after the padding
    for b, n in enumerate(lengths):
        for got, then in ((last, states), (last_norm, norms)):
            assert np.abs(np.asarray(got[b]) - then[n - 1, b]).max() < 1e-4
    assert np.abs(np.asarray(last[1]) - states[-1, 1]).max() > 1e-2


def test_grouped_heads_read_their_own_groups_state():
    """Two query heads a key/value head: heads 0 and 1 read group 0,
    heads 2 and 3 group 1. With group 1's values zeroed its heads'
    outputs vanish and group 0's do not change, in every form."""
    rng = np.random.default_rng(2)
    length = 40
    q = jnp.asarray(rng.normal(size=(1, length, HEADS, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, length, GROUPS, D)), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(gates("random", rng, (1, length, GROUPS)), jnp.float32)
    cut = v.at[:, :, 1].set(0.0)
    whole, _, _ = hybrid.retention_chunked(q, k, v, g, None, 16, 1e-6,
                                           jnp.float32)
    part, _, _ = hybrid.retention_chunked(q, k, cut, g, None, 16, 1e-6,
                                          jnp.float32)
    want = np.asarray(ref.retention(q[0], k[0], cut[0], g[0], 1e-6))
    assert np.abs(np.asarray(part)[0] - want).max() < 1e-4
    assert np.abs(np.asarray(part)[0, :, 2:]).max() == 0.0
    assert np.array_equal(np.asarray(part)[0, :, :2],
                          np.asarray(whole)[0, :, :2])
    assert np.abs(np.asarray(whole)[0, :, 2:]).max() > 0.1
    steps = token_by_token(q, k, cut, g)[0]
    assert np.abs(steps[0, :, 2:]).max() == 0.0
    assert np.abs(steps[0] - want).max() < 1e-4


def test_a_padded_prefill_leaves_the_unpadded_ones_state():
    """``lengths`` in a padded batch: the state, the normaliser and the
    rows before the length are those of the same tokens unpadded (to the
    order of float32 sums: the chunks fall elsewhere), whatever the
    padding holds."""
    rng = np.random.default_rng(3)
    n, padded = 83, 128
    q = jnp.asarray(rng.normal(size=(1, padded, HEADS, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, padded, GROUPS, D)), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(gates("random", rng, (1, padded, GROUPS)), jnp.float32)
    short = hybrid.retention_chunked(q[:, :n], k[:, :n], v[:, :n], g[:, :n],
                                     None, 32, 1e-6, jnp.float32)
    long = hybrid.retention_chunked(q, k, v, g, jnp.asarray([n], jnp.int32),
                                    32, 1e-6, jnp.float32)
    assert np.abs(np.asarray(long[0])[:, :n]
                  - np.asarray(short[0])).max() < 1e-5
    for a, b in zip(long[1:], short[1:]):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5
    unmasked = hybrid.retention_chunked(q, k, v, g, None, 32, 1e-6,
                                        jnp.float32)
    assert np.abs(np.asarray(unmasked[1]) - np.asarray(short[1])).max() > 1e-2


@pytest.mark.parametrize("kind", ["near_0", "random"])
@pytest.mark.parametrize("short", [0, 9], ids=["whole", "padded"])
@pytest.mark.parametrize("pieces", [2, 3])
def test_a_sequence_in_pieces_is_the_whole_sequence(pieces, short, kind):
    """``initial``: a sequence cut at multiples of the chunk, each piece
    continuing from the state and the normaliser the piece before it
    returned (in the cache's layout), makes the whole sequence's products
    in the whole sequence's order: outputs, state and normaliser are the
    same numbers, with the true length inside the last piece too."""
    rng = np.random.default_rng(4)
    length, chunk, dtype = 96, 16, jnp.float32
    q = jnp.asarray(rng.normal(size=(2, length, HEADS, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(2, length, GROUPS, D)), dtype)
            for _ in range(2))
    g = jnp.asarray(gates(kind, rng, (2, length, GROUPS)), jnp.float32)
    lengths = jnp.asarray([length, length - short], jnp.int32)
    whole = hybrid.retention_chunked(q, k, v, g, lengths, chunk, 1e-6, dtype)
    size = length // pieces
    carried, outs = None, []
    for at in range(0, length, size):
        cut = slice(at, at + size)
        out, *carried = hybrid.retention_chunked(
            q[:, cut], k[:, cut], v[:, cut], g[:, cut],
            jnp.clip(lengths - at, 0, size), chunk, 1e-6, dtype,
            initial=carried)
        outs.append(np.asarray(out, np.float32))
    assert carried[0].shape == (2, GROUPS, TURNS, D, D)
    got = np.concatenate(outs, axis=1)
    for b, n in enumerate(np.asarray(lengths)):
        assert np.array_equal(got[b, :n],
                              np.asarray(whole[0], np.float32)[b, :n])
    for mine, want in zip(carried, whole[1:]):
        assert np.array_equal(np.asarray(mine), np.asarray(want))
    # and a piece that starts from nothing is not the same: the carry is
    # what holds the tokens before it (its outputs' first row sees them
    # whatever the gates have let go of since)
    alone = hybrid.retention_chunked(
        q[:, -size:], k[:, -size:], v[:, -size:], g[:, -size:], None, chunk,
        1e-6, dtype)
    assert np.abs(np.asarray(alone[0])[:, 0] - outs[-1][:, 0]).max() > 1e-2


# --------------------------------------------------------------- the model

@pytest.mark.parametrize("length", [100, 301])
def test_forward_matches_the_plain_reference(served, length):
    params, model = served
    toks = tokens(length)
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(toks)[None]))[0]
    want = reference(toks)
    assert np.abs(got - want).max() < F32_TOL
    control = reference(toks, "fp8")
    assert np.abs(control - want).max() > 100 * F32_TOL


def test_parameter_layout_is_the_weight_makers(served):
    params, model = served
    init = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree.structure(init) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree.leaves(init), jax.tree.leaves(params)))
    assert weights_brumby.count(CFG) == sum(
        x.size for x in jax.tree.leaves(params))
    # the published widths: 330.3M parameters a layer, 1,555.8M in the
    # embedding and the head
    full = dict(vocab_size=151936, d_model=5120, d_ff=17408, num_heads=40,
                num_kv_heads=8, head_dim=128)
    one, none = (weights_brumby.count(dict(full, num_layers=n))
                 for n in (1, 0))
    assert none == 2 * 151936 * 5120 + 5120
    assert 330.3e6 < one - none < 330.4e6


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_bfloat16_forward_stays_within_rounding_of_the_reference(served,
                                                                 seed):
    params, _ = served
    toks = tokens(301, seed=seed)
    got = np.asarray(build_model(dict(CFG, dtype="bfloat16")).apply(
        {"params": params}, jnp.asarray(toks)[None]))[0]
    want = reference(toks)
    assert np.abs(got - want).max() < BF16_TOL
    assert np.abs(reference(toks, "fp8") - want).max() > BF16_TOL


def test_neutral_scalings_leave_the_trunk_alone():
    """``scale_depth=None`` is a residual scale of 1 whatever the depth
    (the model has no muP scalings), not ``1 / sqrt(depth)``."""
    toks = jnp.asarray(tokens(20))[None]
    plain = build_model(CFG)
    scaled = plain.clone(scale_depth=1.0)
    params = weights()
    a = np.asarray(plain.apply({"params": params}, toks))
    b = np.asarray(scaled.apply({"params": params}, toks))
    assert np.abs(a - b).max() > 1e-2
