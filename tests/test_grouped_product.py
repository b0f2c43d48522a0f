"""``ops/pallas/grouped_product.py``: the products of rows that lie sorted
by expert, in interpret mode here against a plain loop over the experts in
float32 (``tests/test_tpu_compile.py`` puts the kernels before Mosaic at
the cells' shapes and counts the traces a program of ten layers holds)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas import grouped_product as gp
from horovod_tpu.ops.pallas._backend import kernels_in
from horovod_tpu.ops.pallas.expert_combine import expert_combine

BF16, F32 = jnp.bfloat16, jnp.float32


def plain(rows, w, sizes):
    """``rows[r] @ w[e]`` expert by expert in float32; NaN past the last
    expert's rows, where the product is not defined."""
    rows, w = np.asarray(rows, np.float32), np.asarray(w, np.float32)
    out = np.full((rows.shape[0], w.shape[2]), np.nan, np.float32)
    start = 0
    for e, size in enumerate(sizes):
        out[start:start + size] = rows[start:start + size] @ w[e]
        start += size
    return out


def operands(m, k, n, experts, dtype, seed=0):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.standard_normal((m, k)), dtype)
    gate, up = (jnp.asarray(rng.standard_normal((experts, k, n)) * k ** -0.5,
                            dtype) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((experts, n, k)) * n ** -0.5,
                       dtype)
    return rows, gate, up, down


# (m, k, n, sizes, operands' dtype): a row tile is 512 rows, or all of
# fewer, multiplied 128 rows at a time where it is whole blocks of them
CASES = {
    "sizes-no-multiple-of-a-tile": (1300, 64, 128,
                                    [10, 150, 3, 100, 211, 77, 600], F32),
    "an-expert-with-no-row": (300, 64, 128, [128, 0, 0, 100, 0], F32),
    "all-rows-in-one-expert": (384, 64, 128, [0, 384, 0], F32),
    "an-expert-over-many-tiles": (1600, 32, 128, [1, 1500, 2], F32),
    "no-row-at-all": (256, 64, 128, [0, 0, 0], F32),
    "fewer-rows-than-a-tile": (24, 32, 48, [5, 0, 12], F32),
    "a-ragged-last-tile": (600, 64, 128, [90, 400, 110], F32),
    "bfloat16-operands": (300, 64, 128, [10, 150, 3, 100], BF16),
    # the four cells' (k, n) = (d_model, expert width) at toy m
    "granite-4096x768": (160, 4096, 768, [70, 0, 60], BF16),
    "kexaone-6144x2048": (48, 6144, 2048, [20, 28], BF16),
    "xing-3584x1024": (140, 3584, 1024, [3, 100, 30], BF16),
    "sdar-2048x768": (130, 2048, 768, [64, 1, 0, 65], BF16),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_each_experts_rows_times_its_matrices(case):
    """The way up (gate and up in one kernel, against ``silu(a) * b`` of
    the plain products), the way down in float32 and in the operands'
    dtype, on the experts' rows; the rows past them are poisoned on the
    way in and neither kernel lets them into a row that is read."""
    m, k, n, sizes, dtype = CASES[case]
    live = sum(sizes)
    rows, gate, up, down = operands(m, k, n, len(sizes), dtype)
    rows = rows.at[live:].set(jnp.nan)
    visits = gp.group_visits(jnp.asarray(sizes, jnp.int32), m)
    close = dict(rtol=2e-2, atol=2e-2) if dtype == BF16 \
        else dict(rtol=1e-4, atol=1e-4)

    a, b = plain(rows, gate, sizes), plain(rows, up, sizes)
    hidden = gp.grouped_gate_up(rows, gate, up, visits)
    assert hidden.shape == (m, n) and hidden.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(hidden, np.float32)[:live],
        (a / (1 + np.exp(-a)) * b)[:live], **close)

    # the way down reads the hidden rows as the way up left them: what
    # lies past the experts' rows there is anything at all
    y = gp.grouped_product(hidden, down, visits, F32)
    assert y.shape == (m, k) and y.dtype == F32
    want = plain(hidden, down, sizes)
    np.testing.assert_allclose(np.asarray(y)[:live], want[:live], **close)
    same = gp.grouped_product(hidden, down, visits)
    assert same.dtype == dtype
    np.testing.assert_allclose(np.asarray(same, np.float32)[:live],
                               want[:live], **close)

    # and the next stage reads the experts' rows alone
    token = jnp.arange(m, dtype=jnp.int32) % 8
    summed = expert_combine(y, token, jnp.ones((m,), F32), live, 8)
    assert np.isfinite(np.asarray(summed)).all()
    np.testing.assert_allclose(
        np.asarray(summed),
        np.stack([want[:live][np.arange(live) % 8 == t].sum(0)
                  for t in range(8)]), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("m,sizes", [
    (1300, [10, 150, 3, 100, 211, 77, 600]), (300, [128, 0, 0, 100, 0]),
    (1024, [0, 0, 0]), (2048, [2048]), (2048, [5] * 16),
    (4096, [0, 700, 0, 0, 1500, 513, 0, 1])])
def test_the_visits_are_each_tile_under_each_of_its_experts(m, sizes):
    """:func:`group_visits` against a loop: the tiles of every expert
    that has rows, in sorted order, with the tile's rows that are the
    expert's; after the last visit the list repeats its blocks and has
    no rows."""
    rows = gp.row_tile(m)
    want, start = [], 0
    for e, size in enumerate(sizes):
        for tile in range(start // rows, -(-(start + size) // rows)
                          if size else 0):
            want.append((tile, e, max(start - tile * rows, 0),
                         min(start + size - tile * rows, rows)))
        start += size
    visits = gp.group_visits(jnp.asarray(sizes, jnp.int32), m)
    got = list(zip(*(np.asarray(a).tolist() for a in visits)))
    assert len(got) == -(-m // rows) + len(sizes) - 1
    assert got[:len(want)] == want
    last = want[-1] if want else got[0]
    assert all(v[:2] == last[:2] and v[3] == 0 for v in got[len(want):])


def test_a_layer_is_one_call_and_its_kernels_are_traced_once():
    """:func:`gated_products` is one jitted callable a layer in the
    program's trace - the visits made once inside it and walked by both
    kernels - and another layer of the same shape traces nothing anew."""
    rows, gate, up, down = operands(300, 64, 128, 4, F32)
    sizes = jnp.asarray([10, 150, 3, 100], jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: gp.gated_products(*a)
                           + gp.gated_products(*a))(
        rows, gate, up, down, sizes)
    layers = [eqn.params["jaxpr"] for eqn in jaxpr.eqns
              if eqn.primitive.name in ("pjit", "jit")]
    assert len(layers) == 2 and layers[0] is layers[1]
    assert kernels_in(layers[0]) == ["grouped_product"] * 2
    assert kernels_in(jaxpr) == ["grouped_product"] * 4
    y = gp.gated_products(rows, gate, up, down, sizes)
    a, b = plain(rows, gate, sizes), plain(rows, up, sizes)
    want = plain(a / (1 + np.exp(-a)) * b, down, sizes)
    np.testing.assert_allclose(np.asarray(y)[:263], want[:263], rtol=1e-4,
                               atol=1e-4)


def test_visits_of_another_product_are_refused():
    rows, gate, _, _ = operands(300, 64, 128, 4, F32)
    visits = gp.group_visits(jnp.asarray([1, 2, 3, 4], jnp.int32), 600)
    with pytest.raises(ValueError, match="visits of another product"):
        gp.grouped_product(rows, gate, visits)
