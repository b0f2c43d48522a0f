"""The block-sparse prompt kernel (``ops/pallas/sparse_attention.py``,
interpret mode here) against a plain float32 masked softmax on the same
table of bits: every causal block, the model's own selection past
``dense_len``, lengths that are no whole tiles, queries that begin after
the first key, both dtypes, and dead key tiles counted against the bits.
``tests/test_hybrid_model.py`` holds the layer that calls it against the
cell's reference; ``tests/test_tpu_compile.py`` puts it before Mosaic.

Tolerances: float32 2e-6 on outputs of order 1 (measured 4e-7 to 1.3e-6:
the order of float32 sums, ``exp2`` against ``exp``); bfloat16 2e-2
(measured 8e-3: products of bfloat16 inputs and probabilities cast to
bfloat16 before PV, against float64).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.metrics import registry
from horovod_tpu.models import hybrid
from horovod_tpu.ops.pallas import sparse_attention as sa
from toy_models import SPARSE

TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}
HEADS, GROUPS, D, SIZE = 4, 2, 32, SPARSE["block_size"]


def reference(q, k, v, bits, size, q_offset=0):
    """Softmax over the keys at or before each query whose block is set,
    in float64: (batch, queries, heads, d)."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    queries, keys, per = q.shape[1], k.shape[1], q.shape[2] // k.shape[2]
    s = np.einsum("bthd,bshd->bhts", q, np.repeat(k, per, 2)) \
        / np.sqrt(q.shape[-1])
    mask = np.repeat(np.asarray(bits) != 0, size, -1)[..., :keys]
    mask = mask & (np.arange(keys)[None, :]
                   <= (q_offset + np.arange(queries))[:, None])
    s = np.where(np.repeat(mask, per, 1), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhts,bshd->bthd", p / p.sum(-1, keepdims=True),
                     np.repeat(v, per, 2))


def draw(queries, keys, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(1, queries, HEADS, D)), dtype),
            jnp.asarray(rng.normal(size=(1, keys, GROUPS, D)), dtype),
            jnp.asarray(rng.normal(size=(1, keys, GROUPS, D)), dtype))


def model_bits(q, k):
    """The table the layer itself makes for this prompt (padded, as
    there, to whole blocks of queries), its own rows."""
    batch, seq = q.shape[:2]
    pad = ((0, 0), (0, -seq % min(hybrid.QUERY_BLOCK, seq)), (0, 0), (0, 0))
    q, k = jnp.pad(q, pad), jnp.pad(k, pad)
    compressed = hybrid.compress_keys(k, SPARSE["kernel"], SPARSE["stride"])
    return hybrid.prompt_block_choice(
        q.reshape(batch, -1, GROUPS, HEADS // GROUPS, D), compressed,
        SPARSE, q.dtype)[:, :, :seq, :-(-seq // SIZE)]


def random_bits(queries, keys, q_offset, density, seed=0):
    """Each block at or before the query's own with probability
    ``density``, its own always: (1, GROUPS, queries, blocks) bool."""
    rng = np.random.default_rng(seed)
    blocks = -(-keys // SIZE)
    own = (q_offset + np.arange(queries)) // SIZE
    bits = rng.random((1, GROUPS, queries, blocks)) < density
    bits &= np.arange(blocks)[None, :] <= own[:, None]
    bits[:, :, np.arange(queries), own] = True
    return bits


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("length", [96, 128, 301, 640],
                         ids=["under_dense_len", "at_dense_len",
                              "past_it_no_whole_tile", "two_query_tiles"])
def test_a_prompt_under_the_models_own_selection(length, dtype):
    """At or under ``dense_len`` (128) every causal block is set; past it
    the layer's own ``select_blocks`` chose ``topk`` a query."""
    q, k, v = draw(length, length, dtype)
    bits = model_bits(q, k)
    sums = np.asarray(bits)[0, 0].sum(-1)
    own = np.arange(length) // SIZE
    assert (sums[:SPARSE["dense_len"]] == own[:SPARSE["dense_len"]] + 1).all()
    assert (sums[SPARSE["dense_len"]:] == SPARSE["topk"]).all()
    got, share = sa.sparse_prompt_attention(q, k, v, bits, block_size=SIZE)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = reference(q, k, v, bits, SIZE)
    assert np.abs(np.asarray(got, np.float64) - want).max() < TOL[dtype]
    assert float(share) == 1.0          # a tile of this width never dies


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("queries,keys,q_offset", [
    (200, 456, 256), (128, 333, 205), (77, 77, 0)],
    ids=["from_a_tile_edge", "from_inside_a_block", "no_offset"])
def test_queries_that_begin_after_the_first_key(queries, keys, q_offset,
                                                dtype):
    q, k, v = draw(queries, keys, dtype, seed=1)
    bits = random_bits(queries, keys, q_offset, 0.3)
    got, _ = sa.sparse_prompt_attention(q, k, v, bits, block_size=SIZE,
                                        q_offset=q_offset)
    want = reference(q, k, v, bits, SIZE, q_offset)
    assert np.abs(np.asarray(got, np.float64) - want).max() < TOL[dtype]


@pytest.mark.parametrize("tiles", [(128, 128), (256, 128), (128, 256)],
                         ids=["128x128", "256x128", "128x256"])
def test_dead_key_tiles_are_skipped_and_counted(tiles):
    """Every query attends block 0 and the blocks of its last 40 keys,
    so the key tiles between are dead: the kernel's share is what the
    bits say, and the output what the reference computes."""
    tq, tk = tiles
    length, blocks = 1024, 1024 // SIZE
    q, k, v = draw(length, length, jnp.float32, seed=2)
    at = np.arange(length)
    block = np.arange(blocks)[None, :]
    bits = (block == 0) | ((block >= (at[:, None] - 40) // SIZE)
                           & (block <= at[:, None] // SIZE))
    bits = np.broadcast_to(bits, (1, GROUPS, length, blocks))
    got, share = sa._sparse_prompt_attention(
        q, k, v, jnp.asarray(bits), block_size=SIZE, scale=D ** -0.5,
        q_offset=0, tiles=tiles, interpret=True)
    want = reference(q, k, v, bits, SIZE)
    assert np.abs(np.asarray(got, np.float64) - want).max() < 2e-6
    # by hand from the bits: a (query tile, key tile) pair under the
    # diagonal is live where any of its bits is set, and counts the key
    # blocks of the tile that begin at or before the tile's last query
    ran = under = 0
    for i in range(length // tq):
        last = (i + 1) * tq - 1
        for j in range(last // tk + 1):
            n = min(last // SIZE + 1, (j + 1) * tk // SIZE) - j * tk // SIZE
            under += n
            if bits[0, 0, i * tq:(i + 1) * tq,
                    j * tk // SIZE:(j + 1) * tk // SIZE].any():
                ran += n
    assert 0.3 < ran / under < 0.9
    assert float(share) == pytest.approx(ran / under, abs=1e-6)
    sa.note_live_block_share(share)
    gauge = registry().snapshot()["sparse.live_block_share"]
    assert gauge["values"][0]["value"] == pytest.approx(ran / under, abs=1e-6)


def test_the_schedule_visits_nothing_above_the_diagonal():
    qi, kj = sa.schedule(4, 2, 512, 1024, 0)
    assert list(zip(qi, kj)) == [(0, 0), (1, 0), (2, 0), (2, 1), (3, 0),
                                 (3, 1)]
    # queries from position 1,024: every row reaches the second key tile
    qi, kj = sa.schedule(2, 2, 512, 1024, 1024)
    assert list(zip(qi, kj)) == [(0, 0), (0, 1), (1, 0), (1, 1)]
    # the cell's two buckets: 0.53 and 0.52 of the square's tiles
    for seq, steps in ((16384, 136), (32768, 528)):
        tq, tk = sa.tiles_of(seq, seq, 64)
        assert len(sa.schedule(seq // tq, seq // tk, tq, tk, 0)[0]) == steps


@pytest.mark.parametrize("length,block_size,tiles", [
    (32768, 64, (1024, 1024)), (16384, 64, (1024, 1024)),
    (301, 16, (512, 512)), (100, 16, (128, 128)), (4096, 8, (1024, 1024)),
    (200, 2048, (256, 2048))],
    ids=str)
def test_tile_sides(length, block_size, tiles):
    assert sa.tiles_of(length, length, block_size) == tiles
    tk = tiles[1]
    assert tk % block_size == 0 and sa.LANES % (tk // block_size) == 0


@pytest.mark.parametrize("heads,kv_heads,head_dim,block_size", [
    (4, 3, 32, 16), (4, 2, 12, 16), (4, 2, 32, 4), (4, 2, 32, 48)],
    ids=["heads_no_multiple", "head_width_off_the_sublanes",
         "blocks_under_eight_keys", "blocks_no_power_of_two"])
def test_outside_the_envelope_is_refused_by_name(heads, kv_heads, head_dim,
                                                 block_size):
    assert not sa.takes_kernel(heads, kv_heads, head_dim, block_size)
    assert sa.takes_kernel(32, 2, 128, 64) and sa.takes_kernel(2, 2, 8, 8)
    q = jnp.zeros((1, 64, heads, head_dim))
    k = jnp.zeros((1, 64, kv_heads, head_dim))
    bits = jnp.ones((1, kv_heads, 64, -(-64 // block_size)), bool)
    with pytest.raises(ValueError, match="envelope"):
        sa.sparse_prompt_attention(q, k, k, bits, block_size=block_size)


def test_fewer_keys_than_the_queries_reach_is_refused():
    q, k, v = draw(64, 64, jnp.float32)
    with pytest.raises(ValueError, match="64 queries from position 8"):
        sa.sparse_prompt_attention(q, k, v, random_bits(64, 64, 0, 1.0),
                                   block_size=SIZE, q_offset=8)


def test_the_engine_reads_the_share_with_the_first_token():
    """A block-sparse model's prefill hands the kernel's share up beside
    max |logit| (one array, the transfer that already happens): the
    gauge, the span and ``stats()`` carry it; a model without the layer
    keeps its scalar and says ``None``."""
    from benchmark.runners.serve_sala import build_model
    from horovod_tpu import tracing
    from horovod_tpu.serve.kv_cache import DecodeEngine
    from toy_models import SALA_ALL, sala_weights, tokens

    cfg, params = sala_weights(SALA_ALL)
    engine = DecodeEngine(build_model(cfg), params, num_slots=2)
    assert engine.stats()["prefill_sparse_kernel"] is None   # none traced
    sa.note_live_block_share(0.0)
    pending = engine.prefill(0, tokens(300).tolist())
    assert pending._max_abs.shape == (2,)
    token, max_abs = pending
    assert 0 <= token < cfg["vocab_size"] and max_abs > 0
    assert engine.stats()["prefill_sparse_kernel"] is True
    gauge = registry().snapshot()["sparse.live_block_share"]
    assert gauge["values"][0]["value"] == 1.0
    span = [s for s in tracing.spans() if s["name"] == "engine.prefill"][-1]
    assert span["live_block_share"] == 1.0 and span["sparse"] is True

    lightning = tuple("lightning-attn" for _ in SALA_ALL)
    cfg, params = sala_weights(lightning)
    engine = DecodeEngine(build_model(cfg), params, num_slots=2)
    pending = engine.prefill(0, tokens(300).tolist())
    assert pending._max_abs.shape == ()
    assert engine.stats()["prefill_sparse_kernel"] is None
