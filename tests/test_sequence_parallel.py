"""Ring attention + Ulysses sequence parallelism vs single-device ground
truth, on the virtual 8-device CPU mesh (SURVEY.md §4 strategy)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.metrics import registry
from horovod_tpu.ops.pallas import (
    attention_reference,
    flash_attention,
    flash_attention_partial,
    merge_partials,
)
from horovod_tpu.ops.pallas.flash_attention import live_tile_share
from horovod_tpu.parallel.ring import ring_attention
from horovod_tpu.parallel.ulysses import ulysses_attention

# the module: the package's attribute of that name is the function
flash_module = importlib.import_module(
    "horovod_tpu.ops.pallas.flash_attention")

B, H, S, D = 2, 8, 256, 32
N_DEV = 8


def _qkv(seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    return mk(), mk(), mk()


def _seq_mesh():
    return Mesh(np.array(jax.devices()).reshape(N_DEV), ("sp",))


# ---------------------------------------------------------------------------
# single-device kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(o, ref, atol=2e-5)


def _qkv_sized(seed, q_seq, kv_seq, dtype=jnp.float32):
    """``_qkv`` at other lengths: the default 256 x 256 is ``_qkv``'s own
    (batch 2, 8 heads); longer cases take one row of two heads, which is
    what keeps them quick in interpret mode."""
    if q_seq == kv_seq == S:
        return _qkv(seed, dtype)
    rng = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rng.normal(size=(1, 2, n, D)), dtype)
    return mk(q_seq), mk(kv_seq), mk(kv_seq)


@functools.lru_cache(maxsize=None)
def _dispatch_ref_grads(causal, q_seq=S, kv_seq=S):
    """Reference gradients for test_flash_dispatch_matrix: identical
    across the parametrizations of one shape, so computed once per
    causal flag and shape."""
    q, k, v = _qkv_sized(7, q_seq, kv_seq)

    def loss(q, k, v):
        return jnp.mean(attention_reference(
            q, k, v, causal=causal).astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _tiled(q_seq, kv_seq=None, sides=None, traced=False):
    """A case of the causal schedule (PR 42): the lengths, the tile sides
    the schedule may choose from (None: its own), and whether the offsets
    arrive traced (the ring's path: the ladder of rungs) or as Python
    zeros (the in-model path: one schedule built at trace time)."""
    return dict(blocks=(512, 1024, 1024, 1024), q_seq=q_seq,
                kv_seq=kv_seq or q_seq, sides=sides, traced=traced)


_DISPATCH_CASES = [
    # (block_q, block_k, bwd_block_q, bwd_block_k) spanning the dispatch
    # matrix at S=256:
    ("all-single", (512, 1024, 1024, 1024)),  # resident fwd + dq/dkv
    ("dq-single-wedge", (128, 1024, 128, 1024)),  # resident fwd/dq, dkv general
    ("dkv-single", (512, 1024, 1024, 128)),   # dq general, dkv resident
    ("all-general", (64, 64, 64, 64)),        # fully general (online softmax)
    # the causal schedule, every tile side it can choose at each length
    ("tile256-s256", _tiled(256)),
    ("tile128-s256", _tiled(256, sides=(128,))),
    ("tile128-s384", _tiled(384)),
    ("tile256-s1024", _tiled(1024)),
    ("tile128-s1024", _tiled(1024, sides=(128,))),
    ("ladder256-s256", _tiled(256, traced=True)),
    ("ladder128-s256", _tiled(256, sides=(128,), traced=True)),
    ("ladder128-s384", _tiled(384, traced=True)),
    ("ladder256-s1024", _tiled(1024, traced=True)),
    # more rungs than a ladder may have: one whole masked tile a block
    ("ladder-whole-s640", _tiled(640, traced=True)),
    # q_seq != kv_seq: rows past the last key, keys no row reaches
    ("q512-kv256", _tiled(512, 256)),
    ("q256-kv512", _tiled(256, 512)),
    ("ladder-q512-kv256", _tiled(512, 256, traced=True)),
    ("ladder-q256-kv512", _tiled(256, 512, traced=True)),
]


def _dispatch_params():
    """The four block cases and the q_seq != kv_seq ones both ways; the
    tile cases causal only (the non-causal bodies have no tiles)."""
    for name, case in _DISPATCH_CASES:
        both = isinstance(case, tuple) or case["q_seq"] != case["kv_seq"]
        for causal in ((False, True) if both else (True,)):
            yield pytest.param(causal, case, id=f"{name}-{causal}")


@pytest.mark.parametrize("causal,case", list(_dispatch_params()))
def test_flash_dispatch_matrix(causal, case, monkeypatch):
    """The resident-side specialization has four dispatch paths (a
    direct-softmax forward with the whole key sequence resident;
    scratch-free dq and dk/dv kernels composing with the general pair),
    and since PR 42 the causal ones walk their score matrix in tiles,
    built for one schedule (offsets Python zeros) or as a ladder of
    rungs (traced offsets). Every combination must match the reference
    in both output and gradients: this pins the path selection itself,
    not just the default."""
    if isinstance(case, tuple):
        case = dict(blocks=case, q_seq=S, kv_seq=S, sides=None,
                    traced=False)
    bq, bk, bbq, bbk = case["blocks"]
    q, k, v = _qkv_sized(7, case["q_seq"], case["kv_seq"])
    if case["sides"]:
        monkeypatch.setattr(flash_module, "_TILE_SIDES", case["sides"])

    kw = dict(causal=causal, block_q=bq, block_k=bk,
              bwd_block_q=bbq, bwd_block_k=bbk)
    if case["traced"]:
        flash = jax.jit(lambda q, k, v, at: flash_attention(
            q, k, v, q_offset=at, k_offset=at, **kw))
        fn = lambda q, k, v: flash(q, k, v, jnp.int32(0))
    else:
        fn = lambda q, k, v: flash_attention(q, k, v, **kw)
    o = fn(q, k, v)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(o, ref, atol=2e-5)

    def loss(q, k, v):
        # squared output -> the cotangent do = 2*o/n VARIES per row and
        # block, so a backward BlockSpec indexing the wrong do block
        # cannot cancel out (a constant cotangent would hide it)
        return jnp.mean(fn(q, k, v).astype(jnp.float32) ** 2)

    g_ref = _dispatch_ref_grads(causal, case["q_seq"], case["kv_seq"])
    g_fl = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    q, k, v = _qkv(1)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=causal).astype(jnp.float32) ** 2)

    g = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("path", ["general", "resident-s1024"])
def test_flash_bf16_inputs(causal, path):
    """bf16 q/k/v (what the models feed the kernels): operands are
    up-cast to f32 inside the kernel. Outputs and grads must match the
    f32 reference computed on the same (bf16-rounded) inputs to
    bf16-appropriate tolerance. ``general``: 64-wide blocks at S=256;
    ``resident-s1024``: the models' own call (default blocks) at GPT-2's
    length, which when causal is the tiled schedule of PR 42."""
    if path == "general":
        q, k, v = _qkv(7, dtype=jnp.bfloat16)
        blocks = dict(block_q=64, block_k=64)
    else:
        q, k, v = _qkv_sized(7, 1024, 1024, dtype=jnp.bfloat16)
        blocks = {}
    ref = attention_reference(q, k, v, causal=causal).astype(jnp.float32)

    o = flash_attention(q, k, v, causal=causal, **blocks)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o, np.float32), ref, atol=2e-2)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=causal).astype(jnp.float32) ** 2)

    g = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    scale = max(float(jnp.abs(x).max()) for x in gr)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=3e-2 * scale)


def _at_offsets(fn, traced):
    """``fn(q_offset, k_offset)`` with the offsets as Python numbers or,
    as the ring passes them, traced scalars."""
    if not traced:
        return fn
    jitted = jax.jit(fn)
    return lambda q_off, k_off: jitted(jnp.int32(q_off), jnp.int32(k_off))


@pytest.mark.parametrize("traced", [False, True], ids=["python", "traced"])
@pytest.mark.parametrize("blocks", [
    dict(),                          # resident: the tiled schedule's rungs
    dict(block_q=64, block_k=64),    # general kernels
], ids=["resident", "general"])
def test_flash_cross_offsets(traced, blocks):
    """Offsets shift the causal mask to global positions."""
    q, k, v = _qkv(2)
    full = _at_offsets(lambda a, b: flash_attention(
        q, k, v, causal=True, q_offset=a, k_offset=b, **blocks), traced)
    partial = _at_offsets(lambda a, b: flash_attention_partial(
        q, k, v, causal=True, q_offset=a, k_offset=b, **blocks), traced)
    # queries are the second half of a virtual 2S sequence; keys the first.
    o = full(S, 0)
    # every key is in the past -> equivalent to non-causal
    ref = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(o, ref, atol=2e-5)
    # queries strictly before all keys -> fully masked -> zeros
    o2, lse2 = partial(0, S)
    assert float(jnp.abs(o2).max()) == 0.0
    assert bool(jnp.all(lse2 == float("-inf")))


@pytest.mark.parametrize("traced", [False, True], ids=["python", "traced"])
@pytest.mark.parametrize("q_off,k_off", [
    (0, 0),        # the in-model call when the offsets are Python zeros
    (S, 0),        # every key in every row's past: no mask is needed
    (0, S),        # the ring's future shard: zeros, lse -inf, no gradient
    (0, S // 2),   # tile-aligned crossing: rows under S/2 see no key
    (100, 37),     # the diagonal crosses tiles off their corners
    (37, 100),     # ... and leaves the first 63 rows without a key
    (3 * S, S),    # offsets far past the shard (a long ring)
], ids=lambda x: str(x))
def test_flash_cross_offsets_rows_and_grads(traced, q_off, k_off):
    """The ring's contract at every place the diagonal can take against
    a shard: a row no key reaches gives zeros and ``lse = -inf``, the
    others are masked row by row, and dq, dk and dv match the reference's
    (keys no row reaches get none)."""
    q, k, v = _qkv(11)
    ref_fn = lambda q, k, v: attention_reference(
        q, k, v, causal=True, q_offset=q_off, k_offset=k_off)
    loss = lambda fn: lambda q, k, v: jnp.mean(
        fn(q, k, v).astype(jnp.float32) ** 2)

    o, lse = _at_offsets(lambda a, b: flash_attention_partial(
        q, k, v, causal=True, q_offset=a, k_offset=b), traced)(q_off, k_off)
    np.testing.assert_allclose(o, ref_fn(q, k, v), atol=2e-5)
    no_key = q_off + np.arange(S) < k_off
    assert np.array_equal(np.isneginf(np.asarray(lse)),
                          np.broadcast_to(no_key, lse.shape))
    np.testing.assert_array_equal(np.asarray(o)[:, :, no_key], 0.0)

    grads = _at_offsets(lambda a, b: jax.grad(loss(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, q_offset=a, k_offset=b)),
        argnums=(0, 1, 2))(q, k, v), traced)(q_off, k_off)
    for got, want in zip(grads, jax.grad(loss(ref_fn), argnums=(0, 1, 2))(
            q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5)
    no_row = k_off + np.arange(S) > q_off + S - 1
    for got in grads[1:]:
        np.testing.assert_array_equal(np.asarray(got)[:, :, no_row], 0.0)


@pytest.mark.parametrize("q_seq,kv_seq,tile,kinds,share", [
    (1024, 1024, 128, ("fwd", "dq", "dkv"), 36 / 64),
    (1024, 1024, 256, ("fwd", "dq", "dkv"), 10 / 16),
    (1024, 1024, 512, ("fwd", "dq", "dkv"), 3 / 4),
    (1024, 1024, (512, 1024), ("fwd", "dq", "dkv"), 1.0),  # the old dk/dv
    (512, 256, 256, ("fwd", "dq", "dkv"), 1.0),
    (256, 512, 256, ("fwd", "dq", "dkv"), 1 / 2),
    (1024, 1024, (256, 128), ("fwd", "dq", "dkv"), 20 / 32),
])
def test_live_tile_share(q_seq, kv_seq, tile, kinds, share):
    for kind in kinds:
        assert live_tile_share(kind, q_seq, kv_seq, True, tile) == share
        assert live_tile_share(kind, q_seq, kv_seq, False, tile) == 1.0


def test_live_tile_share_recorded_for_the_gpt2_shape():
    """The regression guard for "dk/dv fell back to the whole square":
    tracing the model's call at ``gpt2s-train-c1``'s shape records what
    each kernel's schedule runs, and none runs 0.65 of the square."""
    spec = jax.ShapeDtypeStruct((16, 12, 1024, 64), jnp.bfloat16)
    gauges = lambda: {kind: registry().snapshot()[
        f"flash.live_tile_share.{kind}"]["values"][0]["value"]
        for kind in ("fwd", "dq", "dkv")}

    def loss(causal):
        return lambda q, k, v: flash_attention(
            q, k, v, causal=causal).astype(jnp.float32).sum()

    jax.eval_shape(jax.value_and_grad(loss(False), argnums=(0, 1, 2)),
                   spec, spec, spec)
    assert gauges() == {"fwd": 1.0, "dq": 1.0, "dkv": 1.0}
    jax.eval_shape(jax.value_and_grad(loss(True), argnums=(0, 1, 2)),
                   spec, spec, spec)
    assert all(0.5 <= share < 0.65 for share in gauges().values()), gauges()
    assert gauges() == {kind: live_tile_share(kind, 1024, 1024, True, 256)
                        for kind in ("fwd", "dq", "dkv")}


def test_flash_partially_masked_block():
    """Regression: rows fully masked within a *processed* k block must give
    exactly zero output and -inf lse (k_offset inside the q range, so the
    kernel cannot skip the block)."""
    q, k, v = _qkv(9)
    o, lse = flash_attention_partial(q, k, v, causal=True,
                                     q_offset=0, k_offset=S // 2)
    # rows < S//2 see no keys at all
    np.testing.assert_array_equal(np.asarray(o[:, :, : S // 2]), 0.0)
    assert bool(jnp.all(lse[:, :, : S // 2] == float("-inf")))
    # remaining rows must match the reference on the shifted window
    ref = attention_reference(q, k, v, causal=True, q_offset=0,
                              k_offset=S // 2)
    np.testing.assert_allclose(np.asarray(o[:, :, S // 2:]),
                               ref[:, :, S // 2:], atol=2e-5)
    # and merging with a genuinely-absent partial must not revive them
    om, _ = merge_partials(o, lse, jnp.zeros_like(o),
                           jnp.full(lse.shape, float("-inf")))
    np.testing.assert_array_equal(np.asarray(om[:, :, : S // 2]), 0.0)


def test_merge_partials_associative():
    q, k, v = _qkv(3)
    third = S // 4
    parts = []
    for i in range(4):
        sl = slice(i * third, (i + 1) * third)
        parts.append(flash_attention_partial(
            q, k[:, :, sl], v[:, :, sl], causal=True,
            q_offset=0, k_offset=i * third))
    o, lse = parts[0]
    for o_p, lse_p in parts[1:]:
        o, lse = merge_partials(o, lse, o_p, lse_p)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(o, ref, atol=2e-5)


# ---------------------------------------------------------------------------
# ring attention under shard_map
# ---------------------------------------------------------------------------


# the non-causal ring schedule lowers to a PartitionId instruction that
# XLA's CPU SPMD partitioner rejects ("PartitionId instruction is not
# supported for SPMD partitioning"); TPU/GPU partitioners implement it
_causal_modes = [
    pytest.param(False, marks=pytest.mark.skipif(
        jax.default_backend() == "cpu",
        reason="XLA CPU SPMD partitioner does not support PartitionId")),
    True,
]


@pytest.mark.parametrize("causal", _causal_modes)
def test_ring_attention_matches_full(causal):
    q, k, v = _qkv(4)
    mesh = _seq_mesh()

    def local(q, k, v):
        return ring_attention(q, k, v, "sp", causal, None, 32, 32)

    f = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp"), check_vma=False))
    o = f(q, k, v)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), ref, atol=2e-5)


@pytest.mark.parametrize("causal", _causal_modes)
def test_ring_attention_grads(causal):
    q, k, v = _qkv(5)
    mesh = _seq_mesh()

    def local(q, k, v):
        return ring_attention(q, k, v, "sp", causal, None, 32, 32)

    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp"), check_vma=False)

    def loss(q, k, v):
        return jnp.sum(sharded(q, k, v).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            attention_reference(q, k, v, causal=causal).astype(jnp.float32)
            ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), b, atol=5e-4)


# ---------------------------------------------------------------------------
# Ulysses under shard_map
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    q, k, v = _qkv(6)
    mesh = _seq_mesh()

    def local(q, k, v):
        return ulysses_attention(q, k, v, "sp", causal=causal,
                                 block_q=32, block_k=32)

    f = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp"), check_vma=False))
    o = f(q, k, v)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), ref, atol=2e-5)


def test_ulysses_grads():
    q, k, v = _qkv(7)
    mesh = _seq_mesh()

    sharded = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=True,
                                          block_q=32, block_k=32),
        mesh=mesh, in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp"), check_vma=False)

    def loss(q, k, v):
        return jnp.sum(sharded(q, k, v).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            attention_reference(q, k, v, causal=True).astype(jnp.float32)
            ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), b, atol=5e-4)


def test_ulysses_rejects_indivisible_heads():
    q, k, v = _qkv(8)
    q3 = q[:, :3]
    mesh = _seq_mesh()
    with pytest.raises(ValueError, match="divisible"):
        jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "sp"),
            mesh=mesh, in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"), check_vma=False)(q3, k[:, :3], v[:, :3])
