"""Ring attention + Ulysses sequence parallelism vs single-device ground
truth, on the virtual 8-device CPU mesh (SURVEY.md §4 strategy)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops.pallas import (
    attention_reference,
    flash_attention,
    flash_attention_partial,
    merge_partials,
)
from horovod_tpu.parallel.ring import ring_attention
from horovod_tpu.parallel.ulysses import ulysses_attention

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


@functools.lru_cache(maxsize=2)
def _dispatch_ref_grads(causal):
    """Reference gradients for test_flash_dispatch_matrix — identical
    across the four block parametrizations, so computed once per
    causal flag."""
    q, k, v = _qkv(7)

    def loss(q, k, v):
        return jnp.mean(attention_reference(
            q, k, v, causal=causal).astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "blocks",
    [
        # (block_q, block_k, bwd_block_q, bwd_block_k) spanning the r5
        # dispatch matrix at S=256:
        (512, 1024, 1024, 1024),  # single fwd + dq/dkv single (defaults)
        (128, 1024, 128, 1024),   # single fwd multi-q (wedge), dkv general
        (512, 1024, 1024, 128),   # dq general, dkv single multi-k
        (64, 64, 64, 64),         # fully general (online softmax)
    ],
    ids=["all-single", "dq-single-wedge", "dkv-single", "all-general"])
def test_flash_dispatch_matrix(causal, blocks):
    """The r5 single-block specialization added four dispatch paths
    (single-k-block direct-softmax fwd with causal wedge; scratch-free
    dq and dk/dv single kernels composing with the general pair). Every
    combination must match the reference in both output and gradients
    — this pins the path selection itself, not just the default."""
    bq, bk, bbq, bbk = blocks
    q, k, v = _qkv(7)

    kw = dict(causal=causal, block_q=bq, block_k=bk,
              bwd_block_q=bbq, bwd_block_k=bbk)
    o = flash_attention(q, k, v, **kw)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(o, ref, atol=2e-5)

    def loss(fn):
        # squared output -> the cotangent do = 2*o/n VARIES per row and
        # block, so a backward BlockSpec indexing the wrong do block
        # cannot cancel out (a constant cotangent would hide it)
        return lambda q, k, v: jnp.mean(
            fn(q, k, v).astype(jnp.float32) ** 2)

    g_ref = _dispatch_ref_grads(causal)
    g_fl = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, **kw)), argnums=(0, 1, 2))(q, k, v)
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
def test_flash_bf16_inputs(causal):
    """bf16 q/k/v (what the models feed the kernels): operands are
    up-cast to f32 inside the kernel. Outputs and grads must match the
    f32 reference computed on the same (bf16-rounded) inputs to
    bf16-appropriate tolerance."""
    q, k, v = _qkv(7, dtype=jnp.bfloat16)
    ref = attention_reference(q, k, v, causal=causal).astype(jnp.float32)

    o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
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


def test_flash_cross_offsets():
    """Offsets shift the causal mask to global positions."""
    q, k, v = _qkv(2)
    # queries are the second half of a virtual 2S sequence; keys the first.
    o = flash_attention(q, k, v, causal=True, q_offset=S, k_offset=0)
    # every key is in the past -> equivalent to non-causal
    ref = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(o, ref, atol=2e-5)
    # queries strictly before all keys -> fully masked -> zeros
    o2, lse2 = flash_attention_partial(q, k, v, causal=True,
                                       q_offset=0, k_offset=S)
    assert float(jnp.abs(o2).max()) == 0.0
    assert bool(jnp.all(lse2 == float("-inf")))


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
