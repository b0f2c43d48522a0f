"""The traffic generator's sizes and determinism, and the FLOP and byte
functions against hand-worked values. Run by hand (see test_trace.py)."""

import numpy as np
import pytest

from benchmark import flops, traffic, weights


def take(it, n):
    return [next(it) for _ in range(n)]


def test_train_batches_are_seeded_and_fresh():
    mix = traffic.load("clm-s1024-b16")
    a = take(traffic.train_batches(mix, 50257, 16, 2**31 + 5), 3)
    b = take(traffic.train_batches(mix, 50257, 16, 2**31 + 5), 3)
    c = take(traffic.train_batches(mix, 50257, 16, 7), 1)
    for (x, _), (y, _) in zip(a, b):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[0][0], a[1][0])      # a fresh batch a step
    assert not np.array_equal(a[0][0], c[0][0])      # another seed
    assert a[0][0].shape == (16, 1024) and a[0][0].dtype == np.int32
    assert 0 <= a[0][0].min() and a[0][0].max() < 50257
    assert len({row.tobytes() for row in a[0][0]}) == 16   # rows all differ


def test_masked_lm_batches():
    mix = traffic.load("mlm-s512-b16")
    inputs, (ids, mask) = next(traffic.train_batches(mix, 30522, 16, 3))
    assert inputs.shape == ids.shape == mask.shape == (16, 512)
    assert abs(mask.mean() - 0.15) < 0.02
    assert (inputs[mask == 1] == 103).all()
    assert np.array_equal(inputs[mask == 0], ids[mask == 0])


def test_request_sizes_do_not_depend_on_the_seed():
    mix = {"sizes_seed": 0, "pool": 4096,
           "prompt_len": {"median": 192, "sigma": 0.8, "min": 16, "max": 768},
           "new_tokens": {"median": 128, "sigma": 0.5, "min": 16, "max": 256}}
    sizes = traffic.request_sizes(mix)
    prompts = np.array([p for p, _ in sizes])
    outputs = np.array([o for _, o in sizes])
    assert 16 <= prompts.min() and prompts.max() <= 768
    assert 16 <= outputs.min() and outputs.max() <= 256
    assert (prompts + outputs).max() <= 1024
    assert abs(np.median(prompts) - 192) < 12
    assert abs(np.median(outputs) - 128) < 6
    one = take(traffic.requests(mix, 50257, 11), 4096)
    two = take(traffic.requests(mix, 50257, 12), 4096)
    again = take(traffic.requests(mix, 50257, 11), 8)
    shape = lambda reqs: sorted((len(p), n) for p, n in reqs)
    assert shape(one) == shape(two) == sorted(sizes)   # same work, any seed
    assert [p for p, _ in one[:8]] == [p for p, _ in again]
    assert [len(p) for p, _ in one[:64]] != [len(p) for p, _ in two[:64]]


def test_train_flops_per_token_gpt2_small():
    cfg = dict(vocab_size=50304, d_model=768, num_layers=12, num_heads=12,
               d_ff=3072, max_seq=1024)
    n = weights.count(cfg, vocab_size=50257)
    # 50257*768 + 1024*768 + 12*(4*768*768 + 4*768 + 2*768*3072 + 3072 + 768
    # + 4*768) + 2*768
    assert n == 124_439_808
    per_token = flops.train_flops_per_token(n, 12, 768, 1024, causal=True)
    assert per_token == 6 * 124_439_808 + 12 * 12 * 1024 * 768 // 2


def test_train_flops_per_token_bert_large():
    cfg = dict(vocab_size=30522, d_model=1024, num_layers=24, num_heads=16,
               d_ff=4096, max_seq=512)
    n = weights.count(cfg)
    assert n == 30522 * 1024 + 512 * 1024 + 24 * 12_596_224 + 2048
    assert flops.train_flops_per_token(n, 24, 1024, 512, causal=False) \
        == 6 * n + 12 * 24 * 512 * 1024


def test_flash_kernel_work():
    shape = dict(batch=16, heads=12, seq=1024, head_dim=64)
    # QK^T and PV, 2*S*S*D each per (row, head); the causal half
    assert flops.flash_flops("fwd", causal=True, **shape) \
        == 2 * 2 * 16 * 12 * 1024 * 1024 * 64 // 2 == 25_769_803_776
    assert flops.flash_flops("dq", causal=False, **shape) \
        == 3 * 2 * 16 * 12 * 1024 * 1024 * 64
    assert flops.flash_flops("dkv", causal=False, **shape) \
        == 4 * 2 * 16 * 12 * 1024 * 1024 * 64
    assert flops.flash_bytes("fwd", **shape) == 4 * 16 * 12 * 1024 * 64 * 2
    assert flops.flash_bytes("dkv", **shape) == 6 * 16 * 12 * 1024 * 64 * 2


def test_peaks_refuse_an_unknown_kind():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
