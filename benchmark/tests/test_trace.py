"""The trace reduction on one small recorded trace (see the head of
recorded_trace.textproto). Run by hand:

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def ops():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    return trace.device_ops(ProfileData.from_text_proto(text))


def test_only_device_ops_are_read(ops):
    assert list(ops) == ["/device:TPU:0"]
    assert len(ops["/device:TPU:0"]) == 7


def test_busy_idle_union(ops):
    busy, window = trace.busy_and_window(ops["/device:TPU:0"])
    # 0 .. 7.521 ms, a 40 us gap, then 7.561 .. 20.929 ms; the all-reduce
    # overlaps the AdamW fusion and is counted once
    assert window == pytest.approx(20.929e-3)
    assert busy == pytest.approx(20.929e-3 - 40e-6)
    gaps = trace.gaps(ops["/device:TPU:0"])
    assert [round(g[1] * 1e6) for g in gaps] == [40]


def test_per_name_sums(ops):
    sums = trace.per_name(ops["/device:TPU:0"])
    fwd = [s for n, s in sums.items() if trace.flash_kind(n) == "fwd"]
    assert fwd == [pytest.approx(2 * 574e-6)]
    kinds = sorted(k for k in map(trace.flash_kind, sums) if k)
    assert kinds == ["dkv", "dq", "fwd"]
    mosaic = sum(s for n, s in sums.items() if trace.is_mosaic(n))
    assert mosaic == pytest.approx((2 * 574 + 751 + 1255) * 1e-6)


def test_exposed_part_of_a_collective(ops):
    total, exposed = trace.collective_seconds(ops["/device:TPU:0"])
    assert total == pytest.approx(7.192e-3)
    # the AdamW fusion runs until 16.759 ms and the last kernel from
    # 20.355 ms: the all-reduce (13.163 .. 20.355 ms) is alone in between
    assert exposed == pytest.approx((20.355 - 16.759) * 1e-3)


def test_reduce_summary(ops):
    reduced = trace.reduce(ops)
    assert reduced["busy_s"] == pytest.approx(20.889e-3)
    assert reduced["window_s"] == pytest.approx(20.929e-3)
    assert len(trace.short(max(reduced["per_name_s"], key=len))) <= 96


def test_merged_intervals():
    assert trace.merged([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_breakdown_groups_the_layers(ops):
    assert trace.signature("%attention.71 = bf16[2]{0} custom-call(%fusion.5)"
                           ) == "%attention = bf16[2]{0} custom-call(%fusion)"
    assert trace.signature("%multiply_reduce_fusion.60.clone = f32[]"
                           ) == "%multiply_reduce_fusion.clone = f32[]"
    out = trace.breakdown(trace.reduce(ops))
    rows = dict((name, s) for name, s in out["device_ops"])
    fwd = [n for n in rows if n.startswith("2x %attention = (bf16[16,12,1024,64]")]
    assert len(fwd) == 1 and rows[fwd[0]] == pytest.approx(2 * 574e-6)
    assert out["idle_gaps"] == [["host", pytest.approx(40e-6)]]
    assert all(len(n) <= 96 for n in rows)
