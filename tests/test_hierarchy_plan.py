"""Hierarchical HOST-ring collectives: plan formation, sweep gating,
and multiprocess numerical parity (ISSUE 18).

tests/test_hierarchical.py covers the two-level decomposition on the
XLA mesh path; this file covers its host TCP-ring port
(`runtime/hierarchy.py`): how ranks group into slices, when the
topology gates the autotune sweep, and — over four real worker
processes on the native wire — that the three-phase decomposition
bit-matches the flat ring on exactly-representable payloads, that the
compressed cross hop stays within the wire dtype's rounding, and that
every rank ends bit-identical to its peers even with compression on
(the PR-10 cross-rank digest contract).
"""

import json
import os
import socket
import sys
import types

import numpy as np
import pytest

from horovod_tpu.runtime import hierarchy
from horovod_tpu.runtime.executor import Executor
from horovod_tpu.runtime.native import native_built


def _net(world, rank, hosts=None):
    """A wire-free stand-in: explicit-group-size planning never touches
    the transport, and the hostname path only calls ``allgatherv``."""
    net = types.SimpleNamespace(world=world, rank=rank)
    if hosts is not None:
        net.allgatherv = lambda payload: [h.encode() for h in hosts]
    return net


class TestBuildPlan:
    def test_explicit_group_size_tiles_contiguously(self):
        plan = hierarchy.build_plan(_net(6, 3), group_size=2)
        assert plan.enabled
        assert (plan.num_groups, plan.group_size) == (3, 2)
        assert plan.members == (2, 3)          # rank 3's slice
        assert plan.cross_members == (1, 3, 5)  # slot-1 ranks, ring order
        assert (plan.group_index, plan.local_index) == (1, 1)
        assert plan.source == "env"

    @pytest.mark.parametrize("world,gsize", [
        (3, 0),   # world too small for two levels at all
        (6, 4),   # does not tile: 6 % 4 != 0
        (4, 4),   # one group is no hierarchy
        (4, 1),   # groups of one are no hierarchy
    ])
    def test_degenerate_topologies_fall_back_flat(self, world, gsize):
        plan = hierarchy.build_plan(_net(world, 0), group_size=gsize)
        assert not plan.enabled
        assert plan.source == "flat"

    def test_host_derived_groups_by_hostname(self):
        hosts = ["a", "a", "b", "b", "c", "c"]
        plan = hierarchy.build_plan(_net(6, 2, hosts), group_size=0)
        assert plan.enabled
        assert (plan.num_groups, plan.group_size) == (3, 2)
        assert plan.members == (2, 3)           # the "b" host
        assert plan.cross_members == (0, 2, 4)  # slot 0 of each host
        assert plan.source == "hosts"

    def test_host_derived_unequal_hosts_fall_back_flat(self):
        # 2+3+1 ranks per host: the cross ring can't pair one member
        # per slice at each slot
        hosts = ["a", "a", "b", "b", "b", "c"]
        plan = hierarchy.build_plan(_net(6, 0, hosts), group_size=0)
        assert not plan.enabled


class TestWireDtype:
    def test_codec_names(self):
        import ml_dtypes

        assert hierarchy.wire_dtype_from_name("none") is None
        assert hierarchy.wire_dtype_from_name("") is None
        for alias in ("fp16", "bf16", "bfloat16"):
            assert hierarchy.wire_dtype_from_name(alias) \
                == np.dtype(ml_dtypes.bfloat16)
        assert hierarchy.wire_dtype_from_name("ieee_fp16") \
            == np.dtype(np.float16)
        with pytest.raises(ValueError):
            hierarchy.wire_dtype_from_name("fp8")


class TestSweepGating:
    """The ISSUE-18 gating fix: `hierarchical_available` must be a
    static topology predicate on the HOST-RING plane too — the old
    mesh-only check meant a multi-host socket job never saw its
    hierarchical knobs join the autotune sweep."""

    def _exec(self, world, gsize):
        return types.SimpleNamespace(
            net=types.SimpleNamespace(world=world),
            _spmd_world=False,
            _hier_group_size=lambda: gsize)

    def test_host_ring_world_that_tiles_is_available(self):
        assert Executor.hierarchical_available(self._exec(4, 2))
        assert Executor.hierarchical_available(self._exec(6, 3))

    def test_auto_grouping_is_sweepable_at_world_ge_4(self):
        # group size 0 (hostname-derived) COULD split any world >= 4 —
        # the knob joins the sweep and a flat-resolving plan is a no-op
        assert Executor.hierarchical_available(self._exec(4, 0))
        assert not Executor.hierarchical_available(self._exec(2, 0))

    def test_non_tiling_group_size_is_unavailable(self):
        assert not Executor.hierarchical_available(self._exec(6, 4))
        assert not Executor.hierarchical_available(self._exec(4, 4))


# ---------------------------------------------------------------------------
# multiprocess parity over the native wire
# ---------------------------------------------------------------------------

WORLD = 4


def _parity_worker():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import ml_dtypes

    from horovod_tpu.runtime.native import NetComm

    rank = int(os.environ["HOROVOD_RANK"])
    world = int(os.environ["HOROVOD_SIZE"])
    net = NetComm(rank, world, "127.0.0.1",
                  int(os.environ["HIER_TEST_PORT"]), 20000)
    plan = hierarchy.build_plan(net, 2)
    checks = {"plan": plan.enabled and plan.num_groups == 2
                      and plan.group_size == 2}
    rng = np.random.default_rng(7)  # same stream on every rank

    # bit parity vs the mathematically exact sum on payloads where fp
    # addition order can't bite — including n=37, which leaves uneven
    # (and empty) ring chunks at k=2
    for dtype in (np.float32, np.int32):
        for n in (8, 37, 1024):
            base = rng.integers(-50, 50, size=(world, n)).astype(dtype)
            buf = base[rank].copy()
            hierarchy.hier_allreduce(net, plan, buf, "sum")
            checks[f"sum_{np.dtype(dtype).name}_{n}"] = \
                bool(np.array_equal(buf, base.sum(axis=0)))

    for op, red in (("max", np.max), ("min", np.min),
                    ("product", np.prod)):
        base = rng.integers(1, 4, size=(world, 16)).astype(np.float32)
        buf = base[rank].copy()
        hierarchy.hier_allreduce(net, plan, buf, op)
        checks[op] = bool(np.array_equal(buf, red(base, axis=0)))

    bf16 = np.dtype(ml_dtypes.bfloat16)
    # small ints are exactly representable in bf16: the compressed hop
    # must be bit-exact, not merely close
    base = rng.integers(-8, 8, size=(world, 64)).astype(np.float32)
    buf = base[rank].copy()
    hierarchy.hier_allreduce(net, plan, buf, "sum", wire_dtype=bf16)
    checks["bf16_exact"] = bool(np.array_equal(buf, base.sum(axis=0)))

    # general floats: error bounded by the wire dtype's rounding, and
    # all ranks bit-identical (the cross-rank digest contract)
    base = rng.standard_normal((world, 256)).astype(np.float32)
    buf = base[rank].copy()
    hierarchy.hier_allreduce(net, plan, buf, "sum", wire_dtype=bf16)
    checks["bf16_err"] = float(np.max(np.abs(buf - base.sum(axis=0))))
    blobs = net.allgatherv(buf.tobytes())
    checks["bf16_agree"] = bool(all(b == blobs[0] for b in blobs))

    # reduce-scatter keeps the flat chunk convention: rank r gets chunk r
    n = 4 * world * 3
    base = rng.integers(-20, 20, size=(world, n)).astype(np.float32)
    chunk = hierarchy.hier_reducescatter(net, plan, base[rank].copy(),
                                         "sum")
    c = n // world
    checks["rs"] = bool(np.array_equal(
        chunk, base.sum(axis=0)[rank * c:(rank + 1) * c]))

    merged = [json.loads(b.decode())
              for b in net.allgatherv(json.dumps(checks).encode())]
    if rank == 0:
        print("CHECKS " + json.dumps(merged), flush=True)
    net.close()


@pytest.mark.skipif(not native_built(),
                    reason="native transport not built")
def test_multiprocess_parity_and_compression_bounds():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    from mp_launch import collect, start  # not in the worker

    procs, logs = [], []
    try:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for rank in range(WORLD):
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       HOROVOD_RANK=str(rank),
                       HOROVOD_SIZE=str(WORLD),
                       HIER_TEST_PORT=str(port),
                       PYTHONPATH=os.pathsep.join(
                           p for p in (repo,
                                       os.environ.get("PYTHONPATH"))
                           if p))
            start(procs, logs,
                  [sys.executable, os.path.abspath(__file__), "--worker"],
                  env)
        outs = collect(procs, logs, 120)
        for rank, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, \
                f"rank {rank} exited {p.returncode}:\n{out[-2000:]}"
        merged = None
        for out in outs:
            for line in out.splitlines():
                if line.startswith("CHECKS "):
                    merged = json.loads(line[len("CHECKS "):])
        assert merged is not None, "no CHECKS line:\n" + "\n".join(outs)
        assert len(merged) == WORLD
        for rank, checks in enumerate(merged):
            err = checks.pop("bf16_err")
            # 256-term sum through a bf16 wire (~8 mantissa bits):
            # comfortably under 0.1 absolute for N(0,1) payloads,
            # and never exactly zero rounding on random floats
            assert 0 < err < 0.1, (rank, err)
            bad = {k: v for k, v in checks.items() if v is not True}
            assert not bad, (rank, bad)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _parity_worker()
