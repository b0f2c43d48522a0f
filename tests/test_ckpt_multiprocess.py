"""Multiprocess crash-consistency acceptance for the two-phase commit.

Fast (tier-1) cell: two real writer processes commit in lockstep over a
shared directory; rank 1 is killed by ``HOROVOD_CKPT_FAULT`` the
instant its step-2 shard is staged (tmp fsync'd, nothing published).
The survivor must abandon the step-2 commit, the step-1 manifest must
stay the newest restorable cut — bit-identical — and the dead writer's
torn tmp must be invisible to restore and reclaimable by pid-liveness.

The full kill-at-every-phase × elastic-re-form matrix (KV barrier,
neighbor-replica moment recovery with sharded AdamW) runs in
tools/chaos_matrix.py; its mid-commit cell is repeated here slow-marked
so a multi-core box exercises it under pytest too.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from horovod_tpu.ckpt import io as ckpt_io
from horovod_tpu.ckpt import manifest as mf
from horovod_tpu.ckpt import restore as rst
from mp_launch import collect, start

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# each worker stages+publishes steps over the shared-fs fallback (no
# rendezvous KV): the leader's publish waits for every rank's shard
# file instead of the staged.<rank> barrier
_WORKER = r"""
import os, sys
import numpy as np
import horovod_tpu  # noqa: F401  (package init)
from horovod_tpu.ckpt.writer import CheckpointManager

d = sys.argv[1]
rank = int(os.environ["HOROVOD_RANK"])
mgr = CheckpointManager(d, async_write=False, keep=10,
                        barrier_timeout=3.0)
for step in (1, 2):
    trees = {"params": {"w": np.full((4,), float(step), np.float32)}}
    mgr.commit(trees, step=step, generation=0, rank=rank, world=2)
mgr.close()
print("WORKER_DONE", rank, flush=True)
"""


def test_kill_while_staging_preserves_previous_cut(tmp_path):
    d = str(tmp_path / "ckpts")
    os.makedirs(d)
    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": "2",
            # staged at step 2, killed before anything is published
            "HOROVOD_CKPT_FAULT": "kill:rank=1:phase=stage:step=2:code=21",
        })
        start(procs, logs, [sys.executable, "-c", _WORKER, d], env)
    outs = collect(procs, logs, 120)
    assert procs[1].returncode == 21, outs[1][-2000:]
    # the survivor abandons step 2 and exits cleanly
    assert procs[0].returncode == 0, outs[0][-2000:]
    assert "WORKER_DONE 0" in outs[0]

    # step 1 is the newest PUBLISHED cut; rank 0's orphaned step-2
    # shard file exists but no manifest names it
    assert mf.all_steps(d) == [1]
    target = {"params": {"w": np.zeros((4,), np.float32)}}
    trees, step = rst.restore_latest(d, target)
    assert step == 1
    np.testing.assert_array_equal(
        np.asarray(trees["params"]["w"]),
        np.full((4,), 1.0, np.float32))  # bit-identical

    # the dead writer's torn tmp: invisible above, reclaimed now that
    # its pid is provably gone
    tmps = [n for n in os.listdir(d) if n.endswith(".tmp")]
    assert len(tmps) == 1, tmps
    assert ckpt_io.clean_stale_tmps(d) == 1


@pytest.mark.slow
def test_chaos_matrix_ckpt_kill_mid_commit():
    """Full elastic cell: KV barrier, publish-phase kill, re-form, and
    bit-identical restore of every surviving manifest."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_matrix.py"),
         "--only", "ckpt_kill_mid_commit"],
        capture_output=True, text=True, timeout=420,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
