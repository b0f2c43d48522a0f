"""Launcher binary end-to-end: `bin/tpurun -np 2` as a real subprocess
(the delta over test_run.py's in-process run_commandline coverage), with
tests/mp_worker.py as the 2-rank workload (reference: the Docker test
images bake `mpirun -np 2 -H localhost:2` as the canonical integration
drive, Dockerfile.test.cpu:53-83)."""

import os
import subprocess
import sys

import jax
import pytest

from horovod_tpu.runtime.native import native_built

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "mp_worker.py")

pytestmark = pytest.mark.skipif(
    not native_built(), reason="native transport not built")

# The default (jax.distributed) launch mode forms a global mesh whose
# collectives are real cross-process XLA computations. The CPU backend
# rejects those with "INVALID_ARGUMENT: Multiprocess computations aren't
# implemented on the CPU backend", so on CPU-only boxes the jax-distributed
# variants can never pass — only the socket-controller data plane can.
_cpu_no_multiprocess = pytest.mark.skipif(
    jax.default_backend() == "cpu",
    reason="CPU backend does not implement multiprocess XLA computations")

# both launcher modes where the platform allows; socket-controller always
_LAUNCH_MODES = dict(
    argvalues=[["--no-jax-distributed"],
               pytest.param([], marks=_cpu_no_multiprocess)],
    ids=["socket-controller", "jax-distributed"])


@pytest.mark.parametrize("extra_args", **_LAUNCH_MODES)
def test_tpurun_binary_two_ranks(extra_args):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", *extra_args, sys.executable, WORKER, "collectives"],
        capture_output=True, text=True, timeout=240, env=env)
    assert result.returncode == 0, result.stdout + result.stderr


@pytest.mark.parametrize("extra_args", **_LAUNCH_MODES)
def test_tpurun_kitchen_sink(extra_args):
    """Named + unnamed + broadcast + ragged allgather interleaved with
    cache churn, in both launcher modes — the scenario that caught the
    multi-controller eager-dispatch ordering bug."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["HOROVOD_CACHE_CAPACITY"] = "6"
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", *extra_args, sys.executable, WORKER, "kitchen_sink"],
        capture_output=True, text=True, timeout=300, env=env)
    assert result.returncode == 0, result.stdout + result.stderr


@pytest.mark.parametrize("extra_args", **_LAUNCH_MODES)
def test_tpurun_torch_sink(extra_args):
    """Torch hooks + accumulation + interleaved eager ops, both modes,
    with a final parameter-identity check across ranks."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", *extra_args, sys.executable, WORKER, "torch_sink"],
        capture_output=True, text=True, timeout=300, env=env)
    assert result.returncode == 0, result.stdout + result.stderr


@pytest.mark.parametrize("extra_args", **_LAUNCH_MODES)
def test_tpurun_tensorflow2_mnist_example(extra_args):
    """The flagship TF2 example under the real launcher at np=2, both
    launch modes: tape averaging + broadcast_variables; the example
    asserts loss descent and cross-rank lockstep itself."""
    pytest.importorskip("tensorflow")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", *extra_args, sys.executable,
         os.path.join(REPO, "examples", "tensorflow2_mnist.py"),
         "--steps", "12"],
        capture_output=True, text=True, timeout=420, env=env)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "lockstep OK" in result.stdout


@pytest.mark.parametrize("width", [
    ["--d-model", "128", "--heads", "4"],
    pytest.param([], marks=pytest.mark.slow)], ids=["toy_width", "full_width"])
def test_tpurun_bert_large_sparse_example(width):
    """BASELINE config #5's example under the real launcher: the
    BERT-Large torch model (CI-sized layer count) with the sparse
    embedding allgather exchange over the full vocabulary; the example
    itself asserts the cross-rank lockstep invariant. What is guarded is
    the exchange and the launcher, which a narrow trunk shows as well;
    the published width (d_model 1024, 16 heads: two ranks of ~90M
    parameters in torch on the CPU, 205 s beside five busy workers) is
    the slow case."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", "--no-jax-distributed", sys.executable,
         os.path.join(REPO, "examples", "pytorch_bert_large_sparse.py"),
         "--layers", "2", "--seq", "32", "--batch", "4", "--steps", "2",
         *width],
        capture_output=True, text=True, timeout=420, env=env)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "lockstep OK" in result.stdout


@_cpu_no_multiprocess
def test_tpurun_bert_mlm_headline_recipe():
    """The r4 headline recipe (gathered MLM head + gradient
    accumulation, docs/perf_experiments.md) through the PUBLIC example
    under the real launcher at np=2: per-rank data shards; the scan
    sums local micro-grads and DistributedOptimizer allreduces ONCE in
    opt.update after the scan."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", sys.executable,
         os.path.join(REPO, "examples", "jax_bert_mlm.py"),
         "--model", "tiny", "--seq", "16", "--batch-size", "2",
         "--steps", "3", "--gathered", "--accum", "2"],
        capture_output=True, text=True, timeout=420, env=env)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "mlm loss" in result.stdout


# ~250s on a single-core box (two np=8 launches, each rank paying the
# TF/torch import serially) — the dominant tier-1 wall-clock sink; lives
# in the slow tier with the other multiprocess soaks
@pytest.mark.slow
def test_tpurun_pod_soak_dress_rehearsal(tmp_path):
    """Pod dress rehearsal (VERDICT r3 ask 3): ONE launcher-driven np=8
    localhost job exercising the whole stack together — native wire,
    autotune on, per-rank timelines, torch + TF + JAX collectives
    interleaved, mid-run rank-0 checkpoint, HARD death (os._exit 137, no
    shutdown), then a resume run that restores step 5, continues to step
    10, and asserts a cross-surface lockstep digest. Afterwards the 8
    per-rank timelines must merge into one valid trace. Documented in
    docs/tpurun.md (Pod dress rehearsal)."""
    pytest.importorskip("tensorflow")
    pytest.importorskip("torch")
    import json

    soak_dir = str(tmp_path)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({"SOAK_DIR": soak_dir, "HOROVOD_AUTOTUNE": "1",
                "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
                "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "5",
                # force the NIC-discovery/task-agent path even though the
                # job is all-local — the dress rehearsal must walk the
                # same init a real pod does
                "HOROVOD_NIC_DISCOVERY": "1"})
    np_ranks = 8

    # run 1: train to step 5, checkpoint, die hard (preemption)
    r1 = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", str(np_ranks), "--no-jax-distributed",
         sys.executable, WORKER, "pod_soak"],
        capture_output=True, text=True, timeout=900, env=env)
    assert r1.returncode != 0  # the job DIED; that is the point
    assert r1.stdout.count("CKPT_SAVED") == np_ranks, \
        r1.stdout + r1.stderr
    assert os.path.exists(os.path.join(soak_dir, "ckpt",
                                       "ckpt_5.msgpack"))

    # run 2: resume from the checkpoint, finish, lockstep
    env["SOAK_RESUME"] = "1"
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", str(np_ranks), "--no-jax-distributed",
         sys.executable, WORKER, "pod_soak"],
        capture_output=True, text=True, timeout=900, env=env)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert r2.stdout.count("SOAK_DONE") == np_ranks, \
        r2.stdout + r2.stderr

    # the resume run's per-rank timelines merge into one valid trace
    from horovod_tpu.timeline import merge_traces

    rank_files = [os.path.join(soak_dir, f"timeline.{r}.json")
                  for r in range(np_ranks)]
    for f in rank_files:
        assert os.path.exists(f), f
    merged = os.path.join(soak_dir, "merged.json")
    n = merge_traces(merged, rank_files)
    assert n > 0
    events = json.load(open(merged))["traceEvents"]
    pids = {e.get("pid") for e in events if e.get("ph") != "M"}
    assert len(pids) >= np_ranks, f"merged trace covers {len(pids)} ranks"


@_cpu_no_multiprocess
def test_tpurun_ring_attention_cross_process():
    """Sequence parallelism over a process-spanning mesh: ring attention's
    ppermute crosses real process boundaries and matches dense attention."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", sys.executable, WORKER, "ring_sp"],
        capture_output=True, text=True, timeout=300, env=env)
    assert result.returncode == 0, result.stdout + result.stderr


@_cpu_no_multiprocess
def test_tpurun_pipeline_and_moe_cross_process():
    """GPipe ppermute and MoE all_to_all across real process boundaries."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", sys.executable, WORKER, "pp_ep_xproc"],
        capture_output=True, text=True, timeout=300, env=env)
    assert result.returncode == 0, result.stdout + result.stderr


@_cpu_no_multiprocess
def test_tpurun_keras_trainer():
    """Keras-style Trainer fit/evaluate under the launcher's global mesh."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", sys.executable, WORKER, "keras"],
        capture_output=True, text=True, timeout=300, env=env)
    assert result.returncode == 0, result.stdout + result.stderr


@_cpu_no_multiprocess
def test_tpurun_lane_misuse_raises():
    """A caller-thread global-mesh dispatch with named async ops in
    flight raises OrderedLaneError instead of the documented hang
    (VERDICT r1 #3; reference misuse-raises philosophy:
    tensor_queue.cc:26-29)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", sys.executable, WORKER, "lane_misuse"],
        capture_output=True, text=True, timeout=240, env=env)
    assert result.returncode == 0, result.stdout + result.stderr


@_cpu_no_multiprocess
def test_tpurun_scaling_benchmark_8dev():
    """The scaling-efficiency command of docs/performance.md on an
    8-device virtual world: one JSON line with imgs_per_sec / n_chips /
    scaling_efficiency, so the v5p recipe is load-and-go (VERDICT r1 #7;
    reference: docs/benchmarks.rst:16-64). Two launcher processes with 4
    virtual CPU devices each form the 8-device global mesh — same sharded
    path as -np 8, but only two compiles on the single-core CI box."""
    import json as json_mod

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    bench = os.path.join(REPO, "examples", "jax_synthetic_benchmark.py")
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", sys.executable, bench,
         "--model", "ResNet18", "--batch-size", "1", "--image-size", "32",
         "--num-warmup-batches", "0", "--num-batches-per-iter", "1",
         "--num-iters", "1", "--json", "--one-chip-rate", "100.0",
         "--platform", "cpu"],
        capture_output=True, text=True, timeout=900, env=env)
    assert result.returncode == 0, result.stdout + result.stderr
    # tpurun prefixes worker stdout with "[rank]<stdout>: "
    json_lines = [l[l.index("{"):] for l in result.stdout.splitlines()
                  if '{"imgs_per_sec"' in l]
    assert json_lines, result.stdout
    payload = json_mod.loads(json_lines[-1])
    assert payload["n_chips"] == 8
    assert payload["imgs_per_sec"] > 0
    assert payload["scaling_efficiency"] is not None


@_cpu_no_multiprocess
def test_tpurun_jit_train_global_mesh():
    """Jitted train step over the jax.distributed global mesh with
    per-process data: gradient averaging must be real cross-process
    collectives (divergent parameters fail the in-worker check)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "tpurun"),
         "-np", "2", sys.executable, WORKER, "jit_train"],
        capture_output=True, text=True, timeout=300, env=env)
    assert result.returncode == 0, result.stdout + result.stderr
