"""chip_smoke.py's contract, as far as a machine without a chip can hold
it: the rehearsal switch runs the whole control flow at toy sizes on the
CPU; without the switch, no accelerator is a failure with no result line.
Plus the compile-cache rule the script uses."""

import json
import os
import subprocess
import sys

import pytest

from mp_launch import collect, start

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # one CPU device, as on a one-chip machine
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def _default_cache_entries():
    path = os.path.join(REPO, ".jax_cache")
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_rehearsal_runs_every_phase_on_the_cpu(tmp_path):
    cache = str(tmp_path / "cache")
    default_before = _default_cache_entries()
    run = subprocess.run(
        [sys.executable, SMOKE, "--rehearse", "--seed", "3"],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
        env=_env(JAX_COMPILATION_CACHE_DIR=cache))
    assert run.returncode == 0, run.stdout + run.stderr
    lines = run.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    out = "\n".join(lines[:-1])
    # the operator's cache directory is used as given, and no other
    assert f"compile cache: {cache}" in out
    assert os.listdir(cache)
    assert _default_cache_entries() == default_before
    for phase in ("native library:", "train: compile", "serve[dense]: 4",
                  "serve[paged]: 4",
                  "dense and paged greedy outputs identical"):
        assert phase in out, out


def test_no_accelerator_without_the_switch_is_a_failure(tmp_path):
    run = subprocess.run([sys.executable, SMOKE], capture_output=True,
                         text=True, timeout=300, cwd=str(tmp_path),
                         env=_env())
    assert run.returncode != 0
    assert "no accelerator" in run.stderr
    assert '"ok"' not in run.stdout


@pytest.mark.parametrize("configured", [True, False],
                         ids=["env-set", "env-unset"])
def test_compile_cache_directory(tmp_path, configured):
    """With JAX_COMPILATION_CACHE_DIR set that directory is the one in
    use and nothing is set in code; unset, it is <checkout>/.jax_cache."""
    want = (str(tmp_path / "cc") if configured
            else os.path.join(REPO, ".jax_cache"))
    env = _env(**({"JAX_COMPILATION_CACHE_DIR": want} if configured
                  else {}))
    run = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from horovod_tpu.utils import compile_cache\n"
         "print(compile_cache.configure())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert run.returncode == 0, run.stderr
    assert run.stdout.split() == [want, want]


def test_mesh_follows_launcher_ranks_when_jax_numbers_processes_otherwise():
    """On TPU the runtime numbers processes by where their chips sit, not
    by the ``process_id`` the launcher asked for (seen on the v5e 2x2
    host: slot 3 came up as process 0). Emulated on the CPU: two workers
    whose jax process ids are the reverse of their launcher ranks must
    still agree, rank by rank, on a broadcast root and an allgather
    order — ``chip_smoke.py``'s worker phase checks exactly that."""
    from horovod_tpu.run import hosts, launcher
    from horovod_tpu.run.rendezvous import RendezvousServer
    from horovod_tpu.runtime.native import native_built

    if not native_built():
        pytest.skip("native transport not built")
    slots = hosts.allocate([hosts.HostInfo("localhost", 2)], 2)
    rendezvous = RendezvousServer()
    http_port = rendezvous.start()
    socket_port, coordinator_port = (launcher._free_port(),
                                     launcher._free_port())
    procs, logs = [], []
    try:
        for slot in slots:
            env = launcher.build_worker_env(
                slot, _env(), "127.0.0.1", socket_port, http_port,
                coordinator_port, num_processes=2)
            env["HOROVOD_PROCESS_ID"] = str(1 - slot.rank)
            start(procs, logs,
                  [sys.executable, SMOKE, "--rehearse", "--phase", "worker"],
                  env, cwd=REPO)
        outs = collect(procs, logs, 240)
    finally:
        for p in procs:
            p.kill()
        rendezvous.stop()
    for slot, proc, out in zip(slots, procs, outs):
        assert proc.returncode == 0, out
        assert f"SMOKE_WORKER rank={slot.rank} " in out, out
