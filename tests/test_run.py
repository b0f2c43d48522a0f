"""Launcher tests — slot allocation, config translation, wire security,
rendezvous, services, and end-to-end tpurun fan-out.

Mirrors the reference's launcher unit tests (reference: test/test_run.py:
53-216 — pure-Python arg/config/env translation, no cluster) plus
end-to-end local fan-out the reference exercises in its Docker images.
"""

import io
import json
import os
import sys
import tempfile
import textwrap

import pytest

from horovod_tpu.run import config_parser, hosts, launcher, service, util
from horovod_tpu.run.rendezvous import KVStoreClient, RendezvousServer
from horovod_tpu.run.run import check_build, parse_args, run_commandline


# ---------------------------------------------------------------------------
# host parsing / slot allocation (reference: gloo_run.py:56-114 semantics)
# ---------------------------------------------------------------------------

def test_parse_hosts():
    infos = hosts.parse_hosts("h1:2, h2:4,h3")
    assert [(h.hostname, h.slots) for h in infos] == [
        ("h1", 2), ("h2", 4), ("h3", 1)]


def test_parse_hostfile(tmp_path):
    path = tmp_path / "hostfile"
    path.write_text("h1 slots=2\n# comment\nh2 slots=4\nh3\n")
    infos = hosts.parse_hostfile(str(path))
    assert [(h.hostname, h.slots) for h in infos] == [
        ("h1", 2), ("h2", 4), ("h3", 1)]


def test_allocate_uniform():
    slots = hosts.allocate(hosts.parse_hosts("h1:2,h2:2"), 4)
    assert [s.rank for s in slots] == [0, 1, 2, 3]
    assert [s.hostname for s in slots] == ["h1", "h1", "h2", "h2"]
    assert [s.local_rank for s in slots] == [0, 1, 0, 1]
    assert all(s.local_size == 2 for s in slots)
    assert [s.cross_rank for s in slots] == [0, 0, 1, 1]
    assert all(s.cross_size == 2 for s in slots)


def test_allocate_heterogeneous():
    # h1:3, h2:1 → local sizes differ; cross_size depends on local_rank
    slots = hosts.allocate(hosts.parse_hosts("h1:3,h2:1"), 4)
    by_rank = {s.rank: s for s in slots}
    assert by_rank[3].hostname == "h2"
    assert by_rank[3].local_rank == 0 and by_rank[3].local_size == 1
    # local_rank 0 exists on both hosts
    assert by_rank[0].cross_size == 2 and by_rank[3].cross_size == 2
    # local_rank 1 and 2 exist only on h1
    assert by_rank[1].cross_size == 1 and by_rank[2].cross_size == 1
    assert by_rank[3].cross_rank == 1


def test_allocate_truncates_to_np():
    slots = hosts.allocate(hosts.parse_hosts("h1:4,h2:4"), 3)
    assert len(slots) == 3
    assert all(s.hostname == "h1" for s in slots)
    assert slots[0].local_size == 3  # only 3 used on h1


def test_allocate_oversubscribe_raises():
    with pytest.raises(ValueError):
        hosts.allocate(hosts.parse_hosts("h1:2"), 3)


def test_slot_env_contract():
    slot = hosts.allocate(hosts.parse_hosts("h1:2,h2:2"), 4)[2]
    env = slot.to_env()
    assert env["HOROVOD_RANK"] == "2"
    assert env["HOROVOD_SIZE"] == "4"
    assert env["HOROVOD_LOCAL_RANK"] == "0"
    assert env["HOROVOD_CROSS_RANK"] == "1"


# ---------------------------------------------------------------------------
# CLI args / config file / env translation (reference: test_run.py:53-216)
# ---------------------------------------------------------------------------

def test_args_to_env():
    args = parse_args(
        ["-np", "2", "--fusion-threshold-mb", "32", "--cycle-time-ms", "7.5",
         "--cache-capacity", "2048", "--timeline-filename", "/tmp/t.json",
         "--autotune", "--log-level", "debug", "python", "train.py"])
    env = config_parser.env_from_args(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "7.5"
    assert env["HOROVOD_CACHE_CAPACITY"] == "2048"
    assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_LOG_LEVEL"] == "debug"
    assert args.command == ["python", "train.py"]


def test_config_file_and_cli_precedence(tmp_path):
    config = tmp_path / "cfg.yaml"
    config.write_text(textwrap.dedent("""\
        params:
            fusion_threshold_mb: 16
            cycle_time_ms: 3.0
        timeline:
            filename: /tmp/from_config.json
        stall_check:
            enabled: false
        """))
    # --cycle-time-ms on the CLI beats the config file
    args = parse_args(
        ["-np", "2", "--config-file", str(config),
         "--cycle-time-ms", "9.0", "cmd"])
    env = config_parser.env_from_args(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(16 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "9.0"
    assert env["HOROVOD_TIMELINE"] == "/tmp/from_config.json"
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "1"


def test_validation_rejects_bad_values():
    with pytest.raises(ValueError):
        parse_args(["-np", "2", "--cycle-time-ms", "-1", "cmd"])


def test_check_build_reports_capabilities():
    out = io.StringIO()
    check_build(out)
    text = out.getvalue()
    assert "JAX" in text and "XLA collectives" in text
    assert "[X] XLA (in-jit SPMD)" in text


# ---------------------------------------------------------------------------
# wire security (reference: network.py:50-84 HMAC framing)
# ---------------------------------------------------------------------------

def test_wire_roundtrip_and_tamper_rejection():
    key = util.make_secret_key()
    wire = util.Wire(key)
    buf = io.BytesIO()
    wire.write({"hello": [1, 2, 3]}, buf)
    buf.seek(0)
    assert wire.read(buf) == {"hello": [1, 2, 3]}

    # flip a payload byte → HMAC must fail before unpickling
    raw = bytearray(buf.getvalue())
    raw[-1] ^= 0xFF
    with pytest.raises(IOError):
        wire.read(io.BytesIO(bytes(raw)))

    # wrong key → reject
    with pytest.raises(IOError):
        util.Wire(util.make_secret_key()).read(io.BytesIO(buf.getvalue()))


def test_secret_encode_roundtrip():
    key = util.make_secret_key()
    assert util.decode_secret(util.encode_secret(key)) == key


# ---------------------------------------------------------------------------
# rendezvous KV store (reference: run/rendezvous/http_server.py)
# ---------------------------------------------------------------------------

def test_rendezvous_put_get_finish():
    server = RendezvousServer("127.0.0.1")
    port = server.start()
    try:
        client = KVStoreClient("127.0.0.1", port, scope="global", timeout=5)
        with pytest.raises(KeyError):
            client.get("addr", wait=False)
        client.set("addr", b"1.2.3.4:99")
        assert client.get("addr") == b"1.2.3.4:99"
        # scoped keys are independent (global vs local_0)
        client.set("addr", b"other", scope="local_0")
        assert client.get("addr", scope="local_0") == b"other"
        assert client.get("addr") == b"1.2.3.4:99"
        client.finish("addr")
        assert server.finished_keys("global") == {"addr"}
    finally:
        server.stop()


def test_rendezvous_port_collision_retry():
    """An explicit port held by a dying server is retried with backoff
    instead of failing the launch (port=0 never retries)."""
    import threading

    holder = RendezvousServer("127.0.0.1")
    port = holder.start()
    # while the holder is alive, a no-retry bind must fail fast
    with pytest.raises(OSError):
        RendezvousServer("127.0.0.1", port=port, bind_retries=0)
    releaser = threading.Timer(0.5, holder.stop)
    releaser.start()
    try:
        server = RendezvousServer("127.0.0.1", port=port, bind_retries=25)
        assert server.start() == port
        server.stop()
    finally:
        releaser.join()


def test_rendezvous_waits_for_publication():
    import threading
    import time as time_mod

    server = RendezvousServer("127.0.0.1")
    port = server.start()
    try:
        client = KVStoreClient("127.0.0.1", port, timeout=10)

        def publish():
            time_mod.sleep(0.3)
            client.set("late", b"v")

        t = threading.Thread(target=publish)
        t.start()
        assert client.get("late") == b"v"  # long-polls until published
        t.join()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# driver/task services (reference: run/common/service/*)
# ---------------------------------------------------------------------------

def test_driver_task_registration_and_command():
    key = util.make_secret_key()
    driver = service.DriverService(key, num_tasks=2)
    tasks = [service.TaskService(key, index=i) for i in range(2)]
    try:
        for t in tasks:
            t.register(("127.0.0.1", driver.port), key)
        driver.wait_for_initial_registration(util.Timeout(10, "registration"))
        addrs = driver.task_addresses()
        assert set(addrs) == {0, 1}

        # driver asks task 0 to run a command, polls its exit code
        client = service.ServiceClient(("127.0.0.1", tasks[0].port), key)
        with tempfile.TemporaryDirectory() as d:
            marker = os.path.join(d, "ran")
            client.call(service.RunCommandRequest(
                f"touch {marker}", dict(os.environ)))
            deadline = 50
            code = None
            while deadline and code is None:
                code = client.call(service.CommandExitCodeRequest())
                deadline -= 1
                if code is None:
                    import time as time_mod
                    time_mod.sleep(0.1)
            assert code == 0
            assert os.path.exists(marker)
    finally:
        driver.shutdown()
        for t in tasks:
            t.shutdown()


def test_wrong_key_rejected_by_service():
    key = util.make_secret_key()
    driver = service.DriverService(key, num_tasks=1)
    try:
        bad_client = service.ServiceClient(
            ("127.0.0.1", driver.port), util.make_secret_key())
        with pytest.raises((EOFError, IOError, RuntimeError)):
            bad_client.call(service.PingRequest())
    finally:
        driver.shutdown()


# ---------------------------------------------------------------------------
# end-to-end local fan-out
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""\
    import json, os, sys
    keys = ["HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_LOCAL_RANK",
            "HOROVOD_LOCAL_SIZE", "HOROVOD_CROSS_RANK", "HOROVOD_CROSS_SIZE",
            "HOROVOD_GLOO_RENDEZVOUS_ADDR", "HOROVOD_GLOO_RENDEZVOUS_PORT",
            "HOROVOD_CYCLE_TIME"]
    out = {k: os.environ.get(k) for k in keys}
    path = os.path.join(os.environ["TEST_OUT_DIR"],
                        "rank_%s.json" % os.environ["HOROVOD_RANK"])
    with open(path, "w") as f:
        json.dump(out, f)
""")


def test_tpurun_local_fanout(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(SCRIPT)
    os.environ["TEST_OUT_DIR"] = str(tmp_path)
    try:
        code = run_commandline(
            ["-np", "3", "--no-jax-distributed", "--cycle-time-ms", "2.5",
             sys.executable, str(script)])
    finally:
        os.environ.pop("TEST_OUT_DIR", None)
    assert code == 0
    ranks = []
    for r in range(3):
        with open(tmp_path / f"rank_{r}.json") as f:
            env = json.load(f)
        ranks.append(int(env["HOROVOD_RANK"]))
        assert env["HOROVOD_SIZE"] == "3"
        assert env["HOROVOD_LOCAL_SIZE"] == "3"
        assert env["HOROVOD_CROSS_SIZE"] == "1"
        assert env["HOROVOD_CYCLE_TIME"] == "2.5"
        assert env["HOROVOD_GLOO_RENDEZVOUS_PORT"] is not None
    assert sorted(ranks) == [0, 1, 2]


def test_tpurun_output_capture(tmp_path):
    outdir = tmp_path / "logs"
    code = run_commandline(
        ["-np", "2", "--no-jax-distributed",
         "--output-filename", str(outdir),
         sys.executable, "-c", "import os; print('hello from', os.environ['HOROVOD_RANK'])"])
    assert code == 0
    for r in range(2):
        content = (outdir / f"rank.{r}" / "stdout").read_text()
        assert f"hello from {r}" in content


def test_tpurun_failure_propagates(tmp_path):
    # rank 1 exits non-zero; job must fail (reference: gloo_run.py:256-262)
    script = tmp_path / "fail.py"
    script.write_text(textwrap.dedent("""\
        import os, sys, time
        if os.environ["HOROVOD_RANK"] == "1":
            sys.exit(3)
        time.sleep(30)  # must be killed, not run 30s
    """))
    import time as time_mod
    t0 = time_mod.monotonic()
    code = run_commandline(
        ["-np", "2", "--no-jax-distributed", sys.executable, str(script)])
    elapsed = time_mod.monotonic() - t0
    assert code == 3
    assert elapsed < 25  # surviving rank was torn down


def test_tpurun_no_command_errors():
    assert run_commandline(["-np", "2"]) == 2


# ---------------------------------------------------------------------------
# one TPU chip per local slot
# ---------------------------------------------------------------------------

def _local_slots(n):
    return hosts.allocate([hosts.HostInfo("localhost", n)], n)


@pytest.mark.parametrize("jax_distributed", [True, False])
def test_each_local_slot_gets_its_own_chip(jax_distributed):
    envs = launcher.tpu_chip_envs(_local_slots(4), chips=4,
                                  use_jax_distributed=jax_distributed)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    if jax_distributed:
        # joined into one global 2x2 mesh: same slice description for
        # everyone, a task id and a port each
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
        (addresses,) = {e["TPU_PROCESS_ADDRESSES"] for e in envs}
        assert addresses.split(",") == [
            f"localhost:{e['TPU_PROCESS_PORT']}" for e in envs]
        assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == list("0123")
    else:
        # socket controller: four isolated one-chip worlds
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
        assert not any("TPU_PROCESS_ADDRESSES" in e for e in envs)


def test_chip_env_leaves_other_layouts_alone():
    # no chips here (or JAX_PLATFORMS=cpu), and one slot per host: the
    # worker environment is untouched
    assert launcher.tpu_chip_envs(_local_slots(3), 0, True) == [{}] * 3
    one_each = hosts.allocate([hosts.HostInfo("a", 1),
                               hosts.HostInfo("b", 1)], 2)
    assert launcher.tpu_chip_envs(one_each, 4, True) == [{}, {}]
    assert launcher.local_tpu_chips({"JAX_PLATFORMS": "cpu"}) == 0


@pytest.mark.parametrize("slots,chips,match", [
    (lambda: _local_slots(8), 4, "8 local slots but this host has 4"),
    (lambda: _local_slots(3), 4, "supported counts"),
    (lambda: hosts.allocate([hosts.HostInfo("a", 2),
                             hosts.HostInfo("b", 2)], 4), 4,
     "across several hosts"),
])
def test_chip_env_refuses_what_would_hang(slots, chips, match):
    with pytest.raises(launcher.SlotLayoutError, match=match):
        launcher.tpu_chip_envs(slots(), chips, True)


def test_tpurun_reports_a_refused_layout(monkeypatch, capsys):
    monkeypatch.setattr(launcher, "local_tpu_chips", lambda env: 2)
    code = run_commandline(["-np", "3", "-H", "localhost:3",
                            sys.executable, "-c", "pass"])
    assert code == 2
    assert "3 local slots but this host has 2" in capsys.readouterr().err


def _run_mp_worker(monkeypatch, scenario, extra_flags=()):
    """tpurun-launch mp_worker.py ranks (workers don't want the parent's
    8-fake-device XLA_FLAGS)."""
    from horovod_tpu.runtime.native import native_built

    if not native_built():
        pytest.skip("native transport not built")
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "mp_worker.py")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    return run_commandline(
        ["-np", "2", *extra_flags, sys.executable, worker, scenario])


# ---------------------------------------------------------------------------
# NIC discovery (reference: run/run.py:195-265 ring probe)
# ---------------------------------------------------------------------------

def test_nic_discovery_filters_unroutable(monkeypatch):
    """The ring probe drops candidate addresses nothing can reach and the
    driver address is one tasks actually used — mocked multi-NIC setup."""
    from horovod_tpu.run import discovery, service, util as run_util

    real_local_addresses = service.local_addresses

    def fake_local_addresses(port):
        # a dead NIC candidate first: TEST-NET-1, guaranteed unroutable
        return [("192.0.2.1", port)] + real_local_addresses(port)

    monkeypatch.setattr(service, "local_addresses", fake_local_addresses)
    key = run_util.make_secret_key()
    result = discovery.discover(
        ["localhost", "localhost"], key, is_local=lambda h: True,
        timeout=60.0)
    assert result.driver_addr and result.driver_addr != "192.0.2.1"
    assert set(result.host_routable) == {0, 1}
    for idx, addrs in result.host_routable.items():
        assert addrs, f"host {idx} has no routable address"
        assert all(ip != "192.0.2.1" for ip, _ in addrs)


def test_nic_discovery_raises_when_unreachable(monkeypatch):
    """No routable address -> a clear error naming the host (reference
    raises the same way, run/run.py:253-262)."""
    from horovod_tpu.run import discovery, service, util as run_util

    # deny only the ring probes (registration still works): the same
    # code path as all-dead NICs between hosts
    real_handle = service.TaskService._handle

    def deny_ring_probe(self, req):
        if isinstance(req, service.ProbeAddressesRequest) and req.addresses:
            return service.OkResponse([])
        return real_handle(self, req)

    monkeypatch.setattr(service.TaskService, "_handle", deny_ring_probe)
    key = run_util.make_secret_key()
    with pytest.raises(RuntimeError, match="no routable address"):
        discovery.discover(["localhost", "localhost"], key,
                           is_local=lambda h: True, timeout=30.0)


def test_ring_probe_runs_concurrently(monkeypatch):
    """32 mocked hosts, each dial costing a fixed delay: the probe phase
    must take ~one probe round (concurrent), not 32 serial rounds — the
    reference launches all task probes at once (run/run.py:195-265)."""
    import time

    from horovod_tpu.run import discovery, util as run_util

    n, dial_delay = 32, 0.2
    task_addresses = {i: [(f"10.0.0.{i}", 9000 + i)] for i in range(n)}

    class FakeClient:
        def __init__(self, addrs):
            self.addrs = addrs

        def call(self, request, timeout=None):
            time.sleep(dial_delay)  # the task->successor probe
            return request.addresses

    def fake_client_for(addresses, key, probe_timeout=3.0):
        time.sleep(dial_delay)  # the driver->task dial
        return FakeClient(addresses)

    monkeypatch.setattr(discovery, "_client_for", fake_client_for)
    key = run_util.make_secret_key()
    t0 = time.perf_counter()
    routable = discovery._ring_probe(task_addresses, key, probe_timeout=1.0)
    wall = time.perf_counter() - t0
    assert set(routable) == set(range(n))
    for i in range(n):
        assert routable[i] == [tuple(a) for a in task_addresses[i]]
    # serial would be n * 2 * dial_delay = 12.8s; concurrent is ~2 dials.
    # Generous bound (4 rounds) for a loaded 1-core CI box.
    assert wall < 4 * 2 * dial_delay, f"probe phase not concurrent: {wall:.2f}s"


def test_task_agent_key_over_stdin(monkeypatch, capsys):
    """--key-stdin reads the HMAC key from stdin (never the command line /
    process environment); a bad driver address makes registration fail
    fast but proves the key parse happened."""
    from horovod_tpu.run import task_agent

    monkeypatch.delenv("HOROVOD_TASK_KEY", raising=False)
    monkeypatch.setattr("sys.stdin", io.StringIO("a1b2c3d4\n"))
    # key parse succeeds (no KeyError on the absent env var); registration
    # then times out against the dead driver address
    with pytest.raises(TimeoutError):
        task_agent.main(["0", "1", "127.0.0.1:1", "0.2", "--key-stdin"])
    # and without --key-stdin the env fallback still applies
    monkeypatch.setenv("HOROVOD_TASK_KEY", "a1b2c3d4")
    with pytest.raises(TimeoutError):
        task_agent.main(["0", "1", "127.0.0.1:1", "0.2"])


def test_tpurun_forced_nic_discovery(monkeypatch):
    """End-to-end: 2-process localhost launch with discovery forced on
    feeds the proven driver address into the rendezvous env."""
    monkeypatch.setenv("HOROVOD_NIC_DISCOVERY", "1")
    assert _run_mp_worker(
        monkeypatch, "collectives", ["--no-jax-distributed"]) == 0


def test_tpurun_end_to_end_collective(monkeypatch):
    """tpurun-launched workers form a world and allreduce through the
    socket controller — the full launcher→init→collective path the
    reference exercises via `horovodrun -np 2 pytest ...`."""
    assert _run_mp_worker(
        monkeypatch, "collectives", ["--no-jax-distributed"]) == 0


def test_tpurun_large_tensor_ring(monkeypatch):
    """32 MB fused buffer through the host ring — regression test for the
    full-duplex exchange (a blocking ring deadlocks once chunks exceed
    kernel socket buffering)."""
    assert _run_mp_worker(
        monkeypatch, "large_allreduce", ["--no-jax-distributed"]) == 0


def test_tpurun_autotune_sync(monkeypatch):
    """--autotune: coordinator tunes, workers apply the per-cycle param
    broadcast; the job converges and stays numerically correct."""
    assert _run_mp_worker(
        monkeypatch, "autotune",
        ["--no-jax-distributed", "--autotune",
         "--autotune-warmup-samples", "0",
         "--autotune-steps-per-sample", "1",
         "--autotune-bayes-opt-max-samples", "2"]) == 0


def test_tpurun_spmd_global_mesh(monkeypatch):
    """Default tpurun mode: jax.distributed global mesh; the enqueue
    runtime's allreduce rides XLA collectives over the mesh (ICI analogue),
    with the socket net as control plane only."""
    import jax

    if jax.default_backend() == "cpu":
        pytest.skip(
            "CPU backend does not implement multiprocess XLA computations")
    assert _run_mp_worker(monkeypatch, "spmd_allreduce") == 0


def test_safe_exec_kills_process_tree():
    import threading
    import time as time_mod

    event = threading.Event()
    results = {}

    def run():
        results["code"] = util.execute(
            f"{sys.executable} -c 'import time; time.sleep(60)'",
            events=[event], prefix_output=False)

    t = threading.Thread(target=run)
    t.start()
    time_mod.sleep(0.5)
    event.set()
    t.join(timeout=20)
    assert not t.is_alive()
    assert results["code"] != 0


# ---------------------------------------------------------------------------
# launch backends (reference: the gloo-vs-mpirun selection seam,
# run/run.py:715-732 — here ssh vs gcloud TPU-VM)
# ---------------------------------------------------------------------------

def test_backend_selection(monkeypatch):
    from horovod_tpu.run import backends

    assert backends.make_backend(None).name == "ssh"
    assert backends.make_backend("gcloud-tpu-vm").name == "gcloud-tpu-vm"
    monkeypatch.setenv("HOROVOD_LAUNCH_BACKEND", "gcloud-tpu-vm")
    assert backends.make_backend(None).name == "gcloud-tpu-vm"
    assert backends.make_backend("ssh").name == "ssh"  # flag beats env
    with pytest.raises(ValueError, match="unknown launch backend"):
        backends.make_backend("mpirun")


def test_ssh_backend_commands():
    from horovod_tpu.run import backends

    b = backends.SSHBackend(ssh_port=2222)
    local = hosts.SlotInfo("localhost", rank=0, local_rank=0, local_size=2,
                           cross_rank=0, cross_size=1, size=2)
    remote = hosts.SlotInfo("worker-7", rank=1, local_rank=1, local_size=2,
                            cross_rank=0, cross_size=1, size=2)
    assert b.command_for_slot(local, "python train.py", {}) == \
        "python train.py"
    cmd = b.command_for_slot(
        remote, "python train.py",
        {"HOROVOD_RANK": "1", "SECRET_TOKEN": "x"})
    assert cmd.startswith("ssh ") and "-p 2222" in cmd and "worker-7" in cmd
    assert "HOROVOD_RANK=1" in cmd
    assert "SECRET_TOKEN" not in cmd  # only whitelisted prefixes exported


def test_gcloud_tpu_vm_backend_commands():
    from horovod_tpu.run import backends

    b = backends.GCloudTPUVMBackend(zone="us-central2-b", project="proj-1")
    slot = hosts.SlotInfo("my-pod", rank=5, local_rank=3, local_size=4,
                          cross_rank=1, cross_size=2, size=8)
    cmd = b.command_for_slot(slot, "python train.py",
                             {"HOROVOD_RANK": "5", "JAX_PLATFORMS": "tpu"})
    assert cmd.startswith("gcloud compute tpus tpu-vm ssh my-pod")
    assert "--worker=3" in cmd
    assert "--zone=us-central2-b" in cmd and "--project=proj-1" in cmd
    assert "HOROVOD_RANK=5" in cmd and "JAX_PLATFORMS=tpu" in cmd


def test_tpurun_gcloud_backend_skips_ssh_check(monkeypatch):
    """--launch-backend gcloud-tpu-vm must not plain-ssh TPU VM names; the
    constructed fan-out commands go through gcloud."""
    import horovod_tpu.run.run as run_mod
    from horovod_tpu.run import launcher as launcher_mod

    captured = {}

    def fake_launch_job(command, slots, **kw):
        captured["backend"] = kw.get("backend")
        captured["slots"] = slots
        return 0

    def boom(*a, **kw):
        raise AssertionError("ssh check must be skipped for gcloud backend")

    monkeypatch.setattr(run_mod.launcher, "launch_job", fake_launch_job)
    monkeypatch.setattr(run_mod, "check_all_hosts_ssh_successful", boom)
    rc = run_commandline(
        ["-np", "2", "-H", "pod-a:2", "--launch-backend", "gcloud-tpu-vm",
         "--gcloud-zone", "z", "python", "x.py"])
    assert rc == 0
    assert captured["backend"].name == "gcloud-tpu-vm"
