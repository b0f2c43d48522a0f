"""Step profiler tests (ISSUE.md PR 6): phase attribution, comm-overlap
accounting, rolling MFU, the merged cross-rank trace, and the
``GET /profile`` endpoint.

The load-bearing guarantees: (1) the four phases sum to the step wall
time exactly — the report can never attribute more (or less) time than
passed; (2) a synchronous allreduce reports ~zero hidden comm while a
depth-2 pipelined pair reports a positive hidden fraction — the
measurement the overlap campaign (ROADMAP item 5) will optimize; (3) the
merged trace is valid Chrome JSON with per-lane monotonic timestamps and
per-rank clock correction applied.
"""

import json
import os
import socket
import sys
import time
import urllib.request

import numpy as np
import pytest

from horovod_tpu.runtime import message as msg, types
from mp_launch import collect, start

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "profiler_worker.py")


@pytest.fixture(autouse=True)
def _pristine_profiler_state(monkeypatch):
    """These tests assert against the module-global profiler's enabled
    state; start each from a known-disabled baseline so an earlier test
    that enabled profiling (e.g. via bench.enable_profiler) can't leak
    into the assertions here. Runs before ``prof``, which re-enables."""
    from horovod_tpu import profiler

    monkeypatch.delenv("HOROVOD_PROFILE", raising=False)
    monkeypatch.delenv("HOROVOD_PROFILE_DIR", raising=False)
    profiler.configure()
    # drain the bounded history rings too: the relative-slicing idiom
    # (n0 = len(history()); history()[n0:]) silently returns [] once the
    # deque hits maxlen (64) — which it always has by this point of a
    # full-suite run
    p = profiler._profiler
    p._steps.clear()
    p._trace_events.clear()
    p._mfu_window.clear()
    p._auto_rec = None
    yield


@pytest.fixture
def prof(monkeypatch):
    """Profiler enabled for the test, disabled (and ring-isolated via
    relative slicing) afterwards."""
    from horovod_tpu import profiler

    monkeypatch.setenv("HOROVOD_PROFILE", "1")
    profiler.configure()
    yield profiler
    monkeypatch.delenv("HOROVOD_PROFILE", raising=False)
    profiler.configure()


class TestPhaseAttribution:
    def test_phases_sum_to_wall_exactly(self, hvd, prof):
        with prof.step("attributed") as rec:
            with prof.annotate("host"):
                time.sleep(0.02)
            time.sleep(0.03)  # unannotated -> compute
            with prof.annotate("optimizer"):
                time.sleep(0.01)
        b = rec.breakdown
        assert b is not None
        assert abs(sum(b["phases"].values()) - b["wall_seconds"]) < 1e-9
        assert b["phases"]["host"] == pytest.approx(0.02, abs=0.015)
        assert b["phases"]["optimizer"] == pytest.approx(0.01, abs=0.015)
        assert b["phases"]["compute"] > 0.02

    def test_input_aliases_host_and_unknown_phase_raises(self, hvd, prof):
        with prof.step() as rec:
            with prof.annotate("input"):
                time.sleep(0.005)
        assert rec.breakdown["phases"]["host"] > 0
        with pytest.raises(ValueError):
            prof.annotate("backward").__enter__()

    def test_auto_step_via_distributed_optimizer(self, hvd, prof):
        """The eager DistributedOptimizer path needs no explicit
        bracketing: every update is an auto step with a positive
        optimizer phase."""
        import optax

        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": np.ones(8, np.float32)}
        state = opt.init(params)
        n0 = len(prof.history())
        for _ in range(3):
            grads = {"w": np.full(8, 0.5, np.float32)}
            _, state = opt.update(grads, state, params)
        prof.auto_step()  # close the last implicit step
        steps = prof.history()[n0:]
        assert len(steps) >= 3
        assert all(s["auto"] for s in steps)
        assert any(s["phases"]["optimizer"] > 0 for s in steps)

    def test_disabled_profiler_records_nothing(self, hvd):
        from horovod_tpu import profiler

        assert not profiler.enabled()
        n0 = len(profiler.history())
        with profiler.step("off") as rec:
            pass
        profiler.auto_step()
        assert rec is None
        assert len(profiler.history()) == n0


class TestCommOverlap:
    def _entries(self, hvd, tag, j=0):
        return [types.TensorTableEntry(
            name=f"prof/{tag}/t{j}",
            tensor=hvd.stack_per_worker(
                [np.full((256,), float(i + j), "float32")
                 for i in range(hvd.size())]),
            reduce_op=types.REDUCE_SUM)]

    def test_sync_allreduce_fully_exposed(self, hvd, prof):
        from horovod_tpu.runtime.runtime import get_runtime

        ex = get_runtime().executor
        with prof.step("sync") as rec:
            entries = self._entries(hvd, "sync")
            pend = ex.dispatch(
                msg.Response(types.ALLREDUCE, [e.name for e in entries]),
                entries)
            pend.complete()  # depth 1: drain immediately after dispatch
        comm = rec.breakdown["comm"]
        assert comm["total_seconds"] > 0
        assert comm["hidden_fraction"] < 0.05

    def test_pipelined_dispatch_hides_comm(self, hvd, prof):
        from horovod_tpu.runtime.runtime import get_runtime

        ex = get_runtime().executor
        with prof.step("depth2") as rec:
            pends = []
            for j in range(2):  # depth 2: both in flight before any drain
                entries = self._entries(hvd, "depth2", j)
                pends.append(ex.dispatch(
                    msg.Response(types.ALLREDUCE,
                                 [e.name for e in entries]), entries))
            time.sleep(0.01)  # overlapped caller work while parked
            for pend in pends:
                pend.complete()
        comm = rec.breakdown["comm"]
        assert comm["total_seconds"] > 0
        assert comm["hidden_fraction"] > 0.0
        assert comm["hidden_fraction_bytes"] > 0.0

    def test_step_metrics_move(self, hvd, prof):
        from horovod_tpu.profiler import _HIDDEN_FRACTION, _STEP_SECONDS

        count0 = _STEP_SECONDS.labels().count
        with prof.step("metrics"):
            time.sleep(0.001)
        assert _STEP_SECONDS.labels().count == count0 + 1
        assert 0.0 <= _HIDDEN_FRACTION.value <= 1.0


class TestMfu:
    def test_gauge_matches_rolling_formula(self, hvd, prof):
        from horovod_tpu.profiler import _MFU

        flops, peak = 2.0e9, 1.0e12
        prof.set_flops_per_step(flops, peak_flops_per_chip=peak)
        n0 = len(prof.history())
        for _ in range(3):
            with prof.step():
                time.sleep(0.005)
        steps = prof.history()[n0:]
        per_step = [flops / s["wall_seconds"] / peak for s in steps]
        for s, expect in zip(steps, per_step):
            assert s["mfu"] == pytest.approx(expect, rel=1e-12)
        window = [s["mfu"] for s in prof.history()
                  if s.get("mfu") is not None]
        assert _MFU.value == pytest.approx(sum(window) / len(window),
                                           rel=1e-12)
        prof.set_flops_per_step(None)

    def test_no_peak_no_mfu(self, hvd, prof):
        prof.profiler()._peak_flops = None
        prof.set_flops_per_step(1e9)  # no peak hint -> mfu stays unset
        with prof.step() as rec:
            pass
        assert rec.breakdown["mfu"] is None


class TestSummaryAndState:
    def test_summary_aggregates(self, hvd, prof):
        n0 = len(prof.history())
        for _ in range(2):
            with prof.step():
                time.sleep(0.002)
        s = prof.summary()
        assert s["steps"] >= 2 and s["steps"] >= len(prof.history()[n0:])
        assert set(s["step_breakdown"]) == set(
            ("host", "compute", "exposed_comm", "optimizer"))
        assert 0.0 <= s["comm_hidden_fraction"] <= 1.0

    def test_flight_recorder_state_provider(self, hvd, prof):
        from horovod_tpu import flight_recorder

        with prof.step("flight"):
            pass
        state = flight_recorder.recorder().snapshot("test")["state"]
        assert "profiler" in state
        assert state["profiler"]["steps"]

    def test_profile_endpoint(self, hvd, prof):
        from horovod_tpu.metrics import registry

        with prof.step("serve"):
            pass
        reg = registry()
        port = reg.serve(0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/profile", timeout=5) as resp:
                assert resp.headers["Content-Type"].startswith(
                    "application/json")
                doc = json.loads(resp.read())
        finally:
            reg.stop_server()
        assert doc["schema"] == "horovod-profiler-v1"
        assert doc["enabled"] is True
        assert doc["steps"], "profiled steps missing from GET /profile"

    def test_dump_writes_schema_and_markers(self, hvd, prof, tmp_path):
        with prof.step("dumped"):
            time.sleep(0.001)
        snap = prof.dump(str(tmp_path / "profile-rank-0.json"), ship=False)
        doc = json.load(open(tmp_path / "profile-rank-0.json"))
        assert doc["schema"] == "horovod-profiler-v1"
        assert doc["steps"] == snap["steps"]
        assert any(e["ph"] == "X" and e["name"] == "dumped"
                   for e in doc["trace_events"])


def _fake_dump(rank, t0, offset=0.0, n_steps=3):
    events = []
    steps = []
    for i in range(n_steps):
        start = t0 + 0.1 * i
        steps.append({"step": i + 1, "name": f"step {i}", "auto": False,
                      "t_start": start, "wall_seconds": 0.05,
                      "phases": {"host": 0.01, "compute": 0.03,
                                 "exposed_comm": 0.005, "optimizer": 0.005},
                      "comm": {"total_seconds": 0.01,
                               "exposed_seconds": 0.005, "bytes": 1024,
                               "hidden_fraction": 0.5,
                               "hidden_fraction_bytes": 0.5},
                      "mfu": 0.4})
        events.append({"ph": "X", "pid": 0, "tid": 0, "ts": start * 1e6,
                       "dur": 0.05 * 1e6, "name": f"step {i}"})
    return {"schema": "horovod-profiler-v1", "rank": rank,
            "launch_rank": rank, "clock_offset_seconds": offset,
            "steps": steps, "trace_events": events,
            "flight_events": [{"t": t0, "kind": "init", "rank": rank}]}


class TestMergedTrace:
    def test_merge_is_valid_chrome_trace(self, tmp_path):
        from horovod_tpu import profiler

        t0 = 1700000000.0
        for rank, offset in ((0, 0.0), (1, 2.5)):
            with open(tmp_path / f"profile-rank-{rank}.json", "w") as f:
                json.dump(_fake_dump(rank, t0, offset), f)
            with open(tmp_path / f"timeline-rank-{rank}.json", "w") as f:
                # a runtime timeline fragment (open JSON array form)
                f.write(json.dumps([
                    {"ph": "B", "pid": 9, "tid": 3, "ts": t0 * 1e6,
                     "name": "ALLREDUCE"},
                    {"ph": "E", "pid": 9, "tid": 3,
                     "ts": (t0 + 0.01) * 1e6}])[:-1] + ",")
        out, n = profiler.merge_profile_dir(str(tmp_path))
        assert os.path.exists(out) and n > 0
        doc = json.load(open(out))  # valid JSON or this raises
        events = doc["traceEvents"]
        labels = {e["args"]["labels"] for e in events
                  if e.get("name") == "process_labels"}
        assert {"rank 0 steps", "rank 1 steps", "rank 0 timeline",
                "rank 1 timeline"} <= labels
        # per-lane timestamps are monotonic
        lanes = {}
        for e in events:
            if e.get("ph") == "M" or not isinstance(
                    e.get("ts"), (int, float)):
                continue
            key = (e.get("pid"), e.get("tid"))
            assert e["ts"] >= lanes.get(key, float("-inf")), key
            lanes[key] = e["ts"]
        # rank 1's events were shifted by its clock offset (+2.5 s)
        r0 = [e["ts"] for e in events
              if e.get("name") == "step 0" and e.get("ph") == "X"]
        assert max(r0) - min(r0) == pytest.approx(2.5e6)

    def test_step_report_names_slowest_rank_and_phase(self, tmp_path):
        from horovod_tpu import profiler

        fast = _fake_dump(0, 1700000000.0)
        slow = _fake_dump(1, 1700000000.0)
        for s in slow["steps"]:
            s["wall_seconds"] = 0.2
            s["phases"] = {"host": 0.01, "compute": 0.02,
                           "exposed_comm": 0.16, "optimizer": 0.01}
        report = profiler.format_step_report([fast, slow])
        assert "slowest: rank 1" in report
        assert "dominant phase: exposed_comm" in report

    def test_profile_report_cli(self, tmp_path, capsys):
        from horovod_tpu.run.run import run_commandline

        with open(tmp_path / "profile-rank-0.json", "w") as f:
            json.dump(_fake_dump(0, 1700000000.0), f)
        assert run_commandline(["--profile-report", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "step-time report" in out
        assert run_commandline(
            ["--profile-report", str(tmp_path / "empty")]) == 1


class TestKnobs:
    def test_defaults(self, monkeypatch):
        from horovod_tpu.utils import env

        for knob in (env.HOROVOD_PROFILE, env.HOROVOD_PROFILE_DIR,
                     env.HOROVOD_PROFILE_HISTORY, env.HOROVOD_PROFILE_JAX):
            monkeypatch.delenv(knob, raising=False)
        cfg = env.Config.from_env()
        assert cfg.profile is False
        assert cfg.profile_dir == ""
        assert cfg.profile_history == env.DEFAULT_PROFILE_HISTORY
        assert cfg.profile_jax is False

    def test_profile_dir_implies_enable(self, monkeypatch):
        from horovod_tpu.utils import env

        monkeypatch.delenv(env.HOROVOD_PROFILE, raising=False)
        monkeypatch.setenv(env.HOROVOD_PROFILE_DIR, "/tmp/prof")
        cfg = env.Config.from_env()
        assert cfg.profile is True
        assert cfg.profile_dir == "/tmp/prof"


# ---------------------------------------------------------------------------
# 2-rank end-to-end merge over the real transport
# ---------------------------------------------------------------------------

def _native_built():
    from horovod_tpu.runtime.native import native_built

    return native_built()


@pytest.mark.skipif(not _native_built(),
                    reason="native transport not built")
def test_two_rank_profile_merge(tmp_path):
    """Acceptance: a 2-rank run with HOROVOD_PROFILE_DIR leaves per-rank
    dumps + timelines that merge into ONE Perfetto-loadable trace with
    both ranks' runtime spans and step markers on a common clock, and the
    cross-rank step report covers both ranks."""
    from horovod_tpu import profiler
    from horovod_tpu.run.rendezvous import RendezvousServer

    profile_dir = tmp_path / "profile"
    os.makedirs(profile_dir)
    rendezvous = RendezvousServer(host="127.0.0.1")
    http_port = rendezvous.start()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        socket_port = s.getsockname()[1]
    world, procs, logs, outs = 2, [], [], []
    try:
        for rank in range(world):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)
            env.update({
                "HOROVOD_RANK": str(rank),
                "HOROVOD_SIZE": str(world),
                "HOROVOD_CONTROLLER": "socket",
                "HOROVOD_GLOO_RENDEZVOUS_ADDR": "127.0.0.1",
                "HOROVOD_GLOO_RENDEZVOUS_PORT": str(socket_port),
                "HOROVOD_RENDEZVOUS_HTTP_ADDR": "127.0.0.1",
                "HOROVOD_RENDEZVOUS_HTTP_PORT": str(http_port),
                "HOROVOD_GLOO_TIMEOUT_SECONDS": "15",
                "HOROVOD_PROFILE_DIR": str(profile_dir),
                "HOROVOD_TIMELINE": str(
                    profile_dir / f"timeline-rank-{rank}.json"),
                "JAX_PLATFORMS": "cpu",
            })
            start(procs, logs, [sys.executable, WORKER], env)
        outs = collect(procs, logs, 180)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        rendezvous.stop()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "DONE" in out, out

    dumps = profiler.load_dumps(str(profile_dir))
    assert sorted(d["launch_rank"] for d in dumps) == [0, 1]
    out_path, n_events = profiler.merge_profile_dir(str(profile_dir))
    assert n_events > 0
    doc = json.load(open(out_path))
    events = doc["traceEvents"]
    labels = {e["args"]["labels"] for e in events
              if e.get("name") == "process_labels"}
    assert {"rank 0 steps", "rank 1 steps"} <= labels
    assert {"rank 0 timeline", "rank 1 timeline"} <= labels, labels
    # step markers from BOTH ranks made it onto the common clock
    step_ranks = {lbl for lbl in labels if lbl.endswith("steps")}
    assert len(step_ranks) == 2
    report = profiler.format_step_report(dumps)
    assert "2 ranks" in report
    assert "rank 0:" in report and "rank 1:" in report
    assert "slowest: rank" in report
