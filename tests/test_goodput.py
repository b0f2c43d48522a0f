"""Goodput ledger (ISSUE 19): category accounting that sums to
wall-clock, the bounded incident ledger and replay attribution, the
surfaces (/goodput route + the JSON route index, merged-trace counter +
incident lanes, hvd_top panel, cross-rank postmortem report) and the
knob plumbing.

Tier-1 safe: everything here drives the tracker directly — no devices,
no timing sensitivity (spans are injected, not measured). The real
multiprocess acceptance (a killed rank's re-form downtime landing in
``elastic_reform`` on every survivor) is at the bottom, and the full
three-disruption attribution proof is tools/chaos_matrix.py's
``goodput_attribution`` cell.
"""

import json
import os
import socket
import sys
import urllib.request

import pytest

from horovod_tpu import flight_recorder, goodput

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracker():
    """A fresh GoodputTracker so tests never fight the singleton."""
    t = goodput.GoodputTracker()
    t.enabled = True
    t.rank, t.world = 0, 2
    t.start_epoch()
    yield t


def _age(t, seconds=3600.0):
    """Backdate the epoch so injected spans fit inside the wall-clock
    (no proportional scale-down) — and pin the first-work mark to the
    epoch so the synthetic past is not misread as startup time."""
    with t._lock:
        t._epoch -= seconds
        t._first_mark = t._epoch


def _rewind_step_mark(t, seconds):
    """Open a synthetic gap since the last accounted step, so injected
    step walls survive the frontier clamp without real sleeps."""
    with t._lock:
        if t._step_mark is not None:
            t._step_mark -= seconds


@pytest.fixture
def singleton():
    """The process-wide tracker, reset before and after (the /goodput
    route, hvd_top panel and bench goodput_rows read the singleton)."""
    t = goodput.tracker()
    saved = (t.enabled, t.rank, t.world, t.report_seconds)
    t.reset()
    t.enabled = True
    t.start_epoch()
    yield t
    t.reset()
    t.enabled, t.rank, t.world, t.report_seconds = saved


class TestAccounting:
    def test_categories_sum_to_wall_exactly(self, tracker):
        _age(tracker)
        tracker.record_step(0.5)
        tracker.record_span("ckpt_stall", 0.2)
        tracker.record_span("collective_stall", 0.1)
        led = tracker.ledger()
        total = led["productive_seconds"] + sum(
            led["badput_seconds"].values())
        # exact pre-rounding; the ledger rounds each entry to 6dp so the
        # recomposed sum can differ by a few ulps per category
        assert abs(total - led["wall_seconds"]) < 1e-4
        assert led["badput_seconds"]["ckpt_stall"] == pytest.approx(
            0.2, abs=1e-6)
        assert led["steps_productive"] == 1

    def test_remainder_lands_in_input_idle(self, tracker):
        tracker.record_step(1e-6)  # attribute ~nothing
        led = tracker.ledger()
        assert led["badput_seconds"].get("input_idle", 0.0) >= 0.0
        assert led["accounted_fraction"] <= 1.0

    def test_over_attribution_scales_down(self, tracker):
        # claim far more than elapsed: the ledger must scale to wall,
        # never report accounted > 1
        tracker.record_span("ckpt_stall", 3600.0)
        tracker.record_span("rollback", 3600.0)
        led = tracker.ledger()
        total = sum(led["badput_seconds"].values()) \
            + led["productive_seconds"]
        assert total == pytest.approx(led["wall_seconds"], abs=1e-4)
        assert led["accounted_fraction"] == pytest.approx(1.0, abs=1e-6)
        # proportionality survives the scale-down
        bp = led["badput_seconds"]
        assert bp["ckpt_stall"] == pytest.approx(bp["rollback"], rel=1e-3)

    def test_unknown_category_dropped(self, tracker):
        tracker.record_span("coffee_break", 5.0)
        assert "coffee_break" not in tracker.ledger()["badput_seconds"]

    def test_disabled_tracker_records_nothing(self, tracker):
        tracker.enabled = False
        tracker.record_step(0.5)
        tracker.record_span("ckpt_stall", 0.2)
        tracker.note_incident("rollback", 1.0)
        led = tracker.ledger()
        assert led["steps_productive"] == 0
        assert led["incidents"] == []

    def test_startup_is_gap_before_first_work(self, tracker):
        import time

        time.sleep(0.05)
        tracker.record_step(0.01)
        led = tracker.ledger()
        assert led["badput_seconds"].get(
            "startup_compile", 0.0) >= 0.04

    def test_nothing_attributed_is_all_startup(self, tracker):
        import time

        time.sleep(0.02)
        led = tracker.ledger()
        assert led["badput_seconds"]["startup_compile"] == pytest.approx(
            led["wall_seconds"], abs=1e-4)
        assert led["goodput_fraction"] == 0.0

    def test_exposed_comm_split_out_of_step(self, tracker):
        _age(tracker)
        tracker.record_step(0.5, exposed_comm=0.1)
        led = tracker.ledger()
        assert led["productive_seconds"] == pytest.approx(0.4, abs=1e-6)
        assert led["badput_seconds"]["exposed_comm"] == pytest.approx(
            0.1, abs=1e-6)

    def test_profiler_claim_clamped_to_gap(self, tracker):
        # frontier guard: a measured step wall can never exceed the
        # unattributed gap since the previous accounted step
        _age(tracker)
        tracker.record_step(1e-4)
        _rewind_step_mark(tracker, 0.01)  # real gap: 10 ms
        tracker.record_step(3600.0)  # absurd measurement
        led = tracker.ledger()
        assert led["productive_seconds"] <= led["wall_seconds"] + 1e-6
        assert led["productive_seconds"] < 1.0  # clamped to the gap
        assert led["steps_productive"] == 2

    def test_commit_source_excludes_badput_spans(self, tracker):
        import time

        tracker.record_step(1e-4)  # pin the step frontier
        time.sleep(0.03)
        tracker.record_span("elastic_reform", 0.025)  # inside the gap
        tracker.record_step()  # commit-style: claims gap MINUS the span
        led = tracker.ledger()
        assert led["badput_seconds"]["elastic_reform"] == pytest.approx(
            0.025, abs=1e-6)
        # productive gets the remainder of the gap, not the whole gap
        assert led["productive_seconds"] < led["wall_seconds"] - 0.02


class TestServePlane:
    def test_serve_steps_are_productive(self, tracker):
        _age(tracker)
        tracker.record_serve_step(0.2, tokens=4)
        led = tracker.ledger()
        assert led["productive_seconds"] == pytest.approx(0.2, abs=1e-6)
        assert led["serve_blocks"] == 1

    def test_preemption_reattributes_net_zero(self, tracker):
        _age(tracker)
        tracker.record_serve_step(0.4, tokens=4)  # cost 0.1 s/token
        before = tracker.ledger()
        tracker.note_serve_preempted(2)
        led = tracker.ledger()
        assert led["badput_seconds"]["serve_preempted"] == pytest.approx(
            0.2, abs=1e-6)
        assert led["productive_seconds"] == pytest.approx(
            before["productive_seconds"] - 0.2, abs=1e-6)

    def test_preemption_clamped_to_available_productive(self, tracker):
        _age(tracker)
        tracker.record_serve_step(0.1, tokens=1)  # cost 0.1 s/token
        tracker.note_serve_preempted(1000)
        led = tracker.ledger()
        assert led["productive_seconds"] == pytest.approx(0.0, abs=1e-6)
        assert led["badput_seconds"]["serve_preempted"] == pytest.approx(
            0.1, abs=1e-6)

    def test_prefill_does_not_poison_token_cost(self, tracker):
        tracker.record_serve_step(0.4, tokens=4)   # decode: cost 0.1
        tracker.record_serve_step(9.0, tokens=0)   # prefill: no tokens
        with tracker._lock:
            assert tracker._serve_token_cost == pytest.approx(0.1)


class TestIncidents:
    def test_incident_record_shape_and_counts(self, tracker):
        _age(tracker)
        tracker.note_incident(
            "elastic_reform", 2.5, generation=1, culprit_rank=3,
            linked_events=["elastic_reform", "workers_down"],
            detail="rank 3 lost")
        (inc,) = tracker.incidents()
        assert inc["cause"] == "elastic_reform"
        assert inc["duration_s"] == pytest.approx(2.5)
        assert inc["generation"] == 1
        assert inc["culprit_rank"] == 3
        assert inc["linked_events"] == ["elastic_reform", "workers_down"]
        led = tracker.ledger()
        assert led["incident_counts"] == {"elastic_reform": 1}
        assert led["badput_seconds"]["elastic_reform"] == pytest.approx(
            2.5, abs=1e-4)

    def test_incident_emits_flight_event(self, tracker):
        before = len([e for e in flight_recorder.recorder().events()
                      if e.get("kind") == "goodput_incident"])
        tracker.note_incident("rollback", 0.5, culprit_rank=1)
        events = [e for e in flight_recorder.recorder().events()
                  if e.get("kind") == "goodput_incident"]
        assert len(events) - before == 1
        assert events[-1]["cause"] == "rollback"
        assert events[-1]["culprit_rank"] == 1

    def test_ledger_is_bounded(self, tracker):
        tracker.set_incident_capacity(4)
        for i in range(10):
            tracker.note_incident("rollback", 0.01, detail="inc %d" % i)
        incidents = tracker.incidents()
        assert len(incidents) == 4
        assert incidents[-1]["detail"] == "inc 9"  # newest kept
        # counts keep the full history even as the ring rolls
        assert tracker.ledger()["incident_counts"]["rollback"] == 10

    def test_unknown_cause_coerced(self, tracker):
        tracker.note_incident("meteor_strike", 1.0)
        assert tracker.incidents()[0]["cause"] == "rollback"


class TestReplayAttribution:
    def test_replayed_steps_charged_to_incident(self, tracker):
        _age(tracker)
        tracker.record_step(0.1)  # one honest step
        tracker.note_incident("rollback", 0.5, replay_steps=2)
        _rewind_step_mark(tracker, 1.0)
        tracker.record_step(0.2)  # replays: badput, not productive
        _rewind_step_mark(tracker, 1.0)
        tracker.record_step(0.2)
        _rewind_step_mark(tracker, 1.0)
        tracker.record_step(0.1)  # countdown exhausted: productive again
        led = tracker.ledger()
        assert led["steps_productive"] == 2
        assert led["steps_replayed"] == 2
        assert led["badput_seconds"]["rollback"] == pytest.approx(
            0.5 + 0.4, abs=1e-4)
        (inc,) = tracker.incidents()
        assert inc["steps_replayed"] == 2
        assert inc["replayed_seconds"] == pytest.approx(0.4, abs=1e-4)

    def test_replay_charged_to_arming_cause(self, tracker):
        _age(tracker)
        tracker.record_step(0.1)
        tracker.note_incident("elastic_reform", 0.2, replay_steps=1)
        _rewind_step_mark(tracker, 1.0)
        tracker.record_step(0.3)
        led = tracker.ledger()
        assert led["badput_seconds"]["elastic_reform"] == pytest.approx(
            0.5, abs=1e-4)
        assert "rollback" not in led["badput_seconds"]


class TestConfigure:
    def test_knobs_and_provider_registration(self, singleton, monkeypatch):
        monkeypatch.setenv("HOROVOD_GOODPUT", "1")
        monkeypatch.setenv("HOROVOD_GOODPUT_INCIDENTS", "7")
        monkeypatch.setenv("HOROVOD_GOODPUT_REPORT_SECONDS", "30")
        goodput.configure(rank=3, world=4)
        assert singleton.enabled is True
        assert singleton.rank == 3 and singleton.world == 4
        assert singleton.report_seconds == 30.0
        with singleton._lock:
            assert singleton._incidents.maxlen == 7
        assert "goodput" in flight_recorder._recorder._providers
        monkeypatch.setenv("HOROVOD_GOODPUT", "0")
        goodput.configure()
        assert singleton.enabled is False
        assert "goodput" not in flight_recorder._recorder._providers
        monkeypatch.setenv("HOROVOD_GOODPUT", "1")
        goodput.configure()  # restore for the fixture teardown

    def test_epoch_survives_reconfigure(self, singleton):
        with singleton._lock:
            epoch = singleton._epoch
        goodput.configure(rank=0, world=2)  # elastic reinit path
        with singleton._lock:
            assert singleton._epoch == epoch

    def test_goodput_state_document(self, singleton):
        singleton.record_step(0.1)
        state = goodput.goodput_state()
        assert state["enabled"] is True
        assert state["steps_productive"] == 1
        assert isinstance(state["samples"], list) and state["samples"]


class TestMetricsRoutes:
    def test_get_goodput_route(self, singleton):
        from horovod_tpu.metrics import MetricsRegistry

        singleton.record_step(0.05)
        reg = MetricsRegistry()
        port = reg.serve(0)
        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/goodput" % port, timeout=5) as r:
                doc = json.loads(r.read().decode())
            assert doc["steps_productive"] == 1
            assert 0.0 <= doc["goodput_fraction"] <= 1.0
            assert "badput_seconds" in doc and "samples" in doc
        finally:
            reg.stop_server()

    def test_root_serves_route_index(self):
        """ISSUE 19 satellite: bare GET / (and /debug/routes) answers a
        JSON index of every route instead of 404."""
        from horovod_tpu.metrics import MetricsRegistry, route_index

        reg = MetricsRegistry()
        port = reg.serve(0)
        try:
            for path in ("/", "/debug/routes"):
                with urllib.request.urlopen(
                        "http://127.0.0.1:%d%s" % (port, path),
                        timeout=5) as r:
                    assert r.headers.get_content_type() == \
                        "application/json"
                    doc = json.loads(r.read().decode())
                for route in ("/metrics", "/goodput", "/comms", "/slo",
                              "/memory", "/healthz", "/serve"):
                    assert route in doc["routes"], (path, doc)
            assert route_index()["routes"] == doc["routes"]
        finally:
            reg.stop_server()

    def test_unknown_route_still_404s(self):
        from horovod_tpu.metrics import MetricsRegistry

        reg = MetricsRegistry()
        port = reg.serve(0)
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(
                    "http://127.0.0.1:%d/nope" % port, timeout=5)
            assert err.value.code == 404
        finally:
            reg.stop_server()


def _dump(rank, gp_state):
    return {"schema": flight_recorder.SCHEMA, "rank": rank,
            "launch_rank": rank, "pid": 1000 + rank,
            "host": "host%d" % rank, "reason": "test", "wall_time": 0.0,
            "clock_offset_seconds": 0.0, "dump_history": [], "events": [],
            "state": {"goodput": gp_state}, "metrics": {}}


def _gp_state(rank, wall, productive, badput, incidents=(),
              replayed=0):
    return {"rank": rank, "world": 2, "wall_time": 0.0,
            "enabled": True, "wall_seconds": wall,
            "goodput_fraction": productive / wall,
            "accounted_fraction": 1.0,
            "productive_seconds": productive,
            "badput_seconds": badput, "steps_productive": 10,
            "steps_replayed": replayed, "serve_blocks": 0,
            "incident_counts": {}, "incidents": list(incidents)}


class TestPostmortemReport:
    def test_cross_rank_report(self):
        dumps = [
            _dump(0, _gp_state(0, 100.0, 80.0,
                               {"ckpt_stall": 5.0, "input_idle": 15.0})),
            _dump(1, _gp_state(
                1, 100.0, 60.0,
                {"elastic_reform": 30.0, "input_idle": 10.0},
                incidents=[{"cause": "elastic_reform", "wall_time": 1.0,
                            "duration_s": 30.0, "generation": 1,
                            "culprit_rank": 2, "steps_replayed": 3,
                            "replayed_seconds": 6.0,
                            "linked_events": [], "detail": None}],
                replayed=3)),
        ]
        text = goodput.format_goodput_report(dumps)
        assert "=== goodput report (2 ranks) ===" in text
        assert "rank 0: goodput 80.0% of 100.0s" in text
        assert "3 step(s) replayed" in text
        # fleet 140/200 time-weighted
        assert "fleet goodput: 70.0% (time-weighted across 2 ranks)" \
            in text
        assert "dominant badput: elastic_reform (30.0s" in text
        assert ("costliest incident: elastic_reform on rank 1 — 36.0s "
                "(gen 1, 3 step(s) replayed, culprit rank 2)") in text

    def test_report_empty_without_goodput_state(self):
        dumps = [_dump(0, None)]
        dumps[0]["state"] = {}
        assert goodput.format_goodput_report(dumps) == ""

    def test_format_postmortem_embeds_goodput_section(self):
        dumps = [_dump(0, _gp_state(0, 10.0, 9.0, {"input_idle": 1.0}))]
        text = flight_recorder.format_postmortem(dumps)
        assert "=== goodput report" in text
        assert "rank 0: goodput 90.0%" in text


class TestHvdTop:
    def _import_hvd_top(self):
        repo_tools = os.path.join(_REPO, "tools")
        if repo_tools not in sys.path:
            sys.path.insert(0, repo_tools)
        import hvd_top
        return hvd_top

    def test_goodput_panel_against_live_endpoint(self, singleton):
        from horovod_tpu.metrics import MetricsRegistry

        hvd_top = self._import_hvd_top()
        singleton.record_step(0.05)
        singleton.note_incident("rollback", 0.2, culprit_rank=1)
        reg = MetricsRegistry()
        port = reg.serve(0)
        try:
            ep = "127.0.0.1:%d" % port
            panel = hvd_top.render_goodput([ep])
            assert "top badput" in panel.splitlines()[0]
            assert "rollback" in panel
            assert "last incident: rollback" in panel
            # the route index drives panel selection
            routes = hvd_top.discover_routes([ep])
            assert "/goodput" in routes
            assert hvd_top.panel_wanted(routes, "/goodput")
            assert not hvd_top.panel_wanted(routes, "/made_up")
        finally:
            reg.stop_server()

    def test_goodput_panel_empty_without_endpoint(self):
        hvd_top = self._import_hvd_top()
        assert hvd_top.render_goodput(["127.0.0.1:1"]) == ""
        # no index reachable: fall back to probing every panel
        assert hvd_top.discover_routes(["127.0.0.1:1"]) is None
        assert hvd_top.panel_wanted(None, "/anything")


class TestMergedTrace:
    def test_fraction_counter_and_incident_instants(self, tmp_path):
        from horovod_tpu import profiler

        t0 = 1700000000.0
        dump = {"schema": "horovod-profiler-v1", "rank": 0,
                "launch_rank": 0, "clock_offset_seconds": 0.0,
                "steps": [], "trace_events": [
                    {"ph": "X", "pid": 0, "tid": 0, "ts": t0 * 1e6,
                     "dur": 1e4, "name": "step 0"}],
                "flight_events": [],
                "goodput_samples": [[t0, 0.9], [t0 + 1.0, 0.5],
                                    ["bogus", None]],
                "goodput_incidents": [
                    {"cause": "elastic_reform", "wall_time": t0 + 0.5,
                     "duration_s": 2.0, "generation": 1,
                     "culprit_rank": 2, "steps_replayed": 0},
                    {"cause": "rollback"},  # no wall_time: skipped
                ]}
        with open(tmp_path / "profile-rank-0.json", "w") as f:
            json.dump(dump, f)
        out, _ = profiler.merge_profile_dir(str(tmp_path))
        events = json.load(open(out))["traceEvents"]
        counters = [e for e in events
                    if e.get("name") == "goodput fraction"]
        assert len(counters) == 2  # malformed row skipped
        assert all(e["ph"] == "C" for e in counters)
        assert counters[0]["args"] == {"productive": 0.9}
        instants = [e for e in events
                    if str(e.get("name", "")).startswith("incident:")]
        assert len(instants) == 1  # wall_time-less record skipped
        assert instants[0]["ph"] == "i"
        assert instants[0]["name"] == "incident: elastic_reform"
        assert instants[0]["args"]["culprit_rank"] == 2

    def test_profiler_snapshot_carries_goodput_trails(self, singleton):
        from horovod_tpu import profiler

        singleton.record_step(0.01)
        singleton.note_incident("rollback", 0.1)
        snap = profiler._profiler.snapshot()
        assert snap["goodput_samples"]
        assert snap["goodput_incidents"][-1]["cause"] == "rollback"


# ---------------------------------------------------------------------------
# multiprocess: a killed rank's downtime lands in elastic_reform
# ---------------------------------------------------------------------------

from horovod_tpu.run.rendezvous import RendezvousServer  # noqa: E402
from horovod_tpu.runtime.native import native_built  # noqa: E402
from mp_launch import collect, start  # noqa: E402


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.skipif(not native_built(),
                    reason="native transport not built")
def test_reform_downtime_attributed_on_survivors(tmp_path):
    """Kill rank 1 mid-run: every survivor's ledger must carry the
    re-form downtime in ``elastic_reform`` (with an incident naming the
    lost rank as culprit) while still accounting >= 90% of wall-clock."""
    world, total = 3, 5
    worker = os.path.join(_REPO, "tools", "chaos_worker.py")
    server = RendezvousServer(host="127.0.0.1")
    http_port = server.start()
    socket_port = _free_port()
    procs, logs = [], []
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
                "HOROVOD_ELASTIC": "1",
                "HOROVOD_ELASTIC_MIN_WORKERS": "2",
                "HOROVOD_ELASTIC_SETTLE_SECONDS": "0.3",
                "HOROVOD_GLOO_TIMEOUT_SECONDS": "5",
                "HOROVOD_FAULT_INJECT": "kill:rank=1:step=2:code=17",
                "HOROVOD_FLIGHT_RECORDER_DIR": str(tmp_path),
                "CHAOS_TOTAL_STEPS": str(total),
                "JAX_PLATFORMS": "cpu",
            })
            start(procs, logs, [sys.executable, worker], env)
        results = {}
        outs = collect(procs, logs, 120)
        for rank, (proc, out) in enumerate(zip(procs, outs)):
            want = 17 if rank == 1 else 0
            assert proc.returncode == want, \
                f"rank {rank} exited {proc.returncode}:\n{out[-2000:]}"
            for line in out.splitlines():
                if line.startswith("CHAOS_RESULT "):
                    results[rank] = json.loads(
                        line[len("CHAOS_RESULT "):])
        assert sorted(results) == [0, 2]
        for rank, res in results.items():
            assert res["step"] == total, res
            assert res["generation"] >= 1, res
            assert res["goodput_badput"].get("elastic_reform", 0) > 0, res
            assert res["goodput_accounted"] >= 0.9, res
            assert res["goodput_incidents"].get("elastic_reform") == 1, res
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()
