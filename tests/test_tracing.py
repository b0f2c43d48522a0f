"""Unit coverage for the tracing + SLO plane (tracing.py; docs/tracing.md).

Pinned-down contracts:

* the span ring is bounded: capacity evicts oldest-first and the
  ``HOROVOD_TRACE`` grammar (off switch / capacity integer) holds;
* trace context survives the KV wire format round-trip on both
  ``Request`` and ``Completion``;
* burn-rate math: bad fraction over the rolling window divided by the
  allowed fraction, budget clamped at zero, ``ok=False`` scores only
  the availability objective;
* a burn-rate crossing emits exactly ONE ``slo_burn_rate`` flight event
  and re-arms when the rate falls back under the threshold;
* ``/slo`` and ``/healthz`` routes: readiness transitions (503 before
  init, 503 while serving without a replica heartbeat, 200 after);
* Chrome conversion + flow arrows: ``merge_profile_dir`` lays out
  per-rank request lanes on the ``/_time``-corrected clock and joins one
  trace_id's spans across lanes;
* the replica loop records queue_wait/prefill/decode/serve spans
  and scores the SLO tracker for every completion.

The 2-rank half (frontend process + a real ``python -m
horovod_tpu.serve`` replica, one trace_id across both ranks in the
merged Perfetto trace) is at the bottom.
"""

import json
import os
import sys
import time
import urllib.error
import urllib.request

import pytest

from horovod_tpu import flight_recorder, profiler, tracing
from horovod_tpu.serve.queue import Completion, Request, RequestQueue
from horovod_tpu.utils.env import (DEFAULT_TRACE_CAPACITY,
                                   HOROVOD_SLO_AVAILABILITY,
                                   HOROVOD_SLO_LATENCY_MS,
                                   HOROVOD_SLO_TTFT_MS, HOROVOD_SLO_WINDOW,
                                   HOROVOD_TRACE, parse_trace)
from mp_launch import collect, start

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- span ring

def test_parse_trace_grammar():
    assert parse_trace(None) == (True, DEFAULT_TRACE_CAPACITY)
    assert parse_trace("1") == (True, DEFAULT_TRACE_CAPACITY)
    assert parse_trace("0") == (False, DEFAULT_TRACE_CAPACITY)
    assert parse_trace("off") == (False, DEFAULT_TRACE_CAPACITY)
    assert parse_trace("128") == (True, 128)


def test_span_ring_bounded_oldest_evicted(monkeypatch):
    monkeypatch.setenv(HOROVOD_TRACE, "16")
    t = tracing.Tracer()
    assert t.capacity == 16
    for i in range(40):
        t.record("s", t0=float(i), dur=0.001, trace_id="t%d" % i)
    spans = t.spans()
    assert len(spans) == 16
    # oldest evicted, newest kept, order preserved
    assert [s["trace_id"] for s in spans] == \
        ["t%d" % i for i in range(24, 40)]


def test_disabled_tracer_records_nothing(monkeypatch):
    monkeypatch.setenv(HOROVOD_TRACE, "0")
    t = tracing.Tracer()
    t.record("s", t0=0.0, dur=0.001)
    assert t.spans() == []


def test_new_trace_ids_unique_and_wire_sized():
    ids = {tracing.new_trace_id() for _ in range(64)}
    assert len(ids) == 64
    assert all(len(i) == 16 for i in ids)


# ------------------------------------------------------- context on the wire

def test_trace_context_survives_kv_roundtrip():
    req = Request(uid="r1", prompt=[1, 2, 3], max_new_tokens=4,
                  trace_id="abcdef0123456789", requeues=2)
    back = Request.from_json(req.to_json())
    assert back.trace_id == "abcdef0123456789" and back.requeues == 2
    done = Completion(uid="r1", tokens=[5], prompt_len=3, rank=1,
                      trace_id="abcdef0123456789", requeues=2)
    back = Completion.from_json(done.to_json())
    assert back.trace_id == "abcdef0123456789" and back.requeues == 2


def test_pre_tracing_wire_format_still_parses():
    # a frontend from an older build omits the context fields entirely
    raw = json.dumps({"uid": "r1", "prompt": [1], "max_new_tokens": 2})
    req = Request.from_json(raw.encode())
    assert req.trace_id == "" and req.requeues == 0


def test_queue_submit_mints_trace_and_records_spans():
    q = RequestQueue()
    uid = q.submit([1, 2, 3], max_new_tokens=4)
    req = q.pull(rank=0, max_n=1)[0]
    assert req.trace_id                      # minted at submit
    q.complete(Completion(uid=uid, tokens=[5], prompt_len=3, rank=0,
                          trace_id=req.trace_id))
    names = [s["name"] for s in tracing.spans()
             if s.get("trace_id") == req.trace_id]
    assert "request.submit" in names and "request.response" in names


def test_eager_collective_records_span(hvd):
    """The eager single-controller dispatch (_op_event) lands on the same
    collective: lane as the enqueue runtime — a training script that never
    touches the runtime still gets comm spans."""
    import jax.numpy as jnp

    before = tracing.tracer().spans_recorded()
    hvd.allreduce(hvd.stack_per_worker(
        [jnp.ones(4) * (r + 1) for r in range(hvd.size())]),
        name="traced_probe")
    spans = [s for s in tracing.spans()
             if s["name"] == "collective:traced_probe"]
    assert spans, [s["name"] for s in tracing.spans()]
    assert spans[-1]["op"] == "allreduce" and spans[-1]["bytes"] > 0
    assert tracing.tracer().spans_recorded() > before


# ----------------------------------------------------------------- SLO math

def _slo_tracker(monkeypatch, *, window=10, availability=0.9,
                 latency_ms=100.0, ttft_ms=50.0, burn_alert=14.0):
    monkeypatch.setenv(HOROVOD_SLO_WINDOW, str(window))
    monkeypatch.setenv(HOROVOD_SLO_AVAILABILITY, str(availability))
    monkeypatch.setenv(HOROVOD_SLO_LATENCY_MS, str(latency_ms))
    monkeypatch.setenv(HOROVOD_SLO_TTFT_MS, str(ttft_ms))
    monkeypatch.setenv("HOROVOD_SLO_BURN_ALERT", str(burn_alert))
    return tracing.SLOTracker()


def test_burn_rate_math(monkeypatch):
    slo = _slo_tracker(monkeypatch)          # window 10, target 0.9
    for _ in range(9):
        slo.record_request(ttft_s=0.01, latency_s=0.05)
    assert slo.burn_rate("latency") == 0.0
    assert slo.error_budget_remaining("latency") == 1.0
    # one slow request in a 10-deep window: bad fraction 0.1, allowed
    # fraction 1 - 0.9 = 0.1 -> burn exactly 1.0, budget exhausted
    slo.record_request(ttft_s=0.01, latency_s=0.5)
    assert slo.burn_rate("latency") == pytest.approx(1.0)
    assert slo.error_budget_remaining("latency") == pytest.approx(0.0)
    # ttft stayed clean throughout
    assert slo.burn_rate("ttft") == 0.0
    st = slo.state()
    assert st["slo"]["latency"]["bad_total"] == 1
    assert st["requests_scored"] == 10


def test_budget_clamps_at_zero(monkeypatch):
    slo = _slo_tracker(monkeypatch)
    for _ in range(5):
        slo.record_request(ttft_s=0.01, latency_s=9.9)   # all bad
    assert slo.burn_rate("latency") > 1.0
    assert slo.error_budget_remaining("latency") == 0.0


def test_failed_request_scores_only_availability(monkeypatch):
    slo = _slo_tracker(monkeypatch)
    slo.record_request(0.0, 0.0, ok=False)
    st = slo.state()["slo"]
    assert st["availability"]["window_observed"] == 1
    assert st["availability"]["bad_total"] == 1
    assert st["ttft"]["window_observed"] == 0
    assert st["latency"]["window_observed"] == 0
    # an unserved request must not pollute the latency percentiles
    assert slo.state()["latency_ms_percentiles"]["p50"] is None


def test_burn_alert_emits_once_then_rearms(monkeypatch):
    # availability 0.5 -> allowed fraction 0.5; alert at burn >= 1.5,
    # i.e. bad fraction >= 0.75 of the window
    slo = _slo_tracker(monkeypatch, window=4, availability=0.5,
                       burn_alert=1.5)

    def alert_events():
        return [e for e in flight_recorder.recorder().events()
                if e.get("kind") == "slo_burn_rate"
                and e.get("objective") == "latency"]

    n0 = len(alert_events())
    for _ in range(4):
        slo.record_request(ttft_s=0.01, latency_s=9.9)
    assert len(alert_events()) == n0 + 1     # one crossing, one event
    slo.record_request(ttft_s=0.01, latency_s=9.9)
    assert len(alert_events()) == n0 + 1     # sustained burn: no storm
    assert slo.state()["slo"]["latency"]["alerting"]
    for _ in range(4):                       # window drains clean
        slo.record_request(ttft_s=0.01, latency_s=0.05)
    assert not slo.state()["slo"]["latency"]["alerting"]
    for _ in range(4):                       # re-crossing fires again
        slo.record_request(ttft_s=0.01, latency_s=9.9)
    assert len(alert_events()) == n0 + 2


def test_slow_request_exemplars_keep_the_slowest(monkeypatch):
    slo = _slo_tracker(monkeypatch, window=64, latency_ms=1e9, ttft_ms=1e9)
    for i in range(12):
        slo.record_request(
            ttft_s=0.01, latency_s=0.1 * (i + 1), trace_id="t%d" % i,
            phases={"queue_wait": 0.01, "decode": 0.09 * (i + 1)})
    ex = slo.state()["slow_request_exemplars"]
    assert len(ex) == 8                      # bounded
    assert ex[0]["trace_id"] == "t11"        # slowest first
    assert ex[0]["slowest_phase"] == "decode"
    assert ex[0]["latency_ms"] == pytest.approx(1200.0)
    lats = [e["latency_ms"] for e in ex]
    assert lats == sorted(lats, reverse=True)


def test_format_slo_report(monkeypatch):
    slo = _slo_tracker(monkeypatch)
    slo.record_request(ttft_s=0.01, latency_s=0.9, trace_id="deadbeef",
                       phases={"decode": 0.8})
    dumps = [{"launch_rank": 0, "state": {"slo": slo.state()}},
             {"launch_rank": 1, "state": {}}]     # pre-tracing dump
    report = tracing.format_slo_report(dumps)
    assert "=== SLO report ===" in report
    assert "rank 0" in report and "deadbeef" in report
    assert tracing.format_slo_report([{"state": {}}]) == ""


# ------------------------------------------------------------- HTTP routes

@pytest.fixture
def metrics_port():
    """The registry's HTTP endpoint on an ephemeral port, stopped again:
    left up, it fails ``tests/test_metrics.py``'s "no socket when the
    environment names no port" wherever that file follows this one on a
    worker (it did, once ``--dist loadfile`` had two more files to deal)."""
    from horovod_tpu.metrics import registry

    yield registry().serve(0)
    registry().stop_server()


def test_healthz_and_slo_routes(monkeypatch, metrics_port):
    port = metrics_port

    def get(route):
        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d%s" % (port, route),
                    timeout=5.0) as r:
                return r.status, json.loads(r.read().decode())
        except urllib.error.HTTPError as exc:
            return exc.code, json.loads(exc.read().decode())

    monkeypatch.setattr(tracing, "_init_ready", False)
    monkeypatch.setattr(tracing, "_serve_started", False)
    monkeypatch.setattr(tracing, "_serve_heartbeat_seen", False)
    code, doc = get("/healthz")
    assert code == 503 and not doc["ready"]
    tracing.mark_initialized(True)
    code, doc = get("/healthz")
    assert code == 200 and doc["ready"]
    # serving without a live replica heartbeat: not ready for traffic
    tracing.note_serve_started()
    code, doc = get("/healthz")
    assert code == 503 and doc["serving"]
    tracing.note_replica_heartbeat()
    code, doc = get("/healthz")
    assert code == 200 and doc["first_replica_heartbeat"]

    code, doc = get("/slo")
    assert code == 200
    assert doc["schema"] == tracing.SCHEMA
    assert set(doc["slo"]) == {"ttft", "latency", "availability"}
    for rec in doc["slo"].values():
        assert 0.0 <= rec["error_budget_remaining"] <= 1.0


# --------------------------------------------- Chrome conversion + merging

def test_spans_to_chrome_shape():
    spans = [{"trace_id": "t1", "name": "request.prefill", "t": 100.0,
              "dur": 0.25, "rank": 1, "uid": "r1"},
             {"name": "bad", "t": "nan"}]      # malformed: skipped
    (ev,) = tracing.spans_to_chrome(spans)
    assert ev["ph"] == "X" and ev["cat"] == "request"
    assert ev["ts"] == pytest.approx(100.0 * 1e6)
    assert ev["dur"] == pytest.approx(0.25 * 1e6)
    assert ev["args"]["trace_id"] == "t1" and ev["args"]["uid"] == "r1"


def test_flow_events_join_multi_span_traces():
    anchors = [
        {"trace_id": "t1", "pid": 0, "tid": 2, "ts": 100.0, "dur": 5.0},
        {"trace_id": "t1", "pid": 4, "tid": 2, "ts": 200.0, "dur": 9.0},
        {"trace_id": "t1", "pid": 4, "tid": 2, "ts": 300.0, "dur": 1.0},
        {"trace_id": "solo", "pid": 0, "tid": 2, "ts": 50.0, "dur": 1.0},
    ]
    flows = tracing.flow_events(anchors)
    assert [f["ph"] for f in flows] == ["s", "t", "f"]   # solo: no flow
    start, step, fin = flows
    assert start["ts"] == pytest.approx(105.0)   # anchored at span END
    assert step["ts"] == pytest.approx(200.0)    # receipt at span start
    assert fin["bp"] == "e"
    assert {f["id"] for f in flows} == {"t1"}


def test_merge_profile_dir_corrects_clocks_and_draws_flows(tmp_path):
    """Two fake rank dumps with different /_time offsets: the merged
    trace must carry both request lanes on ONE corrected clock and join
    the shared trace_id with flow arrows."""
    trace_id = "feedface00000001"
    base = 1000.0
    dump0 = {"launch_rank": 0, "clock_offset_seconds": 0.0,
             "trace_events": [], "flight_events": [],
             "request_spans": [
                 {"trace_id": trace_id, "name": "request.submit",
                  "t": base, "dur": 0.001, "rank": 0}]}
    # rank 1's clock runs 0.5 s fast; its offset estimate corrects it
    dump1 = {"launch_rank": 1, "clock_offset_seconds": -0.5,
             "trace_events": [], "flight_events": [],
             "request_spans": [
                 {"trace_id": trace_id, "name": "request.serve",
                  "t": base + 0.6, "dur": 0.05, "rank": 1}]}
    for rank, dump in ((0, dump0), (1, dump1)):
        with open(tmp_path / f"profile-rank-{rank}.json", "w") as f:
            json.dump(dump, f)
    out, count = profiler.merge_profile_dir(str(tmp_path))
    with open(out) as f:
        merged = json.load(f)["traceEvents"]
    labels = [e["args"]["labels"] for e in merged
              if e.get("name") == "process_labels"]
    assert "rank 0 requests" in labels and "rank 1 requests" in labels
    xs = {e["name"]: e for e in merged
          if e.get("ph") == "X" and e.get("cat") == "request"}
    assert xs["request.submit"]["ts"] == pytest.approx(base * 1e6)
    # 1000.6 on rank 1's fast clock is 1000.1 on the corrected one
    assert xs["request.serve"]["ts"] == pytest.approx((base + 0.1) * 1e6)
    assert xs["request.submit"]["pid"] != xs["request.serve"]["pid"]
    flows = [e for e in merged if e.get("ph") in ("s", "t", "f")
             and e.get("id") == trace_id]
    assert [f["ph"] for f in sorted(flows, key=lambda f: f["ts"])] == \
        ["s", "f"]


# ------------------------------------------------------- replica lifecycle

def test_replica_records_lifecycle_spans_and_scores_slo(monkeypatch):
    from test_serve import _FakeEngine, _replica

    slo = _slo_tracker(monkeypatch, window=16, latency_ms=1e9, ttft_ms=1e9)
    monkeypatch.setattr(tracing, "_slo", slo)
    q = RequestQueue()
    uid = q.submit([1, 2], max_new_tokens=3)
    rep = _replica(_FakeEngine(), q)
    for _ in range(4):
        rep._iterate()
    done = q.result(uid, timeout=1.0)
    assert done.trace_id
    names = {s["name"] for s in tracing.spans()
             if s.get("trace_id") == done.trace_id}
    assert {"request.submit", "request.queue_wait", "request.prefill",
            "request.decode", "request.serve",
            "request.response"} <= names
    st = slo.state()
    assert st["requests_scored"] == 1
    (ex,) = st["slow_request_exemplars"]
    assert ex["trace_id"] == done.trace_id
    assert set(ex["phases_ms"]) == {"queue_wait", "prefill", "decode"}


def test_rejected_request_is_an_availability_bad_event(monkeypatch):
    from test_serve import _FakeEngine, _replica

    slo = _slo_tracker(monkeypatch, window=16)
    monkeypatch.setattr(tracing, "_slo", slo)
    q = RequestQueue()
    uid = q.submit(list(range(100)), max_new_tokens=4)  # > max_seq=64
    rep = _replica(_FakeEngine(), q)
    rep._iterate()
    assert q.result(uid, timeout=1.0).finish == "rejected"
    st = slo.state()["slo"]
    assert st["availability"]["bad_total"] == 1
    assert st["latency"]["window_observed"] == 0


# ------------------------------------------------------------ layer spans

def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def test_span_records_start_duration_and_parent(ring):
    before = time.time()
    with tracing.span("outer", trace_id="t1", k=1) as outer:
        time.sleep(0.002)
        with tracing.span("inner"):
            pass
        outer.set(late=2)
        tracing.record("after.the.fact", before, 0.001)
    inner, fact, out = ring.spans()        # recorded at end time
    assert (inner["name"], fact["name"], out["name"]) == \
        ("inner", "after.the.fact", "outer")
    assert out["trace_id"] == "t1" and out["k"] == 1 and out["late"] == 2
    assert before <= out["t"] <= inner["t"]
    assert out["dur"] >= 0.002 and out["dur"] >= inner["dur"] >= 0.0
    assert "parent" not in out
    # both the nested span and the after-the-fact record know the span
    # they were written in, by name and running number
    assert inner["parent"] == ("outer", out["sid"]) == fact["parent"]
    assert inner["sid"] != out["sid"]


def test_span_stack_unwinds_between_siblings(ring):
    with tracing.span("a"):
        with tracing.span("b"):
            pass
        with tracing.span("c"):
            pass
    with tracing.span("d"):
        pass
    by = _by_name(ring.spans())
    a = by["a"][0]
    assert by["b"][0]["parent"] == by["c"][0]["parent"] == ("a", a["sid"])
    assert "parent" not in by["d"][0]


def test_two_threads_do_not_share_a_stack(ring):
    import threading

    inside = threading.Event()
    release = threading.Event()

    def other():
        with tracing.span("other.outer"):
            inside.set()
            release.wait(5.0)

    t = threading.Thread(target=other)
    with tracing.span("main.outer"):
        t.start()
        assert inside.wait(5.0)
        with tracing.span("main.inner"):   # opened while other.outer is
            pass                           # open on the other thread
        release.set()
        t.join()
    by = _by_name(ring.spans())
    assert by["main.inner"][0]["parent"][0] == "main.outer"
    assert "parent" not in by["other.outer"][0]


@pytest.mark.parametrize("value", ["0", "off"])
def test_disabled_span_is_one_shared_noop(monkeypatch, value):
    monkeypatch.setenv(HOROVOD_TRACE, value)
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    one = tracing.span("serve.step")
    other = tracing.span("engine.decode", rows=3)
    assert one is other                    # nothing allocated per span
    with one as s:
        s.set(decoded=1)
        with other:
            tracing.record("request.queue_wait", 0.0, 1.0)
    assert tracing.spans() == []
    assert not getattr(tracing._open, "stack", None)


def test_exception_inside_a_span_still_records_it(ring):
    with pytest.raises(ValueError):
        with tracing.span("outer"):
            with tracing.span("boom"):
                raise ValueError("x")
    assert [s["name"] for s in ring.spans()] == ["boom", "outer"]
    assert tracing._open.stack == []
    with tracing.span("next"):
        pass
    assert "parent" not in ring.spans()[-1]


def test_span_lands_in_a_running_profiler_trace(ring, tmp_path):
    """The same interval, under the same name, is a host event of the
    jax.profiler trace: the clock the device events are on."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracing.span("serve.step"):
            with tracing.span("engine.decode.wait", rows=3, uid="u1"):
                jnp.ones((64, 64)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = {e.name: e
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name in ("serve.step", "engine.decode.wait")}
    assert set(events) == {"serve.step", "engine.decode.wait"}
    step, wait = events["serve.step"], events["engine.decode.wait"]
    assert step.start_ns <= wait.start_ns
    assert wait.start_ns + wait.duration_ns <= \
        step.start_ns + step.duration_ns + 1
    assert dict(wait.stats).get("rows") == 3
    # and the ring's duration is the annotation's, to clock noise
    ring_wait = _by_name(ring.spans())["engine.decode.wait"][0]
    assert ring_wait["dur"] == pytest.approx(wait.duration_ns * 1e-9,
                                             abs=2e-3)


@pytest.fixture(scope="module")
def tiny_lm():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import Transformer

    model = Transformer(vocab_size=61, d_model=32, num_layers=1,
                        num_heads=2, d_ff=64, max_seq=48, causal=True,
                        dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    return model, params


@pytest.mark.parametrize("max_seq,shares", [
    (256, [0.5, 0.75, 1.0]),      # two lane tiles a row: the kernel
    (48, [None, None, None]),     # off the lane tile: the masked path
], ids=["kernel", "masked"])
def test_engine_decode_span_carries_kv_read_share(ring, max_seq, shares):
    """``engine.decode`` says what share of the rows' lane tiles the
    step's attention read, where the decode program holds the
    decode-attention kernel; it carries no such attribute where the
    program reads whole rows and writes through ``kv_cache_write``. That
    the same kernel wrote the step's new columns is a constant of the
    engine: its ``stats()`` say it, the span does not."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import Transformer
    from horovod_tpu.serve.kv_cache import DecodeEngine

    model = Transformer(vocab_size=61, d_model=32, num_layers=1,
                        num_heads=2, d_ff=64, max_seq=max_seq, causal=True,
                        dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    engine = DecodeEngine(model, params, num_slots=2)
    last = max_seq - 1
    engine.decode([0], [1], [3]).collect()             # 3, -: 2 of 4
    engine.decode([0, 1], [1, 2], [4, last]).collect()     # 3 of 4
    engine.decode([0, 1], [1, 2], [last, last]).collect()  # 4 of 4
    got = [s.get("kv_read_share") for s in ring.spans()
           if s["name"] == "engine.decode"]
    assert got == shares
    kernel = shares[0] is not None
    assert not any("write_fused" in s for s in ring.spans())
    assert engine.stats()["decode_write_fused"] is (True if kernel else None)
    assert [s["rows"] for s in ring.spans()
            if s["name"] == "engine.decode"] == [1, 2, 2]


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_replica_loop_and_engine_spans(ring, tiny_lm, paged):
    """The serving loop through a toy engine: one serve.step per pass
    with its three children, the engine's spans with their dispatch and
    wait parts, and exactly one request.decode per finished request.
    The dense engine's calls are enqueued in one place and collected in
    another, one pass later for a decode step; the paged engine's block,
    and its spans nest as they always did."""
    from test_serve import _replica

    model, params = tiny_lm
    if paged:
        from horovod_tpu.serve.paging import PagedDecodeEngine

        engine = PagedDecodeEngine(model, params, num_slots=2,
                                   page_tokens=16, pool_pages=12)
    else:
        from horovod_tpu.serve.kv_cache import DecodeEngine

        engine = DecodeEngine(model, params, num_slots=2)
    q = RequestQueue()
    uids = [q.submit([1, 2, 3], max_new_tokens=3),
            q.submit([4, 5], max_new_tokens=4)]
    rep = _replica(engine, q)
    for _ in range(8):
        rep._iterate()
    done = [q.result(u, timeout=1.0) for u in uids]
    spans = ring.spans()
    by = _by_name(spans)

    def kids(key):
        return [s["name"] for s in spans if s.get("parent") == key]

    # three passes decode (the longer request has three steps after its
    # prefill); of the five idle passes after them only the first is kept
    # (the dense loop reads its last step's ids in it)
    steps = by["serve.step"]
    assert len(steps) == 4 and len(by["serve.pull"]) == 4
    busy = [s for s in steps if s["decoded"] > 0]
    assert len(busy) == 3
    assert [s["step"] for s in busy] == list(range(1, len(busy) + 1))
    assert busy[0]["admitted"] == 2 and busy[0]["occupancy"] == 2
    assert sum(s["admitted"] for s in steps) == 2
    assert steps[-1]["decoded"] == 0 and steps[-1]["occupancy"] == 0
    first = ("serve.step", busy[0]["sid"])
    assert by["serve.pull"][0]["parent"] == first
    assert by["serve.pull"][0]["n"] == 2
    assert by["serve.admit"][0]["parent"] == first
    assert by["serve.admit"][0]["n"] == 2
    retires = by["serve.retire"]
    assert [r["parent"] for r in retires] == [
        ("serve.step", s["sid"]) for s in (busy if paged else steps)]
    assert sum(s["n"] for s in retires) == 2

    # the engine's spans, children of the loop's
    admit = ("serve.admit", by["serve.admit"][0]["sid"])
    assert len(by["request.prefill"]) == 2
    assert len(by["engine.prefill"]) == 2
    assert len(by["engine.decode"]) == len(busy)
    assert [d["rows"] for d in by["engine.decode"]] == \
        [s["decoded"] for s in busy]
    for pre in by["engine.prefill"]:
        assert pre["bucket"] == 16 and pre["prompt_len"] in (2, 3)
    if paged:
        assert all(s["parent"] == admit for s in by["request.prefill"])
        for pre in by["engine.prefill"]:
            assert pre["parent"] == admit
            assert kids(("engine.prefill", pre["sid"])) == [
                "engine.prefill.dispatch", "engine.prefill.wait"]
        for dec, step in zip(by["engine.decode"], busy):
            assert dec["parent"] == ("serve.step", step["sid"])
            assert kids(("engine.decode", dec["sid"])) == [
                "engine.decode.prep", "engine.decode.dispatch",
                "engine.decode.wait"]
    else:
        # enqueued under serve.admit / serve.step, collected under the
        # pass's serve.retire; engine.prefill and engine.decode are
        # written after the fact, dispatch to result on the host
        collect = ("serve.retire", retires[0]["sid"])
        assert kids(admit).count("engine.prefill.dispatch") == 2
        assert kids(collect)[:6] == [
            "engine.prefill.wait", "engine.prefill", "request.prefill"] * 2
        for pre, part in zip(by["engine.prefill"],
                             by["engine.prefill.dispatch"]):
            assert pre["t"] <= part["t"] and pre["dur"] >= part["dur"]
        for step, prep, part in zip(busy, by["engine.decode.prep"],
                                    by["engine.decode.dispatch"]):
            assert prep["parent"] == part["parent"] == \
                ("serve.step", step["sid"])
        # step k's ids are read in pass k + 1, after step k + 1 went out
        waits = by["engine.decode.wait"]
        assert [w["parent"] for w in waits] == [
            ("serve.retire", r["sid"]) for r in retires[1:]]
        assert [w["ahead"] for w in waits] == [1, 1, 0]
        for wait, part in zip(waits, by["engine.decode.dispatch"][1:]):
            assert part["t"] + part["dur"] <= wait["t"]
        for dec, part, wait in zip(by["engine.decode"],
                                   by["engine.decode.prep"], waits):
            assert dec["parent"] == wait["parent"]
            assert dec["t"] <= part["t"]
            assert dec["t"] + dec["dur"] >= wait["t"] + wait["dur"]

    # request spans: one decode span per request, none per block
    assert "request.decode_block" not in by
    assert sorted(s["trace_id"] for s in by["request.decode"]) == \
        sorted(d.trace_id for d in done)
    for s in by["request.decode"]:
        assert s["parent"][0] == "serve.retire"
        assert s["tokens"] in (3, 4)
        # decode_block=2: 2 steps -> 1 block, 3 steps -> 2 blocks
        assert s["blocks"] == {3: 1, 4: 2}[s["tokens"]]
    assert {s["parent"][0] for s in by["request.queue_wait"]} == \
        {"serve.admit"}


# ------------------------------------------------- the host's stalls

def test_spans_carry_the_thread_that_recorded_them(ring):
    import threading

    def other():
        with tracing.span("worker.span"):
            tracing.record("worker.record", time.time(), 0.0)

    t = threading.Thread(target=other, name="worker-7")
    t.start()
    t.join(5.0)
    with tracing.span("main.span"):
        pass
    by = _by_name(ring.spans())
    assert by["worker.span"][0]["thread"] == "worker-7"
    assert by["worker.record"][0]["thread"] == "worker-7"
    assert by["main.span"][0]["thread"] == threading.current_thread().name


@pytest.fixture
def gc_watch(ring, monkeypatch):
    """A fresh collector watch behind ``tracing.configure()``; whatever
    the test installs is out of ``gc.callbacks`` again afterwards."""
    import gc

    watch = tracing._GcWatch()
    monkeypatch.setattr(tracing, "_gc_watch", watch)
    monkeypatch.setattr(tracing, "_init_ready", tracing._init_ready)
    yield watch
    watch.watch(False)
    assert watch not in gc.callbacks


@pytest.mark.parametrize("generation", [0, 2])
def test_gc_pass_is_a_span_with_its_generation(gc_watch, ring, monkeypatch,
                                               generation):
    import gc

    monkeypatch.setattr(tracing, "GC_SPAN_FLOOR_S", 0.0)
    tracing.configure()
    with tracing.span("outer") as outer:
        gc.collect(generation)
    mine = [s for s in ring.spans() if s["name"] == "host.gc"
            and s["generation"] == generation]
    assert mine, ring.spans()
    last = mine[-1]
    assert last["thread"] == "MainThread" and last["collected"] >= 0
    assert last["parent"] == outer.key and last["dur"] >= 0.0
    total = tracing.gc_totals()[generation]
    assert total["count"] >= 1 and total["longest_s"] <= total["seconds"]
    # the totals ride the GET /slo document beside the spans recorded
    doc = tracing.slo_state()
    assert doc["gc"][str(generation)]["count"] == \
        tracing.gc_totals()[generation]["count"]
    assert doc["spans_recorded"] >= 1
    json.dumps(doc)


def test_gc_pass_under_the_floor_counts_and_stays_out_of_the_ring(
        gc_watch, ring, monkeypatch):
    import gc

    monkeypatch.setattr(tracing, "GC_SPAN_FLOOR_S", 3600.0)
    tracing.configure()
    gc.collect(0)
    gc.collect(0)
    assert tracing.gc_totals()[0]["count"] >= 2
    assert not [s for s in ring.spans() if s["name"] == "host.gc"]


def test_trace_off_hooks_nothing_and_keeps_no_totals(gc_watch, monkeypatch):
    import gc

    monkeypatch.setenv(HOROVOD_TRACE, "0")
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    tracing.configure()
    assert gc_watch not in gc.callbacks
    gc.collect()
    assert tracing.gc_totals() == {} and tracing.spans() == []
    assert tracing.span("serve.step") is tracing.span("host.gc")


def test_configure_twice_installs_one_callback_and_off_removes_it(
        gc_watch, monkeypatch):
    import gc

    tracing.configure()
    tracing.configure()
    assert gc.callbacks.count(gc_watch) == 1
    tracing.mark_initialized(False)         # hvd.shutdown()
    assert gc_watch not in gc.callbacks
    tracing.configure()
    assert gc.callbacks.count(gc_watch) == 1
    monkeypatch.setenv(HOROVOD_TRACE, "0")
    tracing.configure()
    assert gc_watch not in gc.callbacks


def test_steps_and_input_waits_say_whether_a_profiler_was_recording(
        ring, tmp_path):
    import jax

    def spans_now():
        with tracing.span("serve.step"):
            with tracing.span("engine.decode"):
                pass
        with tracing.span("input.wait", depth=1):
            pass

    spans_now()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        spans_now()
    finally:
        jax.profiler.stop_trace()
    spans_now()
    by = _by_name(ring.spans())
    assert [s.get("profiled") for s in by["serve.step"]] == [None, 1, None]
    assert [s.get("profiled") for s in by["input.wait"]] == [None, 1, None]
    assert not any("profiled" in s for s in by["engine.decode"])


class _OnDevice:
    """A device array's stand-in whose ``is_ready()`` is as told: the
    values are the real call's, already on the host."""

    def __init__(self, values, ready):
        import numpy as np

        self._values, self._ready = np.asarray(values), ready

    def is_ready(self):
        return self._ready

    def __array__(self, dtype=None, copy=None):
        return self._values

    def __int__(self):
        return int(self._values)

    def __float__(self):
        return float(self._values)

    def copy_to_host_async(self):
        pass


@pytest.mark.parametrize("ready", [0, 1], ids=["running", "finished"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_starved_and_ready_follow_the_pending_results(ring, tiny_lm, paged,
                                                      ready):
    """``serve.step``'s ``starved`` is what the newest pending result
    said at the pass's first enqueue, and a ``wait`` span's ``ready``
    what its own said when the wait began. The dense loop holds one
    decode step across passes, so its flag follows the device; the paged
    engine's calls block, nothing is ever pending, and every pass that
    enqueues reads 1."""
    from test_serve import _replica

    model, params = tiny_lm
    if paged:
        from horovod_tpu.serve.paging import PagedDecodeEngine

        engine = PagedDecodeEngine(model, params, num_slots=2,
                                   page_tokens=16, pool_pages=12)
        decode_fn = engine._decode_fn

        def told(*args):
            cache, ids, max_abs = decode_fn(*args)
            return cache, _OnDevice(ids, ready), _OnDevice(max_abs, ready)

        engine._decode_fn = told
    else:
        from horovod_tpu.serve.kv_cache import DecodeEngine

        engine = DecodeEngine(model, params, num_slots=2)
        run = engine._run_donating
        engine._run_donating = lambda *args: [
            _OnDevice(x, ready) for x in run(*args)]
    q = RequestQueue()
    uids = [q.submit([1, 2, 3], max_new_tokens=4),
            q.submit([4, 5], max_new_tokens=4)]
    rep = _replica(engine, q)
    for _ in range(6):
        rep._iterate()
    assert all(q.result(u, timeout=1.0).finish == "length" for u in uids)
    by = _by_name(ring.spans())
    enqueued = [s for s in by["serve.step"] if s["decoded"]]
    assert len(enqueued) == 3
    # the first pass finds nothing in flight: the device is dry
    assert [s["starved"] for s in enqueued] == \
        ([1, 1, 1] if paged or ready else [1, 0, 0])
    # the first pass enqueues two prefills and a decode step, the others
    # a decode step: each returns to find what went before it done, or
    # not (before the very first there was nothing: dry)
    assert [s["dry_enqueues"] for s in enqueued] == \
        ([3, 1, 1] if paged or ready else [1, 0, 0])
    assert all("starved" not in s and "dry_enqueues" not in s
               for s in by["serve.step"] if not s["decoded"])
    assert [w["ready"] for w in by["engine.decode.wait"]] == [ready] * 3
    if paged:
        assert {w["ready"] for w in by["engine.prefill.wait"]} <= {0, 1}
    else:
        assert [w["ready"] for w in by["engine.prefill.wait"]] == [ready] * 2
    stats = rep.stats()
    assert stats["starved_steps"] == sum(s["starved"] for s in enqueued)
    assert "lookahead_share" in stats


def test_starved_steps_counts_with_tracing_off(monkeypatch, tiny_lm):
    """The flag is a span's; the count is the replica's own."""
    from horovod_tpu.serve.kv_cache import DecodeEngine
    from test_serve import _replica

    monkeypatch.setenv(HOROVOD_TRACE, "0")
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    model, params = tiny_lm
    q = RequestQueue()
    q.submit([1, 2, 3], max_new_tokens=3)
    rep = _replica(DecodeEngine(model, params, num_slots=2), q)
    for _ in range(4):
        rep._iterate()
    assert rep.completed == 1 and tracing.spans() == []
    assert 1 <= rep.stats()["starved_steps"] <= rep.decode_iterations


def test_lock_waits_show_on_the_dispatch_and_on_engine_stats(ring):
    """A thread that holds the cache's lock keeps both a dispatch of the
    replica's thread and a ``stats()`` of the caller's waiting: each
    span says for how long, and ``engine.stats`` is a span of the thread
    that called it."""
    import threading

    from horovod_tpu.serve.kv_cache import DecodeEngine
    from toy_models import xing

    cfg, params, model = xing()
    engine = DecodeEngine(model, params, num_slots=2)
    engine.prefill(0, [3, 1, 4, 1, 5]).collect()
    engine.stats()                                   # nobody holds it
    calm = [s for s in ring.spans() if s["name"] == "engine.stats"][-1]
    assert calm["lock_ms"] < 40.0 and calm["thread"] == "MainThread"

    def holding(call):
        held = threading.Event()

        def hold():
            with engine._cache_lock:
                held.set()
                time.sleep(0.06)

        t = threading.Thread(target=hold)
        t.start()
        assert held.wait(5.0)
        out = call()
        t.join(5.0)
        assert not t.is_alive()
        return out

    holding(lambda: engine.decode([0], [7], [5])).collect()
    reader = threading.Thread(target=lambda: holding(engine.stats),
                              name="stats-reader")
    reader.start()
    reader.join(10.0)
    assert not reader.is_alive()
    by = _by_name(ring.spans())
    assert by["engine.decode.dispatch"][-1]["lock_ms"] >= 40.0
    assert by["engine.prefill.dispatch"][-1]["lock_ms"] < 40.0
    stats = by["engine.stats"][-1]
    assert stats["lock_ms"] >= 40.0 and stats["thread"] == "stats-reader"
    assert stats["dur"] * 1e3 >= stats["lock_ms"]
    assert "parent" not in stats


def test_prefetch_records_one_wait_and_one_put_per_batch(ring):
    import numpy as np

    from horovod_tpu.data import prefetch_to_device

    batches = [np.full((2, 3), i, np.int32) for i in range(5)]
    got = list(prefetch_to_device(iter(batches), size=2))
    assert [int(b[0, 0]) for b in got] == list(range(5))
    by = _by_name(ring.spans())
    assert len(by["input.put"]) == 5
    # one wait per batch handed out, and the one that met the end marker
    assert len(by["input.wait"]) == 6
    assert all(0 <= s["depth"] <= 2 for s in by["input.wait"])
    assert all("parent" not in s for s in by["input.put"])


@pytest.fixture(scope="module")
def toy_step_text():
    """The lowered text (with op_name locations) of a toy train step
    under ``shard_map`` with ``DistributedOptimizer``, attention through
    the flash kernels."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd_mod
    from horovod_tpu import training
    from horovod_tpu.models.transformer import Transformer, causal_lm_loss

    model = Transformer(vocab_size=64, d_model=32, num_layers=1,
                        num_heads=1, d_ff=64, max_seq=128, causal=True,
                        dtype=jnp.float32)
    tokens = jnp.zeros((2, 128), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    opt = hvd_mod.DistributedOptimizer(optax.sgd(0.1), axis_name="x")
    state = opt.init(params)
    one_step = training._make_one_step(model, opt, causal_lm_loss)
    mesh = Mesh(jax.devices()[:2], ("x",))
    step = jax.jit(jax.shard_map(
        one_step, mesh=mesh, in_specs=(P(), P(), P(), P("x"), P("x")),
        out_specs=(P(), P(), P(), P()), check_vma=False))
    return step.lower(params, {}, state, tokens, tokens).as_text(
        debug_info=True)


@pytest.mark.parametrize("scope", ["loss", "optimizer", "grad_exchange"])
def test_train_step_scopes_reach_op_name(toy_step_text, scope):
    import re

    names = re.findall(r'loc\("([^"]+)"', toy_step_text)
    assert any(scope in n.split("/") or f"jvp({scope})" in n
               for n in names), scope


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_flash_kernels_are_named(toy_step_text, kernel):
    assert kernel in toy_step_text


# --------------------------------------------------- 2-rank merged trace

def test_one_trace_id_spans_both_ranks_in_merged_trace(tmp_path,
                                                       monkeypatch):
    """The acceptance shape of the tentpole, fast-tier: a frontend (this
    process, rank 0) submits ONE traced request to a real replica worker
    process (rank 1, ``python -m horovod_tpu.serve``); both dump profile
    snapshots into one dir; the merged Perfetto trace must show that
    trace_id's spans on BOTH ranks' request lanes, joined by a flow."""
    from horovod_tpu.run.rendezvous import KVStoreClient, RendezvousServer
    from horovod_tpu.serve.queue import KVQueueFrontend

    server = RendezvousServer(host="127.0.0.1")
    port = server.start()
    procs, logs = [], []
    try:
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "HOROVOD_RANK": "1",
            "HOROVOD_RENDEZVOUS_HTTP_ADDR": "127.0.0.1",
            "HOROVOD_RENDEZVOUS_HTTP_PORT": str(port),
            "HOROVOD_PROFILE_DIR": str(tmp_path),
            "HOROVOD_SERVE_ADMISSION_MS": "1",
            "JAX_PLATFORMS": "cpu",
        })
        # the replica's output goes to a file: nobody reads it while
        # it serves, and a pipe would fill (mp_launch.start)
        start(procs, logs,
              [sys.executable, "-m", "horovod_tpu.serve", "--vocab", "64",
               "--d-model", "16", "--layers", "1", "--heads", "1",
               "--d-ff", "32", "--max-seq", "32"], env, cwd=REPO)
        proc, = procs

        front = KVQueueFrontend(
            KVStoreClient("127.0.0.1", port, scope="serve", timeout=10.0))
        assert front.wait_for_replicas(1, timeout=90.0) == [1]
        req = Request(uid="traced-1", prompt=[1, 2, 3, 4],
                      max_new_tokens=4, submitted_s=time.monotonic())
        front.submit(req, rank=1)
        trace_id = req.trace_id
        assert trace_id
        deadline = time.monotonic() + 90.0
        while front.pending() and time.monotonic() < deadline:
            front.poll_responses()
            time.sleep(0.05)
        assert front.pending() == 0, "traced request never completed"
        assert front._done["traced-1"].trace_id == trace_id
        front.stop_fleet()
        out, = collect(procs, logs, 60)
        assert proc.returncode == 0, out[-2000:]

        # the worker's finalize dumped profile-rank-1.json; dump the
        # frontend's spans (this process) alongside it and merge
        profiler.dump(path=str(tmp_path / "profile-rank-0.json"),
                      ship=False)
        merged_path, _ = profiler.merge_profile_dir(str(tmp_path))
        with open(merged_path) as f:
            merged = json.load(f)["traceEvents"]
        ours = [e for e in merged if e.get("ph") == "X"
                and e.get("cat") == "request"
                and (e.get("args") or {}).get("trace_id") == trace_id]
        assert {e["args"]["rank"] for e in ours} == {0, 1}
        assert len({e["pid"] for e in ours}) >= 2   # two request lanes
        names = {e["name"] for e in ours}
        assert "request.submit" in names            # frontend side
        assert "request.serve" in names             # replica side
        flows = [e for e in merged if e.get("ph") in ("s", "t", "f")
                 and e.get("id") == trace_id]
        assert [f for f in flows if f["ph"] == "s"] and \
            [f for f in flows if f["ph"] == "f"]
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        server.stop()
