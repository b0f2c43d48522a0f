"""The program's spans as the benchmark reads them: the steady part of a
hand-made ring, the five readers over it, and ``idle_causes`` on a small
recorded trace with a host plane (recorded_host_trace.textproto)."""

import os

import pytest

from benchmark import run, spans
from benchmark.tools import idle_causes

HERE = os.path.dirname(os.path.abspath(__file__))
SLOTS = 10


def _span(name, sid, parent, dur, **attrs):
    out = {"name": name, "sid": sid, "t": float(sid), "dur": dur,
           "rank": 0, "trace_id": "", **attrs}
    if parent:
        out["parent"] = parent
    return out


def _step(ring, sid, occupancy, *, prefill=0.0, admitted=0, queue_wait=0.0):
    """One serving iteration: 50 ms of decode call (2 prep, 3 dispatch,
    44 wait), an optional prefill (a fifth of it dispatch), 4 ms of the
    loop's own blocks and 1 ms outside any span."""
    step = ("serve.step", sid)
    ring.append(_span("serve.pull", sid + 1, step, 0.001, n=admitted))
    if prefill:
        admit = ("serve.admit", sid + 2)
        ring.append({"name": "request.queue_wait", "t": 0.0,
                     "dur": queue_wait, "rank": 0, "trace_id": "r",
                     "parent": admit})
        request = ("request.prefill", sid + 3)
        engine = ("engine.prefill", sid + 4)
        ring.append(_span("engine.prefill.dispatch", sid + 5, engine,
                          0.2 * prefill))
        ring.append(_span("engine.prefill.wait", sid + 6, engine,
                          0.8 * prefill))
        ring.append(_span(*engine, request, prefill, bucket=32))
        ring.append(_span(*request, admit, prefill))
        ring.append(_span(*admit, step, prefill + 0.001, n=admitted))
    decode = ("engine.decode", sid + 7)
    ring.append(_span("engine.decode.prep", sid + 8, decode, 0.002))
    ring.append(_span("engine.decode.dispatch", sid + 9, decode, 0.003))
    ring.append(_span("engine.decode.wait", sid + 10, decode, 0.044))
    ring.append(_span(*decode, step, 0.050, rows=occupancy))
    ring.append(_span("serve.retire", sid + 11, step, 0.002, n=0))
    ring.append(_span(*step, None, 0.050 + prefill + 0.005 +
                      (0.001 if prefill else 0.0), step=sid // 100,
                      decoded=occupancy, occupancy=occupancy, waiting=0,
                      admitted=admitted))


@pytest.fixture
def ring(monkeypatch):
    """The opening burst (all 10 slots filled by one step), three steady
    steps (one with a 20 ms prefill), an idle pass, the drain (2 slots);
    a request span of the driver's thread outside any step."""
    out = []
    _step(out, 100, 10, prefill=0.030, admitted=10, queue_wait=9.0)
    _step(out, 200, 10)
    _step(out, 300, 9, prefill=0.020, admitted=1, queue_wait=2.0)
    _step(out, 400, 10)
    out.append(_span("serve.step", 500, None, 0.002, step=4, decoded=0,
                     occupancy=10, waiting=0, admitted=0))
    _step(out, 600, 2)
    out.append(_span("request.submit", 700, None, 0.0001))
    monkeypatch.setattr(spans, "ring", lambda: out)
    return out


SUMMARY = {"served_tokens": 1.0, "slots": SLOTS}


def test_steady_drops_the_burst_the_idle_pass_and_the_drain(ring):
    steps = spans.steady(SUMMARY, "serve.step")
    assert [s["sid"] for s in steps] == [200, 300, 400]
    assert [s["rows"] for s in spans.steady(SUMMARY, "engine.decode")] == \
        [10, 9, 10]
    # a span several levels down finds its step through its parents
    (wait,) = spans.steady(SUMMARY, "engine.prefill.wait")
    assert wait["dur"] == pytest.approx(0.016)
    (queued,) = spans.steady(SUMMARY, "request.queue_wait")
    assert queued["dur"] == 2.0            # the burst's 9 s is dropped
    assert spans.steady(SUMMARY, "request.submit") is None
    assert spans.steady(SUMMARY, "no.such.span") is None


def test_serving_split_adds_up(ring):
    split = spans.serving_split(SUMMARY)
    assert split["steps"] == 3
    assert split["step_ms"] == pytest.approx((55 + 76 + 55) / 3)
    assert split["engine.decode"] == pytest.approx(50.0)
    assert split["engine.prefill"] == pytest.approx(20.0 / 3)
    assert split["wait_ms"] == pytest.approx(44.0 + 16.0 / 3)
    assert split["loop_host_ms"] == pytest.approx(
        split["step_ms"] - split["wait_ms"])
    # the three step metrics less the engine's own host parts are the
    # step, but for the 1 ms a decode call spends outside its parts
    parts = (split["engine.prefill"] + split["engine.decode"]
             + split["loop_host_ms"] - split["engine_host_ms"])
    assert parts - split["step_ms"] == pytest.approx(1.0)
    said = []
    spans.say_serving_split(SUMMARY, said.append)
    assert len(said) == 3 and "3 steps" in said[0]
    assert "serve.step 6" in said[2]       # what the ring holds, by name


def test_first_token_parts_by_request():
    ring_ = [
        {"name": "request.queue_wait", "trace_id": "a", "t": 10.0, "dur": 0.5},
        {"name": "request.queue_wait", "trace_id": "b", "t": 10.0, "dur": 0.5},
        {"name": "request.prefill", "trace_id": "a", "t": 10.5, "dur": 0.02},
        # admitted with a: waits for a's prefill before its own
        {"name": "request.prefill", "trace_id": "b", "t": 10.52, "dur": 0.03},
        {"name": "request.prefill", "trace_id": "evicted", "t": 1.0, "dur": 1.0},
    ]
    rows = spans.first_token_parts(ring_)
    assert rows == [(0.5, 0.0, 0.02), (0.5, pytest.approx(0.02), 0.03)]


@pytest.mark.parametrize("name,value", [
    ("queue_wait_ms_p95.serve", 2000.0),
    ("prefill_ms_step.serve", 20.0 / 3),
    ("decode_call_ms.serve", 50.0),
    ("loop_host_ms_step.serve", 62.0 - 44.0 - 16.0 / 3),
])
def test_serving_readers(ring, name, value):
    reader = run.load_module("layer_metrics", name)
    assert reader.read(SUMMARY) == pytest.approx(value)
    assert reader.read({"tokens": 1}) is None      # a training summary


@pytest.mark.parametrize("name", [
    "queue_wait_ms_p95.serve", "prefill_ms_step.serve",
    "decode_call_ms.serve", "loop_host_ms_step.serve",
    "input_wait_ms_step.train"])
@pytest.mark.parametrize("held", ["empty", "before the spans"])
def test_readers_find_nothing_in_a_program_without_the_spans(
        monkeypatch, name, held):
    # the ring off, or a commit from before them: only request spans
    # with no parent
    old = [{"name": "request.queue_wait", "t": 0.0, "dur": 1.0,
            "rank": 0, "trace_id": "r"}]
    monkeypatch.setattr(spans, "ring",
                        lambda: [] if held == "empty" else old)
    reader = run.load_module("layer_metrics", name)
    assert reader.read(SUMMARY) is None
    assert reader.read({"tokens": 1, "trace": {}, "trace_steps": 8}) is None


def test_input_wait_reader_takes_the_last_200_and_drops_the_slice(
        monkeypatch):
    waits = [{"name": "input.wait", "sid": i, "t": float(i), "rank": 0,
              "trace_id": "", "depth": 2,
              "dur": 0.5 if i < 100 else (1e-4 if i < 300 else 7e-3)}
             for i in range(308)]
    puts = [{"name": "input.put", "sid": 1000 + i, "t": float(i),
             "dur": 1.0, "rank": 0, "trace_id": ""} for i in range(308)]
    monkeypatch.setattr(spans, "ring", lambda: waits + puts)
    reader = run.load_module("layer_metrics", "input_wait_ms_step.train")
    summary = {"tokens": 1, "trace_steps": 8, "trace": {"busy_s": 1.0}}
    # the slice's 8 batches (7 ms each: drawn in one burst) are left out
    assert reader.read(summary) == pytest.approx(0.1)
    # an untraced run has no such burst: its last 200 are the last 200
    assert reader.read(dict(summary, trace={})) == pytest.approx(
        (192 * 1e-4 + 8 * 7e-3) * 1e3 / 200)


# ----------------------------------------------------------- idle_causes

@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "recorded_host_trace.textproto")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    return ProfileData.from_text_proto(text)


def test_idle_gaps_go_to_the_innermost_open_span(profile):
    said = []
    out = idle_causes.report(profile, said.append)
    # the 50 us gap is under the floor; another thread's request.submit
    # opens inside it and a host event that is not the program's
    # (PjitFunction) is not a cause
    assert out["idle_s"] == pytest.approx(3.2e-3)
    assert out["by_cause"] == {
        "engine.prefill.dispatch": pytest.approx(3.0e-3),
        "serve.retire": pytest.approx(0.2e-3)}
    assert [g[1] for g in out["gaps"]] == ["engine.prefill.dispatch",
                                           "serve.retire"]
    assert any(line.startswith("named: 100.00%") for line in said)
    # the longest gap ends where the prefill program starts
    assert any("engine.prefill.dispatch  -> jit__prefill_impl" in line
               for line in said)
    # split over every span a gap overlaps: the host was already waiting
    # for the prefill during the gap's last 400 us (launch), and still
    # in the decode's wait during the first 10 us of the other (readback)
    assert out["shares"] == {
        "engine.prefill.dispatch": pytest.approx(2.6e-3),
        "engine.prefill.wait:launch": pytest.approx(0.4e-3),
        "serve.retire": pytest.approx(0.15e-3),
        "serve.step": pytest.approx(0.04e-3),
        "engine.decode.wait:readback": pytest.approx(0.01e-3)}


def test_a_gap_no_span_covers_is_unattributed():
    spans_ = [("serve.step", 0, 100), ("engine.decode", 10, 50)]
    assert idle_causes.cause(spans_, 30) == "engine.decode"
    assert idle_causes.cause(spans_, 70) == "serve.step"
    assert idle_causes.cause(spans_, 150) == "unattributed"


def test_device_seconds_by_scope(profile):
    scopes, carriers = idle_causes.device_seconds_by_scope(profile)
    assert carriers == {"tf_op": 4}
    assert scopes == {"other": pytest.approx(1.0e-3),
                      "grad_exchange": pytest.approx(0.8e-3),
                      "loss": pytest.approx(1.0e-3),
                      "flash_dq": pytest.approx(0.95e-3)}
    assert idle_causes.scope_of(
        "%fusion.3", [("tf_op", "jit(f)/transpose(jvp(Transformer))/mul")]
    ) == "backward"
    assert idle_causes.scope_of(
        "%fusion.3", [("tf_op", "jit(f)/jvp(Transformer)/mul")]) == "forward"


def test_scopes_from_the_metadata_statistics_of_the_file(tmp_path):
    """A TPU trace keeps ``op_name`` on the event's metadata, which
    ``ProfileData`` does not hand out: the file itself is read."""
    from jax.profiler import ProfileData

    if idle_causes.xplane_pb2() is None:
        pytest.skip("no xplane_pb2 installed here")
    with open(os.path.join(HERE, "recorded_host_trace.textproto")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    stat = (' stats { metadata_id: 1 str_value: '
            '"jit(one_step)/jit(main)/jvp(loss)/reduce_sum" }')
    assert stat in text
    text = text.replace(stat, "").replace(
        'value { id: 3 name: "%fusion.7', 'value { id: 3' + stat
        + ' name: "%fusion.7')
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    scopes, carriers = idle_causes.device_seconds_by_scope_from_file(
        str(path))
    assert carriers == {"tf_op": 1}
    # the kernel is found by its name, the loss by its metadata's path
    assert scopes == {"other": pytest.approx(1.8e-3),
                      "loss": pytest.approx(1.0e-3),
                      "flash_dq": pytest.approx(0.95e-3)}
