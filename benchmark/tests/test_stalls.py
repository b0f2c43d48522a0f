"""The host's stalls as the benchmark reads them: the three readers of
``benchmark/stalls.py`` over a hand-made ring, and ``stall_causes`` on
the recorded host trace extended, in a copy, with a third host line (a
collector pass, an ``engine.stats`` and a long event of the profiler's
own)."""

import os

import pytest

from benchmark import run, spans, stalls
from benchmark.tools import stall_causes

HERE = os.path.dirname(os.path.abspath(__file__))
SLOTS = 4
SUMMARY = {"served_tokens": 1.0, "slots": SLOTS, "window_s": 30.0}
LOOP, DRIVER = "serve-r0", "MainThread"


def _span(name, sid, t, dur, thread=LOOP, parent=None, **attrs):
    out = {"name": name, "sid": sid, "t": t, "dur": dur, "rank": 0,
           "trace_id": "", "thread": thread, **attrs}
    if parent:
        out["parent"] = parent
    return out


def _step(ring, sid, t, *, starved, ready, profiled=False, occupancy=SLOTS,
          lock_ms=0.0, dry=0):
    """One 10 ms pass at ``t``: a decode dispatch (1 ms) and the wait for
    the step before (6 ms)."""
    step = ("serve.step", sid)
    ring.append(_span("engine.decode.dispatch", sid + 1, t + 0.001, 0.001,
                      parent=step, lock_ms=lock_ms))
    ring.append(_span("engine.decode.wait", sid + 2, t + 0.003, 0.006,
                      parent=step, ahead=1, ready=ready))
    attrs = {"profiled": 1} if profiled else {}
    ring.append(_span(*step, t, 0.010, step=sid, decoded=occupancy,
                      occupancy=occupancy, waiting=0, admitted=0,
                      starved=starved, dry_enqueues=dry, **attrs))


@pytest.fixture
def ring(monkeypatch):
    """Four steady steps of the window (one starved, two whose result was
    ready), two of the traced slice (both starved), one of the drain; a
    collector pass on the driver's thread that lies half in the second
    step, one on the loop's own thread inside the fourth, one outside
    every step; the driver's submits and an ``engine.stats``."""
    out = [_span("request.submit", 1, 99.0, 0.0005, thread=DRIVER)]
    _step(out, 100, 100.000, starved=0, ready=0)
    _step(out, 200, 100.010, starved=1, ready=1, lock_ms=0.25)
    _step(out, 300, 100.020, starved=0, ready=1, dry=2)
    _step(out, 400, 100.030, starved=0, ready=0)
    out[-3]["dur"] = 0.0305         # one dispatch stalled
    _step(out, 500, 100.100, starved=1, ready=1, profiled=True)
    _step(out, 600, 100.110, starved=1, ready=0, profiled=True)
    _step(out, 700, 100.200, starved=1, ready=1, occupancy=1)
    out += [
        _span("host.gc", 801, 100.006, 0.008, thread=DRIVER, generation=2,
              collected=0),       # 4 ms in the first step, 4 in the second
        _span("host.gc", 802, 100.032, 0.002, parent=("serve.step", 400),
              generation=1, collected=3),
        _span("host.gc", 803, 100.045, 0.050, thread=DRIVER, generation=2,
              collected=0),       # between the window and the slice
        _span("request.submit", 804, 100.012, 0.003, thread=DRIVER),
        _span("request.submit", 805, 100.050, 0.003, thread=DRIVER),
        _span("engine.stats", 806, 100.024, 0.002, thread=DRIVER,
              lock_ms=0.5),
    ]
    monkeypatch.setattr(spans, "ring", lambda: out)
    monkeypatch.setattr(stalls, "_said", False)
    monkeypatch.setattr(stalls, "gc_totals",
                        lambda: {2: {"count": 2, "seconds": 0.058,
                                     "longest_s": 0.05}})
    return out


def test_starved_shares_keep_the_window_and_the_slice_apart(ring):
    assert stalls.starved_shares(SUMMARY) == {"window": (1, 4, 1),
                                              "profiled": (2, 2, 0)}


def test_host_late_counts_the_steady_waits(ring):
    assert stalls.host_late(SUMMARY) == (3, 6)     # the drain's is dropped


def test_gc_pauses_are_what_lies_inside_a_steady_step(ring):
    pauses = stalls.gc_pauses(SUMMARY)
    # 8 ms over two steps + 2 ms in the fourth; the 50 ms pass lies in
    # no step
    assert pauses["ms_step"] == pytest.approx(10.0 / 6)
    assert (pauses["count"], pauses["in_steps"], pauses["steps"]) == (3, 2, 6)
    # the longest: on the driver's thread, in no span of it, 55 ms
    # before the slice's first step, in no steady step; the loop's own
    # lay in its serve.step
    assert pauses["longest"][0] == (pytest.approx(50.0), 2, DRIVER, None,
                                    pytest.approx(0.055), 0.0)
    assert pauses["longest"][2][2:4] == (LOOP, "serve.step")
    assert pauses["by_thread"] == {DRIVER: pytest.approx(0.058),
                                   LOOP: pytest.approx(0.002)}


def test_spans_by_thread_and_the_window_the_ring_holds(ring):
    steps, _ = stalls.steady_steps(SUMMARY, ring)
    threads = stalls.by_thread(steps, ring)
    # outermost spans whose midpoint lies in a steady step, a step: the
    # loop's own steps, one submit of two, the stats call, the first pass
    assert threads == {
        (LOOP, "serve.step"): pytest.approx(10.0),
        (DRIVER, "request.submit"): pytest.approx(3.0 / 6),
        (DRIVER, "engine.stats"): pytest.approx(2.0 / 6),
        (DRIVER, "host.gc"): pytest.approx(8.0 / 6)}
    # from the ring's oldest span to the first profiled step
    assert stalls.window_held(SUMMARY, steps, ring) == pytest.approx(1.1)
    said = []
    stalls.say_once(SUMMARY, said.append)
    stalls.say_once(SUMMARY, said.append)          # once a run
    assert len(said) == 3
    assert "MainThread request.submit 0.5000" in said[0]
    assert "0.2500 ms over 6 steady dispatches" in said[1]
    assert "1 of which took 20 ms or more (30.5)" in said[1]
    assert "0.5000 ms of it waiting" in said[1]
    assert "hold 1.10 s of the window's 30.00 s" in said[2]
    assert "2 of its 6 steady steps ran under the profiler" in said[2]


@pytest.mark.parametrize("name,value", [
    ("starved_step_share.serve", 25.0),
    ("host_late_share.serve", 50.0),
    ("gc_pause_ms_step.serve", 10.0 / 6),
])
def test_stall_readers(ring, name, value):
    reader = run.load_module("layer_metrics", name)
    assert reader.read(SUMMARY) == pytest.approx(value)
    assert reader.read({"tokens": 1}) is None      # a training summary


@pytest.mark.parametrize("name", [
    "starved_step_share.serve", "host_late_share.serve",
    "gc_pause_ms_step.serve"])
def test_stall_readers_find_nothing_in_a_program_without_the_attributes(
        monkeypatch, name):
    """The parent commit's ring: steady steps and waits, no ``thread``,
    ``starved`` or ``ready``; its ``tracing`` has no ``gc_totals``."""
    old = []
    for sid, t in ((100, 1.0), (200, 1.01)):
        step = ("serve.step", sid)
        old.append({"name": "engine.decode.wait", "sid": sid + 2, "t": t,
                    "dur": 0.006, "rank": 0, "trace_id": "", "ahead": 1,
                    "parent": step})
        old.append({"name": "serve.step", "sid": sid, "t": t, "dur": 0.01,
                    "rank": 0, "trace_id": "", "step": sid,
                    "decoded": SLOTS, "occupancy": SLOTS, "waiting": 0,
                    "admitted": 0})
    monkeypatch.setattr(spans, "ring", lambda: old)
    monkeypatch.setattr(stalls, "gc_totals", lambda: None)
    monkeypatch.setattr(stalls, "_said", False)
    reader = run.load_module("layer_metrics", name)
    assert reader.read(SUMMARY) is None
    monkeypatch.setattr(spans, "ring", lambda: [])     # the ring off
    assert reader.read(SUMMARY) is None


def test_a_program_with_the_hook_and_no_long_pass_reads_zero(ring):
    short = [s for s in ring if s["name"] != "host.gc"]
    assert stalls.gc_pauses(SUMMARY, short)["ms_step"] == 0.0
    assert stalls.gc_totals() is not None


# ---------------------------------------------------------- stall_causes

THIRD_LINE = '''  lines {
    id: 5
    name: "MainThread"
    timestamp_ns: 1000000
    events { metadata_id: 10 offset_ps: 2200000000 duration_ps: 2000000000 }
    events { metadata_id: 11 offset_ps: 1020000000 duration_ps: 130000000 }
    events { metadata_id: 12 offset_ps: 2100000000 duration_ps: 1500000000 }
  }
  event_metadata { key: 10 value { id: 10 name: "host.gc" } }
  event_metadata { key: 11 value { id: 11 name: "engine.stats" } }
  event_metadata { key: 12 value { id: 12 name: "XlaCompile" } }
'''
# a line is its thread's native name, its number and its commonest span
LOOP_LINE, MAIN = "serve-r0#0 serve.step", "MainThread#2 host.gc"
ANCHOR = '  event_metadata { key: 1 value { id: 1 name: "serve.step" } }'


@pytest.fixture(scope="module")
def profile():
    """The recorded host trace with a third host line in a copy of its
    text: a collector pass of 2 ms inside the 3 ms gap, an
    ``engine.stats`` inside the 200 us gap and a compile of 1.5 ms."""
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "recorded_host_trace.textproto")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    assert text.count(ANCHOR) == 1
    return ProfileData.from_text_proto(
        text.replace(ANCHOR, THIRD_LINE + ANCHOR))


def test_gaps_name_every_line_and_the_dispatch(profile):
    said = []
    out = stall_causes.report(profile, said.append)
    assert out["loop"] == LOOP_LINE
    assert out["idle_s"] == pytest.approx(3.2e-3)
    long, short = out["gaps"]
    # (a) the loop was dispatching the prefill when the gap opened and
    # waiting for it when it closed; (c) that dispatch began before the
    # gap; (b) the collector ran on another thread for 2 ms of it; (d)
    # and a compile for 1.5 ms
    assert (long["at_start"], long["at_end"]) == (
        "engine.prefill.dispatch", "engine.prefill.wait")
    assert (long["class"], long["dispatch"]) == (
        "runtime late", "engine.prefill.dispatch")
    assert long["others"] == [
        (MAIN, "host.gc", pytest.approx(2_000_000))]
    assert long["host_events"] == [
        (MAIN, "XlaCompile", pytest.approx(1_500_000))]
    # the 200 us gap: no dispatch of the loop's before it closed (the
    # next program is not the engine's), engine.stats on the other thread
    assert (short["at_start"], short["at_end"]) == (
        "engine.decode.wait", "serve.retire")
    assert short["class"] == "no dispatch"
    assert short["others"] == [
        (MAIN, "engine.stats", pytest.approx(130_000))]
    assert out["by_class"] == {
        "runtime late + host.gc": pytest.approx(3.0e-3),
        "no dispatch + engine.stats": pytest.approx(0.2e-3)}
    assert any("runtime late (engine.prefill.dispatch)  next: "
               "jit__prefill_impl" in line for line in said)
    assert any(MAIN + ": host.gc 2.000 ms" in line for line in said)
    assert any(MAIN + ": XlaCompile 1.500 ms" in line for line in said)


@pytest.mark.parametrize("gap,expected", [
    ((5, 30), ("host late", "engine.decode.dispatch")),
    ((15, 30), ("runtime late", "engine.decode.dispatch")),
    ((25, 30), ("runtime late", "engine.decode.dispatch")),
    ((0, 5), ("no dispatch", None)),
    # the first dispatch still open, not the one after it
    ((15, 50), ("runtime late", "engine.decode.dispatch")),
    ((22, 50), ("host late", "engine.prefill.dispatch")),
])
def test_dispatch_class(gap, expected):
    loop = [("serve.step", 0, 100), ("engine.decode.dispatch", 10, 20),
            ("engine.prefill.dispatch", 40, 45)]
    kind, span = stall_causes.dispatch_class(loop, *gap)
    assert (kind, span and span[0]) == expected


def test_the_recorded_trace_as_it_stands_has_no_second_cause():
    """Unextended, the file's other line holds a ``request.submit`` in
    the gap under the floor and nothing in the two that count."""
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "recorded_host_trace.textproto")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    found, loop = stall_causes.gaps(ProfileData.from_text_proto(text))
    assert loop == LOOP_LINE and len(found) == 2
    assert all(not g["others"] and not g["host_events"] for g in found)
    assert stall_causes.label(found[0]) == "runtime late"
