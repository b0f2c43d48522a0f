"""Request-level distributed tracing + the SLO plane.

Every observability layer before this one is step- or process-centric:
metrics (metrics.py) aggregate, the flight recorder (flight_recorder.py)
keeps crash-time evidence, the step profiler (profiler.py) attributes
one *training* step. None of them can answer the serving question "where
did request X's p99 go" — queue wait, KV-transport hop, admission delay,
prefill, decode-block contention, or a requeue after a replica death.

This module is the Dapper-style answer:

* **Trace context.** Every request gets a ``trace_id`` at submit
  (:func:`new_trace_id`); the id rides the request through both queue
  transports (``Request.trace_id`` is part of the KV wire format, so the
  context crosses process boundaries inside the ``serve.req.<rank>``
  record) and into every flight-recorder event on the serve path.
* **Spans.** Each lifecycle phase — submit, queue wait, prefill,
  decode, response, plus the training plane's collectives — records one
  span: a small dict appended to a ``maxlen``-bounded deque (GIL-atomic,
  no lock, same hot-path philosophy as the flight recorder ring). Spans
  are recorded at END time; an abandoned phase simply never appears.
* **Layer spans.** :func:`span` is the one primitive for what the
  program does *between* requests: the serving loop (``serve.step`` and
  its ``serve.pull`` / ``serve.admit`` / ``serve.retire``), the KV-cache
  engine (``engine.prefill``, ``engine.decode`` and their ``dispatch`` /
  ``wait`` parts) and the input feed (``input.wait``, ``input.put``).
  A span goes to the same ring with its ``parent`` (the enclosing open
  span of its thread) and its ``thread`` (the recording thread's name)
  and, under the same name, into whatever ``jax.profiler`` trace is
  running, on the clock the device events are on.
  Spans serialize into the profiler dump (``request_spans``) and merge
  into the Perfetto trace as per-request lanes with flow arrows joining
  one ``trace_id`` across ranks on the ``/_time``-corrected clock
  (profiler.merge_profile_dir).
* **Host stalls.** While tracing is on, one ``gc.callbacks`` entry
  (installed by :func:`configure`, removed at ``hvd.shutdown()``) turns
  every pass of the garbage collector into a ``TraceAnnotation`` and
  running totals by generation
  (:func:`gc_totals`, in the ``GET /slo`` document), and a pass of
  :data:`GC_SPAN_FLOOR_S` or more into a ``host.gc`` span on the thread
  that ran it: whatever the collector held the interpreter for has a
  name, a thread and a place on the profiler's clock.
* **SLOs.** Declared objectives — ``HOROVOD_SLO_TTFT_MS``,
  ``HOROVOD_SLO_LATENCY_MS``, ``HOROVOD_SLO_AVAILABILITY`` — tracked as
  rolling good/bad windows with error-budget and burn-rate gauges
  (``horovod_slo_*``), a ``GET /slo`` route (metrics.py), burn-rate
  threshold crossings as flight-recorder events (surfaced by ``tpurun
  --postmortem``), and per-request span summaries attached to the
  slowest-request exemplars.

Knobs: ``HOROVOD_TRACE`` (default on; ``0`` disables; an integer > 1
sets the span ring capacity, default 16384), ``HOROVOD_SLO_TTFT_MS`` /
``HOROVOD_SLO_LATENCY_MS`` (latency objectives, ms),
``HOROVOD_SLO_AVAILABILITY`` (compliance target for all three
objectives, default 0.999), ``HOROVOD_SLO_WINDOW`` (rolling window, in
requests, default 512), ``HOROVOD_SLO_BURN_ALERT`` (burn-rate crossing
that emits an ``slo_burn_rate`` flight event, default 14 — the classic
fast-burn page threshold). docs/tracing.md is the full model.
"""

from __future__ import annotations

import gc
import itertools
import os
import threading
import time
import uuid
from collections import deque
from typing import Dict, List, Optional

from horovod_tpu.analysis import witness
from horovod_tpu.metrics import registry as _metrics
from horovod_tpu.utils.env import (DEFAULT_SLO_WINDOW,
                                   DEFAULT_TRACE_CAPACITY, HOROVOD_SLO_AVAILABILITY,
                                   HOROVOD_SLO_BURN_ALERT,
                                   HOROVOD_SLO_LATENCY_MS, HOROVOD_SLO_TTFT_MS,
                                   HOROVOD_SLO_WINDOW, HOROVOD_TRACE,
                                   _get_float, _get_int, parse_trace)

SCHEMA = "horovod-tracing-v1"
OBJECTIVES = ("ttft", "latency", "availability")
# slowest-request exemplars kept (each carries its span summary)
_EXEMPLARS_MAX = 8
# a garbage-collector pass this long or longer is a ``host.gc`` span; a
# shorter one only adds to gc_totals() (a young-generation pass every few
# hundred allocations would flood the ring)
GC_SPAN_FLOOR_S = 0.0005
# spans that say whether a jax.profiler session was recording when they
# began (``profiled=1``): a reader keeps a traced slice's steps apart
_MARKS_PROFILED = frozenset(("serve.step", "input.wait"))

_SPANS_TOTAL = _metrics().counter(
    "horovod_trace_spans_total",
    "Spans recorded into the tracing ring buffer.")
_SLO_EVENTS = _metrics().counter(
    "horovod_slo_events_total",
    "Requests scored against each SLO objective, by verdict.",
    labelnames=("objective", "verdict"))
_SLO_BURN = _metrics().gauge(
    "horovod_slo_burn_rate",
    "Observed bad-event rate over the rolling window divided by the "
    "rate the objective allows (1.0 = burning budget exactly at the "
    "sustainable rate).",
    labelnames=("objective",))
_SLO_BUDGET = _metrics().gauge(
    "horovod_slo_error_budget_remaining",
    "Fraction of the rolling window's error budget still unspent "
    "(1.0 = clean window, 0.0 = budget exhausted).",
    labelnames=("objective",))
_SLO_ALERTS = _metrics().counter(
    "horovod_slo_burn_alerts_total",
    "Burn-rate threshold crossings (HOROVOD_SLO_BURN_ALERT).",
    labelnames=("objective",))


def new_trace_id() -> str:
    """A fresh 16-hex-char trace id (the wire format everywhere)."""
    return uuid.uuid4().hex[:16]


class Tracer:
    """Bounded span ring. ``record`` is the hot path: build one small
    dict, append to a maxlen deque — atomic under the GIL, no lock, old
    spans overwritten in O(1)."""

    def __init__(self) -> None:
        enabled, capacity = parse_trace(os.environ.get(HOROVOD_TRACE))
        self.enabled = enabled
        self.capacity = capacity
        self.rank = int(os.environ.get("HOROVOD_RANK", "0") or 0)
        self._spans: deque = deque(maxlen=capacity)

    def configure(self, rank: Optional[int] = None) -> None:
        """Re-read the env knobs (called from ``hvd.init()``, including
        elastic re-init where the rank may have changed)."""
        enabled, capacity = parse_trace(os.environ.get(HOROVOD_TRACE))
        self.enabled = enabled
        if capacity != self.capacity:
            self._spans = deque(self._spans, maxlen=capacity)
            self.capacity = capacity
        if rank is not None:
            self.rank = rank

    # -- hot path ------------------------------------------------------------
    def record(self, name: str, t0: float, dur: float,
               trace_id: str = "", **attrs) -> None:
        """Record one finished span. ``t0`` is epoch seconds (the
        package-wide trace clock domain, correctable by the rendezvous
        ``/_time`` offset at merge time); ``dur`` is seconds. ``parent``
        is the :func:`span` open on this thread, if any, as its
        ``(name, sid)``; ``thread`` is this thread's name."""
        if not self.enabled:
            return
        span = {"trace_id": trace_id, "name": name, "t": t0,
                "dur": dur, "rank": self.rank,
                "thread": threading.current_thread().name}
        stack = getattr(_open, "stack", None)
        if stack:
            span["parent"] = stack[-1]
        span.update(attrs)
        self._spans.append(span)  # GIL-atomic; maxlen evicts the oldest
        _SPANS_TOTAL.inc()

    def spans(self) -> List[dict]:
        return list(self._spans)

    def spans_recorded(self) -> int:
        return int(_SPANS_TOTAL.value)


# the spans open on each thread, innermost last, as ``(name, sid)``
_open = threading.local()
_numbers = itertools.count(1)   # next() is GIL-atomic
_annotation = None              # jax.profiler.TraceAnnotation, on first use


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, imported on the first span (this
    module does not import jax); a stand-in that does nothing where jax
    is not installed."""
    global _annotation
    try:
        from jax.profiler import TraceAnnotation as cls
    except ImportError:
        import contextlib

        cls = lambda name, **_: contextlib.nullcontext()
    _annotation = cls
    return cls


class _Span:
    """One open :func:`span`. ``set`` adds attributes known only at the
    end (they reach the ring, not the profiler's annotation, which is
    written at entry)."""

    __slots__ = ("name", "trace_id", "attrs", "key", "t0", "_annotated",
                 "_keep")

    def __init__(self, name: str, trace_id: str, attrs: dict) -> None:
        self.name = name
        self.trace_id = trace_id
        self.attrs = attrs
        self._keep = True

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def discard(self) -> None:
        """Keep this span out of the ring (the profiler's annotation is
        written all the same): for what would only flood it, such as the
        passes of an idle loop after the first."""
        self._keep = False

    def __enter__(self) -> "_Span":
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.key = (self.name, next(_numbers))
        cls = _annotation or _annotation_class()
        if self.name in _MARKS_PROFILED and _profiling(cls):
            self.attrs["profiled"] = 1
        self._annotated = cls(
            self.name, **{k: v for k, v in self.attrs.items()
                          if isinstance(v, (int, float, str))})
        self._annotated.__enter__()
        stack.append(self.key)
        self.t0 = time.time()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.time() - self.t0
        _open.stack.pop()
        self._annotated.__exit__(*exc)
        if self._keep:
            _tracer.record(self.name, self.t0, dur, trace_id=self.trace_id,
                           sid=self.key[1], **self.attrs)
        return False


class _NoSpan:
    """What :func:`span` hands back with ``HOROVOD_TRACE=0``: one shared
    object, no clock read."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass

    def discard(self) -> None:
        pass

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


def _profiling(cls) -> bool:
    """Is a ``jax.profiler`` session recording (``cls`` is what
    :func:`_annotation_class` gave)?"""
    is_enabled = getattr(cls, "is_enabled", None)
    return bool(is_enabled and is_enabled())


class _GcWatch:
    """The one ``gc.callbacks`` entry (module docstring, "Host stalls").
    The collector never runs inside itself, in this thread or another,
    so one pass is open at a time and its start needs no lock."""

    def __init__(self) -> None:
        # generation -> [passes, seconds, the longest pass's seconds]
        self.totals: Dict[int, List[float]] = {}
        self._t0 = None
        self._annotated = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._annotated = (_annotation or _annotation_class())(
                "host.gc", generation=info["generation"])
            self._annotated.__enter__()
            self._t0 = time.time()
        elif self._t0 is not None:
            t0, self._t0 = self._t0, None
            dur = time.time() - t0
            self._annotated.__exit__(None, None, None)
            total = self.totals.setdefault(info["generation"], [0, 0.0, 0.0])
            total[0] += 1
            total[1] += dur
            total[2] = max(total[2], dur)
            if dur >= GC_SPAN_FLOOR_S:
                _tracer.record("host.gc", t0, dur,
                               generation=info["generation"],
                               collected=info["collected"])

    def watch(self, on: bool) -> None:
        """In ``gc.callbacks`` once while ``on``, not at all otherwise."""
        if on and self not in gc.callbacks:
            gc.callbacks.append(self)
        elif not on and self in gc.callbacks:
            gc.callbacks.remove(self)
            self._t0 = None


_gc_watch = _GcWatch()


def gc_totals() -> Dict[int, dict]:
    """Every garbage-collector pass since tracing came on, by generation:
    ``{generation: {"count", "seconds", "longest_s"}}``. Unlike the ring
    it forgets nothing; empty where tracing never was on."""
    return {gen: {"count": int(n), "seconds": seconds, "longest_s": longest}
            for gen, (n, seconds, longest)
            in sorted(_gc_watch.totals.items())}


class SLOTracker:
    """Rolling good/bad windows per objective + burn-rate alerting.

    One window per objective, ``HOROVOD_SLO_WINDOW`` requests deep. The
    compliance target for every objective is ``HOROVOD_SLO_AVAILABILITY``
    (e.g. 0.999 → "99.9% of requests complete, 99.9% of completions meet
    each latency objective"), so the allowed bad fraction — the error
    budget — is ``1 - target``. Burn rate is the observed bad fraction
    divided by the allowed one: 1.0 spends the budget exactly at the
    sustainable rate, 14 is the classic fast-burn page. Crossing
    ``HOROVOD_SLO_BURN_ALERT`` upward emits ONE ``slo_burn_rate``
    flight-recorder event (re-armed when the rate falls back under), so
    a sustained burn is one postmortem line, not a storm."""

    def __init__(self) -> None:
        self._lock = witness.make_lock("SLOTracker._lock")
        self.configure()

    def configure(self) -> None:
        window = max(1, _get_int(HOROVOD_SLO_WINDOW, DEFAULT_SLO_WINDOW))
        with self._lock:
            self.ttft_ms = _get_float(HOROVOD_SLO_TTFT_MS, 1000.0)
            self.latency_ms = _get_float(HOROVOD_SLO_LATENCY_MS, 10000.0)
            self.target = min(1.0 - 1e-9, max(
                0.0, _get_float(HOROVOD_SLO_AVAILABILITY, 0.999)))
            self.burn_alert = _get_float(HOROVOD_SLO_BURN_ALERT, 14.0)
            self.window = window
            # guarded-by: _lock
            self._windows: Dict[str, deque] = {
                obj: deque(maxlen=window) for obj in OBJECTIVES}
            self._alerting: Dict[str, bool] = {
                obj: False for obj in OBJECTIVES}
            self._latencies: deque = deque(maxlen=window)   # ms
            self._ttfts: deque = deque(maxlen=window)       # ms
            self._exemplars: List[dict] = []
            self._requests = 0
            self._bad = {obj: 0 for obj in OBJECTIVES}  # cumulative

    # -- recording -----------------------------------------------------------
    def record_request(self, ttft_s: float, latency_s: float,
                       ok: bool = True, trace_id: str = "", rank: int = 0,
                       requeues: int = 0,
                       phases: Optional[Dict[str, float]] = None) -> None:
        """Score one finished request against every objective.

        ``ok=False`` (rejected / never served) is an availability bad
        event and skips the latency objectives — an unserved request has
        no meaningful TTFT. ``phases`` (name -> seconds) feeds the
        slowest-phase attribution on slow-request exemplars."""
        verdicts = {"availability": ok}
        if ok:
            verdicts["ttft"] = ttft_s * 1000.0 <= self.ttft_ms
            verdicts["latency"] = latency_s * 1000.0 <= self.latency_ms
        alerts = []
        with self._lock:
            self._requests += 1
            for obj, good in verdicts.items():
                self._windows[obj].append(good)
                if not good:
                    self._bad[obj] += 1
                burn = self._burn_rate_locked(obj)
                if burn >= self.burn_alert and not self._alerting[obj]:
                    self._alerting[obj] = True
                    alerts.append((obj, burn))
                elif burn < self.burn_alert:
                    self._alerting[obj] = False
            if ok:
                self._latencies.append(latency_s * 1000.0)
                self._ttfts.append(ttft_s * 1000.0)
                self._note_exemplar_locked(
                    trace_id, ttft_s, latency_s, rank, requeues, phases)
        for obj, good in verdicts.items():
            _SLO_EVENTS.labels(objective=obj,
                               verdict="good" if good else "bad").inc()
            _SLO_BURN.labels(objective=obj).set(self.burn_rate(obj))
            _SLO_BUDGET.labels(objective=obj).set(
                self.error_budget_remaining(obj))
        # flight emission outside the lock: emit is lock-free but cheap
        # hygiene all the same (never do foreign work under a lock)
        for obj, burn in alerts:
            _SLO_ALERTS.labels(objective=obj).inc()
            from horovod_tpu import flight_recorder

            flight_recorder.emit(
                "slo_burn_rate", objective=obj, burn_rate=round(burn, 2),
                threshold=self.burn_alert, window=self.window,
                trace_id=trace_id)

    def _note_exemplar_locked(self, trace_id: str, ttft_s: float,
                              latency_s: float, rank: int, requeues: int,
                              phases: Optional[Dict[str, float]]) -> None:
        # guarded-by: _lock. Keep the _EXEMPLARS_MAX slowest requests,
        # each with its span summary (slowest phase + requeue count) —
        # the "why was THIS one slow" attachment on the /slo route.
        slowest_phase = None
        if phases:
            slowest_phase = max(phases, key=lambda k: phases[k])
        self._exemplars.append({
            "trace_id": trace_id,
            "latency_ms": round(latency_s * 1000.0, 3),
            "ttft_ms": round(ttft_s * 1000.0, 3),
            "rank": rank,
            "requeues": requeues,
            "slowest_phase": slowest_phase,
            "phases_ms": {k: round(v * 1000.0, 3)
                          for k, v in (phases or {}).items()},
        })
        self._exemplars.sort(key=lambda e: e["latency_ms"], reverse=True)
        del self._exemplars[_EXEMPLARS_MAX:]

    # -- math ----------------------------------------------------------------
    def _bad_fraction_locked(self, objective: str) -> float:
        window = self._windows[objective]
        if not window:
            return 0.0
        return sum(1 for good in window if not good) / len(window)

    def _burn_rate_locked(self, objective: str) -> float:
        allowed = 1.0 - self.target
        return self._bad_fraction_locked(objective) / allowed

    def burn_rate(self, objective: str) -> float:
        with self._lock:
            return self._burn_rate_locked(objective)

    def error_budget_remaining(self, objective: str) -> float:
        with self._lock:
            return max(0.0, 1.0 - self._burn_rate_locked(objective))

    @staticmethod
    def _percentile(values: List[float], q: float) -> Optional[float]:
        if not values:
            return None
        values = sorted(values)
        idx = min(len(values) - 1, int(round(q * (len(values) - 1))))
        return values[idx]

    def state(self) -> dict:
        """JSON-ready document for the ``GET /slo`` route."""
        with self._lock:
            lat = list(self._latencies)
            ttft = list(self._ttfts)
            doc = {
                "schema": SCHEMA,
                "objectives": {
                    "ttft_ms": self.ttft_ms,
                    "latency_ms": self.latency_ms,
                    "availability": self.target,
                },
                "window_requests": self.window,
                "requests_scored": self._requests,
                "burn_alert_threshold": self.burn_alert,
                "slo": {
                    obj: {
                        "window_observed": len(self._windows[obj]),
                        "bad_fraction": round(
                            self._bad_fraction_locked(obj), 6),
                        "burn_rate": round(self._burn_rate_locked(obj), 4),
                        "error_budget_remaining": round(max(
                            0.0, 1.0 - self._burn_rate_locked(obj)), 4),
                        "alerting": self._alerting[obj],
                        "bad_total": self._bad[obj],
                    } for obj in OBJECTIVES},
                "latency_ms_percentiles": {
                    "p50": self._percentile(lat, 0.50),
                    "p99": self._percentile(lat, 0.99)},
                "ttft_ms_percentiles": {
                    "p50": self._percentile(ttft, 0.50),
                    "p99": self._percentile(ttft, 0.99)},
                "slow_request_exemplars": list(self._exemplars),
            }
        doc["spans_recorded"] = _tracer.spans_recorded()
        doc["gc"] = {str(gen): total for gen, total in gc_totals().items()}
        doc["rank"] = _tracer.rank
        return doc


_tracer = Tracer()
_slo = SLOTracker()

# readiness flags for the /healthz route (metrics.py). hvd.init() marks
# initialized; the serve plane marks started (a replica/handle exists)
# and heartbeat-seen (the first replica heartbeat fired) — an external
# load balancer must not route to a worker whose replicas never came up.
_init_ready = False
_serve_started = False
_serve_heartbeat_seen = False


def tracer() -> Tracer:
    return _tracer


def slo() -> SLOTracker:
    return _slo


def enabled() -> bool:
    return _tracer.enabled


def record(name: str, t0: float, dur: float, trace_id: str = "",
           **attrs) -> None:
    """Record one finished span (module-level hot-path entry point)."""
    _tracer.record(name, t0, dur, trace_id=trace_id, **attrs)


def span(name: str, trace_id: str = "", **attrs):
    """Context manager around one layer-boundary interval: stamps start
    and end on the epoch clock and records ``name``, ``t``, ``dur``,
    ``rank``, ``thread``, ``trace_id``, ``attrs``, its running number
    ``sid`` and its ``parent`` (the enclosing open span of this thread)
    into the ring;
    the same interval is a ``jax.profiler.TraceAnnotation`` of the same
    name, so it lands in the host plane of any running profiler session.
    An exception inside still closes and records it."""
    if not _tracer.enabled:
        return _NO_SPAN
    return _Span(name, trace_id, attrs)


def spans() -> List[dict]:
    return _tracer.spans()


def configure(rank: Optional[int] = None) -> None:
    """Adopt the rank, re-read knobs, watch the garbage collector while
    tracing is on, register the flight-recorder state provider and mark
    the process initialized (called from ``hvd.init()``)."""
    global _init_ready
    _tracer.configure(rank=rank)
    _gc_watch.watch(_tracer.enabled)
    _slo.configure()
    _init_ready = True
    from horovod_tpu import flight_recorder

    flight_recorder.set_state_provider("slo", slo_state)


def mark_initialized(ready: bool = True) -> None:
    """``hvd.shutdown()`` marks the process not ready: the collector's
    watch goes with it (what runs after the program is not its stall)."""
    global _init_ready
    _init_ready = ready
    if not ready:
        _gc_watch.watch(False)


def note_serve_started() -> None:
    global _serve_started
    _serve_started = True


def note_replica_heartbeat() -> None:
    global _serve_heartbeat_seen
    _serve_heartbeat_seen = True


def slo_state() -> dict:
    """``GET /slo`` document (also the flight-recorder "slo" state
    provider, so every postmortem dump carries the SLO posture)."""
    return _slo.state()


def healthz_state() -> dict:
    """``GET /healthz`` readiness document. ``ready`` gates the HTTP
    status: 200 only after ``hvd.init()`` ran and — when this process is
    serving — after the first replica heartbeat, so external load
    balancers can gate traffic on it (docs/metrics.md)."""
    ready = _init_ready and (not _serve_started or _serve_heartbeat_seen)
    return {"ready": ready,
            "initialized": _init_ready,
            "serving": _serve_started,
            "first_replica_heartbeat": _serve_heartbeat_seen}


# ---------------------------------------------------------------------------
# Chrome-trace conversion (profiler.merge_profile_dir)
# ---------------------------------------------------------------------------

def spans_to_chrome(span_list: List[dict], tid: int = 2) -> List[dict]:
    """Request spans as Chrome duration ("X") events on their own lane
    (tid 2 keeps them clear of step markers tid 0 / flight instants
    tid 1), epoch-us clock — merge_profile_dir shifts them onto the
    launcher's clock per rank."""
    out = []
    for span in span_list:
        t = span.get("t")
        dur = span.get("dur")
        if not isinstance(t, (int, float)) or \
                not isinstance(dur, (int, float)):
            continue
        args = {k: v for k, v in span.items()
                if k not in ("t", "dur", "name")}
        out.append({"ph": "X", "pid": 0, "tid": tid, "ts": t * 1e6,
                    "dur": max(dur, 0.0) * 1e6,
                    "name": str(span.get("name", "span")),
                    "cat": "request", "args": args})
    return out


def flow_events(anchors: List[dict]) -> List[dict]:
    """Perfetto flow arrows joining one ``trace_id``'s spans across
    lanes. ``anchors`` are merged-clock span anchors — dicts with
    ``trace_id``, ``pid``, ``tid``, ``ts`` (already offset-corrected
    merged-trace us) and ``dur`` — typically collected by
    merge_profile_dir while it lays out the per-rank request lanes.
    Per trace: the earliest span starts the flow ("s"), the latest
    finishes it ("f", bound to the enclosing slice), everything between
    is a step ("t")."""
    by_trace: Dict[str, List[dict]] = {}
    for a in anchors:
        tid = a.get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(a)
    out = []
    for trace_id, group in by_trace.items():
        if len(group) < 2:
            continue  # a single-span trace has nothing to join
        group.sort(key=lambda a: a["ts"])
        last = len(group) - 1
        for i, a in enumerate(group):
            ph = "s" if i == 0 else ("f" if i == last else "t")
            ev = {"ph": ph, "id": trace_id, "cat": "request",
                  "name": "request", "pid": a["pid"], "tid": a["tid"],
                  # flow points bind to the slice under them: anchor the
                  # start at the span's end (the hand-off moment) and
                  # steps/finish at the span's start (the receipt)
                  "ts": a["ts"] + (a.get("dur", 0.0) if i == 0 else 0.0)}
            if ph == "f":
                ev["bp"] = "e"
            out.append(ev)
    return out


def format_slo_report(dumps: List[dict]) -> str:
    """Cross-rank SLO section for ``tpurun --postmortem``: per-rank burn
    rates/budgets from each dump's "slo" state (empty string when no
    dump carries one — pre-tracing dumps render unchanged)."""
    rows = []
    for d in sorted(dumps, key=lambda d: d.get("launch_rank", 0)):
        state = (d.get("state") or {}).get("slo")
        if not isinstance(state, dict) or not state.get("slo"):
            continue
        rank = d.get("launch_rank", d.get("rank", "?"))
        parts = []
        for obj in OBJECTIVES:
            rec = state["slo"].get(obj) or {}
            parts.append("%s burn=%.2f budget=%.0f%%%s" % (
                obj, rec.get("burn_rate", 0.0),
                100.0 * rec.get("error_budget_remaining", 1.0),
                " ALERT" if rec.get("alerting") else ""))
        rows.append("rank %s: %d scored  %s" % (
            rank, state.get("requests_scored", 0), "  ".join(parts)))
        for ex in (state.get("slow_request_exemplars") or ())[:3]:
            rows.append(
                "  slow request %s: %.1f ms (ttft %.1f ms, slowest "
                "phase %s, %d requeue%s)" % (
                    ex.get("trace_id", "?"), ex.get("latency_ms", 0.0),
                    ex.get("ttft_ms", 0.0),
                    ex.get("slowest_phase") or "?",
                    ex.get("requeues", 0),
                    "" if ex.get("requeues", 0) == 1 else "s"))
    if not rows:
        return ""
    return "\n".join(["=== SLO report ==="] + rows)
