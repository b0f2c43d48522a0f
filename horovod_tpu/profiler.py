"""Step-level performance introspection: phase attribution, comm-overlap
accounting, rolling MFU, and the merged cross-rank trace.

Metrics (metrics.py) answer "what are my cumulative rates", the timeline
(timeline.py) answers "what happened to tensor X", and the flight
recorder (flight_recorder.py) answers "what was in flight when we died".
This module answers the live performance question none of them do: *per
training step*, how much wall time was host/input work, compute, exposed
collective time, and optimizer work — and how much collective time was
hidden behind other in-flight work. That is exactly the measurement the
gradient/backward overlap campaign (ROADMAP item 5, acceptance ">70% of
allreduce bytes overlapped") needs before any overlap can be attempted,
and the objective signal the autotuner reboot (ROADMAP item 2) optimizes.

Mechanics
---------

``hvd.profiler.step()`` brackets one training step.  At the boundaries
the profiler diffs cheap cumulative accumulators rather than tracing
anything:

* **exposed_comm** — the ``horovod_handle_wait_seconds`` sum (caller
  time actually blocked in ``RuntimeHandle.wait()``) diffed across the
  step, clamped to the step wall time;
* **host** / **optimizer** — accumulated by ``annotate("host")`` /
  ``annotate("optimizer")`` context managers (``DistributedOptimizer``
  annotates its inner update automatically on the eager path);
* **compute** — the remainder, so the four phases sum to the step wall
  time by construction.

Independently, the executor's comm clock (``executor.comm_totals()``)
splits every collective's lifetime into dispatch-busy, a pipeline
overlap window, and drain-busy; the **comm-hidden fraction** is
``1 − exposed ÷ total`` over the step (plus a bytes-weighted variant).
At pipeline depth 1 the overlap window is empty — a synchronous
allreduce reports ~0; at depth ≥ 2 the window of bin k contains bin
k+1's whole dispatch, so overlap shows up as a positive fraction.

``set_flops_per_step()`` (called by a training script that knows its
model FLOPs and the per-chip peak) turns step wall time into a rolling
in-process MFU.

Every rank with profiling enabled dumps ``profile-rank-N.json`` — the
last ``HOROVOD_PROFILE_HISTORY`` step breakdowns plus Chrome-trace step
markers and a slice of flight-recorder events — into
``HOROVOD_PROFILE_DIR`` and ships a copy to the launcher's rendezvous
store (scope ``profile``).  ``tpurun --profile-dir`` harvests the dumps,
merges them with the per-rank runtime timelines (and any
``jax.profiler`` device traces under the directory) onto one clock using
the flight recorder's ``/_time`` offset estimate, and prints a
cross-rank step-time report naming the slowest phase and rank.

Knobs: ``HOROVOD_PROFILE`` (enable), ``HOROVOD_PROFILE_DIR`` (dump/
harvest directory; implies enable), ``HOROVOD_PROFILE_HISTORY`` (step
ring size, default 64), ``HOROVOD_PROFILE_JAX`` (also capture a
``jax.profiler`` device trace into the profile dir).
"""

from __future__ import annotations

import glob
import json
import os
import socket
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from horovod_tpu import flight_recorder
from horovod_tpu.metrics import registry as _metrics
from horovod_tpu.utils import logging as log
from horovod_tpu.utils.env import (DEFAULT_PROFILE_HISTORY, HOROVOD_PROFILE,
                                   HOROVOD_PROFILE_DIR,
                                   HOROVOD_PROFILE_HISTORY,
                                   HOROVOD_PROFILE_JAX, _get_bool, _get_int)

SCHEMA = "horovod-profiler-v1"
RENDEZVOUS_SCOPE = "profile"
DUMP_PREFIX = "profile-rank-"
MERGED_TRACE = "merged-trace.json"
PHASES = ("host", "compute", "exposed_comm", "optimizer")
# flight-recorder events carried into the merged trace per dump
_FLIGHT_TRACE_EVENTS = 200

_STEP_SECONDS = _metrics().histogram(
    "horovod_step_seconds",
    "Wall time of one profiled training step (hvd.profiler.step()).")
_HIDDEN_FRACTION = _metrics().gauge(
    "horovod_comm_hidden_fraction",
    "Fraction of collective time hidden behind other in-flight work over "
    "the last profiled step (1 - exposed/total; 0 when the step ran no "
    "collectives).")
_MFU = _metrics().gauge(
    "horovod_mfu",
    "Rolling model-FLOPs utilization over the profiled step history "
    "(needs hvd.profiler.set_flops_per_step with a peak-FLOPs hint).")


def _comm_totals() -> dict:
    try:
        from horovod_tpu.runtime import executor

        return executor.comm_totals()
    except Exception:
        return {"total_seconds": 0.0, "exposed_seconds": 0.0,
                "total_bytes": 0, "hidden_bytes": 0.0, "ops": 0}


def _handle_wait_seconds() -> float:
    try:
        from horovod_tpu.runtime import runtime as runtime_mod

        return runtime_mod._HANDLE_WAIT.labels().sum
    except Exception:
        return 0.0


class _StepRecord:
    """Open bookkeeping for one in-flight step."""

    __slots__ = ("index", "name", "auto", "t0", "t0_epoch", "comm0",
                 "wait0", "phase_seconds", "breakdown")

    def __init__(self, index: int, name: Optional[str], auto: bool):
        self.index = index
        self.name = name or f"step {index}"
        self.auto = auto
        self.t0 = time.perf_counter()
        self.t0_epoch = time.time()
        self.comm0 = _comm_totals()
        self.wait0 = _handle_wait_seconds()
        self.phase_seconds = {"host": 0.0, "optimizer": 0.0}
        self.breakdown: Optional[dict] = None  # filled at close


class StepProfiler:
    """Process-wide step profiler (one instance, see ``profiler()``)."""

    def __init__(self) -> None:
        self.enabled = False
        self.dir = ""
        self.history_cap = DEFAULT_PROFILE_HISTORY
        self.launch_rank = int(os.environ.get("HOROVOD_RANK", "0") or 0)
        self.rank = self.launch_rank
        self._steps: deque = deque(maxlen=self.history_cap)
        self._trace_events: deque = deque(maxlen=4 * self.history_cap)
        self._mfu_window: deque = deque(maxlen=self.history_cap)
        self._flops_per_step: Optional[float] = None
        self._peak_flops: Optional[float] = None
        self._step_index = 0
        self._active: Optional[_StepRecord] = None  # explicit step() CM
        self._auto_rec: Optional[_StepRecord] = None
        self._dump_lock = threading.Lock()
        self._jax_tracing = False
        self._profile_state_cache: Optional[Tuple[float, dict]] = None

    # -- configuration ------------------------------------------------------
    def configure(self, rank: Optional[int] = None) -> None:
        """Re-read env knobs (called from ``hvd.init()``, including elastic
        re-init). Enabling registers the flight-recorder state provider so
        every postmortem dump carries the recent step breakdowns."""
        self.dir = os.environ.get(HOROVOD_PROFILE_DIR, "")
        self.enabled = _get_bool(HOROVOD_PROFILE) or bool(self.dir)
        cap = max(1, _get_int(HOROVOD_PROFILE_HISTORY,
                              DEFAULT_PROFILE_HISTORY))
        if cap != self.history_cap:
            self.history_cap = cap
            self._steps = deque(self._steps, maxlen=cap)
            self._trace_events = deque(self._trace_events, maxlen=4 * cap)
            self._mfu_window = deque(self._mfu_window, maxlen=cap)
        if rank is not None:
            self.rank = rank
        if self.enabled:
            flight_recorder.set_state_provider("profiler", self._debug_state)
            if self.dir and _get_bool(HOROVOD_PROFILE_JAX):
                self._start_jax_trace()

    def _start_jax_trace(self) -> None:
        if self._jax_tracing:
            return
        try:
            import jax

            jax.profiler.start_trace(
                os.path.join(self.dir, f"jax-rank-{self.launch_rank}"))
            self._jax_tracing = True
        except Exception as exc:
            log.warning("profiler: jax.profiler trace unavailable: %s", exc)

    def _stop_jax_trace(self) -> None:
        if not self._jax_tracing:
            return
        self._jax_tracing = False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as exc:
            log.debug("profiler: jax.profiler stop failed: %s", exc)

    def set_flops_per_step(self, flops: Optional[float],
                           peak_flops_per_chip: Optional[float] = None
                           ) -> None:
        """Model-FLOPs hint: per-chip FLOPs executed by one profiled step
        (forward + backward + update). With a per-chip peak the profiler
        maintains the rolling ``horovod_mfu`` gauge; without one MFU stays
        unset."""
        self._flops_per_step = flops
        if peak_flops_per_chip is not None:
            self._peak_flops = peak_flops_per_chip

    # -- step bracketing ----------------------------------------------------
    def auto_step(self) -> None:
        """Implicit step boundary (hooked into ``DistributedOptimizer`` /
        ``training.make_train_step``): each call closes the previous
        implicit step and opens the next, so plain training loops get
        breakdowns without touching ``hvd.profiler.step()``. No-op while
        an explicit step is open, or when profiling is off."""
        if not self.enabled or self._active is not None:
            return
        if self._auto_rec is not None:
            self._finish(self._auto_rec)
        self._auto_rec = _StepRecord(self._next_index(), None, auto=True)

    @contextmanager
    def step(self, name: Optional[str] = None):
        """Bracket one training step; yields the finished breakdown dict
        holder (``rec.breakdown`` is filled on exit). Nested use is a
        no-op on the inner level."""
        if not self.enabled or self._active is not None:
            yield None
            return
        if self._auto_rec is not None:  # explicit bracketing wins
            self._finish(self._auto_rec)
            self._auto_rec = None
        rec = _StepRecord(self._next_index(), name, auto=False)
        self._active = rec
        tl = self._timeline()
        if tl is not None:
            tl.start("step", f"STEP_{rec.index}")
        try:
            yield rec
        finally:
            self._active = None
            if tl is not None:
                tl.end("step")
            self._finish(rec)

    @contextmanager
    def annotate(self, phase: str):
        """Attribute the enclosed wall time to ``phase`` ("host"/"input"
        for the data pipeline, "optimizer" for the update) within the
        current step."""
        key = {"input": "host", "host": "host",
               "optimizer": "optimizer"}.get(phase)
        if key is None:
            raise ValueError(f"unknown profiler phase {phase!r}; expected "
                             "'host', 'input' or 'optimizer'")
        rec = self._active or self._auto_rec
        if not self.enabled or rec is None:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            rec.phase_seconds[key] += time.perf_counter() - t0

    def _next_index(self) -> int:
        self._step_index += 1
        return self._step_index

    def _timeline(self):
        try:
            from horovod_tpu.core import state as state_mod

            return state_mod.global_state().timeline
        except Exception:
            return None

    # -- attribution --------------------------------------------------------
    def _finish(self, rec: _StepRecord) -> None:
        wall = max(time.perf_counter() - rec.t0, 1e-9)
        comm1 = _comm_totals()
        comm_total = max(0.0, comm1["total_seconds"]
                         - rec.comm0["total_seconds"])
        comm_exposed = max(0.0, comm1["exposed_seconds"]
                           - rec.comm0["exposed_seconds"])
        comm_bytes = max(0, comm1["total_bytes"] - rec.comm0["total_bytes"])
        hidden_bytes = max(0.0, comm1["hidden_bytes"]
                           - rec.comm0["hidden_bytes"])
        comm_ops = max(0, comm1.get("ops", 0) - rec.comm0.get("ops", 0))
        hidden_fraction = 0.0
        if comm_total > 0.0:
            hidden_fraction = min(1.0, max(0.0,
                                           1.0 - comm_exposed / comm_total))
        hidden_fraction_bytes = 0.0
        if comm_bytes > 0:
            hidden_fraction_bytes = min(1.0, max(0.0,
                                                 hidden_bytes / comm_bytes))

        # phase attribution: annotated host/optimizer + caller-blocked
        # collective time; compute is the remainder so the four phases sum
        # to the step wall time exactly (scaled down proportionally in the
        # rare case annotations overlap the wait)
        host = max(0.0, rec.phase_seconds["host"])
        optimizer = max(0.0, rec.phase_seconds["optimizer"])
        exposed_phase = max(0.0, _handle_wait_seconds() - rec.wait0)
        accounted = host + optimizer + exposed_phase
        if accounted > wall and accounted > 0.0:
            scale = wall / accounted
            host *= scale
            optimizer *= scale
            exposed_phase *= scale
            accounted = wall
        phases = {"host": host,
                  "compute": wall - accounted,
                  "exposed_comm": exposed_phase,
                  "optimizer": optimizer}

        mfu = None
        if self._flops_per_step and self._peak_flops:
            mfu = self._flops_per_step / wall / self._peak_flops
            self._mfu_window.append(mfu)
            _MFU.set(sum(self._mfu_window) / len(self._mfu_window))
        _STEP_SECONDS.observe(wall)
        _HIDDEN_FRACTION.set(hidden_fraction)

        # memory plane: HBM high watermark observed by the end of this
        # step (device peak_bytes_in_use where reported, the tracker's
        # claimed-total watermark on stat-less backends). Cumulative —
        # the allocator does not reset its peak per step.
        peak_hbm = None
        try:
            from horovod_tpu import memory

            peak_hbm = memory.tracker().peak_hbm_bytes()
        except Exception:
            pass

        rec.breakdown = {
            "step": rec.index,
            "name": rec.name,
            "auto": rec.auto,
            "t_start": rec.t0_epoch,
            "wall_seconds": wall,
            "peak_hbm_bytes": peak_hbm,
            "phases": phases,
            "comm": {"total_seconds": comm_total,
                     "exposed_seconds": comm_exposed,
                     "bytes": comm_bytes,
                     # fused executor dispatches this step: a bucketed
                     # backward shows one per released bucket, the
                     # unbucketed path at most a handful
                     "dispatches": comm_ops,
                     "hidden_fraction": hidden_fraction,
                     "hidden_fraction_bytes": hidden_fraction_bytes},
            "mfu": mfu,
        }
        self._steps.append(rec.breakdown)
        # Chrome step marker on the profiler's own lane (epoch us, the
        # package-wide trace clock domain) — merged with the runtime
        # timeline and device traces by merge_profile_dir
        self._trace_events.append({
            "ph": "X", "pid": 0, "tid": 0, "ts": rec.t0_epoch * 1e6,
            "dur": wall * 1e6, "name": rec.name,
            "args": {"phases_ms": {k: round(v * 1e3, 3)
                                   for k, v in phases.items()},
                     "comm_hidden_fraction": round(hidden_fraction, 4)}})
        flight_recorder.emit(
            "profiler_step", step=rec.index,
            wall_ms=round(wall * 1e3, 3),
            hidden_fraction=round(hidden_fraction, 4))
        try:
            # goodput ledger: the measured step wall is productive time,
            # the exposed-comm phase is badput. The tracker's own frontier
            # guard dedups against the State.commit step source.
            from horovod_tpu import goodput

            goodput.record_step(wall, exposed_comm=exposed_phase,
                                step=rec.index)
        except Exception:
            pass  # accounting must never fail a step

    # -- introspection ------------------------------------------------------
    def history(self) -> List[dict]:
        """The last N completed step breakdowns, oldest first."""
        return list(self._steps)

    def summary(self) -> dict:
        """Aggregate over the step history: mean wall/phase seconds and
        comm-hidden fractions."""
        steps = list(self._steps)
        if not steps:
            return {"steps": 0, "wall_seconds": 0.0,
                    "step_breakdown": {k: 0.0 for k in PHASES},
                    "comm_hidden_fraction": 0.0,
                    "comm_hidden_fraction_bytes": 0.0, "mfu": None}
        n = len(steps)
        breakdown = {k: sum(s["phases"][k] for s in steps) / n
                     for k in PHASES}
        comm_total = sum(s["comm"]["total_seconds"] for s in steps)
        comm_exposed = sum(s["comm"]["exposed_seconds"] for s in steps)
        comm_bytes = sum(s["comm"]["bytes"] for s in steps)
        hidden_bytes = sum(s["comm"]["bytes"]
                           * s["comm"]["hidden_fraction_bytes"]
                           for s in steps)
        mfus = [s["mfu"] for s in steps if s.get("mfu") is not None]
        return {
            "steps": n,
            "wall_seconds": sum(s["wall_seconds"] for s in steps) / n,
            "step_breakdown": breakdown,
            "comm_hidden_fraction": (
                min(1.0, max(0.0, 1.0 - comm_exposed / comm_total))
                if comm_total > 0 else 0.0),
            "comm_hidden_fraction_bytes": (
                min(1.0, max(0.0, hidden_bytes / comm_bytes))
                if comm_bytes > 0 else 0.0),
            "mfu": (sum(mfus) / len(mfus)) if mfus else None,
        }

    def _debug_state(self) -> dict:
        """Flight-recorder state provider: recent step breakdowns ride in
        every postmortem dump."""
        return {"flops_per_step": self._flops_per_step,
                "peak_flops_per_chip": self._peak_flops,
                "steps": list(self._steps)}

    def profile_state(self) -> dict:
        """Document for the metrics server's ``GET /profile`` endpoint.
        Rate-limited like the failure-dump path: at most one fresh
        snapshot per second, cached in between, so a scrape loop cannot
        contend with the training loop."""
        now = time.monotonic()
        cached = self._profile_state_cache
        if cached is not None and now - cached[0] < 1.0:
            return cached[1]
        state = {"schema": SCHEMA, "rank": self.rank,
                 "launch_rank": self.launch_rank, "enabled": self.enabled,
                 "summary": self.summary(), "steps": self.history()}
        self._profile_state_cache = (now, state)
        return state

    # -- dump / ship --------------------------------------------------------
    def snapshot(self) -> dict:
        # memory plane: the reconciliation sampler's trail rides in the
        # profile dump so the merged Perfetto trace gets a per-rank
        # memory counter track (merge_profile_dir)
        memory_samples = []
        try:
            from horovod_tpu import memory

            memory_samples = memory.tracker().samples()
        except Exception:
            pass
        # tracing plane: the request/collective span ring rides the dump
        # so merge_profile_dir can lay out per-rank request lanes and
        # join one trace_id across ranks with flow arrows
        request_spans = []
        try:
            from horovod_tpu import tracing

            request_spans = tracing.spans()
        except Exception:
            pass
        # comms plane: the per-record busbw sample ring rides the dump so
        # the merged trace gets a per-rank bus-bandwidth counter track
        comms_samples = []
        try:
            from horovod_tpu import comms

            comms_samples = comms.tracker().samples()
        except Exception:
            pass
        # goodput plane: the goodput-fraction trail + incident ledger
        # ride the dump so the merged trace gets a per-rank "goodput
        # fraction" counter track and an incident instant lane
        goodput_samples: list = []
        goodput_incidents: list = []
        try:
            from horovod_tpu import goodput

            goodput_samples = goodput.tracker().samples()
            goodput_incidents = goodput.tracker().incidents()
        except Exception:
            pass
        return {
            "schema": SCHEMA,
            "rank": self.rank,
            "launch_rank": self.launch_rank,
            "pid": os.getpid(),
            "host": socket.gethostname(),
            "wall_time": time.time(),
            "clock_offset_seconds": flight_recorder.recorder().clock_offset(),
            "flops_per_step": self._flops_per_step,
            "peak_flops_per_chip": self._peak_flops,
            "steps": list(self._steps),
            "trace_events": list(self._trace_events),
            "memory_samples": memory_samples,
            "request_spans": request_spans,
            "comms_samples": comms_samples,
            "goodput_samples": goodput_samples,
            "goodput_incidents": goodput_incidents,
            "flight_events": flight_recorder.recorder().events()
            [-_FLIGHT_TRACE_EVENTS:],
        }

    def dump(self, path: Optional[str] = None, ship: bool = True) -> dict:
        """Write ``profile-rank-N.json`` (to ``path`` or the configured
        dir) and ship a copy to the launcher's rendezvous store. Closes an
        open implicit step first so its breakdown is included. Never
        raises — runs from shutdown paths."""
        with self._dump_lock:
            if self._auto_rec is not None:
                self._finish(self._auto_rec)
                self._auto_rec = None
            self._stop_jax_trace()
            snap = self.snapshot()
            payload = json.dumps(snap)
            target = path or self.dir
            if target:
                try:
                    out = target if target.endswith(".json") else \
                        os.path.join(target,
                                     f"{DUMP_PREFIX}{self.launch_rank}.json")
                    parent = os.path.dirname(out)
                    if parent:
                        os.makedirs(parent, exist_ok=True)
                    with open(out, "w") as f:
                        f.write(payload)
                    log.debug("profiler: wrote %s", out)
                except OSError as exc:
                    log.warning("profiler: dump to %r failed: %s",
                                target, exc)
            if ship:
                try:
                    self._ship(payload)
                except Exception as exc:
                    log.debug("profiler: ship failed: %s", exc)
            return snap

    def _ship(self, payload: str) -> None:
        dest = flight_recorder._rendezvous_addr()
        if dest is None:
            return
        from horovod_tpu.run.rendezvous import KVStoreClient

        client = KVStoreClient(dest[0], dest[1], scope=RENDEZVOUS_SCOPE,
                               timeout=5.0)
        client.set("rank.%d" % self.launch_rank, payload)

    def finalize(self) -> None:
        """Shutdown hook (core/basics.py): dump + ship when enabled."""
        if not self.enabled:
            return
        try:
            self.dump()
        except Exception as exc:
            log.debug("profiler: finalize failed: %s", exc)


_profiler = StepProfiler()


def profiler() -> StepProfiler:
    return _profiler


def configure(rank: Optional[int] = None) -> None:
    _profiler.configure(rank=rank)


def enabled() -> bool:
    return _profiler.enabled


def step(name: Optional[str] = None):
    """``with hvd.profiler.step(): ...`` — bracket one training step."""
    return _profiler.step(name)


def annotate(phase: str):
    """``with hvd.profiler.annotate("host"): ...`` — attribute wall time."""
    return _profiler.annotate(phase)


def auto_step() -> None:
    _profiler.auto_step()


def set_flops_per_step(flops: Optional[float],
                       peak_flops_per_chip: Optional[float] = None) -> None:
    _profiler.set_flops_per_step(flops,
                                 peak_flops_per_chip=peak_flops_per_chip)


def history() -> List[dict]:
    return _profiler.history()


def summary() -> dict:
    return _profiler.summary()


def profile_state() -> dict:
    return _profiler.profile_state()


def dump(path: Optional[str] = None, ship: bool = True) -> dict:
    return _profiler.dump(path=path, ship=ship)


def finalize() -> None:
    _profiler.finalize()


# ---------------------------------------------------------------------------
# Launcher side: harvest, merge, report (tpurun --profile-dir)
# ---------------------------------------------------------------------------

def load_dumps(directory: str) -> List[dict]:
    """Read every ``profile-rank-*.json`` in ``directory`` (unreadable
    files are skipped — a killed worker may have cut one short)."""
    dumps = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return dumps
    for name in names:
        if not (name.startswith(DUMP_PREFIX) and name.endswith(".json")):
            continue
        path = os.path.join(directory, name)
        try:
            with open(path) as f:
                dumps.append(json.load(f))
        except (OSError, ValueError) as exc:
            log.warning("profiler: skipping unreadable dump %s: %s",
                        path, exc)
    return dumps


def _flight_trace_events(dump: dict) -> List[dict]:
    """Flight-recorder events as Chrome instants on their own lane (tid 1),
    epoch-us clock — so negotiation/dispatch/membership events interleave
    with step spans in the merged view."""
    out = []
    for ev in dump.get("flight_events", ()):
        t = ev.get("t")
        if not isinstance(t, (int, float)):
            continue
        args = {k: v for k, v in ev.items() if k not in ("t", "kind")}
        out.append({"ph": "i", "pid": 0, "tid": 1, "ts": t * 1e6,
                    "name": str(ev.get("kind", "event")), "s": "t",
                    "args": args or None})
    return out


def _memory_trace_events(dump: dict) -> List[dict]:
    """The memory sampler's trail as a Chrome counter ("C") track —
    claimed vs actual device bytes per reconciliation sweep, rendered by
    Perfetto as an area chart on the rank's lane."""
    out = []
    for row in dump.get("memory_samples", ()):
        try:
            t, claimed, actual = row[0], int(row[1]), int(row[2])
        except (TypeError, ValueError, IndexError):
            continue
        if not isinstance(t, (int, float)):
            continue
        out.append({"ph": "C", "pid": 0, "tid": 0, "ts": t * 1e6,
                    "name": "device memory (bytes)",
                    "args": {"claimed": claimed, "actual": actual}})
    return out


def _comms_trace_events(dump: dict) -> List[dict]:
    """The comms tracker's busbw sample ring as a Chrome counter ("C")
    track — per-lane bus bandwidth over time next to the rank's step
    spans, so a bandwidth sag lines up visually with the step that paid
    for it (docs/comms.md)."""
    out = []
    for row in dump.get("comms_samples", ()):
        try:
            t, busbw, lane = row[0], float(row[1]), str(row[2])
        except (TypeError, ValueError, IndexError):
            continue
        if not isinstance(t, (int, float)):
            continue
        out.append({"ph": "C", "pid": 0, "tid": 0, "ts": t * 1e6,
                    "name": "bus bandwidth (GB/s)",
                    "args": {lane: round(busbw, 4)}})
    return out


def _goodput_trace_events(dump: dict) -> List[dict]:
    """The goodput tracker's fraction trail as a Chrome counter ("C")
    track plus its incident ledger as an instant ("i") lane — a goodput
    sag lines up visually with the incident that caused it
    (docs/goodput.md)."""
    out = []
    for row in dump.get("goodput_samples", ()):
        try:
            t, frac = row[0], float(row[1])
        except (TypeError, ValueError, IndexError):
            continue
        if not isinstance(t, (int, float)):
            continue
        out.append({"ph": "C", "pid": 0, "tid": 0, "ts": t * 1e6,
                    "name": "goodput fraction",
                    "args": {"productive": round(frac, 4)}})
    for inc in dump.get("goodput_incidents", ()):
        if not isinstance(inc, dict):
            continue
        t = inc.get("wall_time")
        if not isinstance(t, (int, float)):
            continue
        out.append({"ph": "i", "pid": 0, "tid": 1, "ts": t * 1e6,
                    "s": "t",
                    "name": "incident: %s" % inc.get("cause", "?"),
                    "args": {k: inc.get(k) for k in
                             ("duration_s", "generation", "culprit_rank",
                              "steps_replayed")}})
    return out


def _device_trace_files(directory: str) -> List[str]:
    """jax.profiler output below the profile dir: TensorBoard's profile
    plugin writes ``*.trace.json.gz`` under a nested run directory."""
    hits = []
    for pat in ("**/*.trace.json.gz", "**/*.trace.json"):
        hits.extend(glob.glob(os.path.join(directory, pat), recursive=True))
    return sorted(set(hits))


def _rank_of_path(path: str) -> Optional[int]:
    base = os.path.basename(path)
    for token in (os.sep.join(path.split(os.sep)[-3:]).split(os.sep)
                  + [base]):
        for prefix in ("timeline-rank-", "jax-rank-"):
            if token.startswith(prefix):
                digits = token[len(prefix):].split(".")[0]
                try:
                    return int(digits)
                except ValueError:
                    continue
    return None


def merge_profile_dir(directory: str,
                      out_path: Optional[str] = None) -> Tuple[str, int]:
    """Build ONE Chrome trace from everything profiling left in
    ``directory``: per-rank step markers + flight events (from the
    profiler dumps), per-rank runtime timelines (``timeline-rank-N.json``,
    written when tpurun launched with ``--profile-dir``), and any
    ``jax.profiler`` device traces below it. Every rank's events are
    shifted by that rank's ``/_time`` clock-offset estimate so two hosts'
    spans line up on the launcher's clock; each source file gets a private
    pid range labeled ``rank N <kind>``. Request spans (tracing.py) get
    their own ``rank N requests`` lane, and one trace_id's spans across
    ALL lanes are joined by Perfetto flow arrows — a request's life is
    one connected line from the frontend's submit through the serving
    replica's prefill/decode to the response. Returns (path, count)."""
    from horovod_tpu import timeline as timeline_mod
    from horovod_tpu import tracing

    dumps = load_dumps(directory)
    offsets: Dict[int, float] = {}
    lanes: List[Tuple[str, List[dict], float]] = []  # (label, events, off_s)
    for d in dumps:
        rank = d.get("launch_rank", d.get("rank", 0))
        offset = d.get("clock_offset_seconds") or 0.0
        offsets[rank] = offset
        events = [e for e in d.get("trace_events", ())
                  if isinstance(e, dict)]
        events += _flight_trace_events(d)
        events += _memory_trace_events(d)
        events += _comms_trace_events(d)
        events += _goodput_trace_events(d)
        if events:
            lanes.append((f"rank {rank} steps", events, offset))
        spans = [s for s in d.get("request_spans", ())
                 if isinstance(s, dict)]
        if spans:
            lanes.append((f"rank {rank} requests",
                          tracing.spans_to_chrome(spans), offset))
    for path in sorted(glob.glob(os.path.join(directory,
                                              "timeline-rank-*.json"))):
        rank = _rank_of_path(path)
        try:
            events = timeline_mod._load_trace_events(path)
        except (OSError, ValueError) as exc:
            log.warning("profiler: skipping unreadable trace %s: %s",
                        path, exc)
            continue
        lanes.append((f"rank {rank} timeline", events,
                      offsets.get(rank, 0.0)))
    for path in _device_trace_files(directory):
        rank = _rank_of_path(path)
        try:
            events = timeline_mod._load_trace_events(path)
        except (OSError, ValueError) as exc:
            log.warning("profiler: skipping unreadable trace %s: %s",
                        path, exc)
            continue
        lanes.append((f"rank {rank} device" if rank is not None
                      else os.path.basename(path), events,
                      offsets.get(rank, 0.0)))

    merged: List[dict] = []
    anchors: List[dict] = []   # corrected-clock request-span coordinates
    pid_base = 0
    for label, events, offset_s in lanes:
        pids = [e.get("pid", 0) for e in events]
        for orig_pid in sorted(set(pids)):
            merged.append({"ph": "M", "pid": orig_pid + pid_base, "ts": 0,
                           "name": "process_labels",
                           "args": {"labels": label}})
        off_us = offset_s * 1e6
        for e in events:
            e = dict(e)
            e["pid"] = e.get("pid", 0) + pid_base
            if isinstance(e.get("ts"), (int, float)) and e.get("ph") != "M":
                e["ts"] = e["ts"] + off_us
            merged.append(e)
            if e.get("ph") == "X" and e.get("cat") == "request":
                trace_id = (e.get("args") or {}).get("trace_id")
                if trace_id:
                    anchors.append({"trace_id": trace_id, "pid": e["pid"],
                                    "tid": e.get("tid", 0), "ts": e["ts"],
                                    "dur": e.get("dur", 0.0)})
        pid_base += max(pids, default=0) + 2
    # flow arrows must be generated AFTER the layout: they bind to their
    # enclosing slices by exact (pid, tid, ts), which only exist once
    # every lane has its final pid range and corrected clock
    merged.extend(tracing.flow_events(anchors))
    merged.sort(key=lambda e: (e.get("ts") or 0))
    out = out_path or os.path.join(directory, MERGED_TRACE)
    with open(out, "w") as f:
        json.dump({"traceEvents": merged}, f)
    return out, len(merged)


def format_step_report(dumps: List[dict]) -> str:
    """Cross-rank step-time report: per-rank mean wall + phase means, and
    a verdict naming the slowest rank and its dominant phase."""
    lines = ["=== step-time report (%d rank%s) ==="
             % (len(dumps), "" if len(dumps) == 1 else "s")]
    slowest: Optional[Tuple[Any, float, dict]] = None
    for d in sorted(dumps, key=lambda d: d.get("launch_rank", 0)):
        rank = d.get("launch_rank", d.get("rank", "?"))
        steps = d.get("steps", ())
        if not steps:
            lines.append(f"rank {rank}: no profiled steps")
            continue
        n = len(steps)
        wall = sum(s["wall_seconds"] for s in steps) / n
        phases = {k: sum(s["phases"].get(k, 0.0) for s in steps) / n
                  for k in PHASES}
        hidden = [s["comm"]["hidden_fraction"] for s in steps
                  if s.get("comm")]
        mfus = [s["mfu"] for s in steps if s.get("mfu") is not None]
        lines.append(
            "rank %s: %d steps, mean %.3f ms/step  "
            "(host %.3f, compute %.3f, exposed_comm %.3f, optimizer %.3f)"
            "  comm_hidden=%.1f%%%s" % (
                rank, n, wall * 1e3, phases["host"] * 1e3,
                phases["compute"] * 1e3, phases["exposed_comm"] * 1e3,
                phases["optimizer"] * 1e3,
                100.0 * (sum(hidden) / len(hidden) if hidden else 0.0),
                ("  mfu=%.3f" % (sum(mfus) / len(mfus))) if mfus else ""))
        if slowest is None or wall > slowest[1]:
            slowest = (rank, wall, phases)
    if slowest is not None:
        rank, wall, phases = slowest
        phase = max(phases, key=lambda k: phases[k])
        lines.append(
            "slowest: rank %s at %.3f ms/step, dominant phase: %s "
            "(%.3f ms, %.1f%% of step)" % (
                rank, wall * 1e3, phase, phases[phase] * 1e3,
                100.0 * phases[phase] / wall if wall else 0.0))
    return "\n".join(lines)
