"""The per-replica serving loop: pull → admit → enqueue → collect → retire.

One :class:`Replica` drives one :class:`~horovod_tpu.serve.kv_cache.
DecodeEngine` and one :class:`~horovod_tpu.serve.batcher.
ContinuousBatcher` on a single thread. The loop each pass:

1. pulls new requests from the shared queue (in-process or KV-backed,
   behind a small transport adapter) into the batcher's waiting line;
2. when admission is due (decode-block boundary, idle replica, or the
   admission deadline — batcher.py has the policy), ENQUEUES the
   admitted prompts' bucketed prefill programs; the first generated
   token falls out of prefill;
3. enqueues ONE fixed-shape decode step over all slots, counts its
   tokens and frees the slots of the requests it ends (a request ends
   by length, so this needs no token value: a retiring request frees
   its slot for the very next admission check, not a batch boundary).
   A step yields one token a row, or, where the model generates by
   blocks, what the row's schedule says its pass unmasks - none (the
   commit) to several (``batcher.BlockRequest``); either way the count
   is known at dispatch;
4. only now COLLECTS: the ids and guard values of the decode step
   enqueued in the pass before, then this pass's first tokens. The
   device always has the step of (3) queued while the host reads,
   appends, completes requests and comes round to (1) again.

The loop runs ONE step ahead of its readback, no further (retirement is
by count; a second step ahead buys nothing once the queue is never
empty). A token's value and the guard's verdict on the step that made
it reach the host one pass late; a :class:`Completion` is delivered only
when every one of its values is here and every step that produced them
passed the guard. Whatever tears the loop down (quarantine, ``stop()``,
:class:`WorkersDownError`, a loop error) first drops the step in flight
unread: greedy decoding regenerates it elsewhere, and the engine's cache
is always the result of the last program enqueued.

The loop is written against enqueue/collect and asks a result two
things: ``on_host`` (collect it at once?) and, round each enqueue for
the ``starved`` flag and the ``dry_enqueues`` count of its
``serve.step``, ``ready()`` (has the device finished it?). The dense engine's ``prefill`` / ``decode`` return
results that are still on the device, so the loop runs ahead; an engine
whose calls block (the paged engine, which re-plans rows between
dispatch and result: serve/paging.py) hands back plain tuples that are
on the host, and the loop collects each at once, which is the order of a
pass before there was a feed on the device.

Reliability wiring (the serve plane rides the existing stack):

* ``fault_inject.maybe_inject`` fires per DECODE step (the serving
  analogue of the training step counter), so the chaos matrix can kill
  a replica mid-generation;
* a PR-10 :class:`~horovod_tpu.integrity.guards.StepGuard` watches the
  per-step max-|logit|; a non-finite value (or an exhausted guard)
  QUARANTINES the replica — it returns every pulled request to the
  queue, stops heartbeating so the dispatcher reassigns, and parks,
  rather than serving garbage (the verdict on step k arrives after step
  k+1 is enqueued: that step's results are dropped unread, and no token
  of step k or later is ever delivered);
* a :class:`~horovod_tpu.exceptions.WorkersDownError` escaping the step
  (a model whose forward uses collectives under elastic) requeues the
  in-flight work the same way before re-raising to the elastic driver.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import List, Optional, Tuple

from horovod_tpu import flight_recorder, goodput, tracing
from horovod_tpu.elastic import fault_inject
from horovod_tpu.exceptions import NumericalError, WorkersDownError
from horovod_tpu.metrics import COUNT_BUCKETS, registry as _metrics
from horovod_tpu.serve.batcher import ActiveRequest, ContinuousBatcher
from horovod_tpu.serve.kv_cache import DecodeEngine
from horovod_tpu.serve.paging import PagePoolExhausted
from horovod_tpu.serve.queue import (Completion, KVQueueReplica, Request,
                                     RequestQueue, HEARTBEAT_SECONDS)
from horovod_tpu.utils import logging as log

_IDLE_SLEEP_SECONDS = 0.002

_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                    0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

_REQUESTS = _metrics().counter(
    "horovod_serve_requests_total",
    "Serving requests, by outcome (completed/requeued).",
    labelnames=("outcome",))
_TOKENS = _metrics().counter(
    "horovod_serve_tokens_total",
    "Tokens processed by the serving plane, by kind (prefill/decode).",
    labelnames=("kind",))
_OCCUPANCY = _metrics().gauge(
    "horovod_serve_batch_occupancy",
    "Active requests in the continuous batch, per replica.",
    labelnames=("replica",))
_QUEUE_DEPTH = _metrics().gauge(
    "horovod_serve_queue_depth",
    "Requests waiting for admission (queue + batcher), per replica.",
    labelnames=("replica",))
_OCCUPANCY_HIST = _metrics().histogram(
    "horovod_serve_batch_occupancy_steps",
    "Batch occupancy observed at each decode step.",
    buckets=COUNT_BUCKETS)
_LATENCY = _metrics().histogram(
    "horovod_serve_latency_seconds",
    "Request latency by phase: ttft (submit to first token) and total.",
    buckets=_LATENCY_BUCKETS, labelnames=("phase",))
_QUARANTINED = _metrics().counter(
    "horovod_serve_quarantined_total",
    "Replicas quarantined by the serving integrity guard.")


class _LocalTransport:
    """In-process adapter over the shared :class:`RequestQueue`."""

    def __init__(self, queue: RequestQueue, rank: int):
        self._queue = queue
        self._rank = rank

    def pull(self, max_n):
        return self._queue.pull(self._rank, max_n)

    def complete(self, completion):
        self._queue.complete(completion)

    def requeue_all(self) -> int:
        return self._queue.requeue_worker(self._rank)

    def heartbeat(self):
        pass

    def stopped(self) -> bool:
        return False

    def depth(self) -> int:
        return self._queue.depth()


class _KVTransport:
    """Cross-process adapter over the rendezvous-KV queue. Requeueing is
    the DISPATCHER's job in this transport (it owns assignment): on
    quarantine the replica just goes silent — its heartbeat lapses and
    the frontend redistributes everything unanswered.

    Heartbeats come from a dedicated daemon thread, NOT the serve loop:
    a blocking step longer than STALE_SECONDS (first-request XLA
    prefill/decode compiles routinely take many seconds) must not make
    the frontend declare a healthy replica dead and re-dispatch its
    pending work. The thread only writes one KV key; ``silent`` and the
    stop event are its whole shared state (single-word flags, read-only
    here, set by the replica thread).

    The serve loop spins at millisecond cadence; every KV op is an HTTP
    round trip, so the inbox poll and the stop-key check are throttled —
    an idle replica costs the rendezvous server ~60 requests/s, not
    ~1500."""

    _POLL_SECONDS = 0.02
    _STOP_CHECK_SECONDS = 0.25

    def __init__(self, kv: KVQueueReplica):
        self._kv = kv
        self._last_poll = 0.0
        self._last_stop_check = 0.0
        self._stopped = False
        self.silent = False          # set by replica thread on quarantine
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True,
            name="serve-heartbeat")
        self._hb_thread.start()

    def _heartbeat_loop(self) -> None:
        while True:
            if not self.silent:
                try:
                    self._kv.heartbeat()
                    tracing.note_replica_heartbeat()
                except Exception as exc:
                    log.warning("serve: heartbeat failed: %s", exc)
            if self._hb_stop.wait(HEARTBEAT_SECONDS):
                return

    def pull(self, max_n):
        now = time.monotonic()
        if now - self._last_poll < self._POLL_SECONDS:
            return []
        self._last_poll = now
        return self._kv.poll(max_n)

    def complete(self, completion):
        self._kv.complete(completion)

    def requeue_all(self) -> int:
        self.silent = True
        return 0

    def heartbeat(self):
        pass   # the dedicated thread owns liveness

    def shutdown(self) -> None:
        """Stop heartbeating for good (replica drained or crashed) so
        the frontend does not keep dispatching to a gone replica."""
        self._hb_stop.set()
        self._hb_thread.join(timeout=2 * HEARTBEAT_SECONDS)

    def stopped(self) -> bool:
        if self._stopped:
            return True
        now = time.monotonic()
        if now - self._last_stop_check < self._STOP_CHECK_SECONDS:
            return False
        self._last_stop_check = now
        self._stopped = self._kv.stopped()
        return self._stopped

    def depth(self) -> int:
        return 0


class _Collected:
    """What a blocking engine call returned, in the shape of a pending
    result (serve/kv_cache.py ``Pending``): it is on the host already,
    so the loop collects it at once."""

    on_host = True

    def __init__(self, result):
        self._result = result

    def ready(self) -> bool:
        return True

    def collect(self):
        return self._result


def _pending(result):
    """An engine call's result as something to ``collect()``: the dense
    engine's is pending, any other engine's is the plain tuple."""
    return result if hasattr(result, "collect") else _Collected(result)


@dataclasses.dataclass
class _DecodeStep:
    """One decode step between its dispatch and its collection."""

    pending: object              # collect() -> (ids, max |logit|s) of rows
    rows: List[ActiveRequest]    # by slot; some may have retired since
    dispatched_s: float          # monotonic


class Replica:
    """One serving replica; ``run()`` is the loop, single thread."""

    def __init__(self, engine: DecodeEngine, transport, policy, rank: int = 0,
                 name: Optional[str] = None, guard=None):
        self.engine = engine
        self.transport = transport
        self.policy = policy
        self.rank = rank
        self.name = name or f"serve-r{rank}"
        # paged engines (serve/paging.py) switch admission from dense
        # slot rows to free-page accounting: the batcher commits pool
        # pages, discounted by the candidate's current prefix hits
        self.paged = bool(getattr(engine, "paged", False))
        self.batcher = ContinuousBatcher(
            num_slots=engine.num_slots,
            max_batch_tokens=policy.max_batch_tokens,
            admission_ms=policy.admission_ms,
            decode_block=policy.decode_block,
            max_seq=engine.max_seq,
            page_tokens=engine.page_tokens if self.paged else None,
            pool_pages=engine.pool.allocatable if self.paged else None,
            prefix_probe=engine.probe_prefix if self.paged else None,
            block_len=getattr(engine, "block_len", 1),
            unmask=getattr(engine, "unmask", 1))
        # the model generates by blocks: a step is a pass that yields
        # from no token to several a row (serve/kv_cache.py, Blocks)
        self._blocks = self.batcher.block_len > 1
        self.guard = guard
        self.quarantined = False
        self.completed = 0
        self.decode_iterations = 0
        self.occupancy_sum = 0
        self.page_used_sum = 0   # pool pages in use, summed per step
        # of the open serve.step: requests admitted and pulled in it, and
        # the passes before it that pulled, admitted and decoded nothing
        self._admitted = self._pulled = self._idle_passes = 0
        # had the device run dry when this pass came to its first enqueue
        # (None: it has enqueued nothing), how many of its enqueues
        # returned to find the program before them finished, and the
        # passes whose first enqueue found it dry
        self._starved: Optional[int] = None
        self._dry_enqueues = 0
        self.starved_steps = 0
        # enqueued, not collected: the decode step of the pass before,
        # this pass's prefills (with their request and dispatch time),
        # and the requests retired by count whose values are among them
        self._ahead: Optional[_DecodeStep] = None
        self._first_tokens: List[Tuple[ActiveRequest, object, float]] = []
        self._unread: List[ActiveRequest] = []
        self._accounted_s = 0.0   # goodput: serve time told up to here
        self._stop = threading.Event()

    # -- lifecycle ---------------------------------------------------------
    def stop(self) -> None:
        self._stop.set()

    def _finish(self, active, now: float) -> None:
        req = active.request
        epoch_now = time.time()
        # one decode span per request, first token to last (the
        # serve.step spans carry which iterations it lived through)
        decode_dur = max(now - active.first_token_s, 0.0)
        answer = active.answer()
        decode_steps = len(answer["tokens"]) - 1
        tracing.record(
            "request.decode", epoch_now - decode_dur, decode_dur,
            trace_id=req.trace_id, uid=req.uid, slot=active.slot,
            tokens=len(answer["tokens"]),
            blocks=-(-decode_steps // self.policy.decode_block))
        # "cache_limit" (not "length") when the KV cache, not the
        # request, bounded the generation — callers must be able to
        # tell a fulfilled budget from a truncated one
        completion = Completion(
            uid=req.uid, **answer,
            prompt_len=active.prompt_len, rank=self.rank,
            ttft_s=active.first_token_s - req.submitted_s,
            latency_s=now - req.submitted_s,
            finish="cache_limit" if active.capped else "length",
            trace_id=req.trace_id, requeues=req.requeues)
        self.transport.complete(completion)
        self.completed += 1
        _REQUESTS.labels(outcome="completed").inc()
        _LATENCY.labels(phase="total").observe(completion.latency_s)
        serve_dur = max(now - active.admitted_s, 0.0)
        tracing.record(
            "request.serve", epoch_now - serve_dur, serve_dur,
            trace_id=req.trace_id, uid=req.uid, slot=active.slot,
            finish=completion.finish, requeues=req.requeues,
            tokens=len(completion.tokens),
            ttft_ms=round(completion.ttft_s * 1000.0, 3),
            latency_ms=round(completion.latency_s * 1000.0, 3))
        tracing.slo().record_request(
            completion.ttft_s, completion.latency_s, ok=True,
            trace_id=req.trace_id, rank=self.rank, requeues=req.requeues,
            phases={"queue_wait": active.queue_wait_s,
                    "prefill": active.prefill_s,
                    "decode": decode_dur})

    def _reject(self, req, reason: str) -> None:
        """Complete an unservable request (empty, or prompt longer than
        the KV cache) with ``finish="rejected"`` instead of crashing the
        loop on it or stranding its caller in ``result()``."""
        self.transport.complete(Completion(
            uid=req.uid, tokens=[], prompt_len=len(req.prompt),
            rank=self.rank, finish="rejected",
            trace_id=req.trace_id, requeues=req.requeues))
        _REQUESTS.labels(outcome="rejected").inc()
        # an unserved request is an availability bad event — it has no
        # meaningful TTFT, so the latency objectives are not scored
        tracing.slo().record_request(
            0.0, 0.0, ok=False, trace_id=req.trace_id, rank=self.rank,
            requeues=req.requeues)
        log.warning("serve: replica %s rejected request %s (%s)",
                    self.name, req.uid, reason)

    def _quarantine(self, reason: str) -> None:
        """Integrity trip: never serve garbage. Active + waiting work
        goes back to the queue (in-process) or to the dispatcher's
        death-detection (KV: the heartbeat just stops); the replica
        parks until the fleet is stopped."""
        self.quarantined = True
        _QUARANTINED.inc()
        victims = self._evict()
        evicted = len(victims)
        requeued = self.transport.requeue_all()
        _REQUESTS.labels(outcome="requeued").inc(max(evicted, requeued))
        flight_recorder.emit("serve_quarantine", replica=self.name,
                             rank=self.rank, reason=reason,
                             evicted=evicted,
                             trace_ids=[r.trace_id for r in victims])
        log.error("serve: replica %s QUARANTINED (%s); %d request(s) "
                  "returned for redistribution", self.name, reason,
                  max(evicted, requeued))

    def _drop_in_flight(self) -> None:
        """Forget what is enqueued and not collected, unread. The
        engine's cache and feed are the last program's results whatever
        the host has read, so the engine stays usable."""
        self._ahead = None
        self._first_tokens.clear()

    def _evict(self) -> List[Request]:
        """Drop the step in flight and give up every request this
        replica holds - active, waiting, and retired with values still
        unread - for requeueing; nothing of them was delivered."""
        self._drop_in_flight()
        victims = self.batcher.evict_all()
        victims += [a.request for a in self._unread]
        self._unread.clear()
        victims += self.batcher.drain_waiting()
        if self.paged:
            # a dead replica must not pin pool pages: every request-held
            # page goes back (the chaos cell pins request_held == 0)
            self.engine.release_all()
        return victims

    def _preempt_for_pages(self, exclude_slot=None) -> bool:
        """Page-pool exhaustion (paged engines): bounce the newest-
        admitted request back to the queue FRONT and reclaim its pages.
        Returns False when there is no other victim to take."""
        victim = self.batcher.preempt_newest(exclude_slot=exclude_slot)
        if victim is None:
            return False
        self.engine.release_slot(victim.slot)
        self.engine.note_preemption()
        _REQUESTS.labels(outcome="preempted").inc()
        # goodput ledger: the victim's decoded-so-far tokens are work the
        # preemption threw away — re-attributed from productive to
        # serve_preempted badput at the EWMA per-token decode cost
        goodput.note_serve_preempted(victim.received)
        flight_recorder.emit(
            "serve_preempt", replica=self.name, rank=self.rank,
            uid=victim.request.uid, slot=victim.slot,
            trace_id=victim.request.trace_id,
            generated=victim.received,
            requeues=victim.request.requeues)
        log.warning("serve: replica %s preempted request %s (pool "
                    "exhausted); requeued at front", self.name,
                    victim.request.uid)
        return True

    def _guard_ok(self, max_abs: float) -> bool:
        """Non-finite logits always quarantine; the spike guard's EWMA
        feeds the same decision once its skip budget is spent."""
        if not math.isfinite(max_abs):
            return False
        if self.guard is not None:
            try:
                self.guard.observe(max_abs)
            except NumericalError:
                return False
        return True

    # -- the loop ----------------------------------------------------------
    def run(self) -> None:
        flight_recorder.emit("serve_replica_start", replica=self.name,
                             rank=self.rank, slots=self.engine.num_slots)
        # a running loop IS the liveness signal for in-process serving
        # (the KV transport's heartbeat thread also notes it) — flips
        # the /healthz readiness gate
        tracing.note_replica_heartbeat()
        while not self._stop.is_set():
            self.transport.heartbeat()
            if self.transport.stopped():
                break
            if self.quarantined:
                time.sleep(0.05)
                continue
            try:
                self._iterate()
            except WorkersDownError:
                # elastic membership change mid-step: nothing is lost —
                # the pulled work returns to the queue before the
                # elastic driver re-forms us
                victims = self._evict()
                requeued = self.transport.requeue_all()
                requeued += len(victims)
                flight_recorder.emit(
                    "serve_requeue", replica=self.name, rank=self.rank,
                    requeued=requeued,
                    trace_ids=[r.trace_id for r in victims])
                raise
            except Exception as exc:
                # anything else must not silently kill the loop thread
                # and strand its in-flight callers — quarantine instead
                # (which requeues active + waiting work for the other
                # replicas / the dispatcher first)
                log.error("serve: replica %s loop error: %r",
                          self.name, exc)
                self._quarantine(f"loop error: {exc!r}")
        self._drop_in_flight()
        flight_recorder.emit("serve_replica_stop", replica=self.name,
                             rank=self.rank, completed=self.completed)

    def _iterate(self) -> None:
        """One pass of the loop, as one ``serve.step`` span. A pass that
        found no rows and slept is recorded with ``decoded=0``; of a run
        of passes that pulled, admitted and decoded nothing only the
        first is (an idle replica spins every 2 ms and would otherwise
        wipe the ring in seconds). ``starved``: at the pass's first
        enqueue everything enqueued before had finished on the device,
        which then had nothing to run until this pass's program arrived
        (a blocking engine's passes all read 1); ``dry_enqueues``: how
        many of the pass's enqueues returned to find the program before
        them already finished (the device ran dry while the host was
        still dispatching the next: a gap inside the pass)."""
        with tracing.span("serve.step") as step:
            self._admitted = self._pulled = 0
            self._starved, self._dry_enqueues = None, 0
            decoded = self._step()
            if decoded or self._admitted or self._pulled:
                self._idle_passes = 0
            else:
                self._idle_passes += 1
                if self._idle_passes > 1:
                    step.discard()
            step.set(step=self.decode_iterations, decoded=decoded,
                     occupancy=decoded or self.batcher.occupancy(),
                     waiting=self.batcher.waiting(),
                     admitted=self._admitted)
            if self._starved is not None:
                step.set(starved=self._starved,
                         dry_enqueues=self._dry_enqueues)

    def _pull(self, now: float) -> None:
        free = self.engine.num_slots - self.batcher.occupancy()
        if free <= 0 and self.batcher.waiting() != 0:
            return
        with tracing.span("serve.pull") as pulled:
            reqs = self.transport.pull(max(free, 1))
            for req in reqs:
                # unservable prompts answer immediately — an oversized
                # prompt must never reach prefill (where it would blow
                # up the padded copy) or circulate in requeue forever
                if not req.prompt:
                    self._reject(req, "empty prompt")
                elif len(req.prompt) > self.engine.max_seq:
                    self._reject(
                        req, f"prompt length {len(req.prompt)} > "
                             f"max_seq {self.engine.max_seq}")
                else:
                    self.batcher.offer(req, now)
            self._pulled = len(reqs)
            pulled.set(n=len(reqs))
            if not reqs and self._idle_passes:
                pulled.discard()

    def _before_enqueue(self):
        """The newest program the loop has in flight (this pass's last
        prefill, else the decode step of the pass before, if not
        collected yet; ``None``: nothing). Before the pass's first
        enqueue it decides ``starved``: had that program finished?"""
        newest = self._first_tokens[-1][1] if self._first_tokens \
            else self._ahead and self._ahead.pending
        if self._starved is None:
            self._starved = int(newest is None or newest.ready())
            self.starved_steps += self._starved
        return newest

    def _after_enqueue(self, before) -> None:
        """The enqueue has returned: if the program ``before`` it had
        finished by now, the device ran dry before this one reached it
        (a dispatch takes the host a millisecond or more, and the gap
        opens while it is under way: a look before it cannot see it)."""
        self._dry_enqueues += int(before is None or before.ready())

    def _enqueue_prefill(self, active: ActiveRequest):
        """The slot's prefill as something to ``collect()``; ``None``
        when the page pool could not take it and the admission bounced
        back to the queue."""
        before = self._before_enqueue()
        while True:
            try:
                pending = _pending(self.engine.prefill(
                    active.slot, active.request.prompt))
                self._after_enqueue(before)
                return pending
            except PagePoolExhausted:
                # prefill rolled its partial allocations back; preempt
                # the newest OTHER request and retry. With nothing left
                # to preempt, the admission itself bounces back to the
                # queue front (its prefix-hit discount was optimistic)
                if not self._preempt_for_pages(exclude_slot=active.slot):
                    self.batcher.preempt_slot(active.slot)
                    self.engine.note_preemption()
                    _REQUESTS.labels(outcome="preempted").inc()
                    return None

    def _enqueue_decode(self, rows: List[ActiveRequest]):
        # a row's last token is on the host unless the program that makes
        # it is still in flight, and the engine that left it in flight
        # has it in its feed
        before = self._before_enqueue()
        in_flight = self._ahead is not None or bool(self._first_tokens)
        if self._blocks:     # a row's block lives in the engine's feed
            args = None, [a.position for a in rows], \
                [a.next_unmask() for a in rows]
        else:
            args = (None if in_flight else [a.generated[-1] for a in rows],
                    [a.position for a in rows])
        pending = _pending(self.engine.decode([a.slot for a in rows], *args))
        self._after_enqueue(before)
        return pending

    def _admit(self, now: float) -> bool:
        """Enqueue the prefills of what the batcher admits. False when a
        first token read here (a blocking engine's) tripped the
        integrity guard (the replica is quarantined)."""
        # submitted_s and admitted_s are LOCAL monotonic stamps; this maps
        # them onto the epoch trace clock, once for the whole batch
        to_epoch = time.time() - time.monotonic()
        for active in self.batcher.admit(now):
            req = active.request
            # queue-wait span: submitted -> admitted. A request admitted
            # with others then waits its turn among their prefills: the
            # gap between this span's end and its request.prefill
            active.queue_wait_s = max(
                active.admitted_s - req.submitted_s, 0.0)
            tracing.record(
                "request.queue_wait",
                active.admitted_s - active.queue_wait_s + to_epoch,
                active.queue_wait_s, trace_id=req.trace_id,
                uid=req.uid, requeues=req.requeues)
            p0 = time.monotonic()
            pending = self._enqueue_prefill(active)
            if pending is None:
                tracing.record(
                    "request.prefill", p0 + to_epoch, time.monotonic() - p0,
                    trace_id=req.trace_id, uid=req.uid, slot=active.slot,
                    prompt_len=active.prompt_len, preempted=True)
                continue
            active.enqueued = active.from_prefill
            self._admitted += 1
            self._first_tokens.append((active, pending, p0))
            if pending.on_host and not self._collect_first_tokens():
                return False
        self._retire()                  # max_new_tokens == 1
        self._deliver(time.monotonic())
        return True

    def _retire(self) -> None:
        """Free the slots of the requests whose last token is enqueued.
        Their values may still be on the device: they wait in
        ``_unread`` for :meth:`_deliver`."""
        for done in self.batcher.retire_done():
            if self.paged:
                self.engine.release_slot(done.slot)
            self._unread.append(done)

    def _deliver(self, now: float) -> int:
        """Complete the retired requests whose every token value is on
        the host (each appended after its step passed the guard)."""
        waiting, delivered = [], 0
        for retired in self._unread:
            if retired.received < retired.target:
                waiting.append(retired)
            else:
                self._finish(retired, now)
                delivered += 1
        self._unread = waiting
        return delivered

    def _productive(self, since: float, tokens: int = 0) -> None:
        """Tell the goodput ledger of serve time it has not seen: from
        ``since`` (a program's dispatch) or the last collection,
        whichever is later, to now. Programs overlap; wall time is
        counted once."""
        now = time.monotonic()
        goodput.record_serve_step(now - max(since, self._accounted_s),
                                  tokens=tokens)
        self._accounted_s = now

    def _collect_first_tokens(self) -> bool:
        """Read the enqueued prefills' first tokens, oldest first. False
        when one tripped the integrity guard (the replica is
        quarantined)."""
        to_epoch = time.time() - time.monotonic()
        while self._first_tokens:
            active, pending, p0 = self._first_tokens.pop(0)
            token, max_abs = pending.collect()
            if not self._guard_ok(max_abs):
                self._quarantine("non-finite prefill logits")
                return False
            req = active.request
            collected_s = time.monotonic()
            active.prefill_s = collected_s - p0
            # dispatch to first token on the host (after the fact: other
            # prefills and a decode step are enqueued in between)
            tracing.record(
                "request.prefill", p0 + to_epoch, active.prefill_s,
                trace_id=req.trace_id, uid=req.uid, slot=active.slot,
                prompt_len=active.prompt_len)
            # prefill is productive serve time too (tokens=0: the
            # preemption exchange rate stays a pure decode cost)
            self._productive(p0)
            _TOKENS.labels(kind="prefill").inc(active.prompt_len)
            if token is not None:    # None: the first pass that unmasks
                active.generated.append(token)
                self._first_token(active, collected_s)
        return True

    def _first_token(self, active: ActiveRequest, now: float) -> None:
        active.first_token_s = now
        _LATENCY.labels(phase="ttft").observe(
            now - active.request.submitted_s)

    def _collect_decode(self, step: _DecodeStep) -> bool:
        """Read a decode step's ids, put it before the guard and append
        the values to its rows. False when the guard tripped (the
        replica is quarantined, nothing of the step is kept)."""
        ids, max_abs = step.pending.collect()
        # no short-circuit: the guard's EWMA/skip-budget state must
        # see EVERY slot's observation, not a prefix that stops at
        # the first failing slot
        verdicts = [self._guard_ok(m) for m in max_abs]
        if not all(verdicts):
            self._quarantine("non-finite decode logits")
            return False
        # a step yields one token a row, or, of a model that generates by
        # blocks, from none to several; such a request's first token is
        # the first pass that unmasks one
        occupancy, tokens = len(step.rows), 0
        for active, value in zip(step.rows, ids):
            took = active.take(value)
            tokens += took
            if took and not active.first_token_s:
                self._first_token(active, time.monotonic())
        self.occupancy_sum += occupancy
        if self.paged:
            self.page_used_sum += self.engine.pool.used_count()
        _TOKENS.labels(kind="decode").inc(tokens)
        _OCCUPANCY.labels(replica=self.name).set(occupancy)
        _OCCUPANCY_HIST.observe(occupancy)
        # goodput ledger: the tokens a step delivers (one per occupied
        # slot unless the model generates by blocks) are the serve
        # plane's productive unit; the step wall also refreshes the EWMA
        # per-token cost that prices preempted work
        self._productive(step.dispatched_s, tokens=tokens)
        return True

    def _prepare_pages(self, rows: List[ActiveRequest]
                       ) -> List[ActiveRequest]:
        """Paged engines: grow tables across block boundaries / COW
        shared pages BEFORE the step; exhaustion preempts newest-
        admitted until the survivors fit (admission guarantees a sole
        request always does). The rows that are left."""
        while rows:
            try:
                self.engine.prepare_step([a.slot for a in rows],
                                         [a.position for a in rows])
                break
            except PagePoolExhausted:
                if not self._preempt_for_pages():
                    raise   # nothing left to shed: quarantine path
                rows = self.batcher.batch_rows()
        return rows

    def _step(self) -> int:
        """pull -> admit (enqueue prefills) -> enqueue one decode step ->
        collect the step before it and this pass's first tokens ->
        retire. Returns the rows enqueued (0: nothing to decode, or
        quarantined)."""
        now = time.monotonic()
        self._pull(now)
        _QUEUE_DEPTH.labels(replica=self.name).set(
            self.batcher.waiting() + self.transport.depth())

        if self.batcher.admission_due(now):
            with tracing.span("serve.admit") as admit:
                ok = self._admit(now)
                admit.set(n=self._admitted)
            if not ok:
                return 0

        rows = self.batcher.batch_rows()
        if rows and self.paged:
            rows = self._prepare_pages(rows)
            if not rows:
                _OCCUPANCY.labels(replica=self.name).set(0)
                return 0
        if not rows and self._ahead is None and not self._first_tokens:
            _OCCUPANCY.labels(replica=self.name).set(0)
            time.sleep(_IDLE_SLEEP_SECONDS)
            # goodput ledger: an empty loop iteration is queue-idle badput
            goodput.record_span("serve_queue_idle", _IDLE_SLEEP_SECONDS)
            return 0

        step = None
        if rows:
            # the serving step counter: chaos kills aim at decode step N
            self.decode_iterations += 1
            fault_inject.maybe_inject(self.decode_iterations)
            dispatched_s = time.monotonic()
            step = _DecodeStep(self._enqueue_decode(rows), rows,
                               dispatched_s)
            for active in rows:      # counted at dispatch: by length alone
                active.dispatched()
            self.batcher.note_step()
            self._retire()
        # only now read: the device has this pass's programs queued while
        # the host is blocked on, and then works through, the ones before
        previous, self._ahead = self._ahead, step
        with tracing.span("serve.retire") as retire:
            ok = (previous is None or self._collect_decode(previous)) \
                and self._collect_first_tokens()
            if ok and step is not None and step.pending.on_host:
                self._ahead = None
                ok = self._collect_decode(step)
            if not ok:
                return 0
            retire.set(n=self._deliver(time.monotonic()))
        return len(rows)

    # -- introspection -----------------------------------------------------
    def stats(self) -> dict:
        steps = max(self.engine.decode_steps, 1)
        out = {"name": self.name, "rank": self.rank,
               "quarantined": self.quarantined,
               "completed": self.completed,
               "active": self.batcher.occupancy(),
               "waiting": self.batcher.waiting(),
               "decode_steps": self.engine.decode_steps,
               "avg_occupancy": round(self.occupancy_sum / steps, 3),
               # decode steps whose successor was enqueued before they
               # were collected, over decode steps: ~1.0 in a steady
               # loop, 0 where the engine's calls block
               "lookahead_share": round(
                   getattr(self.engine, "decodes_ahead", 0) / steps, 3),
               # passes that came to enqueue with nothing left running
               # on the device (serve.step's ``starved``): a replica
               # where this grows with decode_steps is host-bound
               "starved_steps": self.starved_steps,
               # memory plane: resident KV bytes + the slot-occupancy-
               # weighted share of the cache that did useful work
               "kv_cache_bytes": self.engine.cache_bytes(),
               "kv_utilization": round(
                   self.occupancy_sum
                   / (steps * max(self.engine.num_slots, 1)), 3),
               "engine": self.engine.stats()}
        if self.paged:
            # pool view for /serve and hvd_top's pages row: live pool
            # stats plus the per-decode-step average occupancy
            out["pages"] = self.engine.page_stats()
            out["page_utilization"] = round(
                self.page_used_sum
                / (steps * max(self.engine.pool.allocatable, 1)), 3)
            out["prefix_hit_rate"] = self.engine.prefix_hit_rate()
            out["preemptions"] = self.engine.preemptions
        return out


def run_kv_replica(model, params, policy, rank: int, addr: str, port: int,
                   guard=None) -> Replica:
    """Blocking entrypoint for a cross-process replica (``tpurun
    --serve`` workers, the chaos matrix): serve from the rendezvous KV
    queue until the frontend publishes the stop key."""
    from horovod_tpu.run.rendezvous import KVStoreClient

    client = KVStoreClient(addr, port, scope="serve", timeout=10.0)
    if getattr(policy, "paged", False):
        from horovod_tpu.serve.paging import PagedDecodeEngine

        engine = PagedDecodeEngine(
            model, params, num_slots=policy.slots, name=f"r{rank}",
            page_tokens=policy.page_tokens, pool_pages=policy.page_pool,
            prefix_entries=policy.prefix_cache)
    else:
        engine = DecodeEngine(model, params, num_slots=policy.slots,
                              name=f"r{rank}")
    # the transport's heartbeat thread starts beating here, BEFORE the
    # first (slow, compiling) prefill can run — registration is not
    # gated on the serve loop being responsive
    transport = _KVTransport(KVQueueReplica(client, rank))
    replica = Replica(engine, transport, policy, rank=rank, guard=guard)
    try:
        replica.run()
    finally:
        # stop advertising liveness once we are no longer serving
        transport.shutdown()
    return replica
