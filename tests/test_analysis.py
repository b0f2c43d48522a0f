"""hvd-analyze: static concurrency/collective analysis + runtime witness.

Unit-tests each analyzer pass on synthetic fixtures (known-bad lock
inversion, rank-conditional collective, unguarded mutation, clean file),
the baseline round-trip, the CLI contract, the runtime witness, and —
the CI teeth — that the repo itself analyzes clean against the
checked-in baseline (tier-1 enforced, same pattern as the env-knob
check)."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "hvd_analyze.py")

from horovod_tpu.analysis import baseline, divergence, lockgraph, witness  # noqa: E402
from horovod_tpu.analysis.report import Finding  # noqa: E402


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_text(text)
    return str(p)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# lock-order graph


def test_lock_order_inversion_cycle_detected(tmp_path):
    path = _write(tmp_path, "inv.py", """
import threading

class S:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def two(self):
        with self._b:
            with self._a:
                pass
""")
    res = lockgraph.analyze_paths([path])
    assert "lock-order-cycle" in _rules(res.findings)
    assert ("S._a", "S._b") in res.edges and ("S._b", "S._a") in res.edges


def test_consistent_order_is_clean(tmp_path):
    path = _write(tmp_path, "ok.py", """
import threading

class S:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def two(self):
        with self._a:
            with self._b:
                pass
""")
    res = lockgraph.analyze_paths([path])
    assert res.findings == []
    assert res.edges == [("S._a", "S._b")]


def test_blocking_call_under_lock(tmp_path):
    path = _write(tmp_path, "blk.py", """
import threading, time

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._q = make_queue()

    def bad_get(self):
        with self._lock:
            return self._q.get()

    def ok_get(self):
        with self._lock:
            return self._q.get(timeout=1.0)

    def bad_sleep(self):
        with self._lock:
            time.sleep(1.0)

    def ok_outside(self):
        time.sleep(1.0)
        return self._q.get()
""")
    res = lockgraph.analyze_paths([path])
    blocked = [f for f in res.findings if f.rule == "blocking-under-lock"]
    assert {f.symbol for f in blocked} == {"S.bad_get", "S.bad_sleep"}


def test_blocking_propagates_interprocedurally(tmp_path):
    path = _write(tmp_path, "inter.py", """
import threading, time

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def _helper(self):
        time.sleep(2.0)

    def caller(self):
        with self._lock:
            self._helper()
""")
    res = lockgraph.analyze_paths([path])
    blocked = [f for f in res.findings if f.rule == "blocking-under-lock"]
    assert len(blocked) == 1 and blocked[0].symbol == "S.caller"
    assert "_helper" in blocked[0].message


def test_make_lock_names_become_ids(tmp_path):
    path = _write(tmp_path, "named.py", """
from horovod_tpu.analysis.witness import make_lock

class S:
    def __init__(self):
        self._lock = make_lock("Custom.name")

    def go(self):
        with self._lock:
            sock.recv(4)
""")
    res = lockgraph.analyze_paths([path])
    assert "Custom.name" in res.locks
    blocked = [f for f in res.findings if f.rule == "blocking-under-lock"]
    assert blocked and "Custom.name" in blocked[0].message


def test_guarded_by_mutation_outside_lock(tmp_path):
    path = _write(tmp_path, "guard.py", """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._table = {}  # guarded-by: _lock
        self._count = 0  # guarded-by: _lock

    def good(self, k, v):
        with self._lock:
            self._table[k] = v
            self._count += 1

    def bad(self, k, v):
        self._table[k] = v

    def also_bad(self):
        self._count += 1

    def mutator_call_bad(self):
        self._table.clear()

    def read_ok(self, k):
        return self._table.get(k)
""")
    res = lockgraph.analyze_paths([path])
    bad = [f for f in res.findings if f.rule == "unguarded-mutation"]
    assert {f.symbol for f in bad} == {"S.bad", "S.also_bad", "S.mutator_call_bad"}


def test_holds_lock_annotation_assumed(tmp_path):
    path = _write(tmp_path, "holds.py", """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0  # guarded-by: _lock

    def _bump_locked(self):  # holds-lock: _lock
        self._n += 1

    def outer(self):
        with self._lock:
            self._bump_locked()
""")
    res = lockgraph.analyze_paths([path])
    assert res.findings == []


def test_clean_file_zero_findings(tmp_path):
    path = _write(tmp_path, "clean.py", """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []  # guarded-by: _lock

    def add(self, x):
        with self._lock:
            self._items.append(x)

    def snapshot(self):
        with self._lock:
            return list(self._items)
""")
    res = lockgraph.analyze_paths([path])
    assert res.findings == []


# ---------------------------------------------------------------------------
# divergence lint


def test_rank_conditional_collective_flagged(tmp_path):
    path = _write(tmp_path, "rc.py", """
def step(st, x):
    if st.rank == 0:
        x = allreduce(x, name="only-on-zero")
    return x
""")
    fs = divergence.analyze_paths([path])
    assert _rules(fs) == ["rank-conditional-collective"]


def test_symmetric_branches_not_flagged(tmp_path):
    path = _write(tmp_path, "sym.py", """
def fan(st, blob):
    if st.rank == 0:
        return bcast(blob)
    else:
        return bcast(None)

def fan_early_return(st, blob):
    if st.rank == 0:
        return bcast(blob)
    return bcast(None)
""")
    assert divergence.analyze_paths([path]) == []


def test_rank_early_exit_then_collective_flagged(tmp_path):
    path = _write(tmp_path, "exit.py", """
def save(st, x):
    if st.rank != 0:
        return None
    return broadcast(x, 0)
""")
    fs = divergence.analyze_paths([path])
    assert _rules(fs) == ["rank-conditional-collective"]
    assert "early exit" in fs[0].message


def test_size_conditional_collective_flagged(tmp_path):
    path = _write(tmp_path, "sz.py", """
def sync(st, x):
    if st.size > 1:
        x = broadcast(x, 0)
    return x
""")
    fs = divergence.analyze_paths([path])
    assert _rules(fs) == ["size-conditional-collective"]


def test_size_early_exit_guard_not_flagged(tmp_path):
    path = _write(tmp_path, "szguard.py", """
def sync(st, x):
    if st.size <= 1:
        return x
    return broadcast(x, 0)
""")
    assert divergence.analyze_paths([path]) == []


def test_broadcast_to_shape_op_not_flagged(tmp_path):
    """jnp.broadcast_to / np.broadcast_arrays share the broadcast* prefix
    but are pure shape utilities — a size-conditional use (e.g. the
    bucket wire's replicated-gradient staging) must not be flagged."""
    path = _write(tmp_path, "shapes.py", """
import jax.numpy as jnp

def stage(st, x):
    if st.size > 1:
        x = jnp.broadcast_to(x, (st.size,) + x.shape)
        x, y = jnp.broadcast_arrays(x, x)
    return x
""")
    assert divergence.analyze_paths([path]) == []


def test_nondeterministic_name_flagged(tmp_path):
    path = _write(tmp_path, "nd.py", """
import time, uuid

def a(x):
    return allreduce(x, name=f"grad.{id(x)}")

def b(x):
    return allgather(x, name="t-" + str(uuid.uuid4()))

def c(x):
    return broadcast(x, 0, name=f"bc.{time.time()}")

def fine(x, i):
    return allreduce(x, name=f"grad.{i}")
""")
    fs = divergence.analyze_paths([path])
    assert _rules(fs) == ["nondeterministic-collective-name"]
    assert {f.symbol for f in fs} == {"a", "b", "c"}


# ---------------------------------------------------------------------------
# baseline


def test_baseline_round_trip(tmp_path):
    f1 = Finding(rule="r1", file="a.py", line=3, symbol="A.x", message="m1",
                 detail="d1")
    f2 = Finding(rule="r2", file="b.py", line=7, symbol="B.y", message="m2",
                 detail="d2")
    path = str(tmp_path / "base.json")
    baseline.write(path, [f1, f2], reasons={f1.fingerprint: "reviewed: ok"})
    loaded = baseline.load(path)
    assert set(loaded) == {f1.fingerprint, f2.fingerprint}
    assert loaded[f1.fingerprint]["reason"] == "reviewed: ok"

    # all findings suppressed, none new/stale
    new, sup, stale = baseline.compare([f1, f2], loaded)
    assert (new, len(sup), stale) == ([], 2, [])

    # a fixed finding leaves a stale suppression; a fresh one is new
    f3 = Finding(rule="r3", file="c.py", line=1, symbol="C.z", message="m3")
    new, sup, stale = baseline.compare([f1, f3], loaded)
    assert [f.fingerprint for f in new] == [f3.fingerprint]
    assert [f.fingerprint for f in sup] == [f1.fingerprint]
    assert [e["fingerprint"] for e in stale] == [f2.fingerprint]


def test_baseline_fingerprint_ignores_lines():
    a = Finding(rule="r", file="f.py", line=10, symbol="S.m", message="x",
                detail="d")
    b = Finding(rule="r", file="f.py", line=99, symbol="S.m", message="x",
                detail="d")
    assert a.fingerprint == b.fingerprint


def test_baseline_requires_reasons(tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        json.dump({"schema": baseline.SCHEMA,
                   "suppressions": [{"fingerprint": "abc", "reason": ""}]}, f)
    with pytest.raises(ValueError, match="no reason"):
        baseline.load(path)


def test_repo_baseline_reasons_are_reviewed():
    """Acceptance: the checked-in baseline holds only reviewed
    suppressions, each with a real reason string."""
    entries = baseline.load(os.path.join(REPO, "tools",
                                         "analysis_baseline.json"))
    assert entries, "expected a non-empty reviewed baseline"
    for fp, e in entries.items():
        assert e["reason"].startswith("reviewed:"), (
            f"baseline entry {fp} has an unreviewed reason: {e['reason']!r}")


# ---------------------------------------------------------------------------
# CLI (the CI enforcement — same pattern as check_env_knobs)


def test_cli_repo_is_clean_against_baseline():
    out = subprocess.run([sys.executable, CLI], capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr


def test_cli_json_reports_new_findings(tmp_path):
    _write(tmp_path, "bad.py", """
import threading, time

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def bad(self):
        with self._lock:
            time.sleep(5)
""")
    out = subprocess.run(
        [sys.executable, CLI, "--no-baseline", "--json", str(tmp_path)],
        capture_output=True, text=True)
    assert out.returncode == 1
    report = json.loads(out.stdout)
    assert [f["rule"] for f in report["new"]] == ["blocking-under-lock"]


def test_cli_update_baseline_then_clean(tmp_path):
    _write(tmp_path, "bad.py", """
def f(st, x):
    if st.rank == 0:
        x = allreduce(x)
    return x
""")
    base = str(tmp_path / "base.json")
    up = subprocess.run(
        [sys.executable, CLI, "--baseline", base, "--update-baseline",
         str(tmp_path)], capture_output=True, text=True)
    assert up.returncode == 0, up.stdout + up.stderr
    rerun = subprocess.run(
        [sys.executable, CLI, "--baseline", base, str(tmp_path)],
        capture_output=True, text=True)
    assert rerun.returncode == 0, rerun.stdout + rerun.stderr
    assert "suppressed:" in rerun.stdout
    # stale suppressions fail once the offending code is fixed
    (tmp_path / "bad.py").write_text("def f(st, x):\n    return x\n")
    stale = subprocess.run(
        [sys.executable, CLI, "--baseline", base, str(tmp_path)],
        capture_output=True, text=True)
    assert stale.returncode == 1
    assert "STALE" in stale.stderr


def test_cli_missing_path_is_usage_error():
    out = subprocess.run([sys.executable, CLI, "/nonexistent/dir"],
                         capture_output=True, text=True)
    assert out.returncode == 2


# ---------------------------------------------------------------------------
# runtime witness (DebugLock used directly; no env flip needed)


@pytest.fixture(autouse=True)
def _fresh_witness():
    witness.reset()
    yield
    witness.reset()


def test_witness_records_order_and_inversion():
    a = witness.DebugLock("W1.a")
    b = witness.DebugLock("W1.b")
    with a:
        with b:
            pass
    assert ("W1.a", "W1.b") in witness.order_edges()
    assert witness.violations() == []
    # reversed order on the same thread (locks free, so no deadlock —
    # but the order inversion is the latent bug)
    with b:
        with a:
            pass
    kinds = [v["kind"] for v in witness.violations()]
    assert kinds == ["lock-order-inversion"]
    v = witness.violations()[0]
    assert sorted(v["locks"]) == ["W1.a", "W1.b"]
    assert v["stack"] and v["prior_stack"]


def test_witness_self_deadlock_raises():
    a = witness.DebugLock("W2.a")
    with a:
        with pytest.raises(RuntimeError, match="self-deadlock"):
            a.acquire()
    assert [v["kind"] for v in witness.violations()] == ["self-deadlock"]


def test_witness_reentrant_lock_is_fine():
    a = witness.DebugLock("W3.a", reentrant=True)
    with a:
        with a:
            pass
    assert witness.violations() == []
    assert not a.locked()


def test_witness_hold_warning(monkeypatch):
    monkeypatch.setenv("HOROVOD_LOCK_HOLD_WARN_SECONDS", "0.05")
    a = witness.DebugLock("W4.a")
    with a:
        time.sleep(0.2)
    kinds = [v["kind"] for v in witness.violations()]
    assert "lock-hold" in kinds


def test_witness_detects_real_deadlock():
    a = witness.DebugLock("W5.a")
    b = witness.DebugLock("W5.b")
    ready = threading.Barrier(2)
    results = []

    def t1():
        with a:
            ready.wait()
            got = b.acquire(timeout=2.0)
            results.append(got)
            if got:
                b.release()

    def t2():
        with b:
            ready.wait()
            got = a.acquire(timeout=2.0)
            results.append(got)
            if got:
                a.release()

    th1 = threading.Thread(target=t1)
    th2 = threading.Thread(target=t2)
    th1.start(); th2.start()
    th1.join(timeout=10); th2.join(timeout=10)
    assert not th1.is_alive() and not th2.is_alive()
    kinds = {v["kind"] for v in witness.violations()}
    assert "deadlock" in kinds
    dead = [v for v in witness.violations() if v["kind"] == "deadlock"][0]
    assert sorted(dead["locks"]) == ["W5.a", "W5.b"]


def test_witness_static_consistency():
    a = witness.DebugLock("W6.a")
    b = witness.DebugLock("W6.b")
    with b:
        with a:
            pass
    # static graph claims a before b; runtime observed b->a
    conflicts = witness.check_static_consistency([("W6.a", "W6.b")])
    assert conflicts and "W6.b->W6.a" in conflicts[0]
    # consistent static claim -> no conflict
    assert witness.check_static_consistency([("W6.b", "W6.a")]) == []


def test_make_lock_plain_by_default(monkeypatch):
    monkeypatch.delenv("HOROVOD_DEBUG_LOCKS", raising=False)
    lk = witness.make_lock("W7.plain")
    assert not isinstance(lk, witness.DebugLock)
    monkeypatch.setenv("HOROVOD_DEBUG_LOCKS", "1")
    dbg = witness.make_lock("W7.debug")
    assert isinstance(dbg, witness.DebugLock)


def test_debug_locks_end_to_end_single_process(tmp_path):
    """Single-process tier-1 witness smoke (the multiprocess variant
    lives in test_multiprocess.py): drive the real runtime's named-async
    lane under HOROVOD_DEBUG_LOCKS=1 in a subprocess, assert zero
    violations, static/runtime order consistency and lock_acquire
    events in the flight recorder."""
    script = tmp_path / "drive.py"
    script.write_text("""
import os, sys
import numpy as np
sys.path.insert(0, %r)
import horovod_tpu as hvd
from horovod_tpu import flight_recorder
from horovod_tpu.analysis import lockgraph, witness

hvd.init()
hs = [hvd.allreduce_async(np.ones((32,), np.float32), name=f"t{i}")
      for i in range(4)]
for h in hs:
    hvd.synchronize(h)
assert witness.violations() == [], witness.violations()
assert witness.order_edges(), "no observed lock edges"
static = lockgraph.analyze_paths([os.path.join(%r, "horovod_tpu")], root=%r)
assert witness.check_static_consistency(static.edges) == []
ev = [e for e in flight_recorder.recorder().events()
      if str(e.get("kind", "")).startswith("lock_")]
assert ev, "no lock events"
hvd.shutdown()
print("WITNESS_OK")
""" % (REPO, REPO, REPO))
    env = dict(os.environ)
    # this test is about the ORDER of locks. hvd.init() holds
    # GlobalState.lock while it imports and builds the runtime, which
    # beside five busy workers has taken 9.5 s: a hold read off the wall
    # clock is not a violation of order (test_witness_hold_warning
    # plants a real one)
    env.update({"HOROVOD_DEBUG_LOCKS": "1", "JAX_PLATFORMS": "cpu",
                "HOROVOD_LOCK_HOLD_WARN_SECONDS": "600"})
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "WITNESS_OK" in out.stdout


# ---------------------------------------------------------------------------
# the repo's own guarded-by coverage is real, not an empty ruleset


def test_repo_has_guarded_by_coverage():
    res = lockgraph.analyze_paths([os.path.join(REPO, "horovod_tpu")],
                                  root=REPO)
    guarded_files = {g.file for g in res.guards}
    for expected in ("horovod_tpu/runtime/executor.py",
                     "horovod_tpu/runtime/tensor_queue.py",
                     "horovod_tpu/runtime/fusion_buffer.py",
                     "horovod_tpu/runtime/response_cache.py",
                     "horovod_tpu/elastic/state.py"):
        assert expected in guarded_files, f"no guarded-by rules in {expected}"
    # and the witness-wrapped locks carry analyzer-visible ids
    for lock_id in ("Runtime._inflight_lock", "TensorQueue._lock",
                    "Executor._lock", "FusionBufferManager._lock",
                    "State._spill_lock", "GlobalState.lock",
                    "FlightRecorder._dump_lock"):
        assert lock_id in res.locks, f"lock {lock_id} not extracted"
