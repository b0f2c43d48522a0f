#!/usr/bin/env python
"""Network-chaos acceptance matrix (ISSUE 8).

Runs the fault-mode × phase matrix as real multiprocess scenarios over
the socket/native transport — the rendezvous HTTP store lives in this
process, standing in for the tpurun launcher — and emits ONE JSON
summary on stdout. Exit status 0 only when every scenario meets its
expectations; any unexpected worker death (or a missed invariant) exits
1.

Scenarios (docs/robustness.md has the failure-model table):

* ``flaky_negotiate``   — ``flaky:0.3`` during negotiate: training
  completes with zero lost steps and nonzero retries.
* ``netdelay_negotiate``— fixed per-op latency: completes, injections
  counted, and every rank's shutdown dump embeds the comms-plane ledger
  (the ``comms`` state provider, docs/comms.md) with recorded host-ring
  traffic, rendered by the merged postmortem's comms report.
* ``kv_outage_reform``  — rank 1 killed at step 3 while the rendezvous
  store answers 503 for 5s starting at the first re-form registration:
  survivors bridge the outage and finish.
* ``partition_collective_timeout`` — a permanent partition of rank 1
  mid-run: survivors trip HOROVOD_COLLECTIVE_TIMEOUT, re-form within
  the deadline, finish, and the merged flight-recorder postmortem names
  the partitioned rank.
* ``hier_cross_kill``   — ISSUE 18: two ranks of a six-rank
  hierarchical world (3 groups of 2, netdelay-throttled cross hop)
  killed mid-run; survivors re-form at world 4, the executor recomputes
  the groups (2x2) for the new world, and training finishes with zero
  lost steps.

Checkpoint crash-consistency scenarios (ISSUE 9; docs/checkpointing.md):

* ``ckpt_kill_mid_commit`` — rank 1 killed at the PUBLISH phase of the
  step-3 two-phase commit (after its shard rename, before its
  ``published`` announcement; ``CHAOS_CKPT_PHASE=stage|barrier``
  re-aims the kill at the other protocol points — the invariant is the
  same at every phase): the leader abandons the step-3 manifest,
  the survivors re-form and finish, and afterwards EVERY manifest in
  the directory restores bit-identically (``w == step`` exactly) while
  no step-3 manifest exists — a kill mid-commit can never corrupt or
  publish a partial cut.
* ``ckpt_reform_sharded_adamw`` — rank 1 killed at training step 3
  under ZeRO-1 sharded AdamW: after the re-form the dead rank's
  fp32 moment segments are restored from its left neighbor's replica
  (nonzero, uniform across shards), not zero-filled.

Numerical-integrity scenarios (ISSUE 10; docs/integrity.md):

* ``integrity_bitflip_rollback`` — a one-shot bit flip corrupts rank 1's
  copy of the 5th allreduce result: the per-dispatch digest exchange
  detects the CRC divergence, the cross-rank vote names rank 1, every
  rank rolls back IN PLACE (no process restart, no re-form) to the
  step-4 checkpoint and replays — training finishes with ``w == step``
  bit-identical to an uninjected run, and the merged postmortem names
  the flipped rank.
* ``integrity_nan_skipstep`` — a one-shot NaN poisons rank 1's
  contribution to the 5th allreduce with digests disabled, so the NaN
  reaches every rank's reduced gradient: the step-level spike guard
  skips that step in lockstep (one retry, nothing applied or
  committed) and training converges to the exact final weights.

Goodput-attribution scenario (ISSUE 19; docs/goodput.md):

* ``goodput_attribution`` — one three-rank run, three disruptions: a
  one-shot bit flip on rank 2's 3rd allreduce (in-place rollback +
  replay), then rank 1 killed at step 5 while the rendezvous store
  answers 503 for 5s at the first re-form registration. Every
  survivor's goodput ledger must account >= 90% of its wall-clock, the
  replayed step(s) land in ``rollback`` badput (not productive time),
  the re-form downtime lands in ``elastic_reform``, and the merged
  postmortem's goodput report names the costliest incident and its
  culprit rank.

Serving-plane scenario (ISSUE 11; docs/inference.md):

* ``serve_kill_replica`` — rank 0 drives Poisson-ish load through a
  :class:`KVQueueFrontend` at three serving replicas; rank 2 is killed
  at its 5th decode step, mid-generation. The survivors absorb the
  traffic (the frontend re-dispatches on the lapsed heartbeat), every
  submitted request completes (``zero_lost``), the redistribution
  really happened (``requeued`` nonzero), and the postmortem names the
  dead rank. Needs no native transport — the serving plane rides the
  rendezvous KV store alone.

Usage: python tools/chaos_matrix.py [--only NAME] [--json PATH]
"""

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from horovod_tpu import flight_recorder  # noqa: E402
from horovod_tpu.run.rendezvous import RendezvousServer  # noqa: E402
from horovod_tpu.runtime.native import native_built  # noqa: E402

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


SCENARIOS = {
    "flaky_negotiate": {
        "world": 2,
        "env": {
            "HOROVOD_FAULT_INJECT": "flaky:0.3:seconds=8",
            # 0.3^k exhaustion over thousands of control rounds needs a
            # deeper per-op attempt budget than the default 4
            "HOROVOD_NET_MAX_RETRIES": "12",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
            "CHAOS_STEP_SLEEP": "0.2",
        },
        "require_retries": True,
        "timeout": 180,
    },
    "netdelay_negotiate": {
        "world": 2,
        "env": {
            "HOROVOD_FAULT_INJECT": "netdelay:10",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        },
        "require_injections": True,
        "require_comms_state": True,
        "timeout": 180,
    },
    "kv_outage_reform": {
        "world": 3,
        "env": {
            "HOROVOD_FAULT_INJECT":
                "kill:rank=1:step=3:code=17;kv_outage:5:on=reform",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        },
        "expected_exit": {1: 17},
        "require_retries": True,
        "require_reform": True,
        "timeout": 240,
    },
    # ISSUE 18: ranks killed while the hierarchical allreduce is inside
    # its (netdelay-throttled) cross-group exchange. The six-rank world
    # runs 3 groups of 2; after the two kills the survivors re-form at
    # world 4 and the executor must RECOMPUTE the groups (2x2, not the
    # stale 3x2 plan keyed to the dead transport) and finish with zero
    # lost steps. The intermediate world of 5 exercises the flat
    # fallback (5 % 2 != 0) on the way down.
    "hier_cross_kill": {
        "world": 6,
        "env": {
            "HOROVOD_FAULT_INJECT":
                "netdelay:5:hop=cross;"
                "kill:rank=4:step=3:code=17;"
                "kill:rank=5:step=5:code=19:gen=1",
            "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
            "HOROVOD_HIERARCHY_GROUP_SIZE": "2",
            "HOROVOD_ELASTIC_MIN_WORKERS": "4",
        },
        "expected_exit": {4: 17, 5: 19},
        "require_injections": True,
        "require_reform": True,
        "require_true": ("hier_enabled",),
        "require_hier_groups": 2,
        "timeout": 300,
    },
    "partition_collective_timeout": {
        "world": 3,
        "env": {
            "HOROVOD_FAULT_INJECT": "partition:1:600:after=4",
            "HOROVOD_COLLECTIVE_TIMEOUT": "4",
            "HOROVOD_GLOO_TIMEOUT_SECONDS": "8",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
            "CHAOS_STEP_SLEEP": "1.0",
        },
        "hung_ranks": [1],
        "require_reform": True,
        "require_culprit": 1,
        "timeout": 240,
    },
    "ckpt_kill_mid_commit": {
        "world": 3,
        "ckpt": True,
        "env": {
            # CHAOS_CKPT_PHASE widens the cell to the other protocol
            # points (stage / barrier) without a separate scenario:
            # the acceptance invariant is phase-independent
            "HOROVOD_CKPT_FAULT":
                "kill:rank=1:phase="
                + os.environ.get("CHAOS_CKPT_PHASE", "publish")
                + ":step=3:code=19",
            "HOROVOD_CKPT_ASYNC": "0",
            "HOROVOD_CKPT_KEEP": "20",
            "HOROVOD_CKPT_BARRIER_TIMEOUT_SECONDS": "3",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        },
        "expected_exit": {1: 19},
        "require_reform": True,
        "ckpt_verify": "midcommit",
        "timeout": 240,
    },
    "ckpt_reform_sharded_adamw": {
        "world": 3,
        "worker": "ckpt_chaos_worker.py",
        "ckpt": True,
        "env": {
            "HOROVOD_FAULT_INJECT": "kill:rank=1:step=3:code=17",
            "HOROVOD_CKPT_ASYNC": "0",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        },
        "expected_exit": {1: 17},
        "require_reform": True,
        "check_w": False,
        "require_true": ["steps_ok", "moments_nonzero",
                         "moments_uniform", "replica_restored"],
        "ckpt_verify": "manifest",
        "timeout": 240,
    },
    # ISSUE 20: rank 1 killed INSIDE a stage-2 bucket reduce-scatter —
    # bucket 0's reduce-scatter already in flight, later buckets never
    # released. The survivors' gather fails the orphaned stage-2 tokens
    # with WorkersDownError, the re-formed 2-worker generation resyncs
    # the sharded AdamW shards to the new world, training reaches the
    # expected weights, and no fusion-buffer lease leaks.
    "zero2_kill_mid_reducescatter": {
        "world": 3,
        "worker": "zero2_chaos_worker.py",
        "env": {
            "ZERO2_KILL_STEP": "3",
            "ZERO2_KILL_RANK": "1",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        },
        "expected_exit": {1: 17},
        "require_reform": True,
        "require_true": ["resharded", "leases_ok"],
        "timeout": 240,
    },
    "serve_kill_replica": {
        "world": 4,   # rank 0 = frontend/loadgen, ranks 1-3 = replicas
        "worker": "serve_chaos_worker.py",
        "env": {
            "HOROVOD_FAULT_INJECT": "kill:rank=2:step=5:code=21",
            "HOROVOD_SERVE_SLOTS": "4",
            "HOROVOD_SERVE_MAX_NEW_TOKENS": "16",
            "HOROVOD_SERVE_DECODE_BLOCK": "4",
            "HOROVOD_SERVE_ADMISSION_MS": "10",
        },
        "expected_exit": {2: 21},
        "check_w": False,
        "require_true": ["zero_lost", "requeued"],
        "require_culprit": 2,
        "timeout": 240,
    },
    "integrity_bitflip_rollback": {
        "world": 3,
        "ckpt": True,
        "env": {
            "HOROVOD_FAULT_INJECT": "bitflip:1:after=4",
            "HOROVOD_INTEGRITY": "1",
            "HOROVOD_INTEGRITY_INTERVAL": "1",
            "HOROVOD_CKPT_ASYNC": "0",
            "HOROVOD_ELASTIC_MIN_WORKERS": "3",
        },
        "require_true": ["integrity_violations", "rollbacks"],
        "require_culprit": 1,
        "ckpt_verify": "manifest",
        "timeout": 240,
    },
    # ISSUE 19: the goodput-attribution proof. Fault order: bitflip at
    # the 3rd dispatch (step 3, world still 3 so the digest vote can
    # convict), kill at step 5, kv outage bracketing the re-form. The
    # per-rank ledger assertions live in the require_goodput block of
    # run_scenario.
    "goodput_attribution": {
        "world": 3,
        "ckpt": True,
        "env": {
            "HOROVOD_FAULT_INJECT":
                "bitflip:2:after=2;"
                "kill:rank=1:step=5:code=17;"
                "kv_outage:5:on=reform",
            "HOROVOD_INTEGRITY": "1",
            "HOROVOD_INTEGRITY_INTERVAL": "1",
            "HOROVOD_CKPT_ASYNC": "0",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
            "CHAOS_STEP_SLEEP": "0.2",
        },
        "expected_exit": {1: 17},
        "require_retries": True,
        "require_reform": True,
        "require_true": ["integrity_violations", "rollbacks"],
        "require_goodput": True,
        "ckpt_verify": "manifest",
        "timeout": 240,
    },
    "integrity_nan_skipstep": {
        "world": 2,
        "env": {
            "HOROVOD_FAULT_INJECT": "nan:1:after=4",
            "HOROVOD_INTEGRITY": "1",
            # digests off: the nan flows through the ring to every rank
            # and the step-level guard (not the collective plane) must
            # catch it
            "HOROVOD_INTEGRITY_INTERVAL": "0",
            "CHAOS_INTEGRITY_GUARD": "1",
            "HOROVOD_ELASTIC_MIN_WORKERS": "2",
        },
        "require_true": ["skipped_steps"],
        "timeout": 180,
    },
}


def _verify_ckpt_midcommit(ckpt_dir, total, failures):
    """Every manifest left behind restores bit-identically (the loop
    adds exactly 1.0 per step, so ``w == float32(step)`` exactly), the
    abandoned step-3 manifest does not exist, and the newest cut is the
    final step."""
    import numpy as np

    from horovod_tpu import ckpt
    from horovod_tpu.ckpt import manifest as mf_mod

    steps = mf_mod.all_steps(ckpt_dir)
    if 3 in steps:
        failures.append(
            "step-3 manifest exists — the publish-phase kill should "
            "have abandoned that commit")
    if not steps or max(steps) != total:
        failures.append(
            f"newest manifest is {max(steps) if steps else None}, "
            f"want {total} (steps: {steps})")
    target = {"params": {"w": np.zeros(4, np.float32)}, "optimizer": None}
    for s in steps:
        try:
            trees, _ = ckpt.restore_step(ckpt_dir, s, target)
        except Exception as exc:
            failures.append(f"restore_step({s}) failed: {exc}")
            continue
        w = np.asarray(trees["params"]["w"])
        if not np.array_equal(w, np.full(4, np.float32(s))):
            failures.append(
                f"step {s} restored w={w.tolist()} — not bit-identical "
                f"to the committed value {float(s)}")


def _verify_ckpt_manifest(ckpt_dir, total, failures):
    """The newest manifest is the final step and every shard file it
    names passes its whole-file digest."""
    from horovod_tpu.ckpt import manifest as mf_mod

    steps = mf_mod.all_steps(ckpt_dir)
    if not steps or max(steps) != total:
        failures.append(
            f"newest manifest is {max(steps) if steps else None}, "
            f"want {total} (steps: {steps})")
        return
    try:
        manifest = mf_mod.load_manifest(ckpt_dir, max(steps))
        mf_mod.verify_manifest_files(ckpt_dir, manifest)
    except Exception as exc:
        failures.append(f"final manifest failed verification: {exc}")


def _collect_dumps(flight_dir, server):
    """Local flight-rank-*.json files + dumps shipped to the rendezvous
    ``flight`` scope, deduplicated by launch rank (shipped wins — it is
    at least as recent as the file)."""
    by_rank = {}
    for d in flight_recorder.load_dumps(flight_dir):
        by_rank[d.get("launch_rank", d.get("rank"))] = d
    for key in server.live_keys(flight_recorder.RENDEZVOUS_SCOPE):
        raw = server.get(flight_recorder.RENDEZVOUS_SCOPE, key)
        try:
            d = json.loads(raw)
        except (TypeError, ValueError):
            continue
        by_rank[d.get("launch_rank", d.get("rank"))] = d
    return list(by_rank.values())


def run_scenario(name, spec):
    world = spec["world"]
    timeout = spec.get("timeout", 240)
    hung = set(spec.get("hung_ranks", ()))
    expected_exit = dict(spec.get("expected_exit", {}))
    worker = os.path.join(REPO, "tools",
                          spec.get("worker", "chaos_worker.py"))
    flight_dir = tempfile.mkdtemp(prefix="chaos-flight-")
    ckpt_dir = (tempfile.mkdtemp(prefix="chaos-ckpt-")
                if spec.get("ckpt") else None)
    server = RendezvousServer(host="127.0.0.1")
    http_port = server.start()
    socket_port = _free_port()
    procs, logs = [], []
    outs = [""] * world
    failures = []
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
                "HOROVOD_GLOO_TIMEOUT_SECONDS": "5",
                "HOROVOD_FLIGHT_RECORDER_DIR": flight_dir,
                "JAX_PLATFORMS": "cpu",
            })
            if ckpt_dir:
                env["HOROVOD_CKPT_DIR"] = ckpt_dir
            env.update(spec.get("env", {}))
            # a file, not a pipe: nobody reads a rank while the ranks
            # wait on each other, and a full pipe (64 KB) would block it
            logs.append(tempfile.TemporaryFile("w+"))
            procs.append(subprocess.Popen(
                [sys.executable, worker], env=env,
                stdout=logs[-1], stderr=subprocess.STDOUT))
        # wait for every rank that is expected to terminate on its own;
        # a permanently-partitioned rank blocks forever by design and is
        # reaped after the survivors finish
        deadline = time.monotonic() + timeout
        waiting = {i for i in range(world) if i not in hung}
        while waiting and time.monotonic() < deadline:
            for i in sorted(waiting):
                if procs[i].poll() is not None:
                    waiting.discard(i)
            time.sleep(0.2)
        for i in sorted(waiting):
            failures.append(f"rank {i} did not finish within {timeout}s")
        for i, p in enumerate(procs):
            if p.poll() is None:
                p.kill()
                if i not in hung and i in waiting:
                    pass  # already reported as a timeout above
            p.wait(timeout=30)
            logs[i].seek(0)
            outs[i] = logs[i].read()

        results = {}
        for i, out in enumerate(outs):
            for line in out.splitlines():
                if line.startswith("CHAOS_RESULT "):
                    results[i] = json.loads(line[len("CHAOS_RESULT "):])

        for i in range(world):
            if i in hung:
                if i in results:
                    failures.append(
                        f"rank {i} was expected to hang (partition) but "
                        f"completed: {results[i]}")
                continue
            want = expected_exit.get(i, 0)
            got = procs[i].returncode
            if got != want:
                failures.append(
                    f"rank {i}: unexpected exit {got} (wanted {want}); "
                    f"tail: {outs[i][-800:]!r}")
        survivors = [results[i] for i in sorted(results)
                     if i not in hung and expected_exit.get(i, 0) == 0]
        if not survivors:
            failures.append("no surviving rank reported CHAOS_RESULT")
        total = int(os.environ.get("CHAOS_TOTAL_STEPS", "8"))
        if spec.get("check_w", True):
            for r in survivors:
                if r["step"] != total or abs(r["w"] - total) > 1e-4:
                    failures.append(
                        f"lost steps on rank {r['rank']}: "
                        f"step={r['step']} w={r['w']} (want {total})")
        for field in spec.get("require_true", ()):
            for r in survivors:
                if not r.get(field):
                    failures.append(
                        f"rank {r['rank']}: expected {field}=true, "
                        f"got {r.get(field)!r}")
        want_groups = spec.get("require_hier_groups")
        if want_groups is not None:
            for r in survivors:
                if r.get("hier_groups") != want_groups:
                    failures.append(
                        f"rank {r['rank']}: expected the re-formed plan "
                        f"to run {want_groups} groups, got "
                        f"{r.get('hier_groups')!r}")
        retries = sum(r["net_retries_total"] for r in survivors)
        injections = sum(r["chaos_injected_total"] for r in survivors)
        if spec.get("require_retries") and retries <= 0:
            failures.append("expected nonzero horovod_net_retries_total")
        if spec.get("require_injections") and injections <= 0:
            failures.append(
                "expected nonzero horovod_net_chaos_injected_total")
        if spec.get("require_reform") and not any(
                r["generation"] >= 1 for r in survivors):
            failures.append("expected an elastic re-form (generation >= 1)")

        if ckpt_dir and spec.get("ckpt_verify") == "midcommit":
            _verify_ckpt_midcommit(ckpt_dir, total, failures)
        elif ckpt_dir and spec.get("ckpt_verify") == "manifest":
            _verify_ckpt_manifest(ckpt_dir, total, failures)

        if spec.get("require_comms_state"):
            dumps = _collect_dumps(flight_dir, server)
            ledgers = [(d.get("state") or {}).get("comms") for d in dumps]
            ledgers = [c for c in ledgers if isinstance(c, dict)]
            if len(ledgers) < world:
                failures.append(
                    f"only {len(ledgers)}/{world} dumps embedded the "
                    "comms state provider")
            elif not any(
                    ((c.get("lanes") or {}).get("host_ring") or {})
                    .get("bytes_total") for c in ledgers):
                failures.append(
                    "comms ledgers recorded no host_ring traffic")
            elif "=== comms report" not in                     flight_recorder.format_postmortem(dumps):
                failures.append(
                    "postmortem lacks the comms report section")

        if spec.get("require_goodput"):
            # per-survivor ledger invariants (CHAOS_RESULT goodput_*
            # fields), then the cross-rank forensics in the postmortem
            for r in survivors:
                acct = r.get("goodput_accounted")
                if not isinstance(acct, (int, float)) or acct < 0.9:
                    failures.append(
                        f"rank {r['rank']}: goodput ledger accounts "
                        f"{acct!r} of wall-clock, want >= 0.9")
                badput = r.get("goodput_badput") or {}
                if not badput.get("rollback"):
                    failures.append(
                        f"rank {r['rank']}: no rollback badput — the "
                        f"replayed step(s) were counted as productive "
                        f"time ({badput})")
                if not badput.get("elastic_reform"):
                    failures.append(
                        f"rank {r['rank']}: re-form downtime missing "
                        f"from elastic_reform badput ({badput})")
                if not r.get("goodput_replayed"):
                    failures.append(
                        f"rank {r['rank']}: ledger recorded no "
                        "replayed steps")
            dumps = _collect_dumps(flight_dir, server)
            gp_post = flight_recorder.format_postmortem(dumps)
            if "=== goodput report" not in gp_post:
                failures.append(
                    "postmortem lacks the goodput report section")
            elif "costliest incident:" not in gp_post:
                failures.append(
                    "goodput report does not name the costliest "
                    "incident:\n" + gp_post)
            elif "culprit rank" not in gp_post:
                failures.append(
                    "goodput report's costliest incident names no "
                    "culprit rank:\n" + gp_post)

        postmortem = ""
        culprit = spec.get("require_culprit")
        if culprit is not None:
            dumps = _collect_dumps(flight_dir, server)
            postmortem = flight_recorder.format_postmortem(dumps)
            if f"suspected culprit: rank {culprit}" not in postmortem:
                failures.append(
                    f"postmortem does not name rank {culprit}:\n"
                    + postmortem)
        return {
            "scenario": name,
            "ok": not failures,
            "failures": failures,
            "results": [results.get(i) for i in range(world)],
            "exit_codes": [p.returncode for p in procs],
            "net_retries_total": retries,
            "chaos_injected_total": injections,
            "postmortem_tail": postmortem.splitlines()[-12:]
            if postmortem else [],
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for log in logs:
            log.close()
        server.stop()
        shutil.rmtree(flight_dir, ignore_errors=True)
        if ckpt_dir:
            shutil.rmtree(ckpt_dir, ignore_errors=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--only", help="run a single scenario by name")
    parser.add_argument("--json", help="also write the summary to a file")
    args = parser.parse_args()

    if not native_built():
        print(json.dumps({"ok": False,
                          "error": "native transport not built"}))
        return 1

    names = [args.only] if args.only else list(SCENARIOS)
    for name in names:
        if name not in SCENARIOS:
            print(json.dumps({"ok": False,
                              "error": f"unknown scenario {name!r}"}))
            return 1

    summary = {"ok": True, "scenarios": []}
    for name in names:
        print(f"chaos_matrix: running {name} ...", file=sys.stderr,
              flush=True)
        result = run_scenario(name, SCENARIOS[name])
        summary["scenarios"].append(result)
        if not result["ok"]:
            summary["ok"] = False
        print(f"chaos_matrix: {name}: "
              f"{'ok' if result['ok'] else 'FAILED'}",
              file=sys.stderr, flush=True)

    text = json.dumps(summary, indent=2)
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
