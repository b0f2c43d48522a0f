"""``python -m horovod_tpu.serve`` — a KV-queue replica worker.

This is what ``tpurun --serve`` launches per slot when no command is
given: each rank builds the demo model (random weights, deterministic
seed — every replica must hold identical params), registers with the
rendezvous KV queue, and serves until a dispatcher publishes the stop
key. Point a :class:`~horovod_tpu.serve.queue.KVQueueFrontend` at the
same rendezvous server to drive it (as the chaos matrix's
``serve_chaos_worker.py`` does).

Model shape flags exist so smoke runs stay tiny; a real deployment
replaces this module with its own worker that loads trained params and
calls :func:`horovod_tpu.serve.run_kv_replica`.
"""

from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m horovod_tpu.serve", description=__doc__)
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--heads", type=int, default=2)
    parser.add_argument("--d-ff", type=int, default=128)
    parser.add_argument("--max-seq", type=int, default=128)
    parser.add_argument("--seed", type=int, default=0,
                        help="param seed; identical across the fleet")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from horovod_tpu import profiler, tracing
    from horovod_tpu.models.transformer import Transformer
    from horovod_tpu.serve import ServePolicy, run_kv_replica
    from horovod_tpu.serve.api import _serve_guard

    rank = int(os.environ.get("HOROVOD_RANK", "0"))
    addr = os.environ.get("HOROVOD_RENDEZVOUS_HTTP_ADDR", "127.0.0.1")
    port = int(os.environ.get("HOROVOD_RENDEZVOUS_HTTP_PORT", "0"))
    if not port:
        print("horovod_tpu.serve: HOROVOD_RENDEZVOUS_HTTP_PORT not set "
              "(run under tpurun --serve)", file=sys.stderr)
        return 2

    model = Transformer(
        vocab_size=args.vocab, d_model=args.d_model,
        num_layers=args.layers, num_heads=args.heads, d_ff=args.d_ff,
        max_seq=args.max_seq, causal=True, dtype=jnp.float32)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(args.seed), tokens,
                        train=False)["params"]

    # no hvd.init() here (the serving plane rides the KV store alone),
    # so the tracing/profiling planes adopt the rank explicitly — a
    # replica launched under --profile-dir must dump its request spans
    # for the launcher's merged Perfetto trace
    tracing.configure(rank=rank)
    tracing.note_serve_started()
    profiler.configure(rank=rank)

    policy = ServePolicy.from_env()
    guard = _serve_guard(rank) if policy.quarantine else None
    try:
        replica = run_kv_replica(model, params, policy, rank=rank,
                                 addr=addr, port=port, guard=guard)
    finally:
        profiler.finalize()
    print(f"horovod_tpu.serve: rank {rank} drained "
          f"({replica.completed} completed)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
