#!/usr/bin/env python
"""The quickest proof that horovod_tpu still starts on the chip.

    python chip_smoke.py              # one TPU chip, one process
    python chip_smoke.py --chips 4    # four chips: data parallel + tpurun
    python chip_smoke.py --rehearse   # tiny sizes on whatever backend
                                      # JAX finds (the CPU): control flow
                                      # only, never a substitute

No options: GPT-2-small at its published width and depth (12 layers,
d_model 768, 12 heads x 64, d_ff 3072, 1024 positions, vocab 50257 padded
to 50304), random weights from ``--seed``, through the public entry
points. ``hvd.init()``; three AdamW steps of the jitted
``training.make_train_step`` program at bf16 / sequence 1024 / batch 16;
then the same parameters behind ``hvd.serve()`` at 512 positions, dense
engine and paged engine; then a block-sparse layer beside a lightning
layer (``models/hybrid.py``) behind the same call, a 10,000-token prompt
through the sparse prompt kernel. Every phase checks what it produced and any
failure exits non-zero with no result line. Without an accelerator (and
without ``--rehearse``) that is a failure too. The times and bytes
printed on the way are facts of this run on the device named beside
them, not a benchmark.

``--chips 4`` runs only what exists across chips, each part in a child
process so that no two processes want the same chip: (A) one process
driving four chips against the same seed and global batch on one chip,
plus a ZeRO step; (B) ``tpurun -np 4 -H localhost:4`` with one chip per
worker.

Last line of stdout, and only on success:
    {"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import signal
import subprocess
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Two bf16 ulps, relative: what separates the four-chip losses from the
# one-chip losses on the same seed and global batch (the matmuls round
# to bf16; only the order of the f32 reductions differs across layouts).
LOSS_RTOL = 2.0 ** -7

FULL = dict(
    model=dict(vocab_size=50304, d_model=768, num_layers=12, num_heads=12,
               d_ff=3072),
    seq=1024, batch=16, serve_positions=512, new_tokens=32,
    prompt_ids=(40, 100), prompt_chars=(11, 52),
    # a block-sparse layer (MiniCPM4's sizes) beside a lightning layer,
    # heads of 128 sixteen to two key/value heads: one prompt past
    # dense_len in the 16,384 bucket, one under it
    hybrid=dict(vocab_size=512, d_model=1024, d_ff=2048, num_heads=16,
                num_kv_heads=2, head_dim=128, max_seq=16384,
                sparse=dict(kernel=32, stride=16, block_size=64, topk=64,
                            init_blocks=1, window_size=2048,
                            dense_len=8192)),
    # the lightning layer's MLP: 4 of 16 routed experts held, top-4
    hybrid_experts=dict(num_experts=16, top_k=4, d_ff=256, first=0,
                        count=4),
    hybrid_prompts=(10000, 300))
# --rehearse: same code path, toy widths (head_dim stays 64 so the flash
# kernel's block logic is the real one, in interpret mode)
TINY = dict(
    model=dict(vocab_size=512, d_model=128, num_layers=2, num_heads=2,
               d_ff=256),
    seq=128, batch=4, serve_positions=64, new_tokens=6,
    prompt_ids=(20, 40), prompt_chars=(5, 30),
    hybrid=dict(vocab_size=512, d_model=64, d_ff=128, num_heads=4,
                num_kv_heads=2, head_dim=16, max_seq=512,
                sparse=dict(kernel=8, stride=4, block_size=16, topk=6,
                            init_blocks=1, window_size=32, dense_len=128)),
    hybrid_experts=dict(num_experts=8, top_k=2, d_ff=32, first=0, count=2),
    hybrid_prompts=(300, 60))


def say(msg: str) -> None:
    print(msg, flush=True)


class ByteTokenizer:
    """Text prompts as UTF-8 bytes: ids < 256 fit every vocabulary here."""

    def encode(self, text: str):
        return list(text.encode("utf-8"))


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_device(rehearse: bool, want: int) -> dict:
    """The device as JAX reports it. Anything but ``want`` devices ends
    the run here, and so does anything but a TPU unless ``--rehearse``."""
    import jax

    devices = jax.devices()
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not rehearse and report["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no accelerator (jax.devices() = "
            f"{devices}); this script proves the chip path and does not "
            f"fall back. --rehearse runs the control flow at toy sizes "
            f"on the CPU.")
    if report["count"] != want:
        raise SystemExit(f"chip_smoke: expected {want} device(s), JAX "
                         f"reports {report['count']}: {devices}")
    return report


def check_mesh(hvd, devices) -> None:
    mesh = hvd.mesh().devices
    if mesh.shape != (1, len(devices)) or set(mesh.flatten()) != set(devices):
        raise AssertionError(
            f"hvd.mesh() {hvd.mesh().devices} does not hold exactly "
            f"{devices}")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def build_model(cfg, max_seq):
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import Transformer

    return Transformer(max_seq=max_seq, causal=True, dtype=jnp.bfloat16,
                       **cfg["model"])


def token_batch(cfg, seed):
    import numpy as np

    return np.random.default_rng(seed).integers(
        0, cfg["model"]["vocab_size"], (cfg["batch"], cfg["seq"]),
        dtype=np.int32)


def train(hvd, cfg, seed, label, steps=3, expect_kernels=True,
          expect_allreduce=False):
    """``steps`` AdamW steps after one warm-up step, all on one repeated
    batch. Returns the losses (warm-up first) and the facts of the run,
    which hold the final parameters and optimizer state."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu import training
    from horovod_tpu.models.transformer import causal_lm_loss

    model = build_model(cfg, cfg["seq"])
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4))
    # create_train_state initialises on the default device and sends the
    # parameters through hvd.broadcast_parameters
    state = training.create_train_state(
        model, opt, (1, cfg["seq"]), rng=jax.random.PRNGKey(seed),
        input_dtype=jnp.int32)
    step, batch_sharding = training.make_train_step(
        model, opt, loss_fn=causal_lm_loss)
    tokens = jax.device_put(token_batch(cfg, seed), batch_sharding)

    mesh_devices = set(hvd.mesh().devices.flatten())
    for name, tree in (("parameters", state.params), ("batch", tokens)):
        for leaf in jax.tree.leaves(tree):
            held = {s.device for s in leaf.addressable_shards}
            if held != mesh_devices:
                raise AssertionError(
                    f"{label}: {name} live on {held}, not on every mesh "
                    f"device {mesh_devices}")

    # the profiler/integrity hooks wrap the jit object only when enabled
    jitted = step
    while not hasattr(jitted, "lower"):
        jitted = jitted.__wrapped__
    args = (state.params, state.batch_stats, state.opt_state, tokens,
            tokens)
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    kernels = text.count("tpu_custom_call")
    if expect_kernels and kernels < 3 * cfg["model"]["num_layers"]:
        raise AssertionError(
            f"{label}: {kernels} Mosaic kernels in the compiled step, "
            f"expected the flash forward + two backward kernels in each "
            f"of {cfg['model']['num_layers']} layers: interpret mode or "
            f"attention_reference was taken")
    if expect_allreduce and "all-reduce" not in text:
        raise AssertionError(f"{label}: no all-reduce in the compiled "
                             f"data-parallel step")

    losses, step_s = [], []
    params, stats, opt_state = args[:3]
    for _ in range(steps + 1):
        t0 = time.perf_counter()
        loss, params, stats, opt_state = compiled(params, stats, opt_state,
                                                  tokens, tokens)
        jax.block_until_ready((loss, params, opt_state))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{label}: non-finite loss {losses}")
    if any(b >= a for a, b in zip(losses, losses[1:])):
        raise AssertionError(
            f"{label}: loss not falling on a repeated batch: {losses}")
    return losses, dict(compile_s=compile_s, step_s=step_s[1:],
                        kernels=kernels, params=params)


def report_training(device, losses, facts) -> None:
    import jax

    kind = device["kind"]
    say(f"train: compile {facts['compile_s']:.2f} s [{kind}]; flash "
        f"kernels in the compiled step: {facts['kernels']} "
        f"tpu_custom_call"
        + ("" if facts["kernels"] else " (interpret mode: rehearsal)"))
    say(f"train: warm-up loss {losses[0]:.4f}; steps "
        + ", ".join(f"loss {l:.4f} in {s:.3f} s"
                    for l, s in zip(losses[1:], facts["step_s"]))
        + f" [{kind}]")
    stats = jax.devices()[0].memory_stats()
    peak = (f"{stats['peak_bytes_in_use']:,} bytes"
            if stats and "peak_bytes_in_use" in stats
            else "not reported by this backend")
    say(f"train: memory_stats peak_bytes_in_use {peak} [{kind}]")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def prompts_for(cfg, seed):
    """Four requests of mixed length: two strings, two token-id lists."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    text = "the quick brown fox jumps over the lazy dog and runs on "
    ids = [rng.integers(1, cfg["model"]["vocab_size"], n).tolist()
           for n in cfg["prompt_ids"]]
    strings = [(text * 4)[:n] for n in cfg["prompt_chars"]]
    return [strings[0], ids[0], strings[1], ids[1]]


def serve_once(hvd, model, params, cfg, prompts, paged: bool):
    """Four requests, then the second one again: token counts, greedy
    determinism and no compile on the repeat. Returns the four outputs."""
    label = "paged" if paged else "dense"
    n_new = cfg["new_tokens"]
    t0 = time.perf_counter()
    handle = hvd.serve(model, params, tokenizer=ByteTokenizer(),
                       replicas=1, max_new_tokens=n_new, paged=paged)
    try:
        uids = [handle.submit(p) for p in prompts]
        outs = [handle.result(u, timeout=900.0) for u in uids]
        first_s = time.perf_counter() - t0
        for prompt, out in zip(prompts, outs):
            if len(out.tokens) != n_new or out.finish != "length":
                raise AssertionError(
                    f"serve[{label}]: {len(out.tokens)} tokens "
                    f"(finish={out.finish!r}) for a prompt of "
                    f"{out.prompt_len}, wanted {n_new}")
            if not all(0 <= t < model.vocab_size for t in out.tokens):
                raise AssertionError(f"serve[{label}]: token id out of "
                                     f"the vocabulary: {out.tokens}")
        compiles = handle.compiles_total()
        t0 = time.perf_counter()
        again = handle.generate(prompts[1], timeout=300.0)
        repeat_s = time.perf_counter() - t0
        if again.tokens != outs[1].tokens:
            raise AssertionError(
                f"serve[{label}]: greedy output changed between two "
                f"submissions of one prompt: {outs[1].tokens} then "
                f"{again.tokens}")
        if handle.compiles_total() != compiles:
            raise AssertionError(
                f"serve[{label}]: the repeat compiled "
                f"{handle.compiles_total() - compiles} new program(s)")
        replica = handle.stats()["replicas"][0]
        if replica["quarantined"]:
            raise AssertionError(f"serve[{label}]: replica quarantined: "
                                 f"{replica}")
        if not paged and not replica["engine"]["cache_donated"]:
            raise AssertionError(
                f"serve[{label}]: the runtime declined the KV cache's "
                f"donation (a second cache is live and copied every "
                f"call): {replica['engine']}")
        say(f"serve[{label}]: 4 requests (prompts "
            f"{[o.prompt_len for o in outs]}) x {n_new} new tokens in "
            f"{first_s:.2f} s incl. {compiles} compiles; repeat "
            f"identical in {repeat_s:.3f} s, compiles flat; "
            f"{replica['decode_steps']} decode steps; decode_write_fused "
            f"{replica['engine']['decode_write_fused']}")
    finally:
        handle.close()
    return [o.tokens for o in outs]


def serve(hvd, cfg, params, seed) -> None:
    positions = cfg["serve_positions"]
    model = build_model(cfg, positions)
    # the trained parameters, with the context cut to the serving length
    params = dict(params, pos_embed=params["pos_embed"][:positions])
    prompts = prompts_for(cfg, seed)
    dense = serve_once(hvd, model, params, cfg, prompts, paged=False)
    paged = serve_once(hvd, model, params, cfg, prompts, paged=True)
    if dense != paged:
        raise AssertionError(
            f"serve: greedy output differs between the dense and the "
            f"paged engine:\n  dense {dense}\n  paged {paged}")
    say("serve: dense and paged greedy outputs identical "
        f"({sum(map(len, dense))} tokens)")


def serve_hybrid(hvd, cfg, seed) -> None:
    """A block-sparse layer beside a lightning layer, whose MLP is a
    share of routed experts, behind ``hvd.serve()``: a prompt past
    ``dense_len`` and one under it, greedy output the same twice, the
    prompt kernel, the grouped experts' products (``grouped_product``)
    and their way back (``expert_combine``) in the prefill programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.metrics import registry
    from horovod_tpu.models.hybrid import (BLOCK_SPARSE, DENSE_MLP,
                                           EXPERTS_MLP, LIGHTNING,
                                           HybridDecoder)

    model = HybridDecoder(mixers=(BLOCK_SPARSE, LIGHTNING),
                          mlps=(DENSE_MLP, EXPERTS_MLP),
                          experts=cfg["hybrid_experts"],
                          param_dtype=jnp.bfloat16, **cfg["hybrid"])
    params = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed + 2)
    prompts = [rng.integers(1, model.vocab_size, n).tolist()
               for n in cfg["hybrid_prompts"]]
    n_new = cfg["new_tokens"]
    t0 = time.perf_counter()
    # admission bounded by the slots, not by committed tokens: the
    # default budget (4,096) never admits the long prompt
    handle = hvd.serve(model, params, tokenizer=ByteTokenizer(), replicas=1,
                       max_new_tokens=n_new, slots=2,
                       max_batch_tokens=2 * model.max_seq)
    try:
        outs = [handle.generate(p, timeout=300.0) for p in prompts]
        again = handle.generate(prompts[0], timeout=300.0)
        seconds = time.perf_counter() - t0
        if [len(o.tokens) for o in outs] != [n_new] * len(outs):
            raise AssertionError(
                f"serve[hybrid]: {[len(o.tokens) for o in outs]} tokens, "
                f"wanted {n_new} each")
        if again.tokens != outs[0].tokens:
            raise AssertionError(
                f"serve[hybrid]: greedy output changed between two "
                f"submissions of one prompt: {outs[0].tokens} then "
                f"{again.tokens}")
        engine = handle.stats()["replicas"][0]["engine"]
        if engine["prefill_sparse_kernel"] is not True:
            raise AssertionError(
                "serve[hybrid]: the prefill programs hold no "
                f"sparse_prompt_attention kernel: {engine}")
        if not {"grouped_product", "expert_combine"} \
                <= set(engine["prefill_kernels"]):
            raise AssertionError(
                "serve[hybrid]: the prefill programs group their experts' "
                f"pairs without grouped_product or expert_combine: {engine}")
        share = registry().snapshot()["sparse.live_block_share"][
            "values"][0]["value"]
        say(f"serve[hybrid]: prompts {[o.prompt_len for o in outs]} x "
            f"{n_new} new tokens and the first again in {seconds:.2f} s "
            f"incl. {engine['compiles_total']} compiles; "
            f"decode_write_fused {engine['decode_write_fused']}, "
            f"prefill_sparse_kernel {engine['prefill_sparse_kernel']}, "
            f"prefill_kernels {engine['prefill_kernels']}, "
            f"sparse.live_block_share {share:.4f}")
    finally:
        handle.close()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def note_native_library() -> None:
    from horovod_tpu.runtime import native

    had = os.path.exists(native._LIB_PATH)
    try:
        native.load_library()
    except native.NativeUnavailableError as exc:
        say(f"native library: NOT loaded ({exc}); the cycle path runs its "
            f"Python fallback")
    else:
        say("native library: loaded ("
            + ("already built" if had else "built from source just now")
            + f", {native._LIB_PATH})")


def cache_counter():
    """Persistent-compile-cache hits and misses, from JAX's own events."""
    import jax

    counts = collections.Counter()

    def listen(event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            counts[event.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def phase_main(args, cfg) -> dict:
    """One process, one chip: init, train, serve dense, serve paged."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache

    say(f"compile cache: {compile_cache.configure()}")
    counts = cache_counter()
    hvd.init()
    device = require_device(args.rehearse, 1)
    check_mesh(hvd, jax.devices())
    say(f"device: {jax.devices()} seed {args.seed}")
    note_native_library()

    losses, facts = train(hvd, cfg, args.seed, "train",
                          expect_kernels=not args.rehearse)
    report_training(device, losses, facts)
    serve(hvd, cfg, facts.pop("params"), args.seed)
    serve_hybrid(hvd, cfg, args.seed)
    hvd.shutdown()
    say(f"compile cache: {counts['cache_hits']} hits, "
        f"{counts['cache_misses']} misses this run")
    return device


def phase_dp4(args, cfg) -> dict:
    """Child A: one process driving four chips, against one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.models.transformer import causal_lm_loss
    from horovod_tpu.utils import compile_cache

    say(f"compile cache: {compile_cache.configure()}")
    hvd.init()
    device = require_device(args.rehearse, 4)
    devices = jax.devices()
    check_mesh(hvd, devices)
    say(f"dp4: mesh {hvd.mesh().devices.shape} over {devices}")

    four, facts4 = train(hvd, cfg, args.seed, "dp4",
                         expect_kernels=not args.rehearse,
                         expect_allreduce=True)
    say(f"dp4: four chips, global batch {cfg['batch']}: losses "
        f"{[round(l, 4) for l in four]}; compile "
        f"{facts4['compile_s']:.2f} s, steps "
        f"{[round(s, 3) for s in facts4['step_s']]} s [{device['kind']}]; "
        f"all-reduce in the program, parameters and batch on all four")
    del facts4
    hvd.shutdown()

    hvd.init(devices=[devices[0]])
    one, facts1 = train(hvd, cfg, args.seed, "dp4/one-chip",
                        expect_kernels=not args.rehearse)
    say(f"dp4: one chip, same seed and global batch: losses "
        f"{[round(l, 4) for l in one]}; steps "
        f"{[round(s, 3) for s in facts1['step_s']]} s")
    del facts1
    hvd.shutdown()
    for a, b in zip(four, one):
        if abs(a - b) > LOSS_RTOL * abs(b):
            raise AssertionError(
                f"dp4: four-chip and one-chip losses differ by more than "
                f"{LOSS_RTOL:.4f} relative: {four} vs {one}")
    say(f"dp4: losses agree step by step within {LOSS_RTOL:.4f} relative")

    # ZeRO: optimizer state sharded over the four chips (the eager
    # single-controller plane), one update, loss before and after
    hvd.init()
    model = build_model(cfg, cfg["seq"])
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4),
                                   shard_optimizer_states=True)
    state = training.create_train_state(
        model, opt, (1, cfg["seq"]), rng=jax.random.PRNGKey(args.seed),
        input_dtype=jnp.int32)
    _, batch_sharding = training._shardings()
    tokens = jax.device_put(token_batch(cfg, args.seed), batch_sharding)
    loss_and_grad = jax.jit(jax.value_and_grad(
        lambda p, t: causal_lm_loss(
            model.apply({"params": p}, t, train=True), t)))
    loss0, grads = loss_and_grad(state.params, tokens)
    updates, opt_state = opt.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    loss1, _ = loss_and_grad(params, tokens)
    zero_losses = [float(loss0), float(loss1)]
    for a, b in zip(zero_losses, four):
        if abs(a - b) > LOSS_RTOL * abs(b):
            raise AssertionError(
                f"dp4: ZeRO losses {zero_losses} differ from the "
                f"replicated {four[:2]}")
    per_chip = collections.Counter()
    for leaf in jax.tree.leaves(opt_state):
        if isinstance(leaf, jax.Array):
            for shard in leaf.addressable_shards:
                per_chip[shard.device] += shard.data.nbytes
    replicated = 2 * sum(int(np.prod(p.shape)) * p.dtype.itemsize
                         for p in jax.tree.leaves(state.params))
    share = max(per_chip.values()) / replicated
    # a quarter, plus the padding of each shard up to its size bucket (a
    # power-of-two multiple of the fusion quantum: under 2x, 1.08x here
    # at GPT-2-small)
    if set(per_chip) != set(devices) or not 0.25 <= share < 0.5:
        raise AssertionError(
            f"dp4: ZeRO optimizer state per chip {dict(per_chip)} is "
            f"{share:.3f} of the replicated {replicated} bytes, wanted "
            f"a quarter (plus bucket padding) on each of four chips")
    say(f"dp4: ZeRO step: losses {[round(l, 4) for l in zero_losses]} "
        f"match; optimizer state {max(per_chip.values()):,} bytes per "
        f"chip = {share:.3f} of the replicated {replicated:,}")
    hvd.shutdown()
    return device


def phase_worker(args, cfg) -> None:
    """Child B's worker, one of four under tpurun: a chip of its own, the
    global mesh four, ranks as the launcher numbered them, and named
    collectives that depend on that numbering (plus an exact 64-bit
    payload, which rides the host ring)."""
    import jax
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    world = int(os.environ["HOROVOD_SIZE"])
    local, everything = jax.local_devices(), jax.devices()
    if not args.rehearse and local[0].platform != "tpu":
        raise SystemExit(f"chip_smoke worker: no accelerator: {local}")
    if len(local) != 1 or len(everything) != world or hvd.size() != world:
        raise AssertionError(
            f"worker {hvd.rank()}: sees local {local}, global "
            f"{everything}, hvd.size() {hvd.size()}; wanted one chip of "
            f"its own in a world of {world}")
    rank = hvd.rank()
    if rank != int(os.environ["HOROVOD_RANK"]):
        raise AssertionError(
            f"hvd.rank() {rank} is not the launcher's HOROVOD_RANK "
            f"{os.environ['HOROVOD_RANK']}: the mesh and the host plane "
            f"would name different workers")
    ranks = np.arange(world)
    mean = hvd.allreduce(np.full((8,), float(rank), np.float32),
                         name="smoke.mean")
    np.testing.assert_allclose(np.asarray(mean), ranks.mean())
    big = hvd.allreduce(np.asarray([2 ** 40 + rank], np.int64),
                        name="smoke.int64", average=False)
    if int(np.asarray(big)[0]) != world * 2 ** 40 + ranks.sum():
        raise AssertionError(f"worker {rank}: int64 allreduce gave "
                             f"{np.asarray(big)}")
    root = hvd.broadcast(np.full((4,), float(rank), np.float32),
                         root_rank=1, name="smoke.bcast")
    np.testing.assert_allclose(np.asarray(root), 1.0)
    gathered = hvd.allgather(np.full((1, 2), float(rank), np.float32),
                             name="smoke.gather")
    np.testing.assert_allclose(np.asarray(gathered)[:, 0], ranks)
    say(f"SMOKE_WORKER rank={rank} local={local[0]} "
        f"global={len(everything)} allreduce/broadcast/allgather ok")
    hvd.shutdown()


PHASES = {"main": phase_main, "dp4": phase_dp4, "worker": phase_worker}


# ---------------------------------------------------------------------------
# --chips 4: the parent stays off JAX and runs each part as a child
# ---------------------------------------------------------------------------

def run_child(cmd, env, timeout: float):
    """Run ``cmd`` in its own session, echoing its stdout; returns
    (exit code, stdout lines). The whole session is killed at the time
    limit or on the way out, so nothing this script starts outlives it."""
    proc = subprocess.Popen(cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)

    def kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(timeout, kill)
    timer.start()
    lines = []
    try:
        for line in proc.stdout:
            lines.append(line.rstrip("\n"))
            say(line.rstrip("\n"))
        return proc.wait(), lines
    finally:
        timer.cancel()
        kill()


def four_chips(args) -> dict:
    env = dict(os.environ)
    passthrough = ["--seed", str(args.seed)]
    if args.rehearse:
        passthrough.append("--rehearse")
        env["JAX_PLATFORMS"] = "cpu"
    me = [sys.executable, os.path.abspath(__file__), *passthrough]

    say("chips 4 / A: one process, four chips")
    env_a = dict(env)
    if args.rehearse:
        env_a["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host"
                              "_platform_device_count=4").strip()
    code, lines = run_child(me + ["--phase", "dp4"], env_a, timeout=900)
    if code != 0:
        raise SystemExit(f"chip_smoke: child A exited with {code}")
    device = json.loads(lines[-1])["device"]

    say("chips 4 / B: tpurun -np 4 -H localhost:4, one chip per worker")
    env_b = dict(env)
    env_b.pop("XLA_FLAGS", None)
    code, lines = run_child(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "4",
         "-H", "localhost:4", *me, "--phase", "worker"],
        env_b, timeout=300)
    ranks = {line.split("rank=")[1].split()[0] for line in lines
             if "SMOKE_WORKER" in line}
    if code != 0 or ranks != {"0", "1", "2", "3"}:
        raise SystemExit(f"chip_smoke: child B exited with {code}; "
                         f"workers that finished: {sorted(ranks)}")
    say("chips 4 / B: four workers, one chip each, global mesh of four")
    return device


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the random weights and data")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the data-parallel and launcher "
                             "paths, each in a child process")
    parser.add_argument("--rehearse", action="store_true",
                        help="toy sizes on whatever backend JAX finds "
                             "(the CPU): rehearses the control flow")
    parser.add_argument("--phase", choices=sorted(PHASES), default=None,
                        help=argparse.SUPPRESS)  # set by --chips 4's parent
    args = parser.parse_args()
    cfg = TINY if args.rehearse else FULL
    try:
        if args.phase is None and args.chips == 4:
            device = four_chips(args)
        else:
            device = PHASES[args.phase or "main"](args, cfg)
    except Exception:  # any phase that raises fails the run, loudly
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr, flush=True)
        return 1
    if args.phase == "worker":  # tpurun's four workers report by line
        return 0
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
