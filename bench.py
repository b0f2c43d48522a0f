#!/usr/bin/env python
"""Synthetic ResNet-50 training benchmark — the headline perf harness.

TPU-native port of the reference's measurement harness (reference:
examples/pytorch_synthetic_benchmark.py:37-110,
examples/tensorflow2_synthetic_benchmark.py:72-132): ResNet-50 forward +
backward + optimizer update on synthetic ImageNet-shaped data. Each timed
round is ONE compiled program running BENCH_BATCHES_PER_ROUND (default 20)
train steps via lax.scan — host dispatch latency is excluded, which is the
XLA-native reading of the reference's multi-batch rounds. Warmup runs
ceil(BENCH_WARMUP / BENCH_BATCHES_PER_ROUND) rounds first; reports
images/sec over BENCH_ROUNDS rounds.

Baseline for ``vs_baseline``: the reference's only published absolute
number — 1656.82 images/sec on 16 GPUs (ResNet-101, batch 64, 4xP100
servers; reference: docs/benchmarks.rst:32-43) = 103.55 images/sec/GPU.

Prints exactly one JSON line:
    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N}
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import horovod_tpu as hvd
from horovod_tpu.models.resnet import ResNet50
from horovod_tpu import training
from horovod_tpu.utils import compile_cache

REFERENCE_IMAGES_PER_SEC_PER_CHIP = 1656.82 / 16  # docs/benchmarks.rst:32-43

WARMUP_ITERS = int(os.environ.get("BENCH_WARMUP", "20"))
# 3 timed rounds by default (r5): r4's 10-round medians varied +-0.2%
# across every workload (round-4 record), so 7 extra ~30 s rounds bought
# nothing but driver-window risk. BENCH_ROUNDS restores the long protocol.
TIMED_ROUNDS = int(os.environ.get("BENCH_ROUNDS", "3"))
# 60 batches/round: one round is one executable launch, so a longer round
# amortizes the per-launch host cost over more steps. 60 was chosen on an
# earlier machine whose launch cost ~100 ms; not re-measured on a locally
# attached chip.
BATCHES_PER_ROUND = int(os.environ.get("BENCH_BATCHES_PER_ROUND", "60"))

# Per-model CNN configs: (label, image size, default batch/chip, forward
# FLOPs/image). FLOPs count multiplies AND adds separately (2 per MAC) —
# the SAME convention as the chip's published peak (197 bf16 TFLOP/s on
# v5e is 2xMAC) and as the transformer 6N formula, so MFU is comparable
# across every row. The constants are XLA's own cost analysis of each
# model's forward pass at these input sizes (jit(fwd).lower().compile()
# .cost_analysis()["flops"]) — within ±2.5% of 2x the published MAC
# counts (2x4.089 / 2x5.713 @299² / 2x15.47).
#   ROUND-4 CORRECTION: rounds 1-3 computed CNN MFU on the MAC count
# (4.089e9 for ResNet-50), understating it 2x. The r3 per-conv
# microbenchmarks (docs/perf_experiments.md: 96.3% MFU at 155.9us on a
# 29.6e9-FLOP conv) already used the true 2xMAC convention — this fix
# makes the model-level rows consistent with them and with the
# transformer rows. Throughput (img/s) numbers are unaffected.
# Train step fwd + bwd ≈ 3x forward (bwd ≈ 2x fwd FLOPs). The model trio
# is the reference's published benchmark set (reference:
# docs/benchmarks.rst:13-14). Batch defaults are measured v5e sweet
# spots per model.
CNN_CONFIGS = {
    "resnet50": ("ResNet-50", 224, 128, 8.234e9),
    # r4 sweeps: Inception 16/32/48/64 -> 32 best; VGG 32/64/128/192/256
    # -> 1021/1084/1432/1310/1455 img/s, 256 best (128 within 2%)
    "inception": ("Inception-V3", 299, 32, 11.137e9),
    "vgg": ("VGG-16", 224, 256, 30.342e9),
}

# Published bf16 peak per chip, by device kind (jax.devices()[0].device_kind
# prefix match). Source: Google Cloud TPU documentation, per-generation
# system architecture pages.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_per_chip():
    """Peak of the chip under ``jax.devices()[0]``. An accelerator whose
    kind is not in the table is an error, never a default. The CPU
    backend (the ``--tiny`` smoke runs) is no chip and has no peak:
    ``None``, and ``mfu`` is then null, i.e. not measured."""
    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind
    for prefix in sorted(PEAK_BF16_FLOPS, key=len, reverse=True):
        if kind.startswith(prefix):
            return PEAK_BF16_FLOPS[prefix]
    raise RuntimeError(
        f"no published bf16 peak for device kind {kind!r}: add it to "
        f"PEAK_BF16_FLOPS with its source before reporting MFU")


def mfu(flops_per_sec_per_chip):
    peak = peak_flops_per_chip()
    if peak is None:
        return None
    return round(flops_per_sec_per_chip / peak, 4)


def enable_profiler(flops_per_step=None):
    """Turn on the step profiler for the timed rounds (hvd.profiler): every
    headline then carries a step_breakdown + comm_hidden_fraction, and the
    FLOPs hint feeds the rolling horovod_mfu gauge. Called AFTER warmup so
    compile time never pollutes the step history."""
    os.environ.setdefault("HOROVOD_PROFILE", "1")
    hvd.profiler.configure()
    if flops_per_step is not None:
        hvd.profiler.set_flops_per_step(flops_per_step,
                                        peak_flops_per_chip())


def step_profile(n_rounds):
    """(step_breakdown, comm_hidden_fraction, comm_hidden_fraction_bytes)
    over the last ``n_rounds`` profiled steps — this workload's timed
    rounds; the no-flag sweep's earlier workloads share the profiler
    ring, so slice instead of using the whole-ring summary(). The
    bytes-weighted fraction is the bucket-release acceptance metric:
    payload bytes whose reduction overlapped backward / total reduced
    bytes."""
    steps = hvd.profiler.history()[-n_rounds:]
    if not steps:
        return None, None, None
    n = len(steps)
    breakdown = {k: round(sum(s["phases"][k] for s in steps) / n, 6)
                 for k in ("host", "compute", "exposed_comm", "optimizer")}
    total = sum(s["comm"]["total_seconds"] for s in steps)
    exposed = sum(s["comm"]["exposed_seconds"] for s in steps)
    hidden = (min(1.0, max(0.0, 1.0 - exposed / total))
              if total > 0 else 0.0)
    comm_bytes = sum(s["comm"]["bytes"] for s in steps)
    hidden_bytes = sum(s["comm"]["hidden_fraction_bytes"]
                       * s["comm"]["bytes"] for s in steps)
    hidden_b = (min(1.0, max(0.0, hidden_bytes / comm_bytes))
                if comm_bytes > 0 else 0.0)
    return breakdown, round(hidden, 4), round(hidden_b, 4)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def memory_rows(params_tree=None):
    """Headline memory fields (docs/memory.md): per-subsystem
    ``bytes_per_chip`` from the tracker ledger + ``peak_hbm_bytes``. The
    jitted bench rounds never cross the eager push point in
    DistributedOptimizer, so the caller hands its params tree here for a
    direct push before the pull."""
    try:
        from horovod_tpu import memory

        t = memory.tracker()
        if params_tree is not None:
            t.note_tree_bytes("params", params_tree)
        led = t.ledger()
        per_chip = {name: int(rec["bytes"])
                    for name, rec in led["subsystems"].items()
                    if name != "host_rss" and rec["bytes"]}
        return {"bytes_per_chip": per_chip,
                "peak_hbm_bytes": int(t.peak_hbm_bytes())}
    except Exception:
        return {"bytes_per_chip": None, "peak_hbm_bytes": None}


def comms_rows():
    """Headline comms fields (docs/comms.md): the busiest lane's smoothed
    bus bandwidth + its roofline utilization from the tracker ledger.
    None/None when no collective moved bytes this run (1-chip world with
    nothing on any wire)."""
    try:
        from horovod_tpu import comms

        led = comms.tracker().ledger()
        lanes = {name: rec for name, rec in led["lanes"].items()
                 if rec.get("busbw_gbs")}
        if not lanes:
            return {"busbw_gbs": None, "comms_utilization": None}
        busiest = max(lanes, key=lambda ln: lanes[ln]["bytes_total"])
        rec = lanes[busiest]
        return {"busbw_gbs": rec["busbw_gbs"],
                "comms_utilization": rec.get("utilization")}
    except Exception:
        return {"busbw_gbs": None, "comms_utilization": None}


def goodput_rows():
    """Headline goodput fields (docs/goodput.md): the productive
    fraction of wall-clock from the tracker ledger, gated
    higher-is-better by bench_compare. None when the tracker is off or
    the epoch never started (pre-init entry points)."""
    try:
        from horovod_tpu import goodput

        led = goodput.tracker().ledger()
        if not led.get("wall_seconds"):
            return {"goodput_fraction": None}
        return {"goodput_fraction": led["goodput_fraction"]}
    except Exception:
        return {"goodput_fraction": None}


def bucket_overlap_probe(model, optimizer, state, image_size,
                         batch=8, steps=4):
    """Bytes-weighted hidden fraction of the release plan's wire traffic.

    The jitted round keeps its collectives inside one XLA program, so
    the runtime's dispatch/drain stamps never see them; this probe runs
    a few *eager* bucketed steps (simulated multi-lane wire on the
    single-controller path) on the same model, where each released
    bucket is a real pipelined dispatch. Returns None when nothing hit
    the wire (1-chip world or wire=off)."""
    from horovod_tpu.parallel import buckets as buckets_mod

    plan = buckets_mod.GradReleasePlan()
    one_step = training._make_one_step(model, optimizer,
                                       training._default_loss_fn,
                                       grad_release=plan)
    rng = np.random.RandomState(1)
    images = jnp.asarray(
        rng.uniform(-1, 1, (batch, image_size, image_size, 3)),
        jnp.float32)
    labels = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)
    params, stats, opt_state = (state.params, state.batch_stats,
                                state.opt_state)
    one_step(params, stats, opt_state, images, labels)  # warmup/compile
    for i in range(steps):
        with hvd.profiler.step(f"overlap probe {i}"):
            out = one_step(params, stats, opt_state, images, labels)
            jax.block_until_ready(out[0])
    probe = hvd.profiler.history()[-steps:]
    comm_bytes = sum(s["comm"]["bytes"] for s in probe)
    if not comm_bytes:
        return None
    hidden = sum(s["comm"]["hidden_fraction_bytes"] * s["comm"]["bytes"]
                 for s in probe)
    return round(min(1.0, max(0.0, hidden / comm_bytes)), 4)


def main(model_name: str = "resnet50", allow_env: bool = True):
    label, image_size, default_batch, fwd_flops = CNN_CONFIGS[model_name]
    batch_per_chip, default_size = default_batch, image_size
    if allow_env:  # single-model runs only — a sweep would apply one
        # override to every model, clobbering per-model sweet spots
        batch_per_chip = int(os.environ.get("BENCH_BATCH",
                                            str(default_batch)))
        image_size = int(os.environ.get("BENCH_IMAGE_SIZE",
                                        str(image_size)))
    # conv FLOPs scale ~quadratically with resolution; keep the MFU
    # basis honest when BENCH_IMAGE_SIZE overrides the default
    fwd_flops *= (image_size / default_size) ** 2
    train_flops_per_image = 3 * fwd_flops

    hvd.init()
    n_chips = hvd.size()
    global_batch = batch_per_chip * n_chips
    log(f"devices: {jax.devices()}  global_batch={global_batch}")

    if model_name == "inception":
        from horovod_tpu.models import InceptionV3
        model = InceptionV3(num_classes=1000, dtype=jnp.bfloat16)
    elif model_name == "vgg":
        from horovod_tpu.models import VGG16
        model = VGG16(num_classes=1000, dtype=jnp.bfloat16)
    else:
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    optimizer = hvd.DistributedOptimizer(
        optax.sgd(0.01 * n_chips, momentum=0.9))

    # BENCH_GRAD_BUCKETS=0 restores the post-hoc exchange for A/B; the
    # default rides HOROVOD_GRAD_BUCKET_RELEASE via make_train_round
    # (on the jitted global-batch lane the plan stages the collectives
    # at their backward positions — see docs/performance.md)
    grad_buckets = None
    if allow_env and os.environ.get("BENCH_GRAD_BUCKETS") == "0":
        grad_buckets = False
    elif allow_env and os.environ.get("BENCH_GRAD_BUCKETS") == "1":
        os.environ["HOROVOD_GRAD_BUCKET_RELEASE"] = "1"

    state = training.create_train_state(
        model, optimizer, (1, image_size, image_size, 3))
    # One compiled program per round (lax.scan over the batches) so host
    # dispatch latency stays out of the steady-state measurement.
    round_fn, batch_sharding = training.make_train_round(
        model, optimizer, steps=BATCHES_PER_ROUND,
        grad_release=grad_buckets)

    rng = np.random.RandomState(0)
    images = jax.device_put(
        rng.uniform(-1, 1, (global_batch, image_size, image_size, 3)).astype(np.float32),
        batch_sharding)
    labels = jax.device_put(
        rng.randint(0, 1000, (global_batch,)).astype(np.int32),
        batch_sharding)

    params, stats, opt_state = state.params, state.batch_stats, state.opt_state

    log("compiling + warmup...")
    t0 = time.perf_counter()
    warmup_rounds = max(1, -(-WARMUP_ITERS // BATCHES_PER_ROUND))
    for _ in range(warmup_rounds):
        loss, params, stats, opt_state = round_fn(params, stats, opt_state,
                                                  images, labels)
    jax.block_until_ready(loss)
    log(f"warmup done in {time.perf_counter() - t0:.1f}s "
        f"(loss={float(loss):.3f})")

    enable_profiler(batch_per_chip * BATCHES_PER_ROUND
                    * train_flops_per_image)
    rates = []
    for r in range(TIMED_ROUNDS):
        t0 = time.perf_counter()
        with hvd.profiler.step(f"{label} round {r}"):
            loss, params, stats, opt_state = round_fn(
                params, stats, opt_state, images, labels)
            jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        rates.append(global_batch * BATCHES_PER_ROUND / dt)
        log(f"round {r}: {rates[-1]:.1f} img/s")
    breakdown, hidden_fraction, hidden_bytes = step_profile(TIMED_ROUNDS)
    if grad_buckets is not False:
        probe = bucket_overlap_probe(model, optimizer, state, image_size)
        if probe is not None:
            log(f"bucket overlap probe: hidden_bytes={probe}")
            hidden_bytes = probe

    # median, not mean: one round disturbed by the host reads slow
    imgs_per_sec = float(np.median(rates))
    per_chip = imgs_per_sec / n_chips
    result = {
        "metric": f"images/sec/chip ({label} synthetic, bf16, "
                  f"batch {batch_per_chip}/chip)",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        # the reference's only absolute published number is ResNet-family
        # (1656.82 img/s on 16 P100-era GPUs); Inception/VGG appear in
        # its scaling table without absolutes, so vs_baseline is only
        # meaningful for the ResNet row
        "vs_baseline": (
            round(per_chip / REFERENCE_IMAGES_PER_SEC_PER_CHIP, 3)
            if model_name == "resnet50" else None),
        "mfu": mfu(per_chip * train_flops_per_image),
        "step_breakdown": breakdown,
        "comm_hidden_fraction": hidden_fraction,
        "comm_hidden_fraction_bytes": hidden_bytes,
        **memory_rows(params),
        **comms_rows(),
        **goodput_rows(),
    }
    print(json.dumps(result), flush=True)
    return result


def transformer_main(family: str, allow_env: bool = True,
                     micro_step_cap: int = 512):
    """Transformer headlines: tokens/sec + MFU for BERT-Base/-Large MLM
    (BASELINE progression config #5's model family) and GPT-2-small
    causal LM — all on the Pallas flash-attention path
    (models/transformer.py).

    Batch defaults are the measured v5e sweet spots (r2 sweeps: BERT-Base
    seq 512 — 16 -> 46.5% MFU, 32 -> 50.8%, 64 -> 47.7%)."""
    import optax as _optax

    from horovod_tpu.models.transformer import (BertBase, BertLarge,
                                                GPT2Small, causal_lm_loss,
                                                causal_lm_loss_chunked,
                                                masked_lm_loss,
                                                masked_lm_loss_gathered,
                                                sample_masked_positions)

    hvd.init()
    n_chips = hvd.size()
    causal = family == "gpt2"
    large = family == "bert-large"
    default_seq = "1024" if causal else "512"
    seq = int(os.environ.get("BENCH_BERT_SEQ", default_seq)
              if allow_env else default_seq)
    # v5e sweet spots, re-swept r5 with the single-block flash kernel
    # (cheaper attention moved BERT-Base's spot): BERT-Base 48
    # (r5: 32->182.2k, 48->186.7k, 64->178.6k); BERT-Large 8
    # (r5: 8x-accum beats 16x4 56.8k; r3: 4->47.4%, 8->56.4%,
    # 16->53.1%, 32->OOM); GPT-2 16 (r5: 24->122.0k vs 16->130.1k)
    default_batch = "8" if large else "16" if causal else "48"
    batch = int(os.environ.get("BENCH_BERT_BATCH", default_batch)
                if allow_env else default_batch)
    vocab = 50257 if causal else 30522
    global_batch = batch * n_chips
    label = ("GPT-2-small causal LM" if causal
             else "BERT-Large MLM" if large else "BERT-Base MLM")

    # MLM benches default to the gather-before-projection path (r4): the
    # vocab matrix projects only the masked positions (the standard BERT
    # max_predictions_per_seq data layout), so the (batch, seq, vocab)
    # f32 logits tensor never exists. BENCH_MLM_GATHER=0 restores the
    # full-logits r1-r3 protocol for A/B.
    gather = (not causal) and (
        os.environ.get("BENCH_MLM_GATHER", "1") == "1" if allow_env
        else True)
    # BENCH_ADAM_MU_BF16=1: adamw first moment in bf16 (optimizer-state
    # HBM traffic counter-move; A/B knob, default off)
    mu_bf16 = allow_env and os.environ.get("BENCH_ADAM_MU_BF16") == "1"
    # BENCH_QKV_FUSED=1: single (d, 3d) QKV projection per layer
    # (counter-move A/B knob, default off)
    qkv_fused = allow_env and os.environ.get("BENCH_QKV_FUSED") == "1"
    # BENCH_ACCUM=N: gradient accumulation over N micro-batches per
    # optimizer update (effective batch = N*batch, identical gradients
    # to a single N*batch step). The r4 decomposition measured the f32
    # adamw pass at 16.2 ms — 21% of the BERT-Large step and batch-
    # independent — so keeping the micro-batch at the activation sweet
    # spot and amortizing the update is the large-batch training
    # configuration this chip actually favors. BERT-Large defaults to
    # x16 (r5 re-sweep with the faster kernel: x8 62.5k, x16 63.6k,
    # x32 64.1k — x16 is the knee, effective 128 seqs/chip, a standard
    # large-batch recipe; r4's x8 sweep: x2 +0%, x4 +7%, x8 +10.8%);
    # BERT-Base to x4 (+1.6%); GPT-2 measured a wash (122.1k -> 121.3k
    # at x4) and stays at 1.
    default_accum = "16" if large else "1" if causal else "4"
    if allow_env and os.environ.get("BENCH_FUSED_ADAMW") == "1":
        default_accum = "1"  # the fused-adamw A/B runs un-accumulated
    accum = int(os.environ.get("BENCH_ACCUM", default_accum)
                if allow_env else default_accum)
    # BENCH_FUSED_ADAMW=1: the Pallas single-pass adamw
    # (ops/pallas/fused_adamw.py) instead of optax's transform chain —
    # targets the measured 16.2 ms / 21%-of-step optimizer pass
    fused_opt = allow_env and os.environ.get("BENCH_FUSED_ADAMW") == "1"
    if fused_opt and accum > 1:
        raise SystemExit("BENCH_FUSED_ADAMW and BENCH_ACCUM are separate "
                         "A/B knobs; combine them once either wins alone")

    cls = GPT2Small if causal else BertLarge if large else BertBase
    model = cls(vocab_size=vocab, max_seq=seq, dtype=jnp.bfloat16,
                fused_qkv=qkv_fused)
    rng = np.random.RandomState(0)
    rows = global_batch * accum
    tokens = rng.randint(0, vocab, (rows, seq)).astype(np.int32)
    mask = (rng.rand(rows, seq) < 0.15).astype(np.int32)
    n_pred = max(1, round(0.15 * seq))  # 76 at seq 512 (BERT's layout)
    positions = sample_masked_positions(
        np.random.default_rng(0), rows, seq, n_pred)
    labels = np.take_along_axis(tokens, positions, axis=1)
    if accum > 1:
        reshape = lambda a: a.reshape((accum, global_batch) + a.shape[1:])
        tokens, mask, positions, labels = map(
            reshape, (tokens, mask, positions, labels))

    sample = (tokens[0] if accum > 1 else tokens)[:1]
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(sample),
                        train=False)
    if fused_opt:
        from horovod_tpu.ops.pallas import fused_adamw as _fused_adamw
        fopt = _fused_adamw(1e-4)
        opt = None
        opt_state = fopt.init(params)
    else:
        opt = hvd.DistributedOptimizer(_optax.adamw(
            1e-4, mu_dtype=jnp.bfloat16 if mu_bf16 else None))
        opt_state = opt.init(params)

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    # training FLOPs/token: 6*N (fwd+bwd matmuls) + attention term
    # 12*L*S*d (fwd+bwd QK^T and PV). Causal counts the half score
    # matrix — the standard MODEL-FLOPs convention for MFU (the useful
    # math; at this seq/block config the kernel executes full masked
    # blocks, i.e. hardware FLOPs are higher, which only makes the
    # reported MFU conservative about the hardware's utilization).
    # Gathered MLM: the tied vocab matmul runs at n_pred of seq
    # positions, so its 6*|E| term scales by n_pred/seq — counting the
    # full 6*|E| against the faster step would inflate MFU with FLOPs
    # the model no longer executes. (The input lookup and pos_embed are
    # gathers either way; their overcount — <1% — is shared by every
    # published 6N number.)
    l_layers, d_model = (24, 1024) if large else (12, 768)
    attn = 12 * l_layers * seq * d_model
    n_eff = n_params
    if gather:
        n_embed = vocab * d_model
        n_eff = n_params - n_embed + n_embed * n_pred // seq
    flops_per_token = 6 * n_eff + (attn // 2 if causal else attn)

    # Round sizing under accumulation: a round is one launch, so rounds
    # should be long; the 512 micro-step cap (~35 s at BERT-Large shapes)
    # and the sweep's 256 (~18 s rounds) were set against an earlier
    # machine's per-launch cost and call deadline. Not re-measured.
    updates_per_round = max(1, min(BATCHES_PER_ROUND,
                                   micro_step_cap // accum))

    # BENCH_LM_CHUNK=K: chunked causal loss — the vocab projection runs
    # K seq positions at a time inside the loss, so the (batch, seq,
    # vocab) f32 logits tensor (3.3 GB at GPT-2 bench shapes) never
    # exists. 0 = full-logits (A/B knob; default per measurement below).
    lm_chunk = int(os.environ.get("BENCH_LM_CHUNK", "0")
                   if allow_env and causal else "0")

    def loss_fn(p, toks, msk, pos, lab):
        if causal:
            if lm_chunk:
                hidden = model.apply(p, toks, train=True, output="hidden")
                emb = p["params"]["token_embed"]["embedding"]
                return causal_lm_loss_chunked(hidden, emb, toks,
                                              chunk=lm_chunk)
            return causal_lm_loss(model.apply(p, toks, train=True), toks)
        if gather:
            hidden = model.apply(p, toks, train=True, output="hidden")
            emb = p["params"]["token_embed"]["embedding"]
            return masked_lm_loss_gathered(hidden, emb, pos, lab)
        return masked_lm_loss(model.apply(p, toks, train=True), toks, msk)

    @jax.jit
    def round_fn(p, s, toks, msk, pos, lab):
        def one_update(p, s):
            if accum == 1:
                loss, g = jax.value_and_grad(loss_fn)(p, toks, msk, pos,
                                                      lab)
                if fused_opt:
                    from horovod_tpu.parallel.dp import allreduce_gradients
                    g = allreduce_gradients(g, average=True)
                    p, s = fopt.apply(p, s, g)
                    return p, s, loss
            else:
                # accumulate over micro-batches: mean grad == the grad of
                # one accum*batch step, at batch-8 activation footprint
                def micro(g_sum, mb):
                    t, m, po, la = mb
                    loss, g = jax.value_and_grad(loss_fn)(p, t, m, po, la)
                    return jax.tree_util.tree_map(jnp.add, g_sum, g), loss
                g0 = jax.tree_util.tree_map(jnp.zeros_like, p)
                g, mlosses = jax.lax.scan(micro, g0,
                                          (toks, msk, pos, lab))
                g = jax.tree_util.tree_map(lambda a: a / accum, g)
                loss = mlosses.mean()
            upd, s = opt.update(g, s, p)
            p = _optax.apply_updates(p, upd)
            return p, s, loss

        def body(carry, _):
            p, s = carry
            p, s, loss = one_update(p, s)
            return (p, s), loss

        (p, s), losses = jax.lax.scan(body, (p, s), None,
                                      length=updates_per_round)
        return p, s, losses[-1]

    log(f"{label} seq {seq} batch {batch}/chip "
        f"({n_params / 1e6:.0f}M params"
        f"{', gathered MLM head' if gather else ''}"
        f"{', bf16 adam mu' if mu_bf16 else ''}"
        f"{', fused qkv' if qkv_fused else ''}"
        f"{f', {accum}x grad accumulation' if accum > 1 else ''}"
        f"{', fused pallas adamw' if fused_opt else ''}"
        f"{f', chunked LM loss ({lm_chunk})' if lm_chunk else ''}"
        "), compiling...")
    t0 = time.perf_counter()
    params, opt_state, loss = round_fn(params, opt_state, tokens, mask,
                                       positions, labels)
    jax.block_until_ready(loss)
    log(f"warmup done in {time.perf_counter() - t0:.1f}s "
        f"(loss={float(loss):.3f})")

    enable_profiler(batch * accum * seq * updates_per_round
                    * flops_per_token)
    rates = []
    for r in range(TIMED_ROUNDS):
        t0 = time.perf_counter()
        with hvd.profiler.step(f"{label} round {r}"):
            params, opt_state, loss = round_fn(params, opt_state, tokens,
                                               mask, positions, labels)
            jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        rates.append(global_batch * accum * seq * updates_per_round / dt)
        log(f"round {r}: {rates[-1]:.0f} tokens/s")
    breakdown, hidden_fraction, hidden_bytes = step_profile(TIMED_ROUNDS)

    tokens_per_sec = float(np.median(rates))
    per_chip = tokens_per_sec / n_chips
    batch_label = (f"batch {batch}/chip" if accum == 1 else
                   f"batch {batch}x{accum} accum/chip")
    if lm_chunk:
        batch_label += f", chunked LM loss ({lm_chunk})"
    result = {
        "metric": f"tokens/sec/chip ({label}, bf16, seq {seq}, "
                  f"{batch_label}, flash attention)",
        "value": round(per_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,  # the reference publishes no absolute
        # transformer number (docs/benchmarks.rst is ResNet/VGG only)
        "mfu": mfu(per_chip * flops_per_token),
        "step_breakdown": breakdown,
        "comm_hidden_fraction": hidden_fraction,
        "comm_hidden_fraction_bytes": hidden_bytes,
        **memory_rows(params),
        **comms_rows(),
        **goodput_rows(),
    }
    print(json.dumps(result), flush=True)
    return result


def control_plane_main(fast: bool = False, np_override: int = None):
    """Control-plane benchmark (VERDICT r2 ask 4): negotiation latency,
    cache fast path, fusion throughput, autotune — measured over a real
    np=4 multi-process world on the host wire (tools/control_plane_bench
    .py). Emits one JSON line per metric so the driver captures the
    Horovod-headline numbers (negotiation amortization + fusion).

    ``fast`` (the no-flag sweep): fewer steps and no autotune launch —
    the reported counter metrics drift slightly (shorter windows
    amortize fixed per-window protocol bytes less; see the tool's
    header comment) but stay the same story; the full protocol (r4:
    5.5 min on a 1-core box) stays behind the explicit
    --control-plane flag.

    ``np_override``: world size for the trimmed always-run probe (the
    budget-squeezed sweep runs np=2 so the control-plane rows are never
    silently absent from the artifact)."""
    import subprocess

    np_workers = (str(np_override) if np_override is not None
                  else os.environ.get("BENCH_CONTROL_PLANE_NP", "4"))
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "control_plane_bench.py"),
           "--np", np_workers]
    if fast:
        cmd.append("--fast")
    raw = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                         check=True)
    r = json.loads(raw.stdout.strip().splitlines()[-1])
    results = []
    for metric, value, unit, baseline in [
        ("control-plane bytes/op, fresh-name slow path",
         r["ctrl_bytes_per_op_slow_path"], "bytes/op", None),
        ("control-plane bytes/op, cache fast path",
         r["ctrl_bytes_per_op_fast_path"], "bytes/op",
         r["negotiation_byte_amortization_x"]),
        ("ring kernel steps/op, fused",
         r["ring_steps_per_op_fused"], "steps/op",
         r["fusion_dispatch_reduction_x"]),
    ]:
        results.append({
            "metric": f"{metric} (np={r['world']}, host wire)",
            "value": value, "unit": unit, "vs_baseline": baseline,
        })
        print(json.dumps(results[-1]), flush=True)
    return results


def hierarchy_main(tiny: bool = False, np_override: int = None):
    """Flat-vs-hierarchical host collective A/B (ISSUE 18 tentpole
    evidence; tools/hierarchy_bench.py): per-payload us/op for the seed
    flat ring vs the two-level decomposition (group size 2) with and
    without the fp16 slow-hop codec, each with and without a simulated
    slow cross-group link (``netdelay:...:hop=cross``). The headline is
    the throttled-hop speedup — unit "x" so tools/bench_compare.py
    gates it higher-is-better. Full mode adds the rebooted autotuner's
    convergence ratio vs the hand-tuned configuration.

    ``tiny``: one small size, few steps, no autotune phase — the tier-1
    smoke mode; numbers are meaningless."""
    import subprocess

    np_workers = (str(np_override) if np_override is not None
                  else os.environ.get("BENCH_HIERARCHY_NP", "4"))
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "hierarchy_bench.py"),
           "--np", np_workers]
    if tiny:
        cmd.append("--tiny")
    raw = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=1800, check=True)
    r = json.loads(raw.stdout.strip().splitlines()[-1])
    big = str(r["sizes"][-1])
    rows = [
        ("hierarchical+fp16 vs flat, throttled cross hop",
         r["throttled_hop_speedup_x"], "x"),
        ("hierarchical vs flat, uniform wire",
         r["uniform_wire_ratio_x"], "x"),
        ("flat ring us/op under netdelay",
         r["us_per_op"]["flat_netdelay"][big], "us/op"),
        ("hierarchical+fp16 us/op under netdelay",
         r["us_per_op"]["hier_fp16_netdelay"][big], "us/op"),
    ]
    if not tiny and r.get("autotuned_vs_hand_tuned_x") is not None:
        rows.append(("autotuned vs hand-tuned, throttled cross hop",
                     r["autotuned_vs_hand_tuned_x"], "x"))
    results = []
    for metric, value, unit in rows:
        results.append({
            "metric": (f"{metric} (np={r['world']}, "
                       f"g={r['group_size']}"
                       f"{', tiny' if tiny else ''})"),
            "value": value, "unit": unit, "vs_baseline": None,
        })
        if tiny:
            results[-1]["tiny"] = True
        print(json.dumps(results[-1]), flush=True)
    return results


def collectives_main(tiny: bool = False):
    """Data-plane microbench: steady-state fused allreduce through the
    background runtime — pipelined dispatch, size-bucketed program cache
    and persistent fusion buffer all on the hot path. Emits ONE JSON line
    (the driver records the last parsed line): per-size p50 latency +
    effective per-worker payload bandwidth, plus the XLA compile count
    during the timed (post-warmup) phase. The compile count is the
    regression canary — steady state over fixed named tensors must stay
    at zero new compiles (tests/test_data_plane.py enforces the same
    invariant at tier 1).

    ``tiny`` (--tiny / the tier-1 smoke test): one small size, a couple
    of steps — exercises every code path in seconds; the numbers are
    meaningless and the line is marked ``"tiny": true``."""
    hvd.init()
    from horovod_tpu.runtime import executor as executor_mod
    from horovod_tpu.runtime.fusion_buffer import bucket_elems
    from horovod_tpu.runtime.runtime import get_runtime

    ex = get_runtime().executor
    world = hvd.size()
    tensors_per_step = 2 if tiny else 4
    # Bin groupings are timing-dependent (the background cycle may catch
    # 1..tensors_per_step of the enqueued tensors per bin) but handles are
    # synchronized before the next step, so bins never span steps and the
    # possible fused totals are exactly k*elems for k in 1..tensors_per_step.
    # Warm up until the program cache covers every such bucket AND a full
    # step adds zero compiles, so the timed phase can't hit a first-ever
    # grouping; the early warmup steps enqueue 1, 2, ... tensors to give
    # each total a deliberate chance to compile.
    max_warmup_steps, timed_steps = (6, 2) if tiny else (24, 7)
    rng = np.random.RandomState(0)
    rows = []
    steady_compiles = 0
    # 16 KiB .. 4 MiB per tensor (tiny: one 1 KiB size)
    for elems in ((256,) if tiny else (4096, 65536, 1 << 20)):
        payload = rng.randn(world, elems).astype(np.float32)

        def one_step(step, count=tensors_per_step):
            hs = [hvd.allreduce_async(
                hvd.stack_per_worker(list(payload + np.float32(step))),
                name=f"bench/ar{elems}/t{j}")
                for j in range(count)]
            for h in hs:
                hvd.synchronize(h)

        expected = {bucket_elems(k * elems, 4, ex.fusion_buffers.quantum_bytes)
                    for k in range(1, tensors_per_step + 1)}

        def buckets_warmed():
            # host-ring-only mode compiles nothing; don't wait on it
            if not ex._programs:
                return True
            keys = list(ex._programs)
            return all(any(b in k for k in keys) for b in expected)

        quiet = 0
        for s in range(max_warmup_steps):
            before = executor_mod._PROGRAM_COMPILES.value
            one_step(s, count=min(s + 1, tensors_per_step))
            quiet = quiet + 1 \
                if executor_mod._PROGRAM_COMPILES.value == before else 0
            if quiet >= 2 and buckets_warmed():
                break
        compiles0 = executor_mod._PROGRAM_COMPILES.value
        lat = []
        for s in range(timed_steps):
            t0 = time.perf_counter()
            one_step(max_warmup_steps + s)
            lat.append(time.perf_counter() - t0)
        new_compiles = executor_mod._PROGRAM_COMPILES.value - compiles0
        steady_compiles += new_compiles
        p50 = float(np.median(lat))
        step_bytes = tensors_per_step * elems * 4  # per-worker payload
        rows.append({
            "tensor_bytes": elems * 4,
            "p50_ms": round(p50 * 1e3, 3),
            "payload_gb_s": round(step_bytes / p50 / 1e9, 3),
            "timed_phase_compiles": new_compiles,
        })
        log(f"collectives {elems * 4}B/tensor: p50 {rows[-1]['p50_ms']} ms"
            f"  {rows[-1]['payload_gb_s']} GB/s"
            f"  compiles(timed)={new_compiles}")

    # Flight-recorder overhead (the recorder is on by default, so its cost
    # must be visible next to the latency it taxes): raw emit() throughput,
    # plus the added p50 step latency at pipeline depth 2 — the same fused
    # allreduce path timed with the recorder off, then on.
    from horovod_tpu import flight_recorder

    rec = flight_recorder.recorder()
    n_emit = 1_000 if tiny else 100_000
    t0 = time.perf_counter()
    for i in range(n_emit):
        rec.emit("bench_overhead", op=i)
    emit_per_sec = n_emit / (time.perf_counter() - t0)

    fr_elems = 4096
    fr_payload = rng.randn(world, fr_elems).astype(np.float32)

    def depth2_step(step):
        hs = [hvd.allreduce_async(
            hvd.stack_per_worker(list(fr_payload + np.float32(step))),
            name=f"bench/fr/t{j}") for j in range(2)]
        for h in hs:
            hvd.synchronize(h)

    for s in range(2 if tiny else 4):  # warm the fr-name buckets/programs
        depth2_step(1000 + s)
    # interleave recorder-off/on steps (A/B pairs) so dispatch-latency
    # drift does not masquerade as recorder overhead
    was_enabled = rec.enabled
    lat_off, lat_on = [], []
    for s in range(3 if tiny else 15):
        for enabled, lat in ((False, lat_off), (True, lat_on)):
            rec.enabled = enabled
            t0 = time.perf_counter()
            depth2_step(2000 + 2 * s + int(enabled))
            lat.append(time.perf_counter() - t0)
    rec.enabled = was_enabled
    p50_off = float(np.median(lat_off))
    p50_on = float(np.median(lat_on))
    fr_overhead = {
        "emit_events_per_sec": round(emit_per_sec),
        "p50_ms_depth2_recorder_off": round(p50_off * 1e3, 3),
        "p50_ms_depth2_recorder_on": round(p50_on * 1e3, 3),
        "added_p50_ms_depth2": round((p50_on - p50_off) * 1e3, 3),
        "overhead_pct": (round(100.0 * (p50_on - p50_off) / p50_off, 2)
                         if p50_off > 0 else None),
    }
    log("flight recorder: %d events/sec emit; depth-2 p50 %s -> %s ms "
        "(%s%% overhead)" % (
            fr_overhead["emit_events_per_sec"],
            fr_overhead["p50_ms_depth2_recorder_off"],
            fr_overhead["p50_ms_depth2_recorder_on"],
            fr_overhead["overhead_pct"]))
    result = {
        "metric": f"fused allreduce p50 latency, {tensors_per_step}-tensor "
                  f"cycle at {rows[-1]['tensor_bytes']}B/tensor "
                  f"(np={world}, pipelined data plane)",
        "value": rows[-1]["p50_ms"],
        "unit": "ms",
        "vs_baseline": None,
        "sizes": rows,
        "steady_state_compiles": steady_compiles,
        "program_compiles_total": executor_mod._PROGRAM_COMPILES.value,
        "program_cache_hits_total": executor_mod._PROGRAM_CACHE_HITS.value,
        "flight_recorder": fr_overhead,
        **comms_rows(),
        **goodput_rows(),
    }
    if tiny:
        result["tiny"] = True
    print(json.dumps(result), flush=True)
    return result


def integrity_main(tiny: bool = False):
    """Integrity-plane microbench (ISSUE 10): steady-state cost of the
    in-band collective digests on the fused allreduce path, at
    BERT-Large gradient shapes (one encoder layer's worth of kernels per
    step — the fusion buckets the flagship workload actually reduces).

    Three interleaved phases over identical named tensors so dispatch
    drift cannot masquerade as digest cost: integrity OFF (the pre-PR-10
    data plane), ON at the default ``HOROVOD_INTEGRITY_INTERVAL``
    (headline ``value``: added p50 step %, goal < 1%), and ON checking
    EVERY dispatch (the worst case, reported for context). Warmup runs
    with checks on every dispatch so the masked digest program compiles
    before timing starts; the timed phases must add ZERO new program
    compiles (same canary as --collectives).

    ``tiny`` (--tiny / the tier-1 smoke test): toy shapes + 2 steps."""
    hvd.init()
    from horovod_tpu import integrity as integ
    from horovod_tpu.integrity import digest as integ_digest
    from horovod_tpu.runtime import executor as executor_mod

    world = hvd.size()
    if tiny:
        shapes = [(256,), (64, 8)]
        warmup_steps, timed_steps = 3, 2
    else:
        # one BERT-Large encoder layer's gradient tensors (d=1024,
        # ff=4096): two attention kernels + the MLP pair + a layernorm
        shapes = [(1024, 1024), (1024, 1024), (1024, 4096), (4096, 1024),
                  (1024,)]
        warmup_steps, timed_steps = 6, 7
    rng = np.random.RandomState(0)
    payloads = [rng.randn(world, *s).astype(np.float32) for s in shapes]
    n_elems = sum(int(np.prod(s)) for s in shapes)
    log(f"integrity bench: {len(shapes)} tensors, "
        f"{n_elems * 4 / 1e6:.1f} MB/step/worker, np={world}"
        f"{' (tiny)' if tiny else ''}")

    def one_step(step):
        hs = [hvd.allreduce_async(
            hvd.stack_per_worker(list(payloads[j] + np.float32(step))),
            name=f"integ/t{j}") for j in range(len(shapes))]
        for h in hs:
            hvd.synchronize(h)

    saved = {k: os.environ.get(k)
             for k in ("HOROVOD_INTEGRITY", "HOROVOD_INTEGRITY_INTERVAL")}

    def set_phase(interval):
        if interval is None:
            os.environ.pop("HOROVOD_INTEGRITY", None)
            os.environ.pop("HOROVOD_INTEGRITY_INTERVAL", None)
        else:
            os.environ["HOROVOD_INTEGRITY"] = "1"
            os.environ["HOROVOD_INTEGRITY_INTERVAL"] = str(interval)

    default_iv = integ.DEFAULT_INTEGRITY_INTERVAL
    try:
        # warmup with checks on EVERY dispatch: compiles the fused
        # programs AND the masked digest program for every bucket
        set_phase(1)
        for s in range(warmup_steps):
            one_step(s)
        compiles0 = executor_mod._PROGRAM_COMPILES.value
        checks0 = integ_digest._CHECKS.value

        phases = {"off": (None, []), "default": (default_iv, []),
                  "every": (1, [])}
        for s in range(timed_steps):
            for name, (interval, lat) in phases.items():
                set_phase(interval)
                t0 = time.perf_counter()
                one_step(1000 + s * len(phases))
                lat.append(time.perf_counter() - t0)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    steady_compiles = executor_mod._PROGRAM_COMPILES.value - compiles0
    checks = integ_digest._CHECKS.value - checks0
    p50 = {name: float(np.median(lat))
           for name, (_, lat) in phases.items()}

    def pct(on):
        return (round(100.0 * (p50[on] - p50["off"]) / p50["off"], 2)
                if p50["off"] > 0 else None)

    result = {
        "metric": f"integrity digest steady-state step overhead "
                  f"(in-band digests every {default_iv} dispatches, "
                  f"{'toy' if tiny else 'BERT-Large layer'} gradient "
                  f"shapes, np={world})",
        "value": pct("default"),
        "unit": "%",
        "goal": "< 1%",
        "p50_ms_integrity_off": round(p50["off"] * 1e3, 3),
        "p50_ms_default_interval": round(p50["default"] * 1e3, 3),
        "p50_ms_every_dispatch": round(p50["every"] * 1e3, 3),
        "every_dispatch_overhead_pct": pct("every"),
        "digest_interval": default_iv,
        "digest_checks_timed_phase": int(checks),
        "steady_state_compiles": int(steady_compiles),
    }
    if tiny:
        result["tiny"] = True
    log(f"integrity: p50 off {result['p50_ms_integrity_off']} ms, "
        f"default-interval {result['p50_ms_default_interval']} ms "
        f"({result['value']}%), every-dispatch "
        f"{result['p50_ms_every_dispatch']} ms "
        f"({result['every_dispatch_overhead_pct']}%); "
        f"compiles(timed)={steady_compiles}")
    print(json.dumps(result), flush=True)
    return result


def memory_main(tiny: bool = False):
    """Memory-plane microbench (ISSUE 13): steady-state cost of the
    tracker's push accounting + reconciliation sampler on the fused
    allreduce path, at BERT-Large gradient shapes.

    Two interleaved phases over identical named tensors (the
    --integrity protocol, so dispatch drift cannot masquerade as tracker
    cost): memory plane OFF (tracker disabled, no sampler thread) and ON
    with the sampler at a deliberately hostile cadence (50 ms — 200x the
    default) plus a per-step grads push. Headline ``value``: added p50
    step %, goal < 1%. Also reports the resulting ledger and the
    claimed-vs-actual reconciliation drift.

    ``tiny`` (--tiny / the tier-1 smoke test): toy shapes + 2 steps."""
    hvd.init()
    from horovod_tpu import memory

    world = hvd.size()
    if tiny:
        shapes = [(256,), (64, 8)]
        warmup_steps, timed_steps = 3, 2
    else:
        shapes = [(1024, 1024), (1024, 1024), (1024, 4096), (4096, 1024),
                  (1024,)]
        warmup_steps, timed_steps = 6, 7
    rng = np.random.RandomState(0)
    payloads = [rng.randn(world, *s).astype(np.float32) for s in shapes]
    n_elems = sum(int(np.prod(s)) for s in shapes)
    log(f"memory bench: {len(shapes)} tensors, "
        f"{n_elems * 4 / 1e6:.1f} MB/step/worker, np={world}"
        f"{' (tiny)' if tiny else ''}")

    t = memory.tracker()
    was_enabled = t.enabled

    def one_step(step, push):
        hs = [hvd.allreduce_async(
            hvd.stack_per_worker(list(payloads[j] + np.float32(step))),
            name=f"mem/t{j}") for j in range(len(shapes))]
        outs = [hvd.synchronize(h) for h in hs]
        if push:  # the eager-path per-step accounting under test
            t.note_tree_bytes("grads", outs)

    def set_phase(on):
        t.enabled = on
        if on:
            t.start(interval=0.05)  # hostile cadence: 200x the default
        else:
            t.stop()

    try:
        set_phase(True)
        for s in range(warmup_steps):
            one_step(s, push=True)

        phases = {"off": (False, []), "on": (True, [])}
        for s in range(timed_steps):
            for name, (on, lat) in phases.items():
                set_phase(on)
                t0 = time.perf_counter()
                one_step(1000 + s * len(phases), push=on)
                lat.append(time.perf_counter() - t0)

        set_phase(True)
        led = t.sample()  # one explicit reconcile for the report
    finally:
        t.stop()
        t.enabled = was_enabled
        if was_enabled:
            t.start()

    p50 = {name: float(np.median(lat)) for name, (_, lat) in phases.items()}
    overhead = (round(100.0 * (p50["on"] - p50["off"]) / p50["off"], 2)
                if p50["off"] > 0 else None)
    drift = led.get("reconcile_drift_ratio")
    result = {
        "metric": f"memory tracker steady-state step overhead "
                  f"(sampler at 50 ms + per-step push, "
                  f"{'toy' if tiny else 'BERT-Large layer'} gradient "
                  f"shapes, np={world})",
        "value": overhead,
        "unit": "%",
        "goal": "< 1%",
        "p50_ms_memory_off": round(p50["off"] * 1e3, 3),
        "p50_ms_memory_on": round(p50["on"] * 1e3, 3),
        "reconcile_drift_ratio": (round(drift, 4)
                                  if isinstance(drift, (int, float))
                                  else None),
        "bytes_per_chip": {
            name: int(rec["bytes"])
            for name, rec in led["subsystems"].items()
            if name != "host_rss" and rec["bytes"]},
        "peak_hbm_bytes": int(t.peak_hbm_bytes()),
        "samples_taken": len(t.samples()),
    }
    if tiny:
        result["tiny"] = True
    log(f"memory: p50 off {result['p50_ms_memory_off']} ms, "
        f"on {result['p50_ms_memory_on']} ms ({overhead}%); "
        f"drift={result['reconcile_drift_ratio']}")
    print(json.dumps(result), flush=True)
    return result


def comms_main(tiny: bool = False):
    """Comms-plane microbench (ISSUE 16): steady-state cost of the
    collective-transport observatory on the fused allreduce path, at
    BERT-Large gradient shapes.

    Two interleaved phases over identical named tensors (the --integrity
    protocol, so dispatch drift cannot masquerade as tracker cost):
    comms accounting OFF (tracker disabled — record() returns at the
    guard) and ON (every dispatch pays the algbw/busbw bookkeeping).
    Headline ``value``: added p50 step %, goal < 1%. The timed phases
    must add ZERO new XLA program compiles (the --collectives canary) —
    the observatory only ever watches the wire, never touches programs.

    ``tiny`` (--tiny / the tier-1 smoke test): toy shapes + 2 steps."""
    hvd.init()
    from horovod_tpu import comms
    from horovod_tpu.runtime import executor as executor_mod

    world = hvd.size()
    if tiny:
        shapes = [(256,), (64, 8)]
        warmup_steps, timed_steps = 3, 2
    else:
        shapes = [(1024, 1024), (1024, 1024), (1024, 4096), (4096, 1024),
                  (1024,)]
        warmup_steps, timed_steps = 6, 7
    rng = np.random.RandomState(0)
    payloads = [rng.randn(world, *s).astype(np.float32) for s in shapes]
    n_elems = sum(int(np.prod(s)) for s in shapes)
    log(f"comms bench: {len(shapes)} tensors, "
        f"{n_elems * 4 / 1e6:.1f} MB/step/worker, np={world}"
        f"{' (tiny)' if tiny else ''}")

    t = comms.tracker()
    was_enabled = t.enabled

    def one_step(step):
        hs = [hvd.allreduce_async(
            hvd.stack_per_worker(list(payloads[j] + np.float32(step))),
            name=f"comms/t{j}") for j in range(len(shapes))]
        for h in hs:
            hvd.synchronize(h)

    try:
        t.enabled = True
        for s in range(warmup_steps):
            one_step(s)

        compiles0 = executor_mod._PROGRAM_COMPILES.value
        phases = {"off": (False, []), "on": (True, [])}
        for s in range(timed_steps):
            for name, (on, lat) in phases.items():
                t.enabled = on
                t0 = time.perf_counter()
                one_step(1000 + s * len(phases))
                lat.append(time.perf_counter() - t0)
        steady_compiles = executor_mod._PROGRAM_COMPILES.value - compiles0
        t.enabled = True
        led = t.ledger()
    finally:
        t.enabled = was_enabled

    p50 = {name: float(np.median(lat)) for name, (_, lat) in phases.items()}
    overhead = (round(100.0 * (p50["on"] - p50["off"]) / p50["off"], 2)
                if p50["off"] > 0 else None)
    lanes = {name: rec["busbw_gbs"] for name, rec in led["lanes"].items()
             if rec.get("busbw_gbs")}
    result = {
        "metric": f"comms tracker steady-state step overhead "
                  f"(per-dispatch algbw/busbw accounting, "
                  f"{'toy' if tiny else 'BERT-Large layer'} gradient "
                  f"shapes, np={world})",
        "value": overhead,
        "unit": "%",
        "goal": "< 1%",
        "p50_ms_comms_off": round(p50["off"] * 1e3, 3),
        "p50_ms_comms_on": round(p50["on"] * 1e3, 3),
        "steady_state_compiles": int(steady_compiles),
        "lane_busbw_gbs": lanes,
        **comms_rows(),
        **goodput_rows(),
    }
    if tiny:
        result["tiny"] = True
    log(f"comms: p50 off {result['p50_ms_comms_off']} ms, "
        f"on {result['p50_ms_comms_on']} ms ({overhead}%); "
        f"compiles(timed)={steady_compiles}; lanes={lanes}")
    print(json.dumps(result), flush=True)
    return result


def goodput_main(tiny: bool = False):
    """Goodput-ledger microbench (ISSUE 19): steady-state cost of the
    productive-time accounting on the profiled step path, at BERT-Large
    gradient shapes.

    Two interleaved phases over identical named tensors (the --comms
    protocol, so dispatch drift cannot masquerade as tracker cost), each
    step bracketed by ``profiler.step`` so the goodput hook at the step
    boundary actually fires: ledger OFF (record_step returns at the
    guard) and ON (every step pays the category bookkeeping + fraction
    sample). Headline ``value``: added p50 step %, goal < 1%. The timed
    phases must add ZERO new XLA program compiles — the ledger only ever
    watches the clock, never touches programs.

    ``tiny`` (--tiny / the tier-1 smoke test): toy shapes + 2 steps."""
    hvd.init()
    from horovod_tpu import goodput, profiler
    from horovod_tpu.runtime import executor as executor_mod

    world = hvd.size()
    if tiny:
        shapes = [(256,), (64, 8)]
        warmup_steps, timed_steps = 3, 2
    else:
        shapes = [(1024, 1024), (1024, 1024), (1024, 4096), (4096, 1024),
                  (1024,)]
        warmup_steps, timed_steps = 6, 7
    rng = np.random.RandomState(0)
    payloads = [rng.randn(world, *s).astype(np.float32) for s in shapes]
    n_elems = sum(int(np.prod(s)) for s in shapes)
    log(f"goodput bench: {len(shapes)} tensors, "
        f"{n_elems * 4 / 1e6:.1f} MB/step/worker, np={world}"
        f"{' (tiny)' if tiny else ''}")

    t = goodput.tracker()
    was_enabled = t.enabled
    prof = profiler._profiler
    prof_was_enabled = prof.enabled
    prof.enabled = True  # the goodput step hook rides the profiler

    def one_step(step):
        with profiler.step(f"goodput/s{step}"):
            hs = [hvd.allreduce_async(
                hvd.stack_per_worker(list(payloads[j] + np.float32(step))),
                name=f"goodput/t{j}") for j in range(len(shapes))]
            for h in hs:
                hvd.synchronize(h)

    try:
        t.enabled = True
        t.start_epoch()
        for s in range(warmup_steps):
            one_step(s)

        compiles0 = executor_mod._PROGRAM_COMPILES.value
        phases = {"off": (False, []), "on": (True, [])}
        for s in range(timed_steps):
            for name, (on, lat) in phases.items():
                t.enabled = on
                t0 = time.perf_counter()
                one_step(1000 + s * len(phases))
                lat.append(time.perf_counter() - t0)
        steady_compiles = executor_mod._PROGRAM_COMPILES.value - compiles0
        t.enabled = True
        led = t.ledger()
    finally:
        t.enabled = was_enabled
        prof.enabled = prof_was_enabled

    p50 = {name: float(np.median(lat)) for name, (_, lat) in phases.items()}
    overhead = (round(100.0 * (p50["on"] - p50["off"]) / p50["off"], 2)
                if p50["off"] > 0 else None)
    result = {
        "metric": f"goodput tracker steady-state step overhead "
                  f"(per-step productive-time accounting, "
                  f"{'toy' if tiny else 'BERT-Large layer'} gradient "
                  f"shapes, np={world})",
        "value": overhead,
        "unit": "%",
        "goal": "< 1%",
        "p50_ms_goodput_off": round(p50["off"] * 1e3, 3),
        "p50_ms_goodput_on": round(p50["on"] * 1e3, 3),
        "steady_state_compiles": int(steady_compiles),
        "steps_productive": led["steps_productive"],
        "goodput_fraction": led["goodput_fraction"],
    }
    if tiny:
        result["tiny"] = True
    log(f"goodput: p50 off {result['p50_ms_goodput_off']} ms, "
        f"on {result['p50_ms_goodput_on']} ms ({overhead}%); "
        f"compiles(timed)={steady_compiles}; "
        f"fraction={led['goodput_fraction']}")
    print(json.dumps(result), flush=True)
    return result


def _bert_large_param_shapes():
    """BERT-Large parameter shapes (L=24, d=1024, ff=4096, vocab 30522,
    seq 512) as a flat dict — ~335M params, the flagship workload's
    optimizer-state footprint without building the model."""
    shapes = {
        "embed/token": (30522, 1024), "embed/pos": (512, 1024),
        "embed/type": (2, 1024),
        "embed/ln_scale": (1024,), "embed/ln_bias": (1024,),
        "pooler/kernel": (1024, 1024), "pooler/bias": (1024,),
    }
    for i in range(24):
        p = "layer%02d/" % i
        shapes.update({
            p + "q_kernel": (1024, 1024), p + "q_bias": (1024,),
            p + "k_kernel": (1024, 1024), p + "k_bias": (1024,),
            p + "v_kernel": (1024, 1024), p + "v_bias": (1024,),
            p + "o_kernel": (1024, 1024), p + "o_bias": (1024,),
            p + "mlp_in_kernel": (1024, 4096), p + "mlp_in_bias": (4096,),
            p + "mlp_out_kernel": (4096, 1024), p + "mlp_out_bias": (1024,),
            p + "ln1_scale": (1024,), p + "ln1_bias": (1024,),
            p + "ln2_scale": (1024,), p + "ln2_bias": (1024,),
        })
    return shapes


def sharded_optimizer_main(tiny: bool = False):
    """ZeRO sharded-training microbench: the optimizer UPDATE phase
    (gradient reduction + AdamW + new params on every chip) at the
    BERT-Large parameter shape, replicated vs sharded stages 1/2/3.

    Replicated: ``allreduce_gradients`` + jitted f32 optax adamw —
    every chip holds the full mu/nu. Stage 1: ``hvd.sharded_adamw`` —
    reduce-scatter, fused flat-buffer AdamW on the local fp32
    master/moment shards, allgather. Stage 2: gradients pre-scattered
    (``hvd.scatter_gradients``), so only the scatter half of the
    allreduce rides the wire. Stage 3: params sharded at rest
    (``hvd.shard_params``) and re-gathered bucket-by-bucket with the
    prefetch window, as a forward pass would. Each stage reports p50
    update ms, ``bytes_per_chip`` for params/grads/optimizer state,
    gradient and total wire bytes per step, and the steady-state
    program-build count over the timed phase (must be zero — same
    invariant as the data-plane microbench).

    ``tiny`` (--tiny / the tier-1 smoke test): a toy shape + 2 steps."""
    import optax as _optax

    from horovod_tpu.parallel import zero as zero_mod
    from horovod_tpu.parallel.dp import allreduce_gradients

    hvd.init()
    world = hvd.size()
    if tiny:
        shapes = {"w0": (256, 64), "b0": (64,), "w1": (1000,),
                  "emb": (128, 32)}
        warmup_steps, timed_steps = 1, 2
    else:
        shapes = _bert_large_param_shapes()
        warmup_steps, timed_steps = 2, 8
    rng = np.random.RandomState(0)
    params = {k: jnp.asarray(rng.standard_normal(v).astype(np.float32)
                             * 0.02)
              for k, v in shapes.items()}
    grads = {k: jnp.asarray(rng.standard_normal(v).astype(np.float32))
             for k, v in shapes.items()}
    n_params = sum(int(np.prod(v)) for v in shapes.values())
    log(f"sharded-optimizer bench: {n_params / 1e6:.0f}M params, "
        f"np={world}{' (tiny)' if tiny else ''}")

    def _tree_bytes(tree):
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
                   if hasattr(x, "nbytes"))

    def _metric_value(name, default=None):
        m = hvd.metrics().get(name)
        if not m or not m.get("values"):
            return default
        return m["values"][0]["value"]

    # --- replicated baseline: allreduce + full-state adamw on every chip
    inner = _optax.adamw(1e-4)
    rep_state = inner.init(params)
    rep_bytes = _tree_bytes(rep_state)

    @jax.jit
    def rep_step(g, s, p):
        upd, s = inner.update(g, s, p)
        return _optax.apply_updates(p, upd), s

    def replicated_update(p, s, g):
        g = allreduce_gradients(g, average=True)
        return rep_step(g, s, p)

    lat_rep = []
    p, s = params, rep_state
    for step in range(warmup_steps + timed_steps):
        t0 = time.perf_counter()
        p, s = replicated_update(p, s, grads)
        jax.block_until_ready(jax.tree_util.tree_leaves(p)[0])
        if step >= warmup_steps:
            lat_rep.append(time.perf_counter() - t0)

    # --- sharded: RS + fused flat AdamW on the local shard + AG
    sopt = hvd.sharded_adamw(1e-4)
    sh_state = sopt.init(params)
    lat_sh = []
    builds_before = None
    p = params
    for step in range(warmup_steps + timed_steps):
        if step == warmup_steps:
            builds_before = _metric_value(
                "horovod_sharded_program_builds_total", 0)
        t0 = time.perf_counter()
        p, sh_state = sopt.apply(p, sh_state, grads)
        jax.block_until_ready(jax.tree_util.tree_leaves(p)[0])
        if step >= warmup_steps:
            lat_sh.append(time.perf_counter() - t0)
    steady_builds = (_metric_value("horovod_sharded_program_builds_total",
                                   0) - builds_before)
    sharded_bytes = _metric_value("horovod_sharded_state_bytes",
                                  _tree_bytes(sh_state))

    # --- per-stage rows: stage 2 (grads pre-scattered) and stage 3
    # (params sharded at rest + bucket-wise prefetched gather), with wire
    # bytes per step read off the zero-lane RS/AG counters
    _RS = "horovod_sharded_reducescatter_bytes_total"
    _AG = "horovod_sharded_allgather_bytes_total"

    def _spec_shard_bytes(spec):
        return sum(g.shard_elems * np.dtype(g.dtype).itemsize
                   for g in spec.groups)

    def _timed_stage(step_fn, p0, s0):
        lat, marks = [], None
        p_, s_ = p0, s0
        for step in range(warmup_steps + timed_steps):
            if step == warmup_steps:
                marks = (_metric_value(
                    "horovod_sharded_program_builds_total", 0),
                    _metric_value(_RS, 0), _metric_value(_AG, 0))
            t0 = time.perf_counter()
            p_, s_ = step_fn(p_, s_)
            jax.block_until_ready(jax.tree_util.tree_leaves(p_)[0])
            if step >= warmup_steps:
                lat.append(time.perf_counter() - t0)
        builds = (_metric_value("horovod_sharded_program_builds_total", 0)
                  - marks[0])
        rs = (_metric_value(_RS, 0) - marks[1]) / timed_steps
        ag = (_metric_value(_AG, 0) - marks[2]) / timed_steps
        return float(np.median(lat)), rs, ag, builds, s_

    params_full = _tree_bytes(params)
    grads_full = _tree_bytes(grads)

    def _stage_row(p50_s, rs, ag, builds, pbytes, gbytes):
        return {
            "update_p50_ms": round(p50_s * 1e3, 2),
            "bytes_per_chip": {
                "params": int(pbytes), "grads": int(gbytes),
                "optimizer_state": int(sharded_bytes)},
            "grad_wire_bytes_per_step": int(rs),
            "wire_bytes_per_step": int(rs + ag),
            "steady_state_builds": int(builds),
        }

    # stage 2: scatter each step's gradients, feed the shard to the
    # partition-aligned optimizer; the trailing AG rebuilds full params
    sopt2 = hvd.sharded_adamw(1e-4)
    s2_state = sopt2.init(params)

    def _step2(p_, s_):
        sg = zero_mod.scatter_gradients(grads, spec=s_.spec)
        return sopt2.apply(p_, s_, sg)

    p50_s2, rs2, ag2, builds2, s2_state = _timed_stage(
        _step2, params, s2_state)
    grad_shard_bytes = _spec_shard_bytes(s2_state.spec)
    stage2 = _stage_row(p50_s2, rs2, ag2, builds2,
                        params_full, grad_shard_bytes)

    # stage 3: params sharded at rest; the update keeps them sharded and
    # each step re-gathers bucket-by-bucket under the prefetch window,
    # standing in for the forward pass's on-demand consumption
    sopt3 = hvd.sharded_adamw(1e-4)
    sp3 = hvd.shard_params(params)
    s3_state = sopt3.init(sp3)
    param_shard_bytes = _spec_shard_bytes(sp3.spec)

    def _step3(p_, s_):
        sg = zero_mod.scatter_gradients(grads, spec=s_.spec)
        p_, s_ = sopt3.apply(p_, s_, sg)
        for _gi, _bucket in hvd.iter_param_buckets(p_):
            pass
        return p_, s_

    p50_s3, rs3, ag3, builds3, _ = _timed_stage(_step3, sp3, s3_state)
    stage3 = _stage_row(p50_s3, rs3, ag3, builds3,
                        param_shard_bytes, grad_shard_bytes)
    stage3["gather_hidden_fraction"] = round(
        zero_mod.gather_hidden_fraction(), 4)

    p50_rep = float(np.median(lat_rep))
    p50_sh = float(np.median(lat_sh))
    stage1 = {
        "update_p50_ms": round(p50_sh * 1e3, 2),
        "bytes_per_chip": {
            "params": int(params_full), "grads": int(grads_full),
            "optimizer_state": int(sharded_bytes)},
        # stage 1 exchanges the full gradient: RS + AG = one allreduce
        "grad_wire_bytes_per_step": int(rs2 + ag2),
        "wire_bytes_per_step": int(rs2 + ag2),
        "steady_state_builds": int(steady_builds),
    }
    result = {
        "metric": f"sharded optimizer update p50 (ZeRO-1 fused AdamW, "
                  f"BERT-Large shape {n_params / 1e6:.0f}M params, "
                  f"np={world})",
        "value": round(p50_sh * 1e3, 2),
        "unit": "ms",
        "vs_baseline": round(p50_rep / p50_sh, 3) if p50_sh > 0 else None,
        "replicated_p50_ms": round(p50_rep * 1e3, 2),
        "sharded_p50_ms": round(p50_sh * 1e3, 2),
        "opt_state_bytes_per_chip": {
            "replicated": int(rep_bytes),
            "sharded": int(sharded_bytes),
        },
        "state_bytes_reduction_x": (
            round(rep_bytes / sharded_bytes, 2) if sharded_bytes else None),
        "steady_state_program_builds": int(steady_builds),
        "stages": {"stage1": stage1, "stage2": stage2, "stage3": stage3},
        **memory_rows(),
        **comms_rows(),
        **goodput_rows(),
    }
    if tiny:
        result["tiny"] = True
    log(f"update p50: replicated {result['replicated_p50_ms']} ms, "
        f"sharded {result['sharded_p50_ms']} ms; state bytes/chip "
        f"{rep_bytes} -> {sharded_bytes} "
        f"({result['state_bytes_reduction_x']}x); steady-state program "
        f"builds {steady_builds}")
    for sname, row in result["stages"].items():
        log(f"  {sname}: update p50 {row['update_p50_ms']} ms, "
            f"bytes/chip params={row['bytes_per_chip']['params']} "
            f"grads={row['bytes_per_chip']['grads']} "
            f"opt={row['bytes_per_chip']['optimizer_state']}, grad wire "
            f"{row['grad_wire_bytes_per_step']} B/step, total wire "
            f"{row['wire_bytes_per_step']} B/step, steady-state builds "
            f"{row['steady_state_builds']}")
    print(json.dumps(result), flush=True)
    return result


def checkpoint_main(tiny: bool = False):
    """Crash-consistent checkpoint microbench: commit latency, inline
    (snapshot-to-slab) cost, bytes/rank, and the derived steady-state
    step overhead of periodic async commits at the BERT-Large optimizer
    footprint (params + fp32 Adam moments, ~4 GB/rank at np=1).

    The training step proxy is the jitted full-state AdamW update at the
    same shape — the commit's inline cost amortized over a realistic
    checkpoint interval (every 100 steps), divided by that step time, is
    the headline ``value`` (goal: < 2%). Commits use the same zero-copy
    handoff as the elastic integration (``copy=False`` — the trees are
    an immutable snapshot, so the slab copy is skipped). Also measured directly: one
    step timed WHILE the background writer drains, so compute/IO
    contention shows up as ``contended_step_slowdown_pct`` rather than
    being assumed away.

    ``tiny`` (--tiny / the tier-1 smoke test): toy shapes, one commit."""
    import shutil
    import tempfile

    import optax as _optax

    from horovod_tpu import ckpt as _ckpt
    from horovod_tpu.ckpt import stats as _ckpt_stats

    hvd.init()
    if tiny:
        shapes = {"w0": (256, 64), "b0": (64,), "emb": (128, 32)}
        warmup_steps, timed_steps, n_commits, interval = 1, 2, 1, 100
    else:
        shapes = _bert_large_param_shapes()
        warmup_steps, timed_steps, n_commits, interval = 1, 3, 2, 100
    rng = np.random.RandomState(0)
    params = {k: jnp.asarray(rng.standard_normal(v).astype(np.float32)
                             * 0.02)
              for k, v in shapes.items()}
    grads = {k: jnp.asarray(rng.standard_normal(v).astype(np.float32))
             for k, v in shapes.items()}
    n_params = sum(int(np.prod(v)) for v in shapes.values())
    log(f"checkpoint bench: {n_params / 1e6:.0f}M params"
        f"{' (tiny)' if tiny else ''}")

    inner = _optax.adamw(1e-4)
    opt_state = inner.init(params)

    @jax.jit
    def train_step(g, s, p):
        upd, s = inner.update(g, s, p)
        return _optax.apply_updates(p, upd), s

    # baseline: the update step alone
    p, s = params, opt_state
    lat_step = []
    for step in range(warmup_steps + timed_steps):
        t0 = time.perf_counter()
        p, s = train_step(grads, s, p)
        jax.block_until_ready(jax.tree_util.tree_leaves(p)[0])
        if step >= warmup_steps:
            lat_step.append(time.perf_counter() - t0)
    t_step = float(np.median(lat_step))

    directory = tempfile.mkdtemp(prefix="hvd-bench-ckpt-")
    mgr = _ckpt.CheckpointManager(directory, async_write=True, keep=1)
    trees = {"params": p, "opt": jax.device_get(s)}
    lat_inline, lat_e2e, lat_contended = [], [], []
    bytes_rank = 0
    try:
        for i in range(n_commits):
            t0 = time.perf_counter()
            # copy=False mirrors the elastic integration: the trees are
            # an immutable snapshot (jax arrays; rebound, never mutated)
            mgr.commit(trees, step=i + 1, rank=0, world=1, copy=False)
            lat_inline.append(time.perf_counter() - t0)
            # one step racing the background serialize+write: real
            # contention, not an assumption
            tc = time.perf_counter()
            p, s = train_step(grads, s, p)
            jax.block_until_ready(jax.tree_util.tree_leaves(p)[0])
            lat_contended.append(time.perf_counter() - tc)
            mgr.wait()
            lat_e2e.append(time.perf_counter() - t0)
        latest = _ckpt.latest_step(directory)
        from horovod_tpu.ckpt import manifest as _manifest
        mf = _manifest.load_manifest(directory, latest)
        bytes_rank = int(mf["shards"][0]["bytes"])
    finally:
        mgr.close()
        shutil.rmtree(directory, ignore_errors=True)

    t_inline = float(np.median(lat_inline))
    t_e2e = float(np.median(lat_e2e))
    t_contended = float(np.median(lat_contended))
    overhead_pct = 100.0 * t_inline / (t_inline + interval * t_step) \
        if t_step > 0 else None
    contention_pct = (100.0 * (t_contended - t_step) / t_step
                      if t_step > 0 else None)
    result = {
        "metric": f"checkpoint steady-state step overhead (async commit "
                  f"every {interval} steps, "
                  f"{'toy shape' if tiny else 'BERT-Large shape'} "
                  f"{n_params / 1e6:.0f}M params + fp32 Adam moments)",
        "value": round(overhead_pct, 3) if overhead_pct is not None
        else None,
        "unit": "%",
        "goal": "< 2%",
        "commit_inline_p50_ms": round(t_inline * 1e3, 2),
        "commit_e2e_p50_ms": round(t_e2e * 1e3, 2),
        "step_p50_ms": round(t_step * 1e3, 2),
        "contended_step_slowdown_pct": (
            round(contention_pct, 1) if contention_pct is not None
            else None),
        "bytes_per_rank": bytes_rank,
        "checkpoint_interval_steps": interval,
        "commits_abandoned": int(
            _ckpt_stats.COMMITS_ABANDONED.value
            if hasattr(_ckpt_stats.COMMITS_ABANDONED, "value") else 0),
    }
    if tiny:
        result["tiny"] = True
    log(f"commit inline p50 {result['commit_inline_p50_ms']} ms, e2e "
        f"{result['commit_e2e_p50_ms']} ms, {bytes_rank} bytes/rank; "
        f"step {result['step_p50_ms']} ms -> "
        f"{result['value']}% overhead at every-{interval}-steps "
        f"(contended step +{result['contended_step_slowdown_pct']}%)")
    print(json.dumps(result), flush=True)
    return result


def serve_main(tiny: bool = False, prefix_heavy: bool = False):
    """``--serve``: load-generate Poisson traffic against an in-process
    continuous-batching replica set (serve/; docs/inference.md) and
    report the serving headline — p50/p99 request latency, tokens/s/chip
    and batch occupancy — plus the zero-steady-state-compiles canary:
    after one warmup prefill per prompt-length bucket per replica, the
    measured window must compile NOTHING (the fixed-shape decode program
    and the bucketed prefill programs are already hot).

    ``--prefix-heavy`` switches the traffic to the shared-system-prompt
    shape (every request opens with the same long prefix, RAG/chat
    style) and runs it twice on one paged replica set — unshared
    baseline first, shared second — so the headline carries the prefix-
    cache effect as a pair: ``p50_ttft_ms`` vs ``p50_ttft_ms_no_share``
    and the token-weighted ``prefix_hit_rate``. Forces
    ``HOROVOD_SERVE_PAGED`` semantics (serve/paging.py); the remaining
    paging knobs still come from the environment.

    ``--tiny`` shrinks to a toy model + 16 requests for the tier-1 smoke
    (tests/test_bench_smoke.py); numbers are then meaningless."""
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import GPT2Small, Transformer
    from horovod_tpu.serve import prompt_bucket, serve as hvd_serve

    if tiny:
        model = Transformer(vocab_size=128, d_model=32, num_layers=2,
                            num_heads=2, d_ff=64, max_seq=96, causal=True,
                            dtype=jnp.float32)
        replicas, slots, n_requests = 2, 4, 16
        rate_rps, max_new = 400.0, 8
        prompt_choices = (4, 9, 17, 33)
        prefix_len, tail_len = 48, 5
    else:
        # "GPT-small" replica set: the GPT-2 shape at a serving-friendly
        # context length
        model = GPT2Small(vocab_size=50304, max_seq=512)
        replicas, slots, n_requests = 2, 8, 200
        rate_rps, max_new = 40.0, 32
        prompt_choices = (24, 56, 100, 180, 250)
        prefix_len, tail_len = 192, 12

    log(f"serve: initializing {replicas} replica(s) "
        f"(slots={slots}, max_new={max_new}"
        f"{', prefix-heavy' if prefix_heavy else ''})")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    overrides = dict(slots=slots, max_new_tokens=max_new,
                     admission_ms=25.0, decode_block=4,
                     max_batch_tokens=4096)
    if prefix_heavy:
        overrides["paged"] = True   # prefix reuse needs the paged cache
    handle = hvd_serve(model, params, replicas=replicas, **overrides)
    try:
        # warmup: hit every prompt-length bucket on EVERY replica's own
        # program cache (replicas compile independently), plus one
        # decode step each — all while the queue is idle, so the replica
        # threads never race these direct engine calls. Warmup prompts
        # are DISTINCT per bucket ([b]*b): under HOROVOD_SERVE_PAGED a
        # repeated prompt would prefix-hit the previous bucket's pages,
        # shrink the computed suffix, and leave the larger prefill
        # program cold — a steady-state compile later.
        warm_lens = list(prompt_choices)
        if prefix_heavy:
            # the shared-prefix phase prefills the full prompt once,
            # then only the post-hit tail — warm both bucket shapes
            warm_lens += [tail_len, prefix_len + tail_len]
        buckets = sorted({prompt_bucket(p, model.max_seq)
                          for p in warm_lens})
        # tuple(): the dense engine's calls return pending results;
        # unpacking one blocks until it is on the host
        for replica in handle._replicas:
            for b in buckets:
                tuple(replica.engine.prefill(0, [b % model.vocab_size] * b))
            tuple(replica.engine.decode([0], [1], [0]))
        warm_compiles = handle.compiles_total()
        warm_steps = sum(r.engine.decode_steps for r in handle._replicas)
        log(f"serve: warm ({warm_compiles} compiles across "
            f"{len(buckets)} buckets x {replicas} replicas)")

        rng = np.random.RandomState(0)

        def run_phase(prompts):
            """Poisson-offered load; returns (completions, elapsed_s)."""
            uids = []
            t_phase = time.perf_counter()
            for prompt in prompts:
                time.sleep(rng.exponential(1.0 / rate_rps))
                uids.append(handle.submit(prompt))
            phase_outs = [handle.result(u, timeout=300.0) for u in uids]
            return phase_outs, time.perf_counter() - t_phase

        def random_prompt(length):
            return rng.randint(1, model.vocab_size, length).tolist()

        ttft_no_share_ms = None
        t0 = time.perf_counter()
        if prefix_heavy:
            # phase A — unshared baseline: same lengths, same load, no
            # common prefix, so every prefill computes the full prompt
            base_outs, _ = run_phase(
                [random_prompt(prefix_len + tail_len)
                 for _ in range(n_requests)])
            ttft_no_share_ms = sorted(o.ttft_s * 1000.0
                                      for o in base_outs)
            # phase B — shared system prompt + short unique tails; every
            # 4th request repeats a tail so the exact-replay path (a
            # whole-prompt hit: zero prefill compute) is exercised too
            shared = random_prompt(prefix_len)
            tails = [random_prompt(tail_len) for _ in range(n_requests)]
            for i in range(3, n_requests, 4):
                tails[i] = tails[i - 2]
            reused0 = sum(r.engine.reused_tokens
                          for r in handle._replicas)
            computed0 = sum(r.engine.computed_tokens
                            for r in handle._replicas)
            t0 = time.perf_counter()
            outs, elapsed = run_phase([shared + t for t in tails])
        else:
            outs, elapsed = run_phase(
                [random_prompt(int(rng.choice(prompt_choices)))
                 for _ in range(n_requests)])

        latencies_ms = sorted(o.latency_s * 1000.0 for o in outs)
        ttft_ms = sorted(o.ttft_s * 1000.0 for o in outs)
        decode_tokens = sum(len(o.tokens) for o in outs)
        steps = (sum(r.engine.decode_steps for r in handle._replicas)
                 - warm_steps)
        occ = sum(r.occupancy_sum for r in handle._replicas)

        # interleaved A/B overhead probe: decode-path cost with the
        # tracing plane off vs on, doing the per-step span work the
        # replica loop does — a ``serve.step`` span around the engine's
        # own ``engine.decode`` spans (prep, dispatch, wait). Arms
        # interleave so clock drift and cache effects cancel; runs on the
        # hot decode program with the queue idle, so it must also compile
        # nothing.
        from horovod_tpu import tracing as tracing_mod

        probe_engine = handle._replicas[0].engine
        n_probe = 60 if tiny else 200
        tracer = tracing_mod.tracer()
        was_enabled = tracer.enabled
        off_s, on_s = [], []
        for i in range(2 * n_probe):
            trace_on = i % 2 == 1
            tracer.enabled = trace_on
            t_probe = time.perf_counter()
            with tracing_mod.span("serve.step"):
                tuple(probe_engine.decode([0], [1], [0]))
            (on_s if trace_on else off_s).append(
                time.perf_counter() - t_probe)
        tracer.enabled = was_enabled
        p50_off = float(np.percentile(off_s, 50))
        p50_on = float(np.percentile(on_s, 50))
        tracing_overhead_pct = (100.0 * (p50_on - p50_off) / p50_off
                                if p50_off > 0 else 0.0)
        log(f"serve: tracing A/B decode p50 off={p50_off * 1e3:.3f} ms "
            f"on={p50_on * 1e3:.3f} ms ({tracing_overhead_pct:+.2f}%)")

        # measured AFTER the probe: the tracing arm must not have
        # compiled anything either
        steady_compiles = handle.compiles_total() - warm_compiles
        slo = tracing_mod.slo_state()
        result = {
            "bench": "serve",
            "metric": "serving decode throughput (Poisson load, "
                      "continuous batching)",
            "value": round(decode_tokens / elapsed / replicas, 2),
            "unit": "tokens/sec/chip",
            "replicas": replicas,
            "requests": n_requests,
            "offered_rps": rate_rps,
            "p50_latency_ms": round(
                float(np.percentile(latencies_ms, 50)), 3),
            "p99_latency_ms": round(
                float(np.percentile(latencies_ms, 99)), 3),
            "p50_ttft_ms": round(float(np.percentile(ttft_ms, 50)), 3),
            "p99_ttft_ms": round(float(np.percentile(ttft_ms, 99)), 3),
            "avg_batch_occupancy": round(occ / max(steps, 1), 3),
            "steady_state_compiles": steady_compiles,
            "warmup_compiles": warm_compiles,
            "served_by": sorted({o.rank for o in outs}),
            # KV bytes/chip next to tokens/s/chip (docs/memory.md): the
            # replica stats carry per-replica cache bytes + slot-
            # occupancy-weighted utilization
            "kv_cache_bytes_per_chip": int(
                sum(r.stats()["kv_cache_bytes"]
                    for r in handle._replicas) / max(replicas, 1)),
            "kv_utilization": round(
                sum(r.stats()["kv_utilization"]
                    for r in handle._replicas) / max(replicas, 1), 3),
            "paged": bool(handle.policy.paged),
            # SLO plane (tracing.py; docs/tracing.md): per-objective
            # burn rate + remaining error budget over the run, and the
            # decode-path cost of having the plane on at all
            "tracing_overhead_pct": round(tracing_overhead_pct, 2),
            "spans_recorded": tracing_mod.tracer().spans_recorded(),
            "slo_requests_scored": slo["requests_scored"],
            "slo_burn_rate": {
                obj: slo["slo"][obj]["burn_rate"]
                for obj in ("ttft", "latency", "availability")},
            "slo_error_budget_remaining": {
                obj: slo["slo"][obj]["error_budget_remaining"]
                for obj in ("ttft", "latency", "availability")},
            "tiny": tiny,
            **memory_rows(params),
            **comms_rows(),
            **goodput_rows(),
        }
        if handle.policy.paged:
            # paged-cache headline (serve/paging.py): pool occupancy per
            # decode step, token-weighted prefix reuse, and the
            # admission pressure valves actually firing
            stats = [r.stats() for r in handle._replicas]
            result["page_utilization"] = round(
                sum(s["page_utilization"] for s in stats)
                / max(replicas, 1), 3)
            result["prefix_hit_rate"] = round(
                sum(s["prefix_hit_rate"] for s in stats)
                / max(replicas, 1), 3)
            result["preemptions"] = sum(s["preemptions"] for s in stats)
            result["cow_copies"] = sum(s["pages"]["cow_copies"]
                                       for s in stats)
        if prefix_heavy:
            result["prefix_heavy"] = True
            result["p50_ttft_ms_no_share"] = round(
                float(np.percentile(ttft_no_share_ms, 50)), 3)
            # hit rate over the SHARED phase only — the baseline phase
            # computes everything and would dilute the headline
            reused = (sum(r.engine.reused_tokens
                          for r in handle._replicas) - reused0)
            computed = (sum(r.engine.computed_tokens
                            for r in handle._replicas) - computed0)
            result["prefix_hit_rate"] = round(
                reused / max(reused + computed, 1), 3)
            log(f"serve: prefix-heavy p50 ttft shared "
                f"{result['p50_ttft_ms']} ms vs unshared "
                f"{result['p50_ttft_ms_no_share']} ms, hit rate "
                f"{result['prefix_hit_rate']}")
    finally:
        handle.close()
    print(json.dumps(result), flush=True)
    return result


def tiny_main():
    """Bare ``--tiny``: a toy flagship headline through the REAL measured
    path — DistributedOptimizer + make_train_round + the step profiler —
    in seconds on any backend. The tier-1 smoke for the step_breakdown /
    comm_hidden_fraction fields; the numbers are meaningless."""
    import flax.linen as nn

    class TinyNet(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))

    hvd.init()
    n_chips = hvd.size()
    batch_per_chip, steps_per_round, rounds = 8, 2, 3
    global_batch = batch_per_chip * n_chips
    model = TinyNet()
    optimizer = hvd.DistributedOptimizer(optax.sgd(0.01 * n_chips))
    state = training.create_train_state(model, optimizer, (1, 8, 8, 3))
    round_fn, batch_sharding = training.make_train_round(
        model, optimizer, steps=steps_per_round)
    rng = np.random.RandomState(0)
    images = jax.device_put(
        rng.uniform(-1, 1, (global_batch, 8, 8, 3)).astype(np.float32),
        batch_sharding)
    labels = jax.device_put(
        rng.randint(0, 10, (global_batch,)).astype(np.int32),
        batch_sharding)
    params, stats, opt_state = (state.params, state.batch_stats,
                                state.opt_state)
    loss, params, stats, opt_state = round_fn(params, stats, opt_state,
                                              images, labels)  # warmup
    jax.block_until_ready(loss)
    # ~2x3e4 MACs/image through the two dense layers; fwd+bwd ≈ 3x
    flops_per_image = 3 * 2 * (8 * 8 * 3 * 32 + 32 * 10)
    enable_profiler(batch_per_chip * steps_per_round * flops_per_image)
    rates = []
    for r in range(rounds):
        t0 = time.perf_counter()
        with hvd.profiler.step(f"tiny round {r}"):
            loss, params, stats, opt_state = round_fn(
                params, stats, opt_state, images, labels)
            jax.block_until_ready(loss)
        rates.append(global_batch * steps_per_round
                     / (time.perf_counter() - t0))
    breakdown, hidden_fraction, hidden_bytes = step_profile(rounds)
    per_chip = float(np.median(rates)) / n_chips
    result = {
        "metric": "images/sec/chip (tiny MLP smoke, synthetic)",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "mfu": mfu(per_chip * flops_per_image),
        "step_breakdown": breakdown,
        "comm_hidden_fraction": hidden_fraction,
        "comm_hidden_fraction_bytes": hidden_bytes,
        "tiny": True,
        **memory_rows(params),
        **comms_rows(),
        **goodput_rows(),
    }
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default=None,
                        choices=["resnet50", "inception", "vgg", "bert",
                                 "bert-large", "gpt2"],
                        help="run ONE model headline; default (no flags) "
                             "runs every headline plus the control-plane "
                             "lines")
    parser.add_argument("--all", action="store_true",
                        help="emit every model headline + the "
                             "control-plane lines (same as no flags; "
                             "kept for compatibility with r3 scripts)")
    parser.add_argument("--control-plane", action="store_true",
                        help="benchmark the control plane (negotiation/"
                             "cache/fusion/autotune) at np=4 on host")
    parser.add_argument("--hierarchy", action="store_true",
                        help="A/B flat vs hierarchical host collectives "
                             "(group size 2) with/without a throttled "
                             "cross-group hop and the fp16 slow-hop "
                             "codec, at np=4 on host; full mode adds "
                             "the autotuner convergence ratio")
    parser.add_argument("--collectives", action="store_true",
                        help="microbench the data plane: steady-state "
                             "fused allreduce latency vs payload size + "
                             "XLA compile count (one JSON line)")
    parser.add_argument("--integrity", action="store_true",
                        help="microbench the numerical-integrity plane: "
                             "in-band digest overhead vs interval at "
                             "BERT-Large gradient shapes + compile-count "
                             "canary (one JSON line)")
    parser.add_argument("--sharded-optimizer", action="store_true",
                        help="microbench the ZeRO-1 sharded optimizer "
                             "update phase (replicated vs sharded AdamW "
                             "at the BERT-Large shape; one JSON line)")
    parser.add_argument("--checkpoint", action="store_true",
                        help="microbench crash-consistent checkpointing: "
                             "async commit inline/e2e latency, bytes/rank "
                             "and the derived steady-state step overhead "
                             "at the BERT-Large shape (one JSON line)")
    parser.add_argument("--serve", action="store_true",
                        help="benchmark the online serving plane: Poisson "
                             "arrivals against a GPT-small continuous-"
                             "batching replica set — p50/p99 latency, "
                             "tokens/s/chip, batch occupancy and the "
                             "zero-steady-state-compiles canary (one "
                             "JSON line)")
    parser.add_argument("--prefix-heavy", action="store_true",
                        help="with --serve: shared-system-prompt traffic "
                             "on a paged replica set, run unshared then "
                             "shared — headline adds prefix_hit_rate and "
                             "p50_ttft_ms_no_share (serve/paging.py)")
    parser.add_argument("--memory", action="store_true",
                        help="microbench the memory telemetry plane: "
                             "tracker push + reconciliation sampler "
                             "overhead at BERT-Large gradient shapes, "
                             "interleaved A/B, plus the ledger and "
                             "claimed-vs-actual drift (one JSON line)")
    parser.add_argument("--comms", action="store_true",
                        help="microbench the collective-transport "
                             "observatory: per-dispatch algbw/busbw "
                             "accounting overhead at BERT-Large gradient "
                             "shapes, interleaved A/B + compile-count "
                             "canary (one JSON line)")
    parser.add_argument("--goodput", action="store_true",
                        help="microbench the goodput ledger: per-step "
                             "productive-time accounting overhead at "
                             "BERT-Large gradient shapes, interleaved "
                             "A/B + compile-count canary (one JSON "
                             "line)")
    parser.add_argument("--tiny", action="store_true",
                        help="toy sizes + a couple of steps for "
                             "--collectives/--sharded-optimizer/"
                             "--checkpoint/--serve, or (with "
                             "no workload flag) a toy flagship headline "
                             "with step_breakdown/comm_hidden_fraction — "
                             "the tier-1 smoke-test mode; numbers are "
                             "meaningless")
    parser.add_argument("--budget-seconds", type=float, default=None,
                        help="wall-clock budget for the no-flag sweep; "
                             "bonus workloads are trimmed or skipped "
                             "(loudly) once it would be exceeded "
                             "(default: BENCH_TIME_BUDGET env, 660)")
    cli = parser.parse_args()
    # These two only spawn CPU worker processes: the parent touches no
    # backend (and so holds no chip) before or while they run.
    if cli.control_plane:
        control_plane_main()
        sys.exit(0)
    if cli.hierarchy:
        hierarchy_main(tiny=cli.tiny)
        sys.exit(0)
    log(f"compile cache: {compile_cache.configure()}")
    if cli.serve:
        serve_main(tiny=cli.tiny, prefix_heavy=cli.prefix_heavy)
    elif cli.memory:
        memory_main(tiny=cli.tiny)
    elif cli.comms:
        comms_main(tiny=cli.tiny)
    elif cli.goodput:
        goodput_main(tiny=cli.tiny)
    elif cli.collectives:
        collectives_main(tiny=cli.tiny)
    elif cli.integrity:
        integrity_main(tiny=cli.tiny)
    elif cli.checkpoint:
        checkpoint_main(tiny=cli.tiny)
    elif cli.sharded_optimizer:
        sharded_optimizer_main(tiny=cli.tiny)
    elif cli.model is not None and not cli.all:
        if cli.model in ("bert", "bert-large", "gpt2"):
            transformer_main(cli.model)
        else:
            main(cli.model)
    elif cli.tiny:
        tiny_main()
    else:
        # No flags (or --all) = the full perf picture in one run (VERDICT
        # r3 ask 2): the driver's artifact then carries every headline,
        # not just ResNet. Failures are per-line — one model crashing
        # (e.g. an OOM on a smaller chip) must not blank the whole
        # artifact, but it does fail the run (exit code 1 after the
        # last row). Env overrides are ignored here (see main()).
        #   Ordering (r5): BERT-Large FIRST — it is the flagship number,
        # and r4's alphabetical-ish order let the driver timeout cut it
        # (round 4: rc=124, parsed=GPT-2). Everything after the
        # first line is gravy if the window closes early.
        import traceback
        results = []
        failed = []

        def emit_summary():
            # Cumulative summary after EVERY workload: the driver records
            # the LAST parsed JSON line, and its window may close mid-run
            # (round 4: rc=124) — so the artifact's tail must always be
            # a summary of everything completed SO FAR. value/unit mirror
            # the flagship (BERT-Large) row; "results" holds every line.
            flagship = results[0]
            print(json.dumps({
                "metric": "summary — all headlines (flagship: "
                          + flagship["metric"] + ")",
                "value": flagship["value"], "unit": flagship["unit"],
                "vs_baseline": flagship.get("vs_baseline"),
                "mfu": flagship.get("mfu"),
                "results": results,
            }), flush=True)

        # Time budget: the driver kills a run that overstays its window
        # (round 4: rc=124), and rc=0 with the four core rows beats
        # rc=124 with everything. Core workloads always run; each bonus
        # workload runs only if its rough cost still fits (skips are
        # LOUD — a silent cap would read as "covered everything").
        t_start = time.perf_counter()
        budget = (cli.budget_seconds if cli.budget_seconds is not None
                  else float(os.environ.get("BENCH_TIME_BUDGET", "660")))
        sweep = [
            # (fn, arg, core?, rough cold-cache cost s, micro-step cap)
            # caps keep rounds at 10-20 s (one launch each, short enough
            # to fit the budget; not re-measured): bert-large 256 at
            # accum 16 -> 16-update ~16 s rounds; bert 128 at batch 48
            # -> 32-update ~17 s rounds
            (transformer_main, "bert-large", True, 160, 256),
            (main, "resnet50", True, 45, None),
            (transformer_main, "bert", True, 140, 128),
            (transformer_main, "gpt2", True, 90, 128),
            (main, "inception", False, 85, None),
            (main, "vgg", False, 95, None),
            (sharded_optimizer_main, "sharded-optimizer", False, 60,
             None),
            (memory_main, "memory", False, 40, None),
            (checkpoint_main, "checkpoint", False, 90, None),
            (control_plane_main, None, False, 150, None),
        ]
        for fn, arg, core, est, cap in sweep:
            elapsed = time.perf_counter() - t_start
            trimmed = False
            if not core and elapsed + est > budget:
                if fn is control_plane_main:
                    # never silently drop the control-plane rows: a
                    # trimmed np=2 fast probe (~40 s) still measures the
                    # protocol's byte/step counters
                    trimmed = True
                    log(f"TRIMMED control-plane: {elapsed:.0f}s elapsed "
                        f"+ ~{est}s would exceed the {budget:.0f}s "
                        f"budget (--budget-seconds/BENCH_TIME_BUDGET); "
                        f"running the np=2 fast probe instead — run "
                        f"`python bench.py --control-plane` for the "
                        f"full protocol")
                elif fn is sharded_optimizer_main:
                    trimmed = True
                    log(f"TRIMMED sharded-optimizer: over the "
                        f"{budget:.0f}s budget; running --tiny probe — "
                        f"run `python bench.py --sharded-optimizer` "
                        f"for the real row")
                elif fn is memory_main:
                    trimmed = True
                    log(f"TRIMMED memory: over the {budget:.0f}s budget; "
                        f"running --tiny probe — run "
                        f"`python bench.py --memory` for the real row")
                elif fn is checkpoint_main:
                    trimmed = True
                    log(f"TRIMMED checkpoint: over the {budget:.0f}s "
                        f"budget; running --tiny probe — run "
                        f"`python bench.py --checkpoint` for the real "
                        f"row")
                else:
                    log(f"SKIPPED {arg}: {elapsed:.0f}s elapsed + "
                        f"~{est}s would exceed the {budget:.0f}s budget "
                        f"(--budget-seconds/BENCH_TIME_BUDGET); run "
                        f"`python bench.py --model {arg}` for this row")
                    continue
            try:
                if fn is transformer_main:
                    results.append(fn(arg, allow_env=False,
                                      micro_step_cap=cap))
                elif (fn is sharded_optimizer_main
                        or fn is checkpoint_main or fn is memory_main):
                    results.append(fn(tiny=trimmed))
                elif fn is control_plane_main:
                    results.extend(control_plane_main(
                        fast=True, np_override=2 if trimmed else None))
                else:
                    results.append(fn(arg, allow_env=False))
            except Exception:
                traceback.print_exc(file=sys.stderr)
                failed.append(arg or fn.__name__)
            if results:
                emit_summary()
        if failed:
            # the rows that survived are printed above, but a workload
            # that raised is a failed run: the driver/CI must not see
            # green
            log(f"FAILED workloads: {', '.join(failed)}")
            sys.exit(1)
