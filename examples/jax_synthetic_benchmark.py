"""Synthetic ResNet benchmark — the measurement harness.

TPU-native analogue of the reference's synthetic benchmarks (reference:
examples/pytorch_synthetic_benchmark.py:37-110,
examples/tensorflow2_synthetic_benchmark.py:72-132): ResNet fwd+bwd+update
on synthetic ImageNet-shaped data, 10 warmup batches, then num-iters rounds
of num-batches-per-iter batches; reports images/sec and images/sec/chip.

    python examples/jax_synthetic_benchmark.py --model ResNet50 --batch-size 128
"""

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import models, training


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="ResNet50",
                        choices=["ResNet18", "ResNet34", "ResNet50",
                                 "ResNet101", "ResNet152",
                                 "VGG16", "InceptionV3"])
    parser.add_argument("--batch-size", type=int, default=128,
                        help="per-chip batch size")
    parser.add_argument("--num-warmup-batches", type=int, default=10)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--fp16-allreduce", action="store_true",
                        help="bf16 wire compression for gradient exchange")
    parser.add_argument("--image-size", type=int, default=None,
                        help="override input resolution (CI smoke runs)")
    parser.add_argument("--json", action="store_true",
                        help="rank 0 prints one JSON line with "
                             "imgs_per_sec / n_chips / scaling_efficiency "
                             "(the reference's headline metric, "
                             "docs/benchmarks.rst:16-64)")
    parser.add_argument("--one-chip-rate", type=float,
                        default=float(os.environ.get(
                            "BENCH_ONE_CHIP_IMGS_PER_SEC", "0")) or None,
                        help="stored 1-chip imgs/sec (run once with -np 1) "
                             "for the scaling_efficiency denominator; also "
                             "via BENCH_ONE_CHIP_IMGS_PER_SEC")
    parser.add_argument("--platform", default=None,
                        help="force a jax platform (e.g. 'cpu' for "
                             "virtual-device CI runs; applied before the "
                             "first device use)")
    args = parser.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    hvd.init()
    model = getattr(models, args.model)(num_classes=1000,
                                        dtype=jnp.bfloat16)
    image_size = args.image_size or (
        299 if args.model == "InceptionV3" else 224)
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.01 * hvd.size(), momentum=0.9), compression=compression)

    state = training.create_train_state(
        model, opt, (1, image_size, image_size, 3))
    step, batch_sharding = training.make_train_step(model, opt)

    global_batch = args.batch_size * hvd.size()
    rng = np.random.RandomState(0)
    images = jax.device_put(
        rng.rand(global_batch, image_size, image_size, 3).astype(np.float32),
        batch_sharding)
    labels = jax.device_put(
        rng.randint(0, 1000, (global_batch,)).astype(np.int32),
        batch_sharding)

    params, stats, opt_state = (state.params, state.batch_stats,
                                state.opt_state)

    def run_batch():
        nonlocal params, stats, opt_state
        loss, params, stats, opt_state = step(params, stats, opt_state,
                                              images, labels)
        return loss

    if hvd.rank() == 0:
        print(f"Model: {args.model}, batch size {args.batch_size}/chip, "
              f"{hvd.size()} chips")
    loss = run_batch()  # compile
    for _ in range(args.num_warmup_batches):
        loss = run_batch()
    jax.block_until_ready(loss)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.time()
        for _ in range(max(args.num_batches_per_iter, 1)):
            loss = run_batch()
        jax.block_until_ready(loss)
        dt = time.time() - t0
        rate = global_batch * args.num_batches_per_iter / dt
        img_secs.append(rate)
        if hvd.rank() == 0:
            print(f"Iter #{i}: {rate:.1f} img/sec total")

    if hvd.rank() == 0:
        mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
        print(f"Img/sec total: {mean:.1f} +- {conf:.1f}")
        print(f"Img/sec per chip: {mean / hvd.size():.1f}")
        if args.json:
            import json

            n = hvd.size()
            efficiency = (round(mean / (n * args.one_chip_rate), 4)
                          if args.one_chip_rate else None)
            print(json.dumps({
                "imgs_per_sec": round(float(mean), 1),
                "n_chips": n,
                "scaling_efficiency": efficiency,
            }), flush=True)


if __name__ == "__main__":
    main()
