"""horovod_tpu.data — rank-sharded sampling + device prefetch.

The reference delegates input pipelines to the frameworks but fixes the
*convention* in every example: shard the dataset by rank so each worker
sees a disjoint 1/size slice, reshuffled per epoch with a common seed
(reference: examples/pytorch_mnist.py
``torch.utils.data.distributed.DistributedSampler(num_replicas=hvd.size(),
rank=hvd.rank())``; examples/keras_imagenet_resnet50.py per-rank
generators). This module provides that convention framework-free, plus the
TPU-idiomatic device side: an async prefetcher that keeps the next batches
in flight (host → HBM with the right sharding) so the step program never
waits on input — the jax analogue of the reference's framework loader
worker threads.

* :class:`ShardedSampler` — the DistributedSampler semantics: per-epoch
  deterministic shuffle shared by all workers, split into ``size`` equal
  shards (padded by wrap-around so every worker steps the same count —
  required for collective lockstep), ``set_epoch`` to reshuffle.
* :func:`prefetch_to_device` — wrap a host-batch iterator; batches are
  ``jax.device_put`` with a given sharding a configurable depth ahead, on
  a background thread. XLA's async dispatch overlaps the transfer with the
  running step.
* With the torch binding, ``torch.utils.data.distributed.DistributedSampler
  (num_replicas=hvd.size(), rank=hvd.rank())`` works as in the reference;
  tests/test_data.py pins that integration.
"""

from __future__ import annotations

import queue as queue_mod
import threading
from typing import Iterable, Iterator, Optional

import jax
import numpy as np

from horovod_tpu import tracing

__all__ = ["ShardedSampler", "prefetch_to_device"]


class ShardedSampler:
    """Per-worker view of a dataset: disjoint shards, equal length, common
    per-epoch shuffle (reference convention:
    torch DistributedSampler as used in examples/pytorch_mnist.py).

    ``len(dataset)`` need not divide ``num_replicas``: indices wrap around
    (the reference sampler's padding) so every worker yields exactly
    ``ceil(n / num_replicas)`` indices per epoch and collective calls stay
    in lockstep.
    """

    def __init__(self, dataset_size: int, num_replicas: Optional[int] = None,
                 rank: Optional[int] = None, shuffle: bool = True,
                 seed: int = 0):
        from horovod_tpu.core import basics

        if num_replicas is None:
            num_replicas = basics.size()
        if rank is None:
            rank = basics.rank()
        if not 0 <= rank < num_replicas:
            raise ValueError(
                f"rank {rank} out of range for num_replicas {num_replicas}")
        if dataset_size <= 0:
            raise ValueError("dataset_size must be positive")
        self.dataset_size = dataset_size
        self.num_replicas = num_replicas
        self.rank = rank
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self.num_samples = -(-dataset_size // num_replicas)  # ceil
        self.total_size = self.num_samples * num_replicas

    def set_epoch(self, epoch: int) -> None:
        """Reshuffle for a new epoch — same permutation on every worker
        (seed + epoch), different shard per rank."""
        self.epoch = int(epoch)

    def __iter__(self) -> Iterator[int]:
        if self.shuffle:
            rng = np.random.RandomState(self.seed + self.epoch)
            order = rng.permutation(self.dataset_size)
        else:
            order = np.arange(self.dataset_size)
        pad = self.total_size - self.dataset_size
        if pad:
            order = np.concatenate([order, order[:pad]])
        # interleaved shards of the common permutation (torch
        # DistributedSampler's rank::num_replicas striding)
        shard = order[self.rank::self.num_replicas]
        return iter(shard.tolist())

    def __len__(self) -> int:
        return self.num_samples


_END = object()


def prefetch_to_device(iterator: Iterable, size: int = 2, sharding=None):
    """Iterate ``iterator``'s batches with up to ``size`` batches already
    transferred to device (``jax.device_put`` pytree-wise, with ``sharding``
    if given — e.g. the batch sharding from ``make_train_step``).

    The transfer happens on a background thread and XLA's async dispatch
    overlaps it with the running step, so steady-state steps never wait on
    the host. Exceptions from the source iterator propagate to the
    consumer at the corresponding position. The generator's ``close()``
    (or garbage collection) stops the worker thread.
    """
    if size < 1:
        raise ValueError("prefetch size must be >= 1")

    q: queue_mod.Queue = queue_mod.Queue(maxsize=size)
    stop = threading.Event()

    def put(batch):
        if sharding is not None:
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding), batch)
        return jax.tree_util.tree_map(jax.device_put, batch)

    def worker():
        try:
            for batch in iterator:
                if stop.is_set():
                    return
                with tracing.span("input.put"):
                    batch = put(batch)
                q.put(batch)
                if stop.is_set():
                    return
            q.put(_END)
        except BaseException as exc:  # noqa: BLE001 — relayed to consumer
            q.put(exc)

    thread = threading.Thread(target=worker, daemon=True,
                              name="hvd-data-prefetch")

    def gen():
        # start lazily so a generator that is never consumed never spawns
        # (and never leaks) the worker or its in-flight device batches
        thread.start()
        try:
            while True:
                with tracing.span("input.wait", depth=q.qsize()):
                    item = q.get()
                if item is _END:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            # unblock a worker stuck on a full queue
            try:
                q.get_nowait()
            except queue_mod.Empty:
                pass

    return gen()
