#!/usr/bin/env python
"""BERT-Large through the torch binding with the sparse embedding path.

BASELINE progression config #5: "BERT-Large-style allgather/sparse" —
the model family trained through the framework's torch API with the
token-embedding gradient exchanged SPARSELY (allgather of values+
indices, summed on coalesce) instead of densified, the way the reference
exchanges tf.IndexedSlices (reference: horovod/tensorflow/__init__.py:
64-75; examples/pytorch_synthetic_benchmark.py is the harness shape).

Torch executes on CPU in this stack (the TPU compute path is JAX — for
the chip rate of BERT-Large see the ``bertl-train-c1`` cell of
``benchmark/run.py``); this example demonstrates config #5's *exchange
semantics* end-to-end under the launcher:

    tpurun -np 2 python examples/pytorch_bert_large_sparse.py \
        --layers 2 --d-model 128 --heads 4 --seq 32 --batch 4 \
        --steps 2                                 # CI-sized
    tpurun -np 8 python examples/pytorch_bert_large_sparse.py  # full

Prints per-rank tokens/s and verifies all ranks hold identical weights
after training (the lockstep invariant).
"""

import argparse
import time

import numpy as np
import torch

import horovod_tpu.torch as hvd

VOCAB = 30522


class BertLarge(torch.nn.Module):
    """BERT-Large-shaped encoder MLM (d=1024, 16 heads, ff 4096; layer
    count and width configurable for CI). The token embedding is
    sparse=True so its gradient takes the allgather/sparse path."""

    def __init__(self, layers=24, d_model=1024, heads=16, seq=512):
        super().__init__()
        self.tok = torch.nn.Embedding(VOCAB, d_model, sparse=True)
        self.pos = torch.nn.Embedding(seq, d_model)
        layer = torch.nn.TransformerEncoderLayer(
            d_model, heads, dim_feedforward=4 * d_model,
            batch_first=True, norm_first=True)
        self.encoder = torch.nn.TransformerEncoder(layer, layers)
        self.head = torch.nn.Linear(d_model, VOCAB)

    def forward(self, ids):
        x = self.tok(ids) + self.pos.weight[None, : ids.shape[1]]
        return self.head(self.encoder(x))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--layers", type=int, default=24)
    parser.add_argument("--d-model", type=int, default=1024)
    parser.add_argument("--heads", type=int, default=16)
    parser.add_argument("--seq", type=int, default=512)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--steps", type=int, default=3)
    args = parser.parse_args()

    hvd.init()
    torch.manual_seed(1234 + hvd.rank())  # different init; broadcast fixes
    model = BertLarge(layers=args.layers, d_model=args.d_model,
                      heads=args.heads, seq=args.seq)

    # sparse-compatible optimizer (momentum densifies); the wrapper
    # exchanges the embedding grad by allgather, everything else by
    # allreduce
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.01 * hvd.size()),
        named_parameters=model.named_parameters())
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)

    rng = np.random.RandomState(100 + hvd.rank())  # different data
    loss_fn = torch.nn.CrossEntropyLoss()
    tokens_done = 0
    t0 = time.perf_counter()
    for step in range(args.steps):
        ids = torch.from_numpy(
            rng.randint(0, VOCAB, (args.batch, args.seq)))
        logits = model(ids)
        loss = loss_fn(logits.reshape(-1, VOCAB), ids.reshape(-1))
        loss.backward()
        opt.step()
        opt.zero_grad()
        tokens_done += args.batch * args.seq
        if hvd.rank() == 0:
            print(f"step {step}: loss {loss.item():.3f}", flush=True)
    dt = time.perf_counter() - t0

    # lockstep invariant: every rank holds identical weights
    digest = hvd.allgather(
        torch.cat([p.detach().reshape(-1)[:512]
                   for p in model.parameters()]).reshape(1, -1),
        name="bert/weights")
    for r in range(1, hvd.size()):
        assert torch.equal(digest[0], digest[r]), "ranks diverged"

    print(f"rank {hvd.rank()}: {tokens_done / dt:.1f} tokens/s "
          f"(torch CPU; chip rate: benchmark/run.py bertl-train-c1) — "
          f"lockstep OK", flush=True)


if __name__ == "__main__":
    main()
