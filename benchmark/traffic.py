"""The one general traffic generator: every mix is a data file of
parameters under ``benchmark/traffic/`` that this module reads.

Everything is drawn from ``--seed`` on the host with numpy; the same
seed gives the same inputs. The *sizes* of a mix (sequence length and
batch of a training mix; the pool of prompt and output lengths of a
serving mix) do not depend on the seed, so that seeds change the
content and the order of the work and never its amount.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name, rehearse=False):
    """The parameters of mix ``name``; ``rehearse`` lays the file's toy
    sizes (its ``rehearse`` group) over them."""
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    toy = mix.pop("rehearse", {})
    if rehearse:
        mix.update(toy)
    return mix


def train_batches(mix, vocab_size, rows, seed):
    """An endless iterator of ``(inputs, labels)`` host batches of
    ``rows`` sequences, every step's rows freshly drawn.

    ``objective: causal_lm``: uniform token ids, the labels are the
    inputs. ``objective: masked_lm``: each position is masked with
    probability ``mask_rate``; the inputs carry ``mask_token_id`` there
    and the labels are the pair (original ids, 0/1 mask)."""
    seq = mix["seq"]
    step = 0
    while True:
        rng = np.random.default_rng([seed, step])
        tokens = rng.integers(0, vocab_size, (rows, seq), dtype=np.int32)
        if mix["objective"] == "causal_lm":
            yield tokens, tokens
        elif mix["objective"] == "masked_lm":
            mask = rng.random((rows, seq)) < mix["mask_rate"]
            inputs = np.where(mask, np.int32(mix["mask_token_id"]), tokens)
            yield inputs, (tokens, mask.astype(np.int32))
        else:
            raise ValueError(f"unknown objective {mix['objective']!r}")
        step += 1


def _clipped_lognormal(rng, n, median, sigma, low, high):
    draws = np.exp(rng.normal(np.log(median), sigma, n))
    return np.clip(np.rint(draws), low, high).astype(np.int64)


def request_sizes(mix):
    """The mix's pool of ``(prompt_len, new_tokens)`` pairs: lognormal,
    clipped, drawn from the mix's own ``sizes_seed`` and so the same in
    every run."""
    rng = np.random.default_rng(mix["sizes_seed"])
    n = mix["pool"]
    p, o = mix["prompt_len"], mix["new_tokens"]
    prompts = _clipped_lognormal(rng, n, p["median"], p["sigma"],
                                 p["min"], p["max"])
    outputs = _clipped_lognormal(rng, n, o["median"], o["sigma"],
                                 o["min"], o["max"])
    return list(zip(prompts.tolist(), outputs.tolist()))


def requests(mix, vocab_size, seed):
    """An endless iterator of ``(prompt token ids, new_tokens)``: the
    pool of sizes in an order drawn from ``seed``, round and round, each
    prompt of fresh uniform ids (no shared prefixes)."""
    sizes = request_sizes(mix)
    rng = np.random.default_rng([seed, 1])
    order = rng.permutation(len(sizes))
    while True:
        for i in order:
            prompt_len, new_tokens = sizes[i]
            yield (rng.integers(1, vocab_size, prompt_len).tolist(),
                   int(new_tokens))
