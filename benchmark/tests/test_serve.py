"""The serving runner rehearsed at toy sizes on the CPU, sound and with a
served token altered where it is produced. Run by hand (see conftest.py)."""

import json

import numpy as np
import pytest

from benchmark import run
from benchmark.runners import serve

CELL = "gpt2s-serve-c1"


def rehearse(capsys, *extra):
    run.main(["--workload", CELL, "--seed", "2147483677", "--seconds", "2",
              "--trace", "0", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_no_result_without_a_chip(capsys):
    with pytest.raises(SystemExit) as refusal:
        rehearse(capsys)
    assert refusal.value.code not in (0, None)
    assert not any(l.startswith("{") for l in
                   capsys.readouterr().out.splitlines())


def test_a_sound_rehearsal_is_correct(capsys):
    result, lines = rehearse(capsys, "--rehearse")
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_out_tok_s",
                                      "latency_ms_p95"}
    assert result["attempted"] > 20
    assert any(l.startswith("check served_logit_gap") for l in lines)


def test_an_altered_token_is_not_correct(capsys, monkeypatch):
    from horovod_tpu.serve.kv_cache import DecodeEngine

    sound = DecodeEngine.decode

    def altered(self, slots, tokens, positions):
        ids, max_abs = sound(self, slots, tokens, positions)
        # every eighth step serves the neighbour of the token it computed
        if self.decode_steps % 8 == 0:
            ids = [(i + 1) % self.vocab_size for i in ids]
        return ids, max_abs

    monkeypatch.setattr(DecodeEngine, "decode", altered)
    result, lines = rehearse(capsys, "--rehearse")
    assert result["correct"] is False
    assert any(l.startswith("check served_logit_gap") and
               l.endswith("FAILED") for l in lines)


def test_served_gap_arithmetic():
    logits = np.zeros((6, 5), np.float32)
    logits[2] = [0.0, 3.0, 1.0, 0.0, 0.0]    # predicts the first token
    logits[3] = [0.5, 0.0, 0.0, 2.0, 0.0]    # predicts the second
    # a prompt of 3: position 2 predicts tokens[0], position 3 tokens[1]
    assert serve.served_gap(logits, 3, [1, 3]) == 0.0
    assert serve.served_gap(logits, 3, [2, 0]) == pytest.approx(2.0)


def test_the_sample_holds_the_longest_request():
    class Done:
        def __init__(self, n):
            self.tokens = [0] * n

    finished = [(0.0, 1.0, [1] * p, Done(n))
                for p, n in [(4, 2), (90, 16), (8, 3), (5, 5), (6, 2)]]
    sample = serve.draw_sample(finished, seed=5, size=3)
    assert len(sample) == 3
    assert (len(sample[0][0]), len(sample[0][1])) == (90, 16)
    assert sample == serve.draw_sample(finished, seed=5, size=3)


def test_tokens_are_attributed_to_the_window_in_part():
    class Done:
        tokens = [0] * 100

    inside = (2.0, 4.0, [], Done())          # wholly inside [1, 5]
    early = (0.0, 2.0, [], Done())           # half before the opening
    late = (4.0, 8.0, [], Done())            # three quarters after the close
    outside = (6.0, 7.0, [], Done())
    assert serve.tokens_in_window([inside, early, late, outside], 1.0, 5.0
                                  ) == pytest.approx(100 + 50 + 25)
