"""`correct` shown to fail: the float8 control at a size a test run can
hold, and whole rehearsal runs with the timed path broken underneath.
Run by hand (see conftest.py); about a minute on the CPU."""

import json

import pytest

from benchmark import harness, run
from benchmark.runners import train

CELL = "gpt2s-train-c1"


def rehearse(capsys, *extra):
    run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "1",
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
    assert set(result["metrics"]) == {"setup_s", "train_tok_s_chip"}
    assert result["device"]["platform"] == "cpu"
    # every number compared is printed beside its limit
    assert sum(l.startswith("check ") for l in lines) >= 6


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen(self, batch):
        spare = jax.tree.map(jnp.copy, self.state)
        loss, *_ = self.compiled(*spare, *batch)
        return loss

    monkeypatch.setattr(train.Program, "one_step", frozen)
    result, lines = rehearse(capsys, "--rehearse")
    assert result["correct"] is False
    failed = [l for l in lines if l.endswith("FAILED")]
    assert any("change_norm_gap.worst_leaf" in l for l in failed)
    assert any("grad_norm_gap.worst_leaf" in l for l in failed)


def test_a_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    import jax

    sound = train.Program.one_step

    def short(self, batch):
        # the last quarter of the rows never reaches the step: the first
        # quarter is fed twice in their place
        def cut(x):
            q = x.shape[0] // 4
            return jax.device_put(x.at[-q:].set(x[:q]), x.sharding)
        return sound(self, jax.tree.map(cut, batch))

    monkeypatch.setattr(train.Program, "one_step", short)
    result, lines = rehearse(capsys, "--rehearse")
    assert result["correct"] is False


def test_the_float8_control_fails_where_the_program_passes():
    import jax

    import horovod_tpu as hvd

    _, _, published, config, mix, limits = harness.load_cell(CELL, True)
    program = train.Program(config, published, mix, jax.devices()[:1])
    readings = {}
    for seed in (31, 32, 33):
        readings[seed], _ = program.first_steps(seed, program.start(seed))
        program.stop()
    hvd.shutdown()
    for seed in (31, 32, 33):
        ref = program.reference(seed)
        sound = train.compare(readings[seed], ref, limits)
        low = train.compare(program.reference(seed, precision="fp8"), ref,
                            limits)
        assert all(c.ok for c in sound), [c.line() for c in sound]
        assert not all(c.ok for c in low), [c.line() for c in low]
