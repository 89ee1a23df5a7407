"""The comparison that decides `correct`, on the CPU at a tiny size: a
sound training run passes; the control (the reference in bfloat16 in the
program's place) fails; and so does a run with the timed path broken
underneath, once for each fault a training cell can have."""

import pytest

import onchip_tiny


def test_sound_training_run_is_correct():
    rec = onchip_tiny.run("tiny.train")
    assert rec["correct"], rec["checks"]
    assert rec["checks"]["change_gap"]["value"] < 1e-4


def test_the_training_control_is_not_correct():
    rec = onchip_tiny.run("tiny.train", control=True)
    assert not rec["correct"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    rec = onchip_tiny.run("tiny.train", fault="unchanged")
    assert not rec["correct"]
    assert rec["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_a_loss_over_half_the_batch_is_not_correct():
    rec = onchip_tiny.run("tiny.train", fault="half_batch")
    assert not rec["correct"]
    assert rec["checks"]["loss_gap"]["value"] > \
        rec["checks"]["loss_gap"]["limit"]
