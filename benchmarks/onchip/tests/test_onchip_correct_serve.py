"""The comparison that decides `correct`, on the CPU at a tiny size: a
sound serving run passes; the control (the reference in bfloat16 in the
program's place) fails; and so does a run with the timed path broken
underneath, once for each fault a serving cell can have."""

import pytest

import onchip_tiny


def test_sound_serving_runs_are_correct():
    for cell in ("tiny.server", "tiny.offline"):
        rec = onchip_tiny.run(cell)
        assert rec["correct"], rec["checks"]
        assert rec["attempted"] > 0 and rec["failed"] == 0


def test_the_serving_control_is_not_correct():
    rec = onchip_tiny.run("tiny.server", control=True)
    assert not rec["correct"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
def test_a_broken_serving_path_is_not_correct(fault):
    rec = onchip_tiny.run("tiny.server", fault=fault)
    assert not rec["correct"]
    assert rec["checks"]["logit_gap"]["value"] > \
        rec["checks"]["logit_gap"]["limit"]
