"""The whole run at a CPU size, past the look for a chip, with the timed
path broken underneath: ``correct`` must come out false for every fault
the cell can have, and true for a sound run.  Training runs at published
widths cut to two layers, where the test limits were read (tiny widths
read other gaps)."""
import pytest

import tiny


@pytest.mark.parametrize("fault", ["", "frozen", "half_batch"])
def test_train_faults_fail_the_check(fault):
    res = tiny.run("train-horn-8l", fault, size=tiny.WIDE)
    assert res["correct"] is (fault == "")
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["", "token"])
def test_served_token_altered_fails_the_check(fault):
    res = tiny.run("serve-reasoning", fault)
    assert res["correct"] is (fault == "")
    assert res["attempted"] > 0
