"""The control -- the reference computed in fp8, one precision below the
bf16 the configurations state, put in the program's place -- comes out
not correct where the program comes out correct, at published widths cut
to two layers under the limits read at that size (``tiny.LIMITS``; on
the chip, at the cells' own size and limits: calibrate.py)."""
import jax

import calibrate
import tiny
from harness import common


def test_train_control_is_not_correct():
    c = tiny.cell("train-horn-8l", tiny.WIDE)
    r = calibrate.train_seed(c, 11, control=True, faults=False)
    limits = c.config["checks"]["limits"]
    assert common.limit_checks(r["program"], limits)[1] is True
    assert common.limit_checks(r["control"], limits)[1] is False


def test_serve_control_is_not_correct():
    c = tiny.cell("serve-reasoning", tiny.WIDE)
    r = calibrate.serve_seed(c, 13, 3.0, True, jax.devices()[:1])
    limits = c.config["checks"]["limits"]
    assert common.limit_checks(r["program"], limits)[1] is True
    assert common.limit_checks(r["control"], limits)[1] is False
