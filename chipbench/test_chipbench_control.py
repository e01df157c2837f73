"""The control at a size a test run holds: the reference computed with three
bf16 passes in the program's place must come out not correct under each
cell's limits, while the program itself passes them. A CPU's
``Precision.HIGH`` is f32, so the test emulates the three passes
(``bf16_3x``); on the chip the control is the TPU's own
``Precision.HIGH``, and its readings at the cells' own sizes set the
limits (``calibrate.py``; the readings are in PERF.md and the cells'
files)."""
import warnings

import pytest

from chipbench import calibrate, compare
from chipbench.test_chipbench_drivers import small


@pytest.mark.parametrize("name", ["det720_x6_sat", "seg720_x6_sat",
                                  "seg720_open"])
def test_control_fails_where_the_program_passes(name):
    cell = small(name, size=(96, 160))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # kernel fallback
        r = calibrate.readings(cell, 2 ** 35 + 3, 0.5)
    assert r["failed"] == 0
    assert compare.judge(r["program"], cell["limits"], 0), r
    assert not compare.judge(r["control.bf16_3x"], cell["limits"], 0), r
