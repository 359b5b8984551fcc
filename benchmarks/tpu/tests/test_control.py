"""The control at a size a test run holds, through the same judgement
as a benchmark run (``check.Judge``): the program's own lower-precision
expert path (``weight_dtype``), teacher-forced over the served tokens,
is judged not correct where the sound run is correct, and so is a token
altered where it is produced.  The readings at the cells' own sizes,
from which their limits were set, are in PERF.md."""
import pytest

import control
import tiny

SEEDS = (1, 2, 3)


@pytest.mark.parametrize("name", ["granite.b1_decode", "deepseek.decode_c8"])
def test_control_is_not_correct(name):
    c = tiny.cell(name)
    dtype = c.limits["control"]["dtype"]
    got = control.readings(c, SEEDS, 2.0, [dtype], require_chip=False,
                           log=lambda s: None)
    for seed, r in got.items():
        assert r["sound"]["correct"], (seed, r["sound"])
        assert not r[dtype]["correct"], (seed, r[dtype])
        assert not r["altered"]["correct"], (seed, r["altered"])
