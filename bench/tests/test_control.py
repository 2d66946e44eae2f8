"""The lower-precision control comes out not correct where the program
comes out correct: at toy widths on the CPU, on three seeds, the fp8
reference put in the program's place goes through the run's own check
and fails the limit that the program's served tokens pass
(bench/control.py takes the same readings on the chip at the cells' own
sizes)."""
import json

import pytest

from bench import control
from bench.tests import tiny

LIMIT = tiny.LIMIT


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 10**9 + 7])
def test_control_fails_where_program_passes(seed, tmp_path):
    limits = {"logit_gap_max": {"limit": LIMIT}}
    r = control.readings(dict(tiny.CONFIG, name="tiny"),
                         dict(tiny.MIX, prime_s=0.0), seed,
                         seconds=2.0, limits=limits)
    print(json.dumps(r))
    assert r["tokens"] > 0
    prog, ctrl = r["program"], r["control"]
    assert prog["correct"] is True and ctrl["correct"] is False
    assert prog["checks"]["logit_gap_max"]["value"] <= LIMIT < \
        ctrl["checks"]["logit_gap_max"]["value"]
