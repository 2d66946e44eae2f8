"""The lower-precision control comes out not correct where the program
comes out correct: at toy widths on the CPU, on three seeds, the fp8
reference put in the program's place goes through the run's own check
and fails the limit that the program's served tokens pass
(bench/control.py takes the same readings on the chip at the cells' own
sizes)."""
import json

import pytest

from bench import control, spec
from bench.tests import tiny

LIMIT = tiny.LIMIT


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 10**9 + 7])
def test_control_fails_where_program_passes(seed, tmp_path):
    limits = {"logit_gap_max": {"limit": LIMIT}}
    conf = dict(tiny.CONFIG, name="tiny")
    r = control.readings(conf,
                         dict(tiny.MIX, prime_s=0.0), seed,
                         seconds=2.0, limits=limits,
                         reference=spec.load_reference(conf))
    print(json.dumps(r))
    assert r["tokens"] > 0
    prog, ctrl = r["program"], r["control"]
    assert prog["correct"] is True and ctrl["correct"] is False
    assert prog["checks"]["logit_gap_max"]["value"] <= LIMIT < \
        ctrl["checks"]["logit_gap_max"]["value"]
