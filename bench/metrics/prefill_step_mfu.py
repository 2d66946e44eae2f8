"""prefill_step_mfu (%; layer: model step, lm_prefill_paged): model FLOPs
of the granted prompt rows (bench/costs.py) over the prefill-lane
modules' device time times the bf16 peak."""
from bench.record import step_mfu


def read(rec):
    return step_mfu(rec, "prefill")
