"""decode_step_mfu (%; layer: model step, lm_decode_paged): model FLOPs
of the active decode steps (bench/costs.py) over the decode-cell
modules' device time times the bf16 peak."""
from bench.record import step_mfu


def read(rec):
    return step_mfu(rec, "decode")
