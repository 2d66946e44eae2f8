"""step_mfu.batch (%; layer: model step, whole): model FLOPs of all the
work of the traced window's ticks (granted prompt rows and active decode
steps, bench/costs.py) over the traced window's length times the bf16
peak."""
from bench.record import whole_step_mfu as read  # noqa: F401
