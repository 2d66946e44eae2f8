"""prefill_attn_roofline (%; layer: kernels,
kernels/decode_attention/prefill_paged.py): the least time the chip
needs for the ragged prefill kernel's operations and bytes over the
traced window (bench/costs.py, granted rows only) over the kernel's
device time."""
from bench import costs
from bench.record import roofline_share


def read(rec):
    return roofline_share(rec, "prefill", costs.prefill_attention)
