"""decode_attn_roofline.batch (%; layer: kernels,
kernels/decode_attention/paged.py): in a batch cell, the least time the
chip needs for the paged decode kernel's operations and bytes over the
traced window (bench/costs.py, active steps only) over the kernel's
device time."""
from bench import costs
from bench.record import roofline_share


def read(rec):
    return roofline_share(rec, "decode", costs.decode_attention)
