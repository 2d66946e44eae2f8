"""decode_step_ms.batch (ms; layer: model step, lm_decode_paged): device
time of the decode-cell module per scan step (one token per active slot)
in the traced window of a batch cell."""
from bench.record import decode_step_ms as read  # noqa: F401
