"""device_idle_share.batch (%; layer: device): 100 x (1 - the union of
the intervals in which an op ran on the device / the traced window), in
a batch cell."""
from bench.record import idle_share as read  # noqa: F401
