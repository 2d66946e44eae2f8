"""ttft_p90_ms (ms, host clock): 90th percentile over every request due
in the window of the time from when it was due to the end of the tick
that produced its first token.  A request with no token by the window's
end counts with the time it has waited."""
from bench.record import percentile


def read(rec):
    v = percentile(rec.ttft_s(), 90)
    return None if v is None else v * 1e3
