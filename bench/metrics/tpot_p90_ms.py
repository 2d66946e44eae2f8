"""tpot_p90_ms (ms, host clock): 90th percentile, over requests that
emitted two or more tokens in the window, of (last token time - first
token time) / (tokens - 1), token times being the ends of the ticks that
produced them."""
from bench.record import percentile


def read(rec):
    v = percentile(rec.tpot_s(), 90)
    return None if v is None else v * 1e3
