"""prefill_wait_p90_ms (ms; layer: scheduler + prefill lane): 90th
percentile over requests due in the window and given a slot of the time
from the start of the tick that gave the request its slot to the end of
the tick that produced its first token; one with no token by the
window's end counts with the time it has waited.  A request given its
slot again after a preemption that followed its first token has no
first admission on record and is left out."""
from bench.record import percentile


def read(rec):
    t1 = rec.window.t1
    waits = [(r.first_t if r.first_t is not None else t1) - r.admit_t
             for r in rec.requests if r.admit_t is not None
             and (r.first_t is None or r.first_t >= r.admit_t)]
    v = percentile(waits, 90)
    return None if v is None else v * 1e3
