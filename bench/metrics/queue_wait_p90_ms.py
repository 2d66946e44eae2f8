"""queue_wait_p90_ms (ms; layer: scheduler, serve/scheduler.py admission):
90th percentile over requests due in the window of the time from due to
the start of the tick that gave the request a slot (read from
``engine.status`` after each tick); one still queued at the window's end
counts with the time it has waited."""
from bench.record import percentile


def read(rec):
    t1 = rec.window.t1
    v = percentile([(r.admit_t if r.admit_t is not None else t1) - r.due
                    for r in rec.requests], 90)
    return None if v is None else v * 1e3
