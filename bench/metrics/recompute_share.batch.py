"""recompute_share.batch (%; layer: scheduler, PagedEngine preemption and
resume): 100 x the prompt tokens re-appended on resuming preempted
requests (the engine's counter ``recompute_tokens`` over the window), on
the base of the prompt tokens the window's ticks granted to the prefill
lane (their plans' ``prefill``).  Nothing where no tick granted any."""


def read(rec):
    granted = sum(int(t.prefill.sum()) for t in rec.window.ticks
                  if t.prefill is not None)
    if not granted:
        return None
    return 100.0 * rec.counters["recompute_tokens"] / granted
