"""slot_occupancy.batch (%; layer: scheduler, serve/scheduler.py plan):
mean over the window's ticks of the share of the engine's slots that the
tick's plan gave prefill rows or decode steps."""


def read(rec):
    plans = [t for t in rec.window.ticks if t.steps is not None]
    if not plans:
        return None
    busy = [((t.prefill > 0) | (t.steps > 0)).sum() for t in plans]
    return 100.0 * float(sum(busy)) / (len(plans) * rec.window.slots)
