"""output_tok_s (tokens/s, host clock): tokens the requests emitted in
the window's ticks, over the time from the window's first tick boundary
to its close."""


def read(rec):
    w = rec.window
    return w.tokens / (w.t1 - w.opened) if w.t1 > w.opened else None
