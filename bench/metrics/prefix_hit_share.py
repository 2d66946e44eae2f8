"""prefix_hit_share (%; layer: page cache, serve/cache.py): prompt tokens
served from shared or retained pages (the engine's ``shared_tokens``
counter over the window) per prompt token of the requests admitted in
the window."""


def read(rec):
    admitted = sum(len(r.prompt) for r in rec.requests
                   if r.admit_t is not None)
    if not admitted:
        return None
    return 100.0 * rec.window.shared_tokens / admitted
