"""prefill_kv_write_ms.lat (ms; layer: model step, lm_prefill_paged): the
prefill lane's device seconds in the ``kv_write`` scope (the K/V row
scatter into the page pools) over the traced window, on the base of one
prefill-lane module run.  Nothing where the trace holds no lane or no
such op."""


def read(rec):
    d = rec.span_lane("prefill")
    s = None if d is None else d["scopes"].get("kv_write")
    return None if not s else 1e3 * s / d["runs"]
