"""prefill_step_ms.lat (ms; layer: model step, lm_prefill_paged): mean
device time of one run of the prefill-lane module in the traced window."""


def read(rec):
    d = rec.lane("prefill")
    return None if d is None else 1e3 * d["module_s"] / d["runs"]
