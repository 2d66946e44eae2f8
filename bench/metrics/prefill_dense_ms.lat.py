"""prefill_dense_ms.lat (ms; layer: model step, lm_prefill_paged): the
prefill lane's device seconds in the dense scopes (``spans.DENSE``: qkv,
out_proj, ffn, head, sample; read through the lane's compiled HLO) over
the traced window, on the base of one prefill-lane module run.  Nothing
where the trace holds no lane or no scoped op."""
from bench.spans import dense_s


def read(rec):
    d = rec.span_lane("prefill")
    if d is None or not dense_s(d):
        return None
    return 1e3 * dense_s(d) / d["runs"]
