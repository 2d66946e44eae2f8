"""decode_dense_step_ms.batch (ms; layer: model step, lm_decode_paged): the
decode lane's device seconds in the dense scopes (``spans.DENSE``: qkv,
out_proj, ffn, head, sample; read through the lane's compiled HLO) over
the traced window, per scan step: base (decode-lane module runs x scan
steps per run), in a batch cell.  Nothing where the trace holds no lane or
no scoped op."""
from bench.spans import dense_s


def read(rec):
    d = rec.span_lane("decode")
    if d is None or not dense_s(d):
        return None
    return 1e3 * dense_s(d) / (d["runs"] * rec.window.decode_steps)
