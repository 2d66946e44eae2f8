"""Operations and bytes the work of a tick needs, from the model's shapes
and the live lengths the harness records at each tick's plan.

Counted is the work the requests need, not what the compiled cells pad:
only granted prompt rows and active decode steps, and each attention reads
exactly the key/value rows its query sees.  So a share of a roofline or a
peak built from these counts cannot pass 100% unless the time leaves out
part of the work.

Per tick the harness records ``lengths`` (tokens resident per slot before
the tick's appends), ``prefill`` (prompt tokens granted per slot to the
ragged prefill lane) and ``steps`` (decode steps granted per slot).

The counts are a dense GQA decoder's with full causal attention.  A
config with routed experts, or a query that would see past its
``sliding_window``, is a SpecError here: such a cell brings its own cost
reader, rather than read a plausible but wrong share.
"""
from __future__ import annotations

import numpy as np

from bench.spec import Sizes, SpecError

KV_BYTES = 2                 # bf16 page pools
ACT_BYTES = 2                # bf16 queries and attention outputs


def _prefill_ctx(lengths, grants):
    """Sum over granted rows of the keys each row attends (itself and
    everything before it): sum_t (L + t + 1) for t < g."""
    L = np.asarray(lengths, np.float64)
    g = np.asarray(grants, np.float64)
    return float((g * L + g * (g + 1) / 2).sum())


def _decode_ctx(lengths, steps):
    """Sum over active decode steps of the keys each step attends."""
    L = np.asarray(lengths, np.float64)
    s = np.asarray(steps, np.float64)
    return float((s * L + s * (s + 1) / 2).sum())


def _full_causal(sz: Sizes, lengths, new):
    """Refuse a count where some query sees past the config's window."""
    window = sz.get("sliding_window")
    if window is None:
        return
    seen = np.asarray(lengths, np.float64) + np.asarray(new, np.float64)
    if seen.size and seen.max() > window:
        raise SpecError(f"bench/costs.py counts full causal attention; a "
                        f"context of {int(seen.max())} passes the config's "
                        f"sliding_window {window}")


def prefill_attention(sz: Sizes, lengths, grants):
    """(ops, bytes) of the ragged prefill attention kernel for one
    dispatch, over all layers."""
    _full_causal(sz, lengths, grants)
    g = np.asarray(grants, np.float64)
    ops = 4.0 * sz.heads * sz.head_dim * _prefill_ctx(lengths, grants)
    rows = float((np.asarray(lengths, np.float64) + g)[g > 0].sum())
    nbytes = (2 * rows * sz.kv_heads * sz.head_dim * KV_BYTES
              + 2 * g.sum() * sz.heads * sz.head_dim * ACT_BYTES)
    return sz.layers * ops, sz.layers * float(nbytes)


def decode_attention(sz: Sizes, lengths, steps):
    """(ops, bytes) of the paged decode attention kernel for one decode
    dispatch (all its scan steps), over all layers."""
    _full_causal(sz, lengths, steps)
    s = np.asarray(steps, np.float64)
    ctx = _decode_ctx(lengths, steps)
    ops = 4.0 * sz.heads * sz.head_dim * ctx
    nbytes = (2 * ctx * sz.kv_heads * sz.head_dim * KV_BYTES
              + 2 * s.sum() * sz.heads * sz.head_dim * ACT_BYTES)
    return sz.layers * ops, sz.layers * float(nbytes)


def linear_flops_per_token(sz: Sizes) -> float:
    """Matmul FLOPs of one token through every layer's projections."""
    if sz.get("num_experts") is not None:
        raise SpecError("bench/costs.py counts a dense SwiGLU FFN of "
                        "intermediate_size; a routed-expert config brings "
                        "its own cost reader")
    d, hd = sz.d_model, sz.head_dim
    per_layer = 2 * (d * sz.heads * hd * 2 + d * sz.kv_heads * hd * 2
                     + 3 * d * sz.d_ff)
    return float(sz.layers * per_layer)


def prefill_model_flops(sz: Sizes, lengths, grants) -> float:
    """Model FLOPs of one prefill-lane dispatch: granted rows through the
    layers, their attention, and one unembedding per granted slot."""
    g = np.asarray(grants, np.float64)
    attn = prefill_attention(sz, lengths, grants)[0]
    return (g.sum() * linear_flops_per_token(sz) + attn
            + (g > 0).sum() * 2.0 * sz.d_model * sz.vocab)


def decode_model_flops(sz: Sizes, lengths, steps) -> float:
    """Model FLOPs of one decode dispatch: each active step through the
    layers, its attention and its unembedding."""
    s = np.asarray(steps, np.float64).sum()
    attn = decode_attention(sz, lengths, steps)[0]
    return s * (linear_flops_per_token(sz) + 2.0 * sz.d_model * sz.vocab) \
        + attn
