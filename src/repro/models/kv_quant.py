"""Symmetric int8 row quantization for the paged KV page pools.

The page pools store K/V rows as int8 with one f32 scale PER ROW PER KV
HEAD (pool shape (L, num_pages, KV, page, hd) -> scale shape
(L, num_pages, KV, page, 1)).  Per-row scales are the smallest granularity
that keeps the serving invariants intact:

  * incremental append writes exactly its own row's scale — a page-level
    scale would force a lossy requantization of every already-resident
    row in the page on each append;
  * COW privatization, defrag and retained-prefix adoption copy int8
    rows + scale rows VERBATIM, so shared/retained content stays
    bit-exact (no requantization anywhere after the initial write);
  * storage overhead is 4/hd of the int8 bytes (3% at hd=128), far under
    the 2x the bf16 pools cost.

Quantize and dequantize are the SAME arithmetic everywhere — the write
paths in ``transformer.py``, both Pallas kernels' page sweeps, and the
jnp gather oracles in ``ref.py`` — so interpret-mode equivalence pins
the kernels and the oracles stay the ground truth.
"""
from __future__ import annotations

import jax.numpy as jnp

# symmetric int8: q = round(x / scale) in [-127, 127], scale = absmax / 127
QMAX = 127.0


def quantize_rows(x):
    """Quantize ``x`` (..., hd) -> (int8 rows (..., hd), f32 scales
    (..., 1)) — the scale keeps a unit trailing axis, the layout of the
    scale pools.

    All-zero rows get scale 1.0 so dequantization is exact (zeros) and the
    null page stays all-zero in both pools.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / QMAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scale), -QMAX, QMAX)
    return q.astype(jnp.int8), scale


def dequantize_rows(q, scale):
    """Inverse of ``quantize_rows``: int8 rows (..., hd) + f32 scales
    (..., 1) -> f32 rows.  Exact for the rows ``quantize_rows`` produced
    (round-trip error is bounded by scale/2 per element, zero for all-zero
    rows)."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)
