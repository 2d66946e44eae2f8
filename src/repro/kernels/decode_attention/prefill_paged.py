"""Ragged multi-token paged PREFILL attention as a Pallas TPU kernel.

The chunked-prefill hot path: each slot appends a chunk of up to ``T``
prompt tokens into its pages (the caller scatters the chunk's K/V rows
BEFORE attention runs, exactly like the single-token decode append) and the
(T, H, D) query block then attends CAUSALLY over the slot's live pages —
history plus the in-flight chunk — in ONE kernel launch.  Admitting a
prompt of P tokens therefore costs ``ceil(P / T)`` compiled steps instead
of the P sequential decode-cell steps the prefill-by-decode path paid: the
serving tick's admission latency stops scaling with prompt length while
the kernel's transaction count keeps scaling with live tokens (chunk rows
+ live pages), which ``core.hlo_counters`` pins on the jnp gather oracle.

Shape strategy (mirrors the single-token paged decode kernel in
``paged.py``):

  * grid = (B, KV, max_blocks) — logical blocks are the MINOR axis so one
    (slot, kv-head)'s online-softmax state lives in VMEM scratch across
    the page sweep; the query block rides along whole.
  * the q block is flattened to (T*G, D) rows, t-major (row r holds query
    token ``r // G`` of head group ``r % G``), so the per-page score tile
    is a single (T*G, page) MXU matmul and the causal mask is an iota
    divide away.
  * RAGGED chunks: per-slot ``base`` (tokens resident BEFORE the chunk)
    and ``new_len`` (= base + granted tokens) arrive via scalar prefetch.
    Query row t sits at absolute position base + t and attends positions
    <= base + t (causal) and < new_len (the slot's granted extent); rows
    past the grant produce garbage the caller ignores (their appends
    landed on the null page), but they apply the same masks as the
    oracle, so interpret-mode equivalence holds row-for-row for every
    slot with at least one live position (new_len > 0).  The one
    divergence is a fully EMPTY slot (base == 0 AND grant == 0, i.e. an
    unoccupied batch row): all its rows are fully masked — the kernel's
    guarded finalize emits zeros where the oracle's degenerate all-masked
    softmax goes uniform.  Both are garbage the engine never reads;
    other slots' rows are unaffected (pinned by test).
  * the physical page of logical block j comes from the scalar-prefetched
    block table — dead blocks (j*page >= new_len) are skipped with
    ``pl.when`` and their table entries point at the reserved null page 0,
    so the prefetched DMA address is always valid.
  * MULTI-PAGE BLOCKING (``pages_per_step`` > 1): each grid step scalar-
    prefetches a page LIST — P physically-scattered pages resolved through
    the block table — and sweeps all P through the online-softmax update
    before the next grid step, exactly like ``paged.py``.  Grid steps (and
    their per-step init/finalize + index bookkeeping overhead) shrink by P
    for long histories — the shape a speculative VERIFY chunk over a long
    decode hits every tick; the tiles fetched are identical, so the
    transaction census is unchanged.  The block table is padded to a
    multiple of P with null-page entries so every prefetched address stays
    valid (``grid_steps``/``padded_blocks`` in ``paged.py`` expose the
    blocking arithmetic).
  * GQA without materializing repeated kv heads: each page runs
    [T*G, D] x [D, page] on the MXU.
  * the pool stays STACKED and head-major, (L, num_pages, KV, page, D);
    the layer-scan caller passes its trip counter as the ``layer``
    scalar.  One (slot, kv-head) page tile is the whole (page, D) plane
    of the array, the block shape the TPU compiler accepts (see
    ``paged.py``).

Inference-only: no VJP (the jnp gather oracle in ``ref.py`` carries
gradients where needed).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged import grid_steps, padded_blocks

NEG_INF = -1e30


def _kernel(base_ref, len_ref, tbl_ref, layer_ref, q_ref, *refs,
            scale: float, page: int, num_steps: int, pages_per_step: int,
            groups: int, quantized: bool):
    P = pages_per_step
    k_refs = refs[:P]
    v_refs = refs[P:2 * P]
    if quantized:                        # int8 pages + per-row f32 scales
        ks_refs = refs[2 * P:3 * P]
        vs_refs = refs[3 * P:4 * P]
        rest = refs[4 * P:]
    else:
        ks_refs = vs_refs = (None,) * P
        rest = refs[2 * P:]
    o_ref = rest[0]
    m_scr, l_scr, acc_scr = rest[1:]
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    base = base_ref[b]
    kv_len = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)                  # (T*G, D)

    def _sweep(p, k_ref, v_ref, ks_ref, vs_ref):
        # logical block j*P + p holds positions [bj*page, (bj+1)*page):
        # live iff it overlaps [0, new_len) — per-slot positions start at
        # 0 on the slot's own pages
        bj = j * P + p

        @pl.when(bj * page < kv_len)
        def _body():
            k = k_ref[0, 0, 0].astype(jnp.float32)       # (page, D)
            v = v_ref[0, 0, 0].astype(jnp.float32)
            if quantized:                # dequantize in the f32 accumulator
                k = k * ks_ref[0, 0, 0]                  # (page, 1) scales
                v = v * vs_ref[0, 0, 0]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (T*G, page)
            tpos = bj * page + jax.lax.broadcasted_iota(jnp.int32,
                                                        s.shape, 1)
            # row r is query token r // G at absolute position base + r // G
            qpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   0) // groups
            s = jnp.where((tpos <= qpos) & (tpos < kv_len), s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p_ = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * corr + p_.sum(axis=1, keepdims=True)
            acc_scr[...] = (acc_scr[...] * corr
                            + jax.lax.dot_general(
                                p_, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
            m_scr[...] = m_new

    for p in range(P):                   # unrolled page-list sweep
        _sweep(p, k_refs[p], v_refs[p], ks_refs[p], vs_refs[p])

    @pl.when(j == num_steps - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_prefill_attention_fwd(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, block_table: jax.Array,
                                base_len: jax.Array, new_len: jax.Array,
                                layer: jax.Array | int = 0, *,
                                k_scale: jax.Array | None = None,
                                v_scale: jax.Array | None = None,
                                pages_per_step: int = 1,
                                interpret: bool = False) -> jax.Array:
    """q (B, T, H, D) — the chunk's query block (its K/V rows must already
    be scattered into the pool); k_pool, v_pool (L, num_pages, KV, page, D)
    stacked head-major pools (a 4D single-layer pool is promoted); block_table
    (B, max_blocks) int32 physical page ids (0 = reserved null page);
    base_len (B,) int32 tokens resident before the chunk; new_len (B,)
    int32 = base_len + granted chunk tokens (rows past a slot's grant are
    masked like the oracle and ignored by the caller); layer — which pool
    layer to address; k_scale, v_scale — optional (L, num_pages, KV, page,
    1) f32 per-row-per-head scales for int8 pools, dequantized inside the
    page sweep; pages_per_step — pages swept per grid step (1 = the
    original one-page grid).  Returns (B, T, H, D).
    """
    B, T, H, D = q.shape
    quantized = k_scale is not None
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
        if quantized:
            k_scale, v_scale = k_scale[None], v_scale[None]
    _, num_pages, KV, page, _ = k_pool.shape
    NB = block_table.shape[1]
    P = max(1, pages_per_step)
    steps = grid_steps(NB, P)
    NBp = padded_blocks(NB, P)
    G = H // KV
    TG = T * G
    scale = 1.0 / math.sqrt(D)

    # t-major row flattening: row r = query token r // G, head group r % G
    qg = q.reshape(B, T, KV, G, D).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, KV, TG, D)
    tbl = jnp.asarray(block_table, jnp.int32)
    if NBp != NB:                                # pad with null-page entries
        tbl = jnp.pad(tbl, ((0, 0), (0, NBp - NB)))
    tbl = tbl.reshape(B * NBp)
    base = jnp.asarray(base_len, jnp.int32).reshape(B)
    kvl = jnp.asarray(new_len, jnp.int32).reshape(B)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)

    def _page_map(p):
        # the p-th page of grid step j: physical id tbl[b*NBp + j*P + p]
        def index_map(b, h, j, base_ref, len_ref, tbl_ref, lay_ref):
            return (lay_ref[0], tbl_ref[b * NBp + j * P + p], h, 0, 0)
        return index_map

    page_spec = [pl.BlockSpec((1, 1, 1, page, D), _page_map(p))
                 for p in range(P)]
    # scale rows of the same physical page: (page, 1) per kv head
    scale_spec = [pl.BlockSpec((1, 1, 1, page, 1), _page_map(p))
                  for p in range(P)]
    scale_ins = ([*scale_spec, *scale_spec] if quantized else [])
    scale_args = (([k_scale] * P + [v_scale] * P) if quantized else [])
    kernel = functools.partial(_kernel, scale=scale, page=page,
                               num_steps=steps, pages_per_step=P,
                               groups=G, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, KV, steps),
            in_specs=[
                pl.BlockSpec((1, 1, TG, D), lambda b, h, j, *_: (b, h, 0, 0)),
                *page_spec,                       # k pages 0..P-1
                *page_spec,                       # v pages 0..P-1
                *scale_ins,                       # k then v scales (int8)
            ],
            out_specs=pl.BlockSpec((1, 1, TG, D),
                                   lambda b, h, j, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((TG, 1), jnp.float32),    # running row max
                pltpu.VMEM((TG, 1), jnp.float32),    # running row sum
                pltpu.VMEM((TG, D), jnp.float32),    # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, TG, D), q.dtype),
        interpret=interpret,
        name="ragged_prefill_attention",
    )(base, kvl, tbl, lay, qg, *([k_pool] * P), *([v_pool] * P),
      *scale_args)
    out = out.reshape(B, KV, T, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, D)
