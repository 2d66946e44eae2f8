"""Paged single-token decode attention as a Pallas TPU kernel.

The paged serving hot path: one new query per slot attends over that slot's
PAGES of a shared (num_pages, KV, page, D) pool.  The physical page holding
each logical block comes from a scalar-prefetched block table — the DMA
address is computed from SMEM before the tile is fetched, so the kernel
streams ONLY the pages a slot actually owns.  That is the point for the
instruction roofline: the decode step's transaction count is proportional to
live tokens (max_blocks x page per slot) instead of ``max_seq``, which
``core.hlo_counters`` verifies on the jnp gather oracle (the dense cache
reads every row of a (B, max_seq, KV, D) cache whether or not it is live).

Shape strategy (mirrors the dense decode kernel in ``decode.py``):

  * grid = (B, KV, ceil(max_blocks / P)) with P = ``pages_per_step`` —
    logical blocks are the MINOR axis, so the online-softmax state for one
    (slot, kv-head) lives in VMEM scratch across the page sweep.
  * MULTI-PAGE BLOCKING (``pages_per_step`` > 1): each grid step scalar-
    prefetches a page LIST — P physically-scattered pages resolved through
    the block table — and sweeps all P through the online-softmax update
    before the next grid step.  Grid steps (and their per-step init/
    finalize + index bookkeeping overhead) shrink by P for long slots; the
    tiles fetched are identical, so the transaction census is unchanged.
    The block table is padded to a multiple of P with null-page entries so
    every prefetched address stays valid (``grid_steps``/``padded_blocks``
    expose the blocking arithmetic for tests).
  * GQA without materializing repeated kv heads: q reshaped to
    (B, KV, G, D), each page runs [G, D] x [D, page] on the MXU.
  * per-slot ``kv_len`` + the flattened block table + the layer index
    arrive via scalar prefetch (SMEM): the k/v BlockSpec index_maps read
    ``tbl[b * padded_blocks + j * P + p]`` to pick the p-th physical page
    of grid step j, and pages at or beyond the slot's length are skipped
    with ``pl.when`` (their table entries point at the reserved null page
    0, so the prefetch address is always valid).
  * the pool stays STACKED across layers: the layer-scan caller passes
    its trip counter as the ``layer`` scalar and the index_map
    addresses (layer, page, head) directly — no per-layer pool slice
    is ever materialized (a dynamic-slice of the full pool per layer is
    exactly the max_seq-proportional traffic the paged design removes).
  * HEAD-MAJOR pages (L, num_pages, KV, page, D): one (slot, kv-head)
    tile is the block (1, 1, 1, page, D), whose last two dims are the
    whole (page, D) plane of the array — the Mosaic TPU compiler accepts
    such a block at any page size, where a (page, 1, D) slice of a
    token-major (page, KV, D) page is refused (a block's last two dims
    must be (8, 128)-divisible or whole).  int8 scales ride the same
    way as (L, num_pages, KV, page, 1).

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

NEG_INF = -1e30


def grid_steps(num_blocks: int, pages_per_step: int) -> int:
    """Grid steps along the block axis: P pages per step -> ceil(NB / P)."""
    return -(-num_blocks // max(1, pages_per_step))


def padded_blocks(num_blocks: int, pages_per_step: int) -> int:
    """Block-table width after padding to a multiple of ``pages_per_step``
    (pad entries are null-page references the kernel skips)."""
    return grid_steps(num_blocks, pages_per_step) * max(1, pages_per_step)


def _kernel(kvlen_ref, tbl_ref, layer_ref, q_ref, *refs, scale: float,
            page: int, num_steps: int, pages_per_step: int,
            quantized: bool):
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

    kv_len = kvlen_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)                  # (G, D)

    def _sweep(p, k_ref, v_ref, ks_ref, vs_ref):
        # logical block j*P + p holds positions [bj*page, (bj+1)*page):
        # live iff it overlaps [0, kv_len) — per-slot positions start at 0
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
                preferred_element_type=jnp.float32) * scale   # (G, page)
            tpos = bj * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(tpos < kv_len, s, NEG_INF)
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


def paged_decode_attention_fwd(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_table: jax.Array,
                               kv_len: jax.Array,
                               layer: jax.Array | int = 0, *,
                               k_scale: jax.Array | None = None,
                               v_scale: jax.Array | None = None,
                               pages_per_step: int = 1,
                               interpret: bool = False) -> jax.Array:
    """q (B, 1, H, D); k_pool, v_pool (L, num_pages, KV, page, D) stacked
    head-major pools (a 4D (num_pages, KV, page, D) single-layer pool is
    promoted);
    block_table (B, max_blocks) int32 physical page ids (0 = reserved null
    page for unallocated blocks); kv_len (B,) int32 per-slot token counts
    (positions >= kv_len[b] are masked); layer — which pool layer to
    address (the layer-scan trip counter); k_scale, v_scale — optional
    (L, num_pages, KV, page, 1) f32 per-row-per-head scales for int8 pools
    (each page's scale rows ride the same scalar-prefetched address as the
    page itself; int8 tiles are upcast and scaled inside the f32
    online-softmax accumulator); pages_per_step — pages swept per grid
    step (1 = the original one-page grid).  Returns (B, 1, H, D).
    """
    B, S, H, D = q.shape
    assert S == 1, "paged decode kernel is single-token"
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
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, KV, G, D)                  # kv-major head grouping
    tbl = jnp.asarray(block_table, jnp.int32)
    if NBp != NB:                                # pad with null-page entries
        tbl = jnp.pad(tbl, ((0, 0), (0, NBp - NB)))
    tbl = tbl.reshape(B * NBp)
    kvl = jnp.asarray(kv_len, jnp.int32).reshape(B)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)

    def _page_map(p):
        # the p-th page of grid step j: physical id tbl[b*NBp + j*P + p]
        def index_map(b, h, j, kvl_ref, tbl_ref, lay_ref):
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
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, KV, steps),
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, j, *_: (b, h, 0, 0)),
                *page_spec,                       # k pages 0..P-1
                *page_spec,                       # v pages 0..P-1
                *scale_ins,                       # k then v scales (int8)
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, j, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),     # running row max
                pltpu.VMEM((G, 1), jnp.float32),     # running row sum
                pltpu.VMEM((G, D), jnp.float32),     # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(kvl, tbl, lay, qg, *([k_pool] * P), *([v_pool] * P), *scale_args)
    return out.reshape(B, 1, H, D)
