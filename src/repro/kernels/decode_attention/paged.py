"""Paged single-token decode attention as a Pallas TPU kernel.

The paged serving hot path: one new query per slot attends over that slot's
PAGES of a shared (num_pages, KV, page, D) pool.  The physical page holding
each logical block comes from a scalar-prefetched block table, and the
kernel copies ONLY the pages that hold a slot's ``kv_len`` tokens: its work
and its HBM traffic follow live tokens, not the table's width
(``max_blocks``) and not ``max_seq``.  ``core.hlo_counters`` verifies the
same property on the jnp gather oracle (the dense cache reads every row of
a (B, max_seq, KV, D) cache whether or not it is live).

Shape strategy:

  * grid = (B,): one grid step per slot.  The slot's ``kv_len``, the
    flattened block table and the layer index arrive via scalar prefetch
    (SMEM).
  * the pools stay in HBM (``memory_space=pl.ANY``).  Inside the step a
    ``fori_loop`` runs ``block_trips(kv_len, P * page)`` iterations — a
    trip count read from SMEM, so no iteration runs for a block at or past
    ``kv_len``.  Iteration n copies the block's P live pages
    ``pool[layer, tbl[b, n*P + p]]``, each a whole head-major (KV, page, D)
    page, contiguous in HBM, with one async copy per page and pool.
  * DOUBLE BUFFERING: the copies land in a (2, P, KV, page, row) VMEM
    scratch; block n+1 is fetched while block n is computed.  Pages past
    ``kv_len`` in the last block are not copied; their rows are masked.
  * ALL KV HEADS in the step: q arrives as (KV, G, D) and each KV head runs
    the online softmax over the block's P*page rows, [G, D] x [D, P*page]
    on the MXU (GQA without materializing repeated kv heads).  bf16 tiles
    feed the MXU as they are, accumulating in f32; softmax, probabilities
    and the accumulator are f32.
  * the block size P comes from the pool's shape and dtype
    (``block_pages``): about 512 KiB of VMEM per pool and block, 16 pages
    of 16 tokens at 8 KV heads of 128 bf16 lanes.  ``pages_per_step``
    overrides it (tests cover small and odd P).
  * the pool stays STACKED across layers: the layer-scan caller passes its
    trip counter as the ``layer`` scalar and each copy addresses
    (layer, page) directly — no per-layer pool slice is ever materialized.
  * the online-softmax state of each KV head rides the loop's carry, and
    each block computes every head's scores before any head's update, so
    one head's matmuls never wait on another's softmax.
  * on the TPU every row of a pool lies in memory as whole 128-lane rows,
    and Mosaic's DMA moves whole rows: a copy takes a page's rows at that
    padded width and the kernel reads their first D lanes, so any head
    dim works (interpret mode copies the rows as they are).
  * int8 pools bring (L, num_pages, KV, page, 1) f32 per-row scales,
    copied beside their pages (one 128-lane row per scale on the TPU);
    int8 rows are upcast to f32 and scaled by their rows' scales before
    the matmuls, as the oracle dequantizes.

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
BLOCK_BYTES = 512 * 1024     # VMEM one pool's block of pages takes
_LANES = 128                 # a VMEM row holds a multiple of 128 lanes


def grid_steps(num_blocks: int, pages_per_step: int) -> int:
    """Grid steps along the block axis: P pages per step -> ceil(NB / P)."""
    return -(-num_blocks // max(1, pages_per_step))


def padded_blocks(num_blocks: int, pages_per_step: int) -> int:
    """Block-table width after padding to a multiple of ``pages_per_step``
    (pad entries are null-page references the kernel skips)."""
    return grid_steps(num_blocks, pages_per_step) * max(1, pages_per_step)


def block_pages(kv_heads: int, page: int, head_dim: int, dtype,
                quantized: bool = False) -> int:
    """Pages per loop iteration: as many as fit ``BLOCK_BYTES`` of VMEM
    per pool, counting each page row at its padded lane width, and an
    int8 row's f32 scale at the 128 lanes its copy takes."""
    row = _padded(head_dim) * jnp.dtype(dtype).itemsize
    if quantized:
        row += _LANES * 4
    return max(1, BLOCK_BYTES // (kv_heads * page * row))


def block_trips(kv_len, block_tokens: int):
    """Loop iterations for a slot: blocks of ``block_tokens`` that overlap
    [0, kv_len) — ceil(kv_len / block_tokens), 0 for an empty slot.  The
    kernel calls it on the SMEM scalar; tests call it on ints."""
    return (kv_len + block_tokens - 1) // block_tokens


def _padded(width: int) -> int:
    """A row's width in TPU memory: whole 128-lane rows."""
    return -(-width // _LANES) * _LANES


def _kernel(kvlen_ref, tbl_ref, layer_ref, q_ref, *refs, scale: float,
            page: int, num_blocks: int, pages_per_step: int,
            quantized: bool, copied: tuple):
    P = pages_per_step
    n_pools = 4 if quantized else 2      # k, v (+ their int8 row scales)
    # buffers (2, P, KV, page, copied[i])
    hbm, (o_ref, *bufs, sems) = refs[:n_pools], refs[n_pools:]
    _, KV, G, D = q_ref.shape
    T = P * page                         # tokens per block
    b = pl.program_id(0)
    kv_len = kvlen_ref[b]
    live_pages = block_trips(kv_len, page)
    lay = layer_ref[0]

    @pl.when(b == 0)
    def _zero():
        # rows past kv_len are masked, but a never-written buffer could
        # hold NaN, and 0 * NaN would leak into the accumulator
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    def _copy(n, slot, fn):
        """Apply ``fn`` (start or wait) to block n's live-page copies into
        buffer ``slot``: one whole (KV, page, row) page per pool and page,
        its rows at their ``copied`` width."""
        def _page(p, carry):
            phys = tbl_ref[b * num_blocks + n * P + p]
            for i, (src, buf) in enumerate(zip(hbm, bufs)):
                rows = src.at[lay, phys, :, :, pl.ds(0, copied[i])]
                fn(pltpu.make_async_copy(rows, buf.at[slot, p],
                                         sems.at[i, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(P, live_pages - n * P), _page, 0)

    def _rows(i, slot, h):
        """Block rows (T, D) of kv head h from pool i (0 = k, 1 = v): the
        first D lanes of each copied row; int8 rows are dequantized to f32
        by their per-row scales."""
        x = bufs[i][slot, :, h, :, pl.ds(0, D)]           # (P, page, D)
        if quantized:
            x = x.astype(jnp.float32) * bufs[2 + i][slot, :, h, :,
                                                    pl.ds(0, 1)]
        return x.reshape(T, D)

    def _scores(h, slot, pos0):
        """Masked scores (G, T) of kv head h's queries against the block's
        rows at positions pos0.."""
        q, k = q_ref[0, h], _rows(0, slot, h)             # (G, D), (T, D)
        if k.dtype != q.dtype:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(pos < kv_len, s, NEG_INF)

    def _update(state, s, v):
        """Online-softmax update of one kv head's (m, l, acc) with scores
        s (G, T) over rows v (T, D)."""
        m_prev, l_prev, acc = state
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p_ = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p_.sum(axis=1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p_, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    def _block(n, state):
        slot = n % 2

        @pl.when(n + 1 < trips)
        def _prefetch():
            _copy(n + 1, 1 - slot, lambda c: c.start())

        _copy(n, slot, lambda c: c.wait())
        # every head's scores first, then every head's update: the
        # heads' matmuls do not wait on each other's softmax
        scores = [_scores(h, slot, n * T) for h in range(KV)]
        return tuple(_update(state[h], scores[h], _rows(1, slot, h))
                     for h in range(KV))

    trips = block_trips(kv_len, T)

    @pl.when(trips > 0)
    def _first():
        _copy(0, 0, lambda c: c.start())

    init = (jnp.full((G, 1), NEG_INF, jnp.float32),
            jnp.zeros((G, 1), jnp.float32), jnp.zeros((G, D), jnp.float32))
    state = jax.lax.fori_loop(0, trips, _block, (init,) * KV)
    for h, (_, l, acc) in enumerate(state):
        # an empty slot (no trips) yields zeros
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_fwd(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_table: jax.Array,
                               kv_len: jax.Array,
                               layer: jax.Array | int = 0, *,
                               k_scale: jax.Array | None = None,
                               v_scale: jax.Array | None = None,
                               pages_per_step: int | None = None,
                               interpret: bool = False) -> jax.Array:
    """q (B, 1, H, D); k_pool, v_pool (L, num_pages, KV, page, D) stacked
    head-major pools (a 4D (num_pages, KV, page, D) single-layer pool is
    promoted);
    block_table (B, max_blocks) int32 physical page ids (0 = reserved null
    page for unallocated blocks); kv_len (B,) int32 per-slot token counts
    (positions >= kv_len[b] are masked; a slot at 0 yields zeros); layer —
    which pool layer to address (the layer-scan trip counter); k_scale,
    v_scale — optional (L, num_pages, KV, page, 1) f32 per-row-per-head
    scales for int8 pools (int8 rows are upcast to f32 and scaled by
    their rows' scales inside the kernel); pages_per_step —
    pages copied per loop iteration (None = ``block_pages`` of the pool's
    shape and dtype).
    Returns (B, 1, H, D).
    """
    B, S, H, D = q.shape
    assert S == 1, "paged decode kernel is single-token"
    quantized = k_scale is not None
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
        if quantized:
            k_scale, v_scale = k_scale[None], v_scale[None]
    _, _, KV, page, _ = k_pool.shape
    NB = block_table.shape[1]
    P = pages_per_step or block_pages(KV, page, D, k_pool.dtype, quantized)
    P = max(1, min(P, NB))               # a block never outgrows the table
    G = H // KV
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, KV, G, D)                  # kv-major head grouping
    tbl = jnp.asarray(block_table, jnp.int32).reshape(B * NB)
    kvl = jnp.asarray(kv_len, jnp.int32).reshape(B)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)

    pools = [k_pool, v_pool] + ([k_scale, v_scale] if quantized else [])
    # on the TPU a row lies in memory as whole 128-lane rows whatever its
    # width, and Mosaic's DMA moves whole rows: each copy takes a page's
    # rows at that padded width (the kernel reads their first D lanes, or
    # an int8 scale's first); interpret mode copies the rows as they are
    copied = tuple(x.shape[-1] if interpret else _padded(x.shape[-1])
                   for x in pools)
    kernel = functools.partial(_kernel, scale=scale, page=page,
                               num_blocks=NB, pages_per_step=P,
                               quantized=quantized, copied=copied)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, KV, G, D), lambda b, *_: (b, 0, 0, 0)),
                *[pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
            ],
            out_specs=pl.BlockSpec((1, KV, G, D), lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                *[pltpu.VMEM((2, P, KV, page, w), x.dtype)
                  for x, w in zip(pools, copied)],
                pltpu.SemaphoreType.DMA((len(pools), 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(kvl, tbl, lay, qg, *pools)
    return out.reshape(B, 1, H, D)
