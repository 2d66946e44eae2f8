"""Pure-jnp oracles for the Pallas decode-attention kernels: the direct
softmax attention with kv_len / kv_start window masking
(repro.models.attention.direct_attention) — interpret-mode tests assert the
kernels match them bit-for-bit in fp32.

``paged_decode_attention_ref`` is also the production jnp path for the paged
cache (``cfg.attention_impl == "reference"``): a block-table gather
materializes each slot's logical view of the pool, so the HLO census sees
gather traffic proportional to live pages — the roofline claim the paged
design exists to make measurable."""
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.attention import direct_attention


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len, kv_start: Optional[jax.Array] = None
                         ) -> jax.Array:
    """q (B, 1, H, D); k, v (B, T, KV, D).  Returns (B, 1, H, D)."""
    kv_len_m1 = kv_len - 1
    return direct_attention(q, k, v, causal=True, q_offset=kv_len_m1,
                            kv_len=kv_len, kv_start=kv_start)


def _gather_logical(q, k_pool, v_pool, block_table, layer,
                    k_scale=None, v_scale=None):
    """Gather each slot's pages (and, for int8 pools, their per-row scales)
    into the logical (B, max_blocks*page, KV, D) view — live pages only,
    never the whole pool.  Quantized pools are dequantized with the SAME
    arithmetic as the kernels' page sweeps (upcast int8 to f32, multiply by
    the row's scale), so interpret-equivalence pins both paths."""
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    B = block_table.shape[0]
    _, _, KV, page, D = k_pool.shape
    NB = block_table.shape[1]
    kg = k_pool[layer, block_table]          # (B, NB, KV, page, D)
    vg = v_pool[layer, block_table]
    if k_scale is not None:                  # int8 pages + per-row scales
        kg = kg.astype(jnp.float32) * k_scale[layer, block_table]
        vg = vg.astype(jnp.float32) * v_scale[layer, block_table]
        kg = kg.astype(q.dtype)
        vg = vg.astype(q.dtype)
    # head-major pages -> the token-major logical view
    return (kg.transpose(0, 1, 3, 2, 4).reshape(B, NB * page, KV, D),
            vg.transpose(0, 1, 3, 2, 4).reshape(B, NB * page, KV, D))


def paged_decode_attention_ref(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_table: jax.Array,
                               kv_len: jax.Array, layer=0,
                               k_scale: Optional[jax.Array] = None,
                               v_scale: Optional[jax.Array] = None
                               ) -> jax.Array:
    """q (B, 1, H, D); k_pool, v_pool (L, num_pages, KV, page, D) stacked
    head-major pools (4D single-layer pools are promoted); block_table
    (B, max_blocks) int32; kv_len (B,) int32 per-slot token counts; layer —
    the pool layer to address; k_scale, v_scale — optional
    (L, num_pages, KV, page, 1) f32 per-row scales for int8 pools
    (dequantized after the gather).  Gathers
    each slot's pages into its logical (max_blocks*page, KV, D) view in ONE
    (layer, page) gather — live pages only, never the whole pool — then
    masks positions >= kv_len[b].  Returns (B, 1, H, D)."""
    kg, vg = _gather_logical(q, k_pool, v_pool, block_table, layer,
                             k_scale, v_scale)
    return direct_attention(q, kg, vg, causal=False, kv_len=kv_len)


def paged_prefill_attention_ref(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, block_table: jax.Array,
                                base_len: jax.Array, new_len: jax.Array,
                                layer=0,
                                k_scale: Optional[jax.Array] = None,
                                v_scale: Optional[jax.Array] = None
                                ) -> jax.Array:
    """Oracle for the ragged multi-token paged PREFILL kernel: q
    (B, T, H, D) — a chunk whose K/V rows are already scattered into the
    pool; base_len (B,) tokens resident before the chunk; new_len (B,)
    = base_len + granted tokens.  Gathers each slot's logical view in one
    (layer, page) gather — live pages only — then applies the per-slot
    CAUSAL mask (query row t attends positions <= base_len[b] + t) and the
    per-slot extent mask (< new_len[b]).  Rows past a slot's grant are
    masked the same way the kernel masks them (their output is garbage the
    engine ignores, but the two paths agree row-for-row).
    Returns (B, T, H, D)."""
    kg, vg = _gather_logical(q, k_pool, v_pool, block_table, layer,
                             k_scale, v_scale)
    return direct_attention(q, kg, vg, causal=True, q_offset=base_len,
                            kv_len=new_len)
