"""Dispatch wrapper for the Pallas decode-attention kernel.

Runs the real kernel on TPU and interpret mode on the CPU backend (tests).
Called from inside the jitted decode step (transformer.attn_decode when
``cfg.attention_impl == "pallas"``), so no jit wrapper here.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import auto_interpret
from repro.kernels.decode_attention import decode as _decode
from repro.kernels.decode_attention import paged as _paged
from repro.kernels.decode_attention import prefill_paged as _prefill


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len, kv_start: Optional[jax.Array] = None,
                     block_kv: int = 128,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q (B, 1, H, D); k, v (B, T, KV, D); kv_len scalar; kv_start (B,) or
    None.  Returns (B, 1, H, D)."""
    return _decode.decode_attention_fwd(
        q, k, v, kv_len, kv_start, block_kv=block_kv,
        interpret=auto_interpret(interpret))


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           kv_len: jax.Array, layer=0,
                           pages_per_step: Optional[int] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q (B, 1, H, D); k_pool, v_pool (L, num_pages, KV, page, D) stacked
    head-major pools (4D single-layer accepted); block_table
    (B, max_blocks) int32 (page 0 = reserved null page); kv_len (B,) int32
    per-slot token counts;
    layer — pool layer to address; pages_per_step — pages copied per loop
    iteration (None = derived from the pool's shape and dtype); k_scale,
    v_scale — optional (L, num_pages, KV, page, 1) f32 per-row scales for
    int8 pools.  Returns (B, 1, H, D)."""
    return _paged.paged_decode_attention_fwd(
        q, k_pool, v_pool, block_table, kv_len, layer,
        k_scale=k_scale, v_scale=v_scale,
        pages_per_step=pages_per_step,
        interpret=auto_interpret(interpret))


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_table: jax.Array,
                            base_len: jax.Array, new_len: jax.Array,
                            layer=0,
                            pages_per_step: int = 1,
                            k_scale: Optional[jax.Array] = None,
                            v_scale: Optional[jax.Array] = None,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Ragged multi-token paged prefill: q (B, T, H, D) chunk (its K/V
    rows already scattered into the pool); k_pool, v_pool
    (L, num_pages, KV, page, D) stacked head-major pools (4D single-layer
    accepted);
    block_table (B, max_blocks) int32 (page 0 = reserved null page);
    base_len (B,) int32 tokens resident before the chunk; new_len (B,)
    int32 = base_len + granted chunk tokens; layer — pool layer to
    address; pages_per_step — page-list blocking factor (P pages swept
    per grid step); k_scale, v_scale — optional (L, num_pages, KV, page,
    1) f32 per-row scales for int8 pools.  Returns (B, T, H, D)."""
    return _prefill.paged_prefill_attention_fwd(
        q, k_pool, v_pool, block_table, base_len, new_len, layer,
        k_scale=k_scale, v_scale=v_scale,
        pages_per_step=pages_per_step,
        interpret=auto_interpret(interpret))
