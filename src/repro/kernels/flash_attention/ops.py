"""Jitted wrapper for the Pallas flash attention kernel.

Forward runs the Pallas kernel (interpret mode on CPU); backward falls back
to the custom-VJP jnp flash (same math, O(S) memory).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import auto_interpret
from repro.kernels.flash_attention import flash as _flash
from repro.models.flash import flash_attention_ref


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_vjp(q, k, v, causal, block_q, block_kv):
    return _flash.flash_attention_fwd(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=auto_interpret())


def _fwd(q, k, v, causal, block_q, block_kv):
    out = _flash_vjp(q, k, v, causal, block_q, block_kv)
    return out, (q, k, v)


def _bwd(causal, block_q, block_kv, res, dout):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_ref(
            q_, k_, v_, causal, block_q, block_kv), q, k, v)
    return vjp(dout)


_flash_vjp.defvjp(_fwd, _bwd)


@partial(jax.jit, static_argnames=("causal", "block_q", "block_kv"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_kv: int = 512):
    """q (B,S,H,D); k, v (B,T,H,D) (kv repeated to H heads)."""
    S, T = q.shape[1], k.shape[1]
    bq = min(block_q, S)
    bkv = min(block_kv, T)
    return _flash_vjp(q, k, v, causal, bq, bkv)
