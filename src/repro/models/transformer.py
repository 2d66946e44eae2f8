"""Model assembly for all assigned families.

  * decoder-only LM (dense / moe / vlm[stub frontend])  — ``lm_*``
  * encoder-decoder (whisper, stub frontend)            — ``whisper_*``
  * pure SSM LM (falcon-mamba)                          — handled by ``lm_*``
    via mamba blocks
  * hybrid (zamba2: mamba2 trunk + shared attn block)   — ``lm_*`` grouped

Layers are ``lax.scan``-stacked (compact HLO, fast 512-device compiles).
Every forward works in three modes:
  train    — full sequence, no cache, returns (logits, aux)
  prefill  — full sequence, returns (logits, aux, cache)
  decode   — one token + cache, returns (logits, cache)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import constrain
from repro.models import ssm as ssm_mod
from repro.models.attention import attention, direct_attention
from repro.models.kv_quant import quantize_rows
from repro.models.layers import (
    apply_rope, dense, embed, gelu_mlp, layernorm, mrope_angles, rmsnorm,
    rope_angles, sinusoidal_positions, swiglu, unembed)
from repro.models.moe import moe_ffn
from repro.models.params import ParamDecl, stacked


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def apply_norm(h, p, cfg: ArchConfig):
    if cfg.norm_kind == "layernorm":
        return layernorm(h, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(h, p["scale"], cfg.norm_eps)


def _norm_decl(d: int) -> Dict[str, ParamDecl]:
    return {"scale": ParamDecl((d,), (None,), "ones"),
            "bias": ParamDecl((d,), (None,), "zeros")}


# ---------------------------------------------------------------------------
# schemas
# ---------------------------------------------------------------------------

def _attn_decls(cfg: ArchConfig) -> Dict[str, ParamDecl]:
    d, hd = cfg.d_model, cfg.head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    decls = {
        "wq": ParamDecl((d, H * hd), ("fsdp", "model")),
        "wk": ParamDecl((d, KV * hd), ("fsdp", None)),
        "wv": ParamDecl((d, KV * hd), ("fsdp", None)),
        "wo": ParamDecl((H * hd, d), ("model", "fsdp")),
    }
    if cfg.qkv_bias:
        decls["bq"] = ParamDecl((H * hd,), ("model",), "zeros")
        decls["bk"] = ParamDecl((KV * hd,), (None,), "zeros")
        decls["bv"] = ParamDecl((KV * hd,), (None,), "zeros")
    return decls


def _ffn_decls(cfg: ArchConfig) -> Dict[str, ParamDecl]:
    d, ff = cfg.d_model, cfg.d_ff
    if cfg.ffn_kind == "swiglu":
        return {"w_gate": ParamDecl((d, ff), ("fsdp", "model")),
                "w_up": ParamDecl((d, ff), ("fsdp", "model")),
                "w_down": ParamDecl((ff, d), ("model", "fsdp"))}
    return {"w_up": ParamDecl((d, ff), ("fsdp", "model")),
            "b_up": ParamDecl((ff,), ("model",), "zeros"),
            "w_down": ParamDecl((ff, d), ("model", "fsdp")),
            "b_down": ParamDecl((d,), (None,), "zeros")}


def _moe_decls(cfg: ArchConfig) -> Dict[str, ParamDecl]:
    d, ff, E, G = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.ep_shards
    ffp = E * ff // G
    decls = {
        "router": ParamDecl((d, E), ("fsdp", None)),
        "w1": ParamDecl((G, d, ffp), ("ep", "moe_fsdp", None)),
        "w2": ParamDecl((G, ffp, d), ("ep", None, "moe_fsdp")),
    }
    if cfg.ffn_kind == "swiglu":
        decls["w3"] = ParamDecl((G, d, ffp), ("ep", "moe_fsdp", None))
    return decls


def _mamba_decls(cfg: ArchConfig) -> Dict[str, ParamDecl]:
    d = cfg.d_model
    d_in = d * cfg.ssm_expand
    N, K = cfg.ssm_state, cfg.ssm_conv
    if cfg.mamba_version == 1:
        dt_rank = max(1, d // 16)
        return {
            "in_proj": ParamDecl((d, 2 * d_in), ("fsdp", "model")),
            "conv_w": ParamDecl((d_in, K), ("model", None)),
            "conv_b": ParamDecl((d_in,), ("model",), "zeros"),
            "x_proj": ParamDecl((d_in, dt_rank + 2 * N), ("model", None)),
            "dt_proj": ParamDecl((dt_rank, d_in), (None, "model")),
            "dt_bias": ParamDecl((d_in,), ("model",), "ssm_dt"),
            "A_log": ParamDecl((d_in, N), ("model", None), "ssm_a"),
            "D": ParamDecl((d_in,), ("model",), "ones"),
            "out_proj": ParamDecl((d_in, d), ("model", "fsdp")),
        }
    hd = cfg.ssm_head_dim
    H = d_in // hd
    return {
        "w_zx": ParamDecl((d, 2 * d_in), ("fsdp", "model")),
        "w_bc": ParamDecl((d, 2 * N), ("fsdp", None)),
        "w_dt": ParamDecl((d, H), ("fsdp", "model")),
        "conv_w": ParamDecl((d_in, K), ("model", None)),
        "conv_b": ParamDecl((d_in,), ("model",), "zeros"),
        "dt_bias": ParamDecl((H,), ("model",), "ssm_dt"),
        "A_log": ParamDecl((H,), ("model",), "ssm_a_scalar"),
        "D": ParamDecl((H,), ("model",), "ones"),
        "norm": ParamDecl((d_in,), ("model",), "ones"),
        "out_proj": ParamDecl((d_in, d), ("model", "fsdp")),
    }


def _block_decls(cfg: ArchConfig) -> Dict[str, Any]:
    if cfg.mamba_version:
        return {"ln1": _norm_decl(cfg.d_model),
                "mixer": _mamba_decls(cfg)}
    block = {"ln1": _norm_decl(cfg.d_model), "ln2": _norm_decl(cfg.d_model),
             "attn": _attn_decls(cfg)}
    block["moe" if cfg.n_experts else "ffn"] = (
        _moe_decls(cfg) if cfg.n_experts else _ffn_decls(cfg))
    return block


def _shared_attn_decls(cfg: ArchConfig) -> Dict[str, Any]:
    """zamba2's shared transformer block (attention + FFN, one weight set)."""
    return {"ln1": _norm_decl(cfg.d_model), "ln2": _norm_decl(cfg.d_model),
            "attn": _attn_decls(cfg), "ffn": _ffn_decls(cfg)}


def _stack_tree(tree, n: int):
    return jax.tree.map(lambda d: stacked(d, n),
                        tree, is_leaf=lambda x: isinstance(x, ParamDecl))


def schema(cfg: ArchConfig) -> Dict[str, Any]:
    d, V = cfg.d_model, cfg.vocab_size
    sch: Dict[str, Any] = {}
    # the token embedding table is always present: stub-frontend archs
    # (audio/vlm) still decode text tokens through it
    sch["embed"] = ParamDecl((V, d), ("model", "fsdp"), "embed")
    if not cfg.tie_embeddings:
        sch["unembed"] = ParamDecl((V, d), ("model", "fsdp"), "embed")
    sch["ln_f"] = _norm_decl(d)
    if cfg.is_encoder_decoder:
        enc_block = {"ln1": _norm_decl(d), "ln2": _norm_decl(d),
                     "attn": _attn_decls(cfg), "ffn": _ffn_decls(cfg)}
        dec_block = {"ln1": _norm_decl(d), "ln2": _norm_decl(d),
                     "ln3": _norm_decl(d), "attn": _attn_decls(cfg),
                     "cross": _attn_decls(cfg), "ffn": _ffn_decls(cfg)}
        sch["encoder"] = _stack_tree(enc_block, cfg.encoder_layers)
        sch["decoder"] = _stack_tree(dec_block, cfg.n_layers)
        sch["ln_enc"] = _norm_decl(d)
        return sch
    if cfg.family == "hybrid":
        sch["blocks"] = _stack_tree(_block_decls(cfg), cfg.n_layers)
        sch["shared_attn"] = _shared_attn_decls(cfg)
        return sch
    sch["blocks"] = _stack_tree(_block_decls(cfg), cfg.n_layers)
    return sch


# ---------------------------------------------------------------------------
# attention block application
# ---------------------------------------------------------------------------

def _rope(cfg: ArchConfig, positions) -> Optional[Tuple]:
    if not cfg.rope_theta:
        return None
    if cfg.mrope_sections:
        return mrope_angles(positions, cfg.head_dim, cfg.rope_theta,
                            cfg.mrope_sections)
    return rope_angles(positions, cfg.head_dim, cfg.rope_theta)


def _qkv(hn, p, cfg: ArchConfig, rope, decode: bool = False):
    B, S, d = hn.shape
    hd = cfg.head_dim
    q = dense(hn, p["wq"], p.get("bq")).reshape(B, S, cfg.n_heads, hd)
    k = dense(hn, p["wk"], p.get("bk")).reshape(B, S, cfg.n_kv_heads, hd)
    v = dense(hn, p["wv"], p.get("bv")).reshape(B, S, cfg.n_kv_heads, hd)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if decode:
        # decode attends against a sequence-sharded cache; keep the tiny
        # q/k/v replicated across the model axis so GSPMD keeps the cache
        # stationary and all-reduces only softmax partials.
        q = constrain(q, "batch", None, None, None)
    else:
        q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, None, None)
    v = constrain(v, "batch", None, None, None)
    return q, k, v


def attn_apply(h, p, cfg: ArchConfig, rope, causal=True):
    """train/prefill path: p is the block param dict (ln1 + attn).
    Returns (out, (k, v))."""
    hn = apply_norm(h, p["ln1"], cfg)
    a = p["attn"]
    q, k, v = _qkv(hn, a, cfg, rope)
    out = attention(q, k, v, causal=causal, chunk_q=cfg.attn_chunk_q,
                    chunk_kv=cfg.attn_chunk_kv, impl=cfg.attention_impl)
    B, S, _, _ = out.shape
    out = dense(out.reshape(B, S, -1), a["wo"])
    return constrain(out, "batch", None, None), (k, v)


def attn_decode(h, p, cfg: ArchConfig, rope, k_cache, v_cache, pos,
                start=None):
    """decode path: h (B, 1, d); k_cache/v_cache (B, T, KV, hd); updates at
    ``pos`` and attends over [start[b], pos] (``start`` is the per-slot
    window base of the continuous-batching engine, None -> 0)."""
    hn = apply_norm(h, p["ln1"], cfg)
    a = p["attn"]
    q, k, v = _qkv(hn, a, cfg, rope, decode=True)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
    # keep the cache sequence-sharded through the in-place update — without
    # this GSPMD may replicate the updated cache across the model axis
    k_cache = constrain(k_cache, "batch", "cache_seq", None, None)
    v_cache = constrain(v_cache, "batch", "cache_seq", None, None)
    if cfg.attention_impl == "pallas":
        from repro.kernels.decode_attention.ops import decode_attention
        out = decode_attention(q, k_cache, v_cache, kv_len=pos + 1,
                               kv_start=start)
    else:
        out = direct_attention(q, k_cache, v_cache, causal=True,
                               q_offset=pos, kv_len=pos + 1, kv_start=start)
    B = h.shape[0]
    out = dense(out.reshape(B, 1, -1), a["wo"])
    return out, k_cache, v_cache


def _row_index(layer, phys, off, n_kv):
    """Index of N appended K/V rows in a head-major (L, pages, KV, page, ...)
    pool: row n, head h lands at (layer, phys[n], h, off[n]), so ``.at[]``
    takes (N, KV, ...) updates.  Naming every head explicitly keeps all
    four indexed dims major, so the row scatter runs in the pool's own
    layout; slicing the head axis with ``:`` instead makes XLA relayout
    the whole pool around every append."""
    heads = jnp.arange(n_kv, dtype=jnp.int32)[None, :]
    return layer, phys[:, None], heads, off[:, None]


def attn_decode_paged(h, p, cfg: ArchConfig, rope, k_pool, v_pool, layer,
                      table, lengths, active, k_scale=None, v_scale=None):
    """decode path over the paged KV pool: h (B, 1, d); k_pool/v_pool are
    the STACKED head-major (L, num_pages, KV, page, hd) pools — appended
    to and gathered from with an explicit (layer, page) scatter/gather so no
    pool-sized per-layer slice is ever materialized (that slice is exactly
    the max_seq-proportional traffic the paged cache removes; the HLO
    census asserts the step's bytes scale with live pages).  table
    (B, max_blocks) int32 physical page ids (page 0 = reserved null page,
    where inactive slots' writes land); lengths (B,) int32 per-slot token
    counts; active (B,) bool.

    Appends this step's K/V at each slot's OWN position (page
    ``table[b, lengths[b] // page]``, row ``lengths[b] % page``) and attends
    positions [0, lengths[b]] — no shared cache position, no start-window
    masking: a slot's window is exactly the pages its table references.

    COW-aware append invariant: with prefix sharing a physical page may be
    referenced by SEVERAL block tables.  The append path assumes the page
    at the slot's write position is exclusively owned — the host scheduler
    copy-on-write privatizes any shared page before granting the steps
    that would write it, so a write through one table can never reach rows
    another table still exposes.  Reads need no such care: rope positions
    are request-relative, so the K/V rows of an identical token prefix are
    bit-identical whichever slot computed them, and rows past a sharer's
    ``length`` in a shared trailing page are masked by its own kv_len.

    With quantized pools (k_scale/v_scale not None) the appended row is
    int8-quantized per KV head and the row's f32 scale lands at the same
    (layer, page, head, row) address — the scale travels with its page.

    The step's work is named by ``jax.named_scope`` (``qkv``, ``kv_write``,
    ``attn``, ``out_proj``), so each device op's ``op_name`` says which
    part of the layer it belongs to."""
    a = p["attn"]
    with jax.named_scope("qkv"):
        hn = apply_norm(h, p["ln1"], cfg)
        q, k, v = _qkv(hn, a, cfg, rope, decode=True)
    B = h.shape[0]
    with jax.named_scope("kv_write"):
        page = k_pool.shape[3]
        nb = table.shape[1]
        blk = jnp.minimum(lengths // page, nb - 1)
        phys = jnp.where(active, table[jnp.arange(B), blk], 0)
        off = lengths % page
        at = _row_index(layer, phys, off, k.shape[2])
        if k_scale is not None:            # quantize the appended row
            kq, ks = quantize_rows(k[:, 0])  # (B, KV, hd) int8, (B, KV, 1)
            vq, vs = quantize_rows(v[:, 0])
            k_pool = k_pool.at[at].set(kq)
            v_pool = v_pool.at[at].set(vq)
            k_scale = k_scale.at[at].set(ks)
            v_scale = v_scale.at[at].set(vs)
            k_scale = constrain(k_scale, None, "cache_seq", None, None, None)
            v_scale = constrain(v_scale, None, "cache_seq", None, None, None)
        else:
            k_pool = k_pool.at[at].set(k[:, 0].astype(k_pool.dtype))
            v_pool = v_pool.at[at].set(v[:, 0].astype(v_pool.dtype))
        # keep the pool page-sharded through the in-place update
        k_pool = constrain(k_pool, None, "cache_seq", None, None, None)
        v_pool = constrain(v_pool, None, "cache_seq", None, None, None)
    with jax.named_scope("attn"):
        kv_len = lengths + 1
        if cfg.attention_impl == "pallas":
            from repro.kernels.decode_attention.ops import (
                paged_decode_attention)
            out = paged_decode_attention(q, k_pool, v_pool, table, kv_len,
                                         layer,
                                         pages_per_step=cfg.pages_per_step,
                                         k_scale=k_scale, v_scale=v_scale)
        else:
            from repro.kernels.decode_attention.ref import (
                paged_decode_attention_ref)
            out = paged_decode_attention_ref(q, k_pool, v_pool, table,
                                             kv_len, layer, k_scale=k_scale,
                                             v_scale=v_scale)
    with jax.named_scope("out_proj"):
        out = dense(out.reshape(B, 1, -1), a["wo"])
    return out, k_pool, v_pool, k_scale, v_scale


def attn_prefill_paged(h, p, cfg: ArchConfig, rope, k_pool, v_pool, layer,
                       table, base, new_len, k_scale=None, v_scale=None):
    """Ragged multi-token CHUNKED-PREFILL path over the paged KV pool:
    h (B, T, d) — a chunk of up to T prompt tokens per slot; base (B,)
    int32 tokens resident before the chunk; new_len (B,) int32 = base +
    the slot's granted chunk tokens (rows past the grant are dead: their
    K/V appends land on the null page and their outputs are ignored).

    Scatters ALL the chunk's K/V rows in one (layer, page) scatter — row t
    of slot b goes to page ``table[b, (base[b]+t) // page]``, row
    ``(base[b]+t) % page`` — then computes CAUSAL attention of the whole
    (T, ...) query block against the slot's live pages plus the in-flight
    chunk (query row t attends positions <= base[b]+t, so the pre-scattered
    future rows of the same chunk are invisible to earlier rows).  One
    kernel step appends and attends T tokens; the prefill-by-decode path
    paid T sequential decode-cell steps for the same rows.

    The COW-aware append invariant of ``attn_decode_paged`` carries over
    verbatim: the scheduler privatizes any shared page the chunk's rows
    would touch (and grants prefill in page-aligned token blocks) BEFORE
    the tick, so a chunk scatter can never reach rows another block table
    still exposes.  Named scopes as in ``attn_decode_paged``."""
    a = p["attn"]
    with jax.named_scope("qkv"):
        hn = apply_norm(h, p["ln1"], cfg)
        q, k, v = _qkv(hn, a, cfg, rope, decode=True)
    B, T, _ = h.shape
    with jax.named_scope("kv_write"):
        page = k_pool.shape[3]
        nb = table.shape[1]
        tok_pos = base[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        blk = jnp.minimum(tok_pos // page, nb - 1)
        live = tok_pos < new_len[:, None]                   # granted rows
        phys = jnp.where(live, jnp.take_along_axis(table, blk, axis=1), 0)
        off = (tok_pos % page).reshape(B * T)
        phys = phys.reshape(B * T)
        KV, hd = k.shape[2], k.shape[3]
        at = _row_index(layer, phys, off, KV)
        if k_scale is not None:            # quantize all the chunk's rows
            kq, ks = quantize_rows(k.reshape(B * T, KV, hd))
            vq, vs = quantize_rows(v.reshape(B * T, KV, hd))
            k_pool = k_pool.at[at].set(kq)
            v_pool = v_pool.at[at].set(vq)
            k_scale = k_scale.at[at].set(ks)
            v_scale = v_scale.at[at].set(vs)
            k_scale = constrain(k_scale, None, "cache_seq", None, None, None)
            v_scale = constrain(v_scale, None, "cache_seq", None, None, None)
        else:
            k_pool = k_pool.at[at].set(
                k.reshape(B * T, KV, hd).astype(k_pool.dtype))
            v_pool = v_pool.at[at].set(
                v.reshape(B * T, KV, hd).astype(v_pool.dtype))
        # keep the pool page-sharded through the in-place update
        k_pool = constrain(k_pool, None, "cache_seq", None, None, None)
        v_pool = constrain(v_pool, None, "cache_seq", None, None, None)
    with jax.named_scope("attn"):
        if cfg.attention_impl == "pallas":
            from repro.kernels.decode_attention.ops import (
                paged_prefill_attention)
            out = paged_prefill_attention(q, k_pool, v_pool, table, base,
                                          new_len, layer,
                                          pages_per_step=cfg.pages_per_step,
                                          k_scale=k_scale, v_scale=v_scale)
        else:
            from repro.kernels.decode_attention.ref import (
                paged_prefill_attention_ref)
            out = paged_prefill_attention_ref(q, k_pool, v_pool, table,
                                              base, new_len, layer,
                                              k_scale=k_scale,
                                              v_scale=v_scale)
    with jax.named_scope("out_proj"):
        out = dense(out.reshape(B, T, -1), a["wo"])
    return out, k_pool, v_pool, k_scale, v_scale


def ffn_apply(h, p, cfg: ArchConfig):
    hn = apply_norm(h, p["ln2"], cfg)
    f = p["ffn"]
    if cfg.ffn_kind == "swiglu":
        out = swiglu(hn, f["w_gate"], f["w_up"], f["w_down"])
    else:
        out = gelu_mlp(hn, f["w_up"], f["b_up"], f["w_down"], f["b_down"])
    return constrain(out, "batch", None, None)


def decode_ffn(h, p, cfg: ArchConfig):
    """The post-attention residual of a decode/prefill step: routed MoE
    (aux dropped — no load-balance loss at inference) or the dense FFN.
    Shared by the dense decode, paged decode and paged prefill bodies so
    the lanes cannot silently diverge.  Named scope ``ffn``."""
    with jax.named_scope("ffn"):
        if cfg.n_experts:
            m = p["moe"]
            hn = apply_norm(h, p["ln2"], cfg)
            o, _ = moe_ffn(hn, m["router"], m["w1"], m["w2"], m.get("w3"),
                           cfg)
            return h + o
        return h + ffn_apply(h, p, cfg)


def dense_block(h, p, cfg: ArchConfig, rope):
    out, kv = attn_apply(h, p, cfg, rope, causal=cfg.causal)
    h = h + out
    if cfg.n_experts:
        m = p["moe"]
        hn = apply_norm(h, p["ln2"], cfg)
        out, aux = moe_ffn(hn, m["router"], m["w1"], m["w2"],
                           m.get("w3"), cfg)
        h = h + out
    else:
        aux = jnp.zeros((), jnp.float32)
        h = h + ffn_apply(h, p, cfg)
    return constrain(h, "batch", None, None), aux, kv


def mamba_block_apply(h, p, cfg: ArchConfig, state=None):
    hn = apply_norm(h, p["ln1"], cfg)
    fn = (ssm_mod.mamba1_block if cfg.mamba_version == 1
          else ssm_mod.mamba2_block)
    out, new_state = fn(hn, p["mixer"], cfg, state)
    return constrain(h + out, "batch", None, None), new_state


def shared_attn_block(h, p, cfg: ArchConfig, rope):
    out, kv = attn_apply(h, p, cfg, rope, causal=True)
    h = h + out
    h = h + ffn_apply(h, p, cfg)
    return constrain(h, "batch", None, None), kv


def shared_attn_decode(h, p, cfg: ArchConfig, rope, k_c, v_c, pos,
                       start=None):
    out, k_c, v_c = attn_decode(h, p, cfg, rope, k_c, v_c, pos, start)
    h = h + out
    h = h + ffn_apply(h, p, cfg)
    return h, k_c, v_c


def _maybe_remat(fn, cfg: ArchConfig):
    if cfg.remat in ("block", "full"):
        return jax.checkpoint(fn)
    return fn


# ---------------------------------------------------------------------------
# decoder-only LM forward (dense / moe / vlm / ssm / hybrid)
# ---------------------------------------------------------------------------

def _embed_in(params, cfg: ArchConfig, inputs):
    if cfg.embed_inputs:
        h = embed(inputs, params["embed"]).astype(cfg.param_dtype)
    else:
        h = inputs.astype(cfg.param_dtype)
    return constrain(h, "batch", None, None)


def _logits(params, cfg: ArchConfig, h):
    h = apply_norm(h, params["ln_f"], cfg)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(h, table)
    return constrain(logits, "batch", None, "model")


def _logits_exact(params, cfg: ArchConfig, h):
    """f32 unembed for positions whose logits DECIDE a token (decode steps
    and the prefill last position).  The activation-dtype unembed rounds
    logits to bf16 (~2^-8 relative), coarse enough to flip an argmax
    near-tie between two numerically-equivalent lanes (batched prefill vs
    prefill-by-decode picked different tokens on ragged workloads); at f32
    the gap that could flip is ~1e-7 of the logit scale, below any real
    cross-lane divergence the harness would want to catch.  Full-sequence
    training logits stay in activation dtype — the loss path upcasts
    inside the fused unembed+CE and never samples."""
    h = apply_norm(h, params["ln_f"], cfg)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(h.astype(jnp.float32), table.astype(jnp.float32))
    return constrain(logits, "batch", None, "model")


def lm_forward(params, cfg: ArchConfig, inputs, positions,
               mode: str = "train"):
    """inputs: tokens (B, S) int32 or embeddings (B, S, d).
    positions: (B, S) or (3, B, S) for M-RoPE.
    mode: train | prefill | hidden (hidden returns the post-ln_f hidden
    states instead of logits — the fused unembed+CE loss consumes that;
    prefill returns only the LAST position's logits, (B, 1, V) at f32 via
    _logits_exact, since their sole consumer samples the next token).
    Returns (logits_or_hidden, aux, cache_parts or None)."""
    assert mode in ("train", "prefill", "hidden")
    h = _embed_in(params, cfg, inputs)
    rope = _rope(cfg, positions)

    if cfg.family == "ssm":
        def body(carry, p):
            h, = carry
            h, _ = mamba_block_apply(h, p, cfg)
            return (h,), None
        (h,), _ = jax.lax.scan(_maybe_remat(body, cfg), (h,),
                               params["blocks"])
        if mode == "hidden":
            return (apply_norm(h, params["ln_f"], cfg),
                    jnp.zeros((), jnp.float32), None)
        if mode == "prefill":
            return (_logits_exact(params, cfg, h[:, -1:]),
                    jnp.zeros((), jnp.float32), None)
        return _logits(params, cfg, h), jnp.zeros((), jnp.float32), None

    if cfg.family == "hybrid":
        return _hybrid_forward(params, cfg, h, rope, mode)

    def body(carry, p):
        h, aux = carry
        h, aux_i, kv = dense_block(h, p, cfg, rope)
        return (h, aux + aux_i), (kv if mode == "prefill" else None)

    g = cfg.remat_group
    if g > 1 and cfg.n_layers % g == 0 and mode != "prefill":
        # layer-grouped remat: checkpoint every g layers — the saved
        # residual stack is (L/g, B, S, d) instead of (L, B, S, d); the
        # backward recomputes g layers per checkpoint (each layer still
        # recomputed exactly once).
        grouped = jax.tree.map(
            lambda x: x.reshape((cfg.n_layers // g, g) + x.shape[1:]),
            params["blocks"])

        def group_body(carry, gp):
            carry, _ = jax.lax.scan(body, carry, gp)
            return carry, None

        (h, aux), kvs = jax.lax.scan(_maybe_remat(group_body, cfg),
                                     (h, jnp.zeros((), jnp.float32)),
                                     grouped)
    else:
        (h, aux), kvs = jax.lax.scan(_maybe_remat(body, cfg),
                                     (h, jnp.zeros((), jnp.float32)),
                                     params["blocks"])
    if mode == "hidden":
        return apply_norm(h, params["ln_f"], cfg), aux, None
    if mode == "prefill":
        # prefill logits exist only to SAMPLE the next token after the
        # prompt: unembed just the last position, at f32 (shape (B, 1, V)
        # so callers' logits[:, -1] keeps working)
        return (_logits_exact(params, cfg, h[:, -1:]), aux,
                {"k": kvs[0], "v": kvs[1]})          # (L, B, S, KV, hd)
    return _logits(params, cfg, h), aux, None


def lm_decode(params, cfg: ArchConfig, tokens, cache):
    """tokens (B, 1); cache per family (see init_cache).

    ``pos`` is both the cache ROW the new token is written to and its rope
    position (whole-batch generation never rebases rows; the paged path
    has per-slot positions instead)."""
    B = tokens.shape[0] if cfg.embed_inputs else tokens.shape[0]
    pos = cache["pos"]
    positions = jnp.full((B, 1), pos, jnp.int32)
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions, (3, B, 1))
    rope = _rope(cfg, positions)
    h = _embed_in(params, cfg, tokens)

    # Caches are carried through the layer scan as FULL stacked buffers and
    # updated in place (dynamic_update_index_in_dim on the layer axis):
    # emitting per-layer caches through scan ys materializes a second copy
    # of every cache (measured +2x cache bytes of decode temps).
    if cfg.family == "ssm":
        def body(carry, p):
            h, conv_all, ssm_all, li = carry
            state = ssm_mod.Mamba1State(
                conv=jax.lax.dynamic_index_in_dim(conv_all, li, 0, False),
                ssm=jax.lax.dynamic_index_in_dim(ssm_all, li, 0, False))
            h, new = mamba_block_apply(h, p, cfg, state)
            conv_all = jax.lax.dynamic_update_index_in_dim(
                conv_all, new.conv.astype(conv_all.dtype), li, 0)
            ssm_all = jax.lax.dynamic_update_index_in_dim(
                ssm_all, new.ssm, li, 0)
            return (h, conv_all, ssm_all, li + 1), None
        (h, conv, ssm_s, _), _ = jax.lax.scan(
            body, (h, cache["conv"], cache["ssm"], jnp.int32(0)),
            params["blocks"])
        new_cache = dict(cache, conv=conv, ssm=ssm_s, pos=pos + 1)
        return _logits_exact(params, cfg, h)[:, 0], new_cache

    if cfg.family == "hybrid":
        return _hybrid_decode(params, cfg, h, rope, cache)

    start = cache.get("start")

    def body(carry, p):
        h, k_all, v_all, li = carry
        k_c = jax.lax.dynamic_index_in_dim(k_all, li, 0, False)
        v_c = jax.lax.dynamic_index_in_dim(v_all, li, 0, False)
        out, k_c, v_c = attn_decode(h, p, cfg, rope, k_c, v_c, pos, start)
        h = h + out
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, k_c, li, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, v_c, li, 0)
        k_all = constrain(k_all, None, "batch", "cache_seq", None, None)
        v_all = constrain(v_all, None, "batch", "cache_seq", None, None)
        h = decode_ffn(h, p, cfg)
        return (h, k_all, v_all, li + 1), None

    (h, k, v, _), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"], jnp.int32(0)), params["blocks"])
    new_cache = dict(cache, k=k, v=v, pos=pos + 1)
    return _logits_exact(params, cfg, h)[:, 0], new_cache


def lm_decode_paged(params, cfg: ArchConfig, tokens, cache, active):
    """tokens (B, 1); cache {"k"/"v" (L, num_pages, KV, page, hd) pools,
    "table" (B, max_blocks) int32, "length" (B,) int32}; active (B,) bool.

    The NON-LOCKSTEP decode step: every slot advances at its own
    ``length`` — rope positions are per-slot (request-relative, starting at
    0 on the slot's own pages), appends go to the slot's own pages via the
    block table, and inactive slots write only the reserved null page 0
    without advancing.  Decoder-only attention LMs only.

    Every op of the step sits in one named scope (``embed``, ``qkv``,
    ``kv_write``, ``attn``, ``out_proj``, ``ffn``, ``head``), which the
    compiler carries into each instruction's ``op_name``: a device trace
    of the step is read per part of the layer by those names."""
    if cfg.mamba_version or cfg.is_encoder_decoder:
        raise ValueError("paged decode requires a decoder-only attention LM")
    lengths = cache["length"]
    table = cache["table"]
    B = tokens.shape[0]
    with jax.named_scope("qkv"):
        positions = lengths[:, None]                   # per-slot positions
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions[None], (3, B, 1))
        rope = _rope(cfg, positions)
    with jax.named_scope("embed"):
        h = _embed_in(params, cfg, tokens)

    def body(carry, p):
        h, k_all, v_all, ks_all, vs_all, li = carry
        out, k_all, v_all, ks_all, vs_all = attn_decode_paged(
            h, p, cfg, rope, k_all, v_all, li, table, lengths, active,
            k_scale=ks_all, v_scale=vs_all)
        with jax.named_scope("out_proj"):
            h = h + out
        h = decode_ffn(h, p, cfg)
        return (h, k_all, v_all, ks_all, vs_all, li + 1), None

    # scale pools ride the carry only for quantized pools (None is an empty
    # pytree, so the bf16 path's carry structure is unchanged)
    (h, k, v, ks, vs, _), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"], cache.get("k_scale"),
               cache.get("v_scale"), jnp.int32(0)), params["blocks"])
    new_cache = dict(cache, k=k, v=v,
                     length=lengths + active.astype(jnp.int32))
    if ks is not None:
        new_cache.update(k_scale=ks, v_scale=vs)
    with jax.named_scope("head"):
        return _logits_exact(params, cfg, h)[:, 0], new_cache


def lm_prefill_paged(params, cfg: ArchConfig, tokens, cache, grants,
                     unembed_all: bool = False):
    """Ragged multi-token paged prefill: tokens (B, T) int32 — each slot's
    next chunk of prompt tokens (row i's first ``grants[i]`` entries are
    real; the rest are pad the masks ignore); cache as in
    ``lm_decode_paged``; grants (B,) int32 — prompt tokens granted to each
    slot this chunk (0 = slot idle: nothing appended, length frozen).

    Appends all granted rows in ONE (layer, page) scatter per layer and
    attends causally over history + in-flight chunk, so admitting a
    P-token prompt costs ceil(P / T) compiled steps instead of P decode
    steps.  Only the logits at each slot's LAST granted position are
    unembedded (the next token after any earlier position is a known
    prompt token) — the unembed cost stays chunk-size independent.

    Returns (logits (B, V) at position grants-1 per slot, new cache with
    length advanced by grants).  With ``unembed_all`` every chunk position
    is unembedded instead — logits (B, T, V) at f32, the shape a
    speculative verify consumes (each position decides a token there).
    Decoder-only attention LMs only."""
    if cfg.mamba_version or cfg.is_encoder_decoder:
        raise ValueError("paged prefill requires a decoder-only attention "
                         "LM")
    lengths = cache["length"]
    table = cache["table"]
    B, T = tokens.shape
    grants = jnp.asarray(grants, jnp.int32)
    new_len = lengths + grants
    with jax.named_scope("qkv"):
        positions = (lengths[:, None]
                     + jnp.arange(T, dtype=jnp.int32)[None, :])
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions[None], (3, B, T))
        rope = _rope(cfg, positions)
    with jax.named_scope("embed"):
        h = _embed_in(params, cfg, tokens)

    def body(carry, p):
        h, k_all, v_all, ks_all, vs_all, li = carry
        out, k_all, v_all, ks_all, vs_all = attn_prefill_paged(
            h, p, cfg, rope, k_all, v_all, li, table, lengths, new_len,
            k_scale=ks_all, v_scale=vs_all)
        with jax.named_scope("out_proj"):
            h = h + out
        h = decode_ffn(h, p, cfg)
        return (h, k_all, v_all, ks_all, vs_all, li + 1), None

    (h, k, v, ks, vs, _), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"], cache.get("k_scale"),
               cache.get("v_scale"), jnp.int32(0)), params["blocks"])
    new_cache = dict(cache, k=k, v=v, length=new_len)
    if ks is not None:
        new_cache.update(k_scale=ks, v_scale=vs)
    with jax.named_scope("head"):
        if unembed_all:
            return _logits_exact(params, cfg, h), new_cache  # (B, T, V)
        # last granted position per slot (grants==0 -> clipped; caller
        # ignores)
        last = jnp.maximum(grants - 1, 0)[:, None, None]
        h_last = jnp.take_along_axis(h, last, axis=1)        # (B, 1, d)
        return _logits_exact(params, cfg, h_last)[:, 0], new_cache


def lm_verify_paged(params, cfg: ArchConfig, tokens, cache, grants):
    """Speculative VERIFY step on the prefill lane: tokens (B, T) int32 —
    row i holds [feed, p_1 .. p_{g-1}, pad] where feed is the slot's next
    input token and p_1.. are draft proposals (``grants[i]`` = g rows are
    real; 0 = slot idle); cache/grants as in ``lm_prefill_paged``.

    Runs the SAME ragged chunk forward as ``lm_prefill_paged`` (one
    scatter + one causal kernel step per layer) but unembeds ALL T
    positions at f32 (PR-7 discipline: every position here DECIDES a
    token) and reduces accept lengths on device:

      * ``greedy[b, t]`` = argmax over position t's logits — the target's
        greedy successor of tokens[b, :t+1].
      * proposal p_{t+1} = tokens[b, t+1] is ACCEPTED iff every earlier
        proposal matched and ``greedy[b, t] == tokens[b, t+1]`` — i.e.
        ``accept[b]`` is the longest common prefix of the target's greedy
        continuations and the draft's proposals.
      * the tick then emits ``greedy[b, :accept[b] + 1]``: the accepted
        proposals ARE the target's greedy tokens, and position accept[b]
        contributes the BONUS token (the target's correction after the
        first mismatch, or the free extra token after an all-accept) —
        so the emitted stream is bit-identical to plain greedy decode by
        construction.

    Returns (greedy (B, T) int32, accept (B,) int32 in [0, g-1], new
    cache with length advanced by the FULL grant — the caller truncates
    rejected rows by rolling ``length`` back to base + accept + 1, which
    the paged cache already supports)."""
    T = tokens.shape[1]
    grants = jnp.asarray(grants, jnp.int32)
    logits, new_cache = lm_prefill_paged(params, cfg, tokens, cache,
                                         grants, unembed_all=True)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, T)
    # leading-run length of proposal matches, masked to the g-1 proposals
    prop_ok = (greedy[:, :T - 1] == tokens[:, 1:])
    in_grant = (jnp.arange(T - 1, dtype=jnp.int32)[None, :]
                < (grants - 1)[:, None])
    run = jnp.cumprod((prop_ok & in_grant).astype(jnp.int32), axis=1)
    accept = run.sum(axis=1)                                 # (B,)
    accept = jnp.minimum(accept, jnp.maximum(grants - 1, 0))
    return greedy, accept, new_cache


# ---------------------------------------------------------------------------
# zamba2 hybrid: grouped scan (attn_every mamba layers + shared attn block)
# ---------------------------------------------------------------------------

def _hybrid_split(cfg: ArchConfig, tree):
    """Split a stacked (L, ...) tree into ((G, k, ...) grouped, (R, ...)
    tail) with k = attn_every, G = L // k, R = L - G*k."""
    k = cfg.attn_every
    G = cfg.n_layers // k
    R = cfg.n_layers - G * k

    def split(x):
        head = x[: G * k].reshape((G, k) + x.shape[1:])
        tail = x[G * k:]
        return head, tail

    heads = jax.tree.map(lambda x: split(x)[0], tree)
    tails = jax.tree.map(lambda x: split(x)[1], tree)
    return heads, tails, G, R


def _hybrid_forward(params, cfg: ArchConfig, h, rope, mode):
    heads, tails, G, R = _hybrid_split(cfg, params["blocks"])
    shared = params["shared_attn"]

    def inner(h, p):
        h, _ = mamba_block_apply(h, p, cfg)
        return h, None

    def group(carry, gp):
        h = carry
        h, _ = jax.lax.scan(inner, h, gp)
        h, kv = shared_attn_block(h, shared, cfg, rope)
        return h, (kv if mode == "prefill" else None)

    h, kvs = jax.lax.scan(_maybe_remat(group, cfg), h, heads)
    if R:
        h, _ = jax.lax.scan(inner, h, tails)
    if mode == "hidden":
        return (apply_norm(h, params["ln_f"], cfg),
                jnp.zeros((), jnp.float32), None)
    if mode == "prefill":
        return (_logits_exact(params, cfg, h[:, -1:]),
                jnp.zeros((), jnp.float32),
                {"attn_k": kvs[0], "attn_v": kvs[1]})  # (G, B, S, KV, hd)
    return _logits(params, cfg, h), jnp.zeros((), jnp.float32), None


def _hybrid_decode(params, cfg: ArchConfig, h, rope, cache):
    heads, tails, G, R = _hybrid_split(cfg, params["blocks"])
    pos = cache["pos"]
    start = cache.get("start")
    shared = params["shared_attn"]

    def mamba_step(carry, p):
        h, conv_all, ssm_all, li = carry
        st = ssm_mod.Mamba2State(
            conv=jax.lax.dynamic_index_in_dim(conv_all, li, 0, False),
            ssm=jax.lax.dynamic_index_in_dim(ssm_all, li, 0, False))
        h, new = mamba_block_apply(h, p, cfg, st)
        conv_all = jax.lax.dynamic_update_index_in_dim(
            conv_all, new.conv.astype(conv_all.dtype), li, 0)
        ssm_all = jax.lax.dynamic_update_index_in_dim(ssm_all, new.ssm,
                                                      li, 0)
        return (h, conv_all, ssm_all, li + 1), None

    def group(carry, gp):
        h, conv_all, ssm_all, li, k_all, v_all, gi = carry
        (h, conv_all, ssm_all, li), _ = jax.lax.scan(
            mamba_step, (h, conv_all, ssm_all, li), gp)
        k_c = jax.lax.dynamic_index_in_dim(k_all, gi, 0, False)
        v_c = jax.lax.dynamic_index_in_dim(v_all, gi, 0, False)
        h, k_c, v_c = shared_attn_decode(h, shared, cfg, rope, k_c, v_c, pos,
                                         start)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, k_c, gi, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, v_c, gi, 0)
        return (h, conv_all, ssm_all, li, k_all, v_all, gi + 1), None

    carry0 = (h, cache["conv"], cache["ssm"], jnp.int32(0),
              cache["attn_k"], cache["attn_v"], jnp.int32(0))
    (h, conv_all, ssm_all, li, k_n, v_n, _), _ = jax.lax.scan(
        group, carry0, heads)
    if R:
        (h, conv_all, ssm_all, _), _ = jax.lax.scan(
            mamba_step, (h, conv_all, ssm_all, li), tails)
    new_cache = dict(cache, conv=conv_all, ssm=ssm_all, attn_k=k_n,
                     attn_v=v_n, pos=pos + 1)
    return _logits_exact(params, cfg, h)[:, 0], new_cache


# ---------------------------------------------------------------------------
# whisper encoder-decoder
# ---------------------------------------------------------------------------

def whisper_encode(params, cfg: ArchConfig, frames):
    """frames (B, S, d) — precomputed frame embeddings (frontend stub)."""
    B, S, _ = frames.shape
    pos = jnp.arange(S)[None]
    h = frames.astype(cfg.param_dtype) + sinusoidal_positions(
        pos, cfg.d_model).astype(cfg.param_dtype)
    h = constrain(h, "batch", None, None)

    def body(h, p):
        out, _ = attn_apply(h, p, cfg, rope=None, causal=False)
        h = h + out
        h = h + ffn_apply(h, p, cfg)
        return constrain(h, "batch", None, None), None

    h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, params["encoder"])
    return apply_norm(h, params["ln_enc"], cfg)


def _cross_kv(enc_out, p, cfg: ArchConfig):
    B, S, _ = enc_out.shape
    hd = cfg.head_dim
    k = dense(enc_out, p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = dense(enc_out, p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    return k, v


def _cross_attend(h, p, cfg: ArchConfig, k, v):
    hn = apply_norm(h, p["ln3"], cfg)
    B, S, _ = hn.shape
    hd = cfg.head_dim
    q = dense(hn, p["cross"]["wq"]).reshape(B, S, cfg.n_heads, hd)
    out = attention(q, k, v, causal=False, chunk_q=cfg.attn_chunk_q,
                    chunk_kv=cfg.attn_chunk_kv)
    return dense(out.reshape(B, S, -1), p["cross"]["wo"])


def whisper_forward(params, cfg: ArchConfig, frames, tokens,
                    mode: str = "train"):
    """Returns (logits, aux, cache or None)."""
    enc = whisper_encode(params, cfg, frames)
    B, S = tokens.shape
    pos = jnp.arange(S)[None]
    h = embed(tokens, params["embed"]).astype(cfg.param_dtype)
    h = h + sinusoidal_positions(pos, cfg.d_model).astype(cfg.param_dtype)
    h = constrain(h, "batch", None, None)

    def body(h, p):
        out, kv = attn_apply(h, p, cfg, rope=None, causal=True)
        h = h + out
        ck, cv = _cross_kv(enc, p["cross"], cfg)
        h = h + _cross_attend(h, p, cfg, ck, cv)
        h = h + ffn_apply(h, p, cfg)
        return constrain(h, "batch", None, None), (
            (kv, (ck, cv)) if mode == "prefill" else None)

    h, ys = jax.lax.scan(_maybe_remat(body, cfg), h, params["decoder"])
    if mode == "hidden":
        return (apply_norm(h, params["ln_f"], cfg),
                jnp.zeros((), jnp.float32), None)
    if mode == "prefill":
        (k, v), (ck, cv) = ys
        return (_logits_exact(params, cfg, h[:, -1:]),
                jnp.zeros((), jnp.float32),
                {"k": k, "v": v, "cross_k": ck, "cross_v": cv})
    return _logits(params, cfg, h), jnp.zeros((), jnp.float32), None


def whisper_decode(params, cfg: ArchConfig, tokens, cache):
    pos = cache["pos"]
    start = cache.get("start")
    B = tokens.shape[0]
    h = embed(tokens, params["embed"]).astype(cfg.param_dtype)
    h = h + sinusoidal_positions(
        jnp.full((B, 1), pos, jnp.int32), cfg.d_model).astype(cfg.param_dtype)

    def body(carry, xs):
        h, k_all, v_all, li = carry
        p, ck, cv = xs                      # cross caches are read-only xs
        k_c = jax.lax.dynamic_index_in_dim(k_all, li, 0, False)
        v_c = jax.lax.dynamic_index_in_dim(v_all, li, 0, False)
        out, k_c, v_c = attn_decode(h, p, cfg, rope=None,
                                    k_cache=k_c, v_cache=v_c, pos=pos,
                                    start=start)
        h = h + out
        h = h + _cross_attend(h, p, cfg, ck, cv)
        h = h + ffn_apply(h, p, cfg)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, k_c, li, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, v_c, li, 0)
        return (h, k_all, v_all, li + 1), None

    (h, k, v, _), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"], jnp.int32(0)),
        (params["decoder"], cache["cross_k"], cache["cross_v"]))
    new_cache = dict(cache, k=k, v=v, pos=pos + 1)
    return _logits_exact(params, cfg, h)[:, 0], new_cache


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def cache_decls(cfg: ArchConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    """Shapes/dtypes (as ParamDecl so the same schema machinery yields
    zeros / ShapeDtypeStructs / PartitionSpecs).

    KV caches are SEQUENCE-sharded over the 'cache_seq' axes
    (flash-decoding-style): a 32k MQA cache replicated across the model
    axis would not fit HBM, whereas seq sharding costs only tiny softmax
    partial all-reduces per layer."""
    hd, KV = cfg.head_dim, cfg.n_kv_heads
    kv_axes = (None, "batch", "cache_seq", None, None)
    d_in = cfg.d_model * cfg.ssm_expand
    K = cfg.ssm_conv
    f32 = jnp.float32
    bf = cfg.param_dtype
    decls: Dict[str, Any] = {
        "pos": ParamDecl((), (), "zeros", jnp.int32),
        # per-slot attention-window base: slot b attends cache positions
        # [start[b], pos].  0 for whole-batch generation; the decode
        # kernels keep the windowed path (serving uses the paged cache's
        # per-slot block tables instead).
        "start": ParamDecl((batch,), ("batch",), "zeros", jnp.int32)}
    if cfg.family == "ssm":
        decls["conv"] = ParamDecl((cfg.n_layers, batch, K - 1, d_in),
                                  (None, "batch", None, "model"), "zeros", bf)
        decls["ssm"] = ParamDecl((cfg.n_layers, batch, d_in, cfg.ssm_state),
                                 (None, "batch", "model", None), "zeros", f32)
        return decls
    if cfg.family == "hybrid":
        H = d_in // cfg.ssm_head_dim
        G = cfg.n_layers // cfg.attn_every
        decls["conv"] = ParamDecl((cfg.n_layers, batch, K - 1, d_in),
                                  (None, "batch", None, "model"), "zeros", bf)
        decls["ssm"] = ParamDecl(
            (cfg.n_layers, batch, H, cfg.ssm_head_dim, cfg.ssm_state),
            (None, "batch", "model", None, None), "zeros", f32)
        decls["attn_k"] = ParamDecl((G, batch, max_seq, KV, hd), kv_axes,
                                    "zeros", bf)
        decls["attn_v"] = ParamDecl((G, batch, max_seq, KV, hd), kv_axes,
                                    "zeros", bf)
        return decls
    L = cfg.n_layers
    decls["k"] = ParamDecl((L, batch, max_seq, KV, hd), kv_axes, "zeros", bf)
    decls["v"] = ParamDecl((L, batch, max_seq, KV, hd), kv_axes, "zeros", bf)
    if cfg.is_encoder_decoder:
        decls["cross_k"] = ParamDecl((L, batch, max_seq, KV, hd), kv_axes,
                                     "zeros", bf)
        decls["cross_v"] = ParamDecl((L, batch, max_seq, KV, hd), kv_axes,
                                     "zeros", bf)
    return decls


def paged_cache_decls(cfg: ArchConfig, batch: int, max_blocks: int,
                      page_size: int, num_pages: int) -> Dict[str, Any]:
    """Paged decode cache: a shared head-major page pool
    (num_pages, KV, page, hd) per layer plus a per-slot block table and
    per-slot lengths — NO shared position, NO start window.  Head-major
    pages make one (page, kv-head) tile a whole (page, hd) plane, the
    block the Pallas kernels fetch.  Page 0 is the reserved null page (never
    allocated; inactive slots' appends and unallocated table entries land
    there).  Several block tables may reference the SAME physical page
    (prefix sharing; see serve/cache.py for the refcount/COW discipline —
    the device arrays carry no refcounts, only the host manager does).
    The pool is sharded over its page axis ('cache_seq'), the
    flash-decoding seq-sharding of the dense cache carried over page-wise.

    With ``cfg.kv_dtype == "int8"`` the pools are int8 and the cache grows
    ``k_scale``/``v_scale`` — (L, num_pages, KV, page, 1) f32
    per-row-per-head scales that travel WITH their pages through every
    copy path (COW, defrag, retained-prefix adoption); see
    models/kv_quant.py."""
    if cfg.mamba_version or cfg.is_encoder_decoder:
        raise ValueError("paged KV cache requires a decoder-only attention "
                         "LM (per-slot page tables)")
    hd, KV, L = cfg.head_dim, cfg.n_kv_heads, cfg.n_layers
    pool_axes = (None, "cache_seq", None, None, None)
    bf = jnp.int8 if cfg.kv_quantized else cfg.param_dtype
    decls = {
        "k": ParamDecl((L, num_pages, KV, page_size, hd), pool_axes,
                       "zeros", bf),
        "v": ParamDecl((L, num_pages, KV, page_size, hd), pool_axes,
                       "zeros", bf),
        "table": ParamDecl((batch, max_blocks), ("batch", None), "zeros",
                           jnp.int32),
        "length": ParamDecl((batch,), ("batch",), "zeros", jnp.int32),
    }
    if cfg.kv_quantized:
        decls["k_scale"] = ParamDecl((L, num_pages, KV, page_size, 1),
                                     pool_axes, "zeros", jnp.float32)
        decls["v_scale"] = ParamDecl((L, num_pages, KV, page_size, 1),
                                     pool_axes, "zeros", jnp.float32)
    return decls
