"""Mixture-of-Experts FFN with explicit expert parallelism.

Design (DESIGN.md section 4): activations arrive replicated across the model
axis (post attention all-reduce), so dispatch needs NO all-to-all — each
model-axis member selects, from the full local token set, the tokens routed
to the expert block(s) it owns, runs its expert FFN shard, scatters weighted
partial outputs back, and a single psum over the model axis plays the role
of the dense-FFN tensor-parallel all-reduce.

Expert weights are stored in a mesh-friendly block layout
``(G, d, ffp)`` where ``G = cfg.ep_shards`` blocks partition the
``E x d_ff`` expert volume:  ``shards_per_expert = G // E`` and
``ffp = E * d_ff // G``.  Block g holds expert ``g // shards_per_expert``,
ff-slice ``g % shards_per_expert``.  Because the down-projection contracts
over ff, the per-block partial outputs *sum* to the full expert output —
the same psum that combines experts also completes the ff contraction
(works for llama4: E=16,G=16 and grok: E=8,G=16 alike).

Capacity: per data shard, ``C = capacity_factor * n_local * k / E`` tokens
per expert; overflow drops (Switch-style), underflow pads with zeros.

Without an active mesh (unit tests / 1-device smoke) the identical math runs
locally over all G blocks.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.dist.sharding import active_rules, current_mesh
from repro.models.layers import dense

def route(xf: jax.Array, router_w: jax.Array, cfg: ArchConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """xf (n, d) -> (gates (n,k), experts (n,k), probs (n,E)).

    The router dot stays in the activation dtype (MXU accumulates fp32);
    only the softmax runs in fp32.  A pure ``xf.astype(f32)`` here makes
    XLA hoist an f32 copy of the whole remat-saved residual stack out of
    the backward loop (llama4: +8 GiB)."""
    logits = jnp.dot(xf, router_w.astype(xf.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts, probs


def aux_loss(experts: jax.Array, probs: jax.Array, n_experts: int
             ) -> jax.Array:
    """Switch-style load-balancing loss: E * sum_e f_e * P_e."""
    one_hot = jax.nn.one_hot(experts[..., 0], n_experts)       # top-1 counts
    f = one_hot.mean(axis=0)
    p = probs.mean(axis=0)
    return n_experts * jnp.sum(f * p)


def _expert_block_compute(xf, gates, experts, w1g, w2g, w3g, e: int,
                          capacity: int, ffn_kind: str):
    """Tokens routed to expert ``e`` -> weighted partial FFN output scattered
    back to (n, d)."""
    n = xf.shape[0]
    w_tok = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)   # (n,)
    sel = w_tok > 0
    # stable gather of up-to-capacity selected tokens
    score = jnp.where(sel, jnp.arange(n), n + jnp.arange(n))
    order = jnp.argsort(score)[:capacity]
    valid = sel[order]
    toks = xf[order] * valid[:, None].astype(xf.dtype)              # (C, d)
    if ffn_kind == "swiglu":
        h = jax.nn.silu(dense(toks, w1g)) * dense(toks, w3g)
    else:
        h = jax.nn.gelu(dense(toks, w1g), approximate=True)
    y = dense(h, w2g)                                               # (C, d)
    y = y * (w_tok[order] * valid)[:, None].astype(y.dtype)
    out = jnp.zeros_like(xf)
    return out.at[order].add(y, mode="drop")


def _moe_blocks_local(xf, gates, experts, w1, w2, w3, cfg: ArchConfig,
                      blocks: range, capacity: int):
    shards_per_e = max(1, cfg.ep_shards // cfg.n_experts)
    out = jnp.zeros_like(xf)
    for bi, g in enumerate(blocks):
        e = g // shards_per_e
        w3g = w3[bi] if w3 is not None else None
        out = out + _expert_block_compute(
            xf, gates, experts, w1[bi], w2[bi], w3g, e, capacity,
            cfg.ffn_kind)
    return out


def moe_ffn(x: jax.Array, router_w: jax.Array, w1: jax.Array, w2: jax.Array,
            w3, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar).

    w1 (G, d, ffp); w2 (G, ffp, d); w3 (G, d, ffp) or None (gelu experts).
    """
    B, S, d = x.shape
    mesh = current_mesh()
    rules = active_rules()
    G = cfg.ep_shards

    xf_full = x.reshape(B * S, d)
    gates, experts, probs = route(xf_full, router_w, cfg)
    aux = aux_loss(experts, probs, cfg.n_experts)

    ep_axes = rules.ep_axes if rules is not None else ("model",)
    tp_ep = 1
    if mesh is not None and rules is not None:
        for a in ep_axes:
            if a in mesh.shape:
                tp_ep *= mesh.shape[a]

    if tp_ep == 1 or G % tp_ep != 0:
        n = B * S
        capacity = max(1, int(cfg.capacity_factor * n
                              * cfg.experts_per_token / cfg.n_experts))
        out = _moe_blocks_local(xf_full, gates, experts, w1, w2, w3, cfg,
                                range(G), capacity)
        return out.reshape(B, S, d), aux

    # --- expert-parallel island ---------------------------------------------
    # expert blocks are sharded over ep_axes (train: the model axis; big-MoE
    # serving: data x model — fully weight-stationary).  Any fsdp axes not
    # consumed by EP still shard the weights' d dim and are gathered here.
    batch_axes = rules.batch_axes
    fsdp_axes = (tuple(a for a in rules.fsdp_axes if a not in ep_axes)
                 if rules.use_fsdp else ())
    blocks_per_rank = G // tp_ep
    n_dp = 1
    for a in batch_axes:
        n_dp *= mesh.shape[a]
    n_local = max(1, (B // max(1, n_dp)) * S)
    capacity = max(1, int(cfg.capacity_factor * n_local
                          * cfg.experts_per_token / cfg.n_experts))
    shards_per_e = max(1, G // cfg.n_experts)

    def _axis_entry(axes):
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]

    ep_entry = _axis_entry(ep_axes)
    fsdp_entry = _axis_entry(fsdp_axes)
    w_specs = P(ep_entry, fsdp_entry, None)
    w2_spec = P(ep_entry, None, fsdp_entry)
    tok_spec = P(_axis_entry(batch_axes), None)

    def island(xf, g8, e8, w1l, w2l, w3l):
        # gather FSDP-sharded weight dims
        if fsdp_axes:
            w1l = jax.lax.all_gather(w1l, fsdp_axes, axis=1, tiled=True)
            w2l = jax.lax.all_gather(w2l, fsdp_axes, axis=2, tiled=True)
            if w3l is not None:
                w3l = jax.lax.all_gather(w3l, fsdp_axes, axis=1, tiled=True)
        r = jnp.int32(0)
        for a in ep_axes:                      # row-major combined EP rank
            r = r * mesh.shape[a] + jax.lax.axis_index(a)
        out = jnp.zeros_like(xf)
        for bi in range(blocks_per_rank):
            g = r * blocks_per_rank + bi
            e = g // shards_per_e
            w3g = w3l[bi] if w3l is not None else None
            # e is traced (depends on r) — _expert_block_compute only uses it
            # in comparisons, which is fine.
            out = out + _expert_block_compute(
                xf, g8, e8, w1l[bi], w2l[bi], w3g, e, capacity, cfg.ffn_kind)
        return jax.lax.psum(out, ep_axes)

    in_specs = (tok_spec, tok_spec, tok_spec, w_specs, w2_spec,
                (w_specs if w3 is not None else None))
    if w3 is None:
        island_fn = lambda xf, g8, e8, w1l, w2l: island(xf, g8, e8, w1l,
                                                        w2l, None)
        sm = jax.shard_map(island_fn, mesh=mesh,
                           in_specs=in_specs[:5], out_specs=tok_spec,
                           check_vma=False)
        out = sm(xf_full, gates, experts, w1, w2)
    else:
        sm = jax.shard_map(island, mesh=mesh, in_specs=in_specs,
                           out_specs=tok_spec, check_vma=False)
        out = sm(xf_full, gates, experts, w1, w2, w3)
    return out.reshape(B, S, d).astype(x.dtype), aux
