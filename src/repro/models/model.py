"""Model facade: one object per ArchConfig exposing init / abstract specs /
partition specs / loss / prefill / decode, plus ``input_specs`` for AOT
lowering (ShapeDtypeStructs — never allocates).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.dist.sharding import MeshRules
from repro.models import transformer as T
from repro.models.params import (
    ParamDecl, abstract_params, init_params, param_pspecs)


def sample_token(logits: jax.Array, key: jax.Array, temperature: float):
    """Greedy/temperature sampling step: returns (tokens (B,) int32, key).

    THE one sampler — the fused decode_many scan, the legacy per-token
    loop, and the continuous-batching engine step all call this, so the
    key-split discipline stays identical and the three paths remain
    token-identical for a given seed (tests assert it)."""
    if temperature > 0:
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(sub, logits / temperature, axis=-1)
    else:
        tok = jnp.argmax(logits, axis=-1)
    return tok.astype(jnp.int32), key


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits (B, S, V) fp32; labels (B, S) int32. Mean NLL."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


@dataclasses.dataclass
class Model:
    cfg: ArchConfig

    # -- parameters ----------------------------------------------------------
    def schema(self):
        return T.schema(self.cfg)

    def init(self, key: jax.Array):
        return init_params(self.schema(), key, self.cfg.param_dtype)

    def abstract_params(self):
        return abstract_params(self.schema(), self.cfg.param_dtype)

    def param_pspecs(self, rules: MeshRules):
        return param_pspecs(self.schema(), rules)

    # -- caches ---------------------------------------------------------------
    def cache_decls(self, batch: int, max_seq: int):
        return T.cache_decls(self.cfg, batch, max_seq)

    def init_cache(self, batch: int, max_seq: int):
        return init_params(self.cache_decls(batch, max_seq), jax.random.key(0),
                           self.cfg.param_dtype)

    def abstract_cache(self, batch: int, max_seq: int):
        return abstract_params(self.cache_decls(batch, max_seq),
                               self.cfg.param_dtype)

    def cache_pspecs(self, batch: int, max_seq: int, rules: MeshRules):
        return param_pspecs(self.cache_decls(batch, max_seq), rules)

    # -- paged caches ---------------------------------------------------------
    def paged_cache_decls(self, batch: int, max_blocks: int, page_size: int,
                          num_pages: int):
        return T.paged_cache_decls(self.cfg, batch, max_blocks, page_size,
                                   num_pages)

    def init_paged_cache(self, batch: int, max_blocks: int, page_size: int,
                         num_pages: int):
        return init_params(
            self.paged_cache_decls(batch, max_blocks, page_size, num_pages),
            jax.random.key(0), self.cfg.param_dtype)

    def abstract_paged_cache(self, batch: int, max_blocks: int,
                             page_size: int, num_pages: int):
        return abstract_params(
            self.paged_cache_decls(batch, max_blocks, page_size, num_pages),
            self.cfg.param_dtype)

    def paged_cache_pspecs(self, batch: int, max_blocks: int, page_size: int,
                           num_pages: int, rules: MeshRules):
        return param_pspecs(
            self.paged_cache_decls(batch, max_blocks, page_size, num_pages),
            rules)

    def prefill_cache_pspecs(self, shape: ShapeConfig, rules: MeshRules):
        """PartitionSpecs matching the cache-parts pytree that prefill()
        actually returns (a subset of the decode cache)."""
        full = self.cache_pspecs(shape.global_batch, shape.seq_len, rules)
        cfg = self.cfg
        if cfg.family == "ssm":
            return None
        if cfg.family == "hybrid":
            return {"attn_k": full["attn_k"], "attn_v": full["attn_v"]}
        keys = ["k", "v"]
        if cfg.is_encoder_decoder:
            keys += ["cross_k", "cross_v"]
        return {k: full[k] for k in keys}

    # -- steps ----------------------------------------------------------------
    def loss(self, params, batch: Dict[str, Any]) -> jax.Array:
        """batch: tokens|embeds (+frames for enc-dec), labels, positions?

        Uses the fused unembed + softmax-CE (never materializes full
        logits — see models/losses.py)."""
        from repro.models.losses import fused_unembed_xent
        cfg = self.cfg
        labels = batch["labels"]
        if cfg.is_encoder_decoder:
            hidden, aux, _ = T.whisper_forward(
                params, cfg, batch["frames"], batch["tokens"], mode="hidden")
        else:
            inputs = batch["tokens"] if cfg.embed_inputs else batch["embeds"]
            positions = batch.get("positions")
            if positions is None:
                B, S = labels.shape
                positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            hidden, aux, _ = T.lm_forward(params, cfg, inputs, positions,
                                          mode="hidden")
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        ce = fused_unembed_xent(hidden, table, labels)
        return ce + cfg.router_aux_weight * aux

    def prefill(self, params, batch: Dict[str, Any]):
        """Returns (last-position logits (B, V), cache-parts).  The logits
        are f32 (exact unembed): they exist to pick the first generated
        token, and sampling at activation dtype flips argmax near-ties."""
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            logits, _, cache = T.whisper_forward(
                params, cfg, batch["frames"], batch["tokens"], mode="prefill")
        else:
            inputs = batch["tokens"] if cfg.embed_inputs else batch["embeds"]
            positions = batch.get("positions")
            if positions is None:
                if cfg.embed_inputs:
                    B, S = inputs.shape
                else:
                    B, S, _ = inputs.shape
                positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            logits, _, cache = T.lm_forward(params, cfg, inputs, positions,
                                            mode="prefill")
        return logits[:, -1], cache

    def decode_step(self, params, tokens, cache):
        """tokens (B, 1) int32 (always token ids — decode emits tokens even
        for stub-frontend archs); returns (logits (B, V), cache)."""
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            return T.whisper_decode(params, cfg, tokens, cache)
        if not cfg.embed_inputs:
            # stub-frontend archs decode text tokens through the embed table
            cfg2 = dataclasses.replace(cfg, embed_inputs=True)
            return T.lm_decode(params, cfg2, tokens, cache)
        return T.lm_decode(params, cfg, tokens, cache)

    def decode_step_paged(self, params, tokens, cache, active=None):
        """Non-lockstep decode step over the paged cache: tokens (B, 1)
        int32; cache from ``init_paged_cache``; active (B,) bool (None ->
        all slots advance).  Returns (logits (B, V), cache) — each slot's
        new K/V lands on its OWN pages at its OWN position."""
        cfg = self.cfg
        if active is None:
            active = jnp.ones((tokens.shape[0],), bool)
        if not cfg.embed_inputs:
            cfg = dataclasses.replace(cfg, embed_inputs=True)
        return T.lm_decode_paged(params, cfg, tokens, cache, active)

    def prefill_step_paged(self, params, tokens, cache, grants):
        """Ragged multi-token paged prefill step: tokens (B, T) int32 —
        each slot's next prompt chunk (row i's first ``grants[i]`` entries
        real, rest pad); cache from ``init_paged_cache``; grants (B,)
        int32 chunk tokens granted per slot (0 = idle).  Appends all
        granted rows and attends causally in ONE compiled step — a
        P-token prompt costs ceil(P / T) steps instead of P decode steps.
        Returns (logits (B, V) at each slot's last granted position,
        cache with length advanced by grants)."""
        cfg = self.cfg
        if not cfg.embed_inputs:
            cfg = dataclasses.replace(cfg, embed_inputs=True)
        return T.lm_prefill_paged(params, cfg, tokens, cache, grants)

    def prefill_many_paged(self, params, tokens, cache, key, grants, *,
                           temperature: float = 0.0):
        """The engine's prefill-lane cell: one ``prefill_step_paged`` plus
        on-device sampling of the ONE token the chunk produces — the
        logits at each slot's last granted position predict either the
        next (known) prompt token, which the host discards, or the
        request's FIRST output token when the grant drains the prompt.

        The sampler key splits once per prefill chunk (not once per
        token): prompt positions never consume randomness, so greedy
        serving is token-identical to the prefill-by-decode path, and
        temperature serving stays self-consistent within a lane.

        Returns (next_tok (B,) int32, cache, key)."""
        logits, cache = self.prefill_step_paged(params, tokens, cache,
                                                grants)
        with jax.named_scope("sample"):
            nxt, key = sample_token(logits, key, temperature)
        return nxt, cache, key

    def verify_many_paged(self, params, tokens, cache, grants):
        """The engine's speculative VERIFY cell: one ragged prefill-lane
        step over tokens (B, T) = [feed, p_1 .. p_{k}] per slot (grants
        (B,) int32 = 1 + proposals granted, 0 = idle) that unembeds ALL T
        positions at f32 and reduces the accepted prefix on device.

        Greedy-only by design: a proposal is accepted iff it EQUALS the
        target's greedy argmax at its position, so the emitted stream
        ``greedy[:, :accept + 1]`` is bit-identical to plain greedy
        decode and no PRNG is consumed (the engine refuses speculation at
        temperature > 0).

        Returns (greedy (B, T) int32, accept (B,) int32, cache with
        length advanced by the FULL grant — the host rolls rejected rows
        back by truncating ``length``)."""
        cfg = self.cfg
        if not cfg.embed_inputs:
            cfg = dataclasses.replace(cfg, embed_inputs=True)
        return T.lm_verify_paged(params, cfg, tokens, cache, grants)

    def decode_many_paged(self, params, tokens, cache, key, active,
                          forced_tok=None, forced_mask=None, *,
                          num_steps: int, temperature: float = 0.0):
        """Fused multi-token paged decode: one compiled ``lax.scan`` over
        ``num_steps`` non-lockstep decode steps with on-device sampling —
        the SAME cell serves chunked prefill and decode, so the whole
        serving path is one module family ``core.hlo_counters`` can census.

        tokens (B, 1) int32 — each slot's last emitted token.
        active — the per-step activity plan, in one of three forms: a
        (num_steps, B) bool PER-STEP mask; a (B,) bool mask broadcast to
        every step; or a (B,) INTEGER grant vector ``steps`` (slot ``i``
        active for the first ``steps[i]`` steps of the chunk — the tick
        scheduler's native form, expanded to the mask ON DEVICE so the
        host uploads B ints instead of num_steps x B bools every tick).
        An inactive slot writes only the null page, does not advance its
        length, and its token stream is FROZEN (the carry re-emits its
        last token) so the host reads a stable value at the slot's final
        active step regardless of later steps.
        forced_tok / forced_mask (num_steps, B) — where the mask is set the
        emitted token is OVERRIDDEN by forced_tok (prompt feeding: chunked
        prefill routes prompt tokens through the decode cell); None means
        nothing forced.  eos handling is the caller's (the engine truncates
        on the host — per-slot attention means post-eos steps of one slot
        cannot perturb any other slot).

        Returns (out_tokens (num_steps, B) int32, cache, key).
        """
        B = tokens.shape[0]
        if forced_tok is None:
            forced_tok = jnp.zeros((num_steps, B), jnp.int32)
            forced_mask = jnp.zeros((num_steps, B), bool)
        active = jnp.asarray(active)
        if active.dtype != jnp.bool_:
            # (B,) per-slot step grants -> per-step mask, built on device
            active = (jnp.arange(num_steps, dtype=active.dtype)[:, None]
                      < active[None, :])
        elif active.ndim == 1:
            active = jnp.broadcast_to(active[None], (num_steps, B))

        def step(carry, xs):
            tok, cache, key = carry
            f_tok, f_mask, act = xs
            logits, cache = self.decode_step_paged(params, tok, cache, act)
            with jax.named_scope("sample"):
                nxt, key = sample_token(logits, key, temperature)
                nxt = jnp.where(f_mask, f_tok, nxt)
                nxt = jnp.where(act, nxt, tok[:, 0])
            return (nxt[:, None], cache, key), nxt

        (_, cache, key), toks = jax.lax.scan(
            step, (tokens, cache, key), (forced_tok, forced_mask, active),
            length=num_steps)
        return toks, cache, key

    def decode_many(self, params, tokens, cache, key, num_steps: int,
                    temperature: float = 0.0, eos_id: int = -1,
                    pad_id: int = 0):
        """Fused multi-token decode: one compiled ``lax.scan`` over
        ``num_steps`` decode steps with ON-DEVICE sampling and per-slot stop
        conditions — no host round-trip per token, and (jitted with
        ``donate_argnums``) the KV cache is updated in place instead of
        re-materialized every step.

        tokens (B, 1) int32 — the last already-sampled token per slot.
        key — sampler PRNG key (carried and split per step; unused when
        ``temperature <= 0``).  ``eos_id < 0`` disables stop conditions.
        Finished slots keep advancing the cache in lockstep but emit
        ``pad_id`` (their output is frozen).

        Returns (out_tokens (num_steps, B) int32, cache, key, done (B,)).
        """
        B = tokens.shape[0]
        done0 = (tokens[:, 0] == eos_id) if eos_id >= 0 else \
            jnp.zeros((B,), bool)

        def step(carry, _):
            tok, cache, key, done = carry
            logits, cache = self.decode_step(params, tok, cache)
            nxt, key = sample_token(logits, key, temperature)
            nxt = jnp.where(done, jnp.int32(pad_id), nxt)
            if eos_id >= 0:
                done = done | (nxt == eos_id)
            return (nxt[:, None], cache, key, done), nxt

        (_, cache, key, done), toks = jax.lax.scan(
            step, (tokens, cache, key, done0), None, length=num_steps)
        return toks, cache, key, done

    # -- AOT input specs -------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every step input (no allocation)."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32, bf = jnp.int32, cfg.param_dtype
        sds = jax.ShapeDtypeStruct
        if shape.kind in ("train", "prefill"):
            batch: Dict[str, Any] = {"labels": sds((B, S), i32)}
            if cfg.is_encoder_decoder:
                batch["frames"] = sds((B, S, cfg.d_model), bf)
                batch["tokens"] = sds((B, S), i32)
            elif cfg.embed_inputs:
                batch["tokens"] = sds((B, S), i32)
            else:
                batch["embeds"] = sds((B, S, cfg.d_model), bf)
                if cfg.mrope_sections:
                    batch["positions"] = sds((3, B, S), i32)
            if shape.kind == "prefill":
                batch.pop("labels")
            return batch
        # decode: one token + cache
        return {"tokens": sds((B, 1), i32),
                "cache": self.abstract_cache(B, S)}

    def batch_pspecs(self, shape: ShapeConfig, rules: MeshRules):
        cfg = self.cfg
        specs: Dict[str, Any] = {}
        b = rules.resolve("batch")
        if shape.kind in ("train", "prefill"):
            if shape.kind == "train":
                specs["labels"] = P(b, None)
            if cfg.is_encoder_decoder:
                specs["frames"] = P(b, None, None)
                specs["tokens"] = P(b, None)
            elif cfg.embed_inputs:
                specs["tokens"] = P(b, None)
            else:
                specs["embeds"] = P(b, None, None)
                if cfg.mrope_sections:
                    specs["positions"] = P(None, b, None)
            return specs
        return {"tokens": P(b, None),
                "cache": self.cache_pspecs(shape.global_batch, shape.seq_len,
                                           rules)}

    # -- roofline bookkeeping ---------------------------------------------------
    def model_flops(self, shape: ShapeConfig) -> float:
        """Algorithmic FLOPs for one step: 6·N_active·D for train,
        2·N_active·D for prefill/decode forward (D = processed tokens)."""
        n_active = self.cfg.active_params()
        tokens = shape.global_batch * (shape.seq_len
                                       if shape.kind != "decode" else 1)
        mult = 6.0 if shape.kind == "train" else 2.0
        return mult * n_active * tokens


def get_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
