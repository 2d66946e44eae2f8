"""Plain float32 reference of a routed-expert decoder, the program's
grok-1 wiring, for the CPU test of a configuration that brings its own
reference (bench/tests/test_seam.py).  It is what such a configuration
adds as a new file, whole in itself: the interface of bench/reference.py,
and nothing imported from it.

Per layer: RMSNorm -> q, k, v -> rotary embedding (rotate-half, over the
whole head dim) -> causal GQA attention -> output projection ->
residual; RMSNorm -> routed FFN -> residual.  Final RMSNorm, then logits
against the unembedding (or the embedding, tied).

Routing: softmax over the router's logits, the top ``num_experts_per_tok``
experts, their gates renormalised to sum to 1; a token's FFN output is
the gated sum of its experts' gelu(x W1) W2 (tanh gelu), with nothing
dropped.  The weights hold the experts in ``G`` blocks over the expert
width: block g is expert g // (G / E), slice g % (G / E) of its width,
so the blocks of one expert sum to its output.  ``E`` and the number
per token are the published keys ``num_experts`` and
``num_experts_per_tok``, read through ``Sizes.get``.

It is written for toy widths: the whole sequence at once, unjitted.
``fp8=True`` rounds every matmul operand to float8 e4m3 with an absmax
scale per row of activations and per output column of weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import Sizes

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8):
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(x, a, sz: Sizes, fp8):
    S, hd = x.shape[0], sz.head_dim
    q = _rope(_mm(x, a["wq"], fp8).reshape(S, sz.heads, hd), sz.rope_theta)
    k = _rope(_mm(x, a["wk"], fp8).reshape(S, sz.kv_heads, hd),
              sz.rope_theta)
    v = _mm(x, a["wv"], fp8).reshape(S, sz.kv_heads, hd)
    group = sz.heads // sz.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,thd->hqt", q, k, precision=HIGHEST) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    out = jnp.einsum("hqt,thd->qhd", jax.nn.softmax(s, -1), v,
                     precision=HIGHEST)
    return _mm(out.reshape(S, sz.heads * hd), a["wo"], fp8)


def _moe(x, m, sz: Sizes, fp8):
    E, k = sz.get("num_experts"), sz.get("num_experts_per_tok")
    probs = jax.nn.softmax(_mm(x, m["router"], fp8), axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    gates = gates / gates.sum(-1, keepdims=True)
    S = x.shape[0]
    weight = jnp.zeros((S, E)).at[jnp.arange(S)[:, None], experts].set(gates)
    G = m["w1"].shape[0]
    out = 0.0
    for g in range(G):
        y = jax.nn.gelu(_mm(x, m["w1"][g], fp8), approximate=True)
        out = out + _mm(y, m["w2"][g], fp8) * weight[:, g * E // G, None]
    return out


def logits(weights, sz: Sizes, tokens: np.ndarray, rows: np.ndarray,
           fp8: bool = False) -> np.ndarray:
    """float32 logits (len(rows), vocab) at positions ``rows`` of the
    sequence ``tokens`` (the logits at row p predict token p + 1)."""
    w = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), weights)
    with jax.default_matmul_precision("highest"):
        h = w["embed"][jnp.asarray(tokens)]
        for layer in range(sz.layers):
            p = jax.tree.map(lambda x: x[layer], w["blocks"])
            h = h + _attention(_rms(h, p["ln1"]["scale"], sz.norm_eps),
                               p["attn"], sz, fp8)
            h = h + _moe(_rms(h, p["ln2"]["scale"], sz.norm_eps),
                         p["moe"], sz, fp8)
        hf = _rms(h[jnp.asarray(rows)], w["ln_f"]["scale"], sz.norm_eps)
        table = w["embed"] if sz.tied else w["unembed"]
        return np.asarray(_mm(hf, table.T, fp8))
