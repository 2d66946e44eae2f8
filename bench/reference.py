"""Plain float32 reference of the dense GQA decoder the configurations
describe, in straightforward ``jax.numpy``: no kernels, no cache, no
batching, ``jax.default_matmul_precision("highest")``.

It is the reference of every config file that names none.  A config of
another architecture names its own module under ``"reference"`` (a path
from the checkout root, e.g. ``"bench/<module>.py"``), found by
``spec.load_reference``, which implements the same interface:

    logits(weights, sizes, tokens, rows, fp8=False) -> np.ndarray

  weights  the benchmark's weights (bench/weights.py): the tree of the
           program's ``abstract_params()``, made from the run's seed;
  sizes    ``spec.Sizes`` of the config file: the dense fields, and any
           other published key through ``sizes.get(key)``;
  tokens   int32 (S,), one sequence: prompt + served tokens;
  rows     int32 positions into it; the logits at row p predict token
           p + 1;
  fp8      the lower-precision control: every matmul operand rounded to
           float8 e4m3 with absmax scales (below);

and returns float32 logits (len(rows), vocab).  It imports nothing of the
program, and is whole in itself: it copies what it needs of this module
rather than call its private helpers.

Per layer: RMSNorm -> q, k, v projections -> rotary embedding (rotate-half,
inverse frequencies theta^(-i / (hd/2)), over the whole head dim) ->
causal softmax attention, query head h reading key/value head
h // (heads / kv_heads) -> output projection -> residual; RMSNorm ->
SwiGLU (silu(x W_gate) * (x W_up)) W_down -> residual.  Final RMSNorm,
then logits against the embedding table (tied) or the unembedding.

It imports nothing of the program.  It reads the benchmark's own weights
(bench/weights.py) one layer at a time, upcast to float32 inside the
layer's jitted step, and the vocabulary in slices, so it fits on the chip
next to the bf16 weights.

``fp8=True`` is the lower-precision control: every matmul operand is
rounded to float8 e4m3 with an absmax scale per row of activations and
per output column of weights, as an fp8 serving path would compute.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import Sizes

HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 512                # sequences are padded to a multiple of this
QBLOCK = 256                # query rows per attention block
ROWBLOCK = 128              # logit rows are padded to a multiple of this
VBLOCK = 32768              # vocabulary rows per logits slice
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


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (S, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, sz: Sizes):
    """Causal GQA attention, q (S, H, hd), k/v (S, KV, hd), in query
    blocks so the scores of one block are all that is held."""
    S = q.shape[0]
    G = sz.heads // sz.kv_heads
    qb = q.reshape(S // QBLOCK, QBLOCK, sz.kv_heads, G, sz.head_dim)
    tpos = jnp.arange(S)

    def block(args):
        qi, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HIGHEST)
        s = s / np.sqrt(sz.head_dim)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(qpos[:, None] >= tpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (qb, jnp.arange(S // QBLOCK)))
    return out.reshape(S, sz.heads * sz.head_dim)


@functools.partial(jax.jit, static_argnames=("sz", "fp8"))
def _layer(h, blocks, layer, *, sz: Sizes, fp8: bool):
    p = jax.tree.map(lambda x: x[layer].astype(jnp.float32), blocks)
    a, f = p["attn"], p["ffn"]
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rms(h, p["ln1"]["scale"], sz.norm_eps)
    q = _mm(x, a["wq"], fp8).reshape(S, sz.heads, sz.head_dim)
    k = _mm(x, a["wk"], fp8).reshape(S, sz.kv_heads, sz.head_dim)
    v = _mm(x, a["wv"], fp8).reshape(S, sz.kv_heads, sz.head_dim)
    q, k = _rope(q, pos, sz.rope_theta), _rope(k, pos, sz.rope_theta)
    h = h + _mm(_attention(q, k, v, sz), a["wo"], fp8)
    x = _rms(h, p["ln2"]["scale"], sz.norm_eps)
    g = jax.nn.silu(_mm(x, f["w_gate"], fp8)) * _mm(x, f["w_up"], fp8)
    return h + _mm(g, f["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("sz", "fp8", "lo", "hi"))
def _logits(hf, table, *, sz: Sizes, fp8: bool, lo: int, hi: int):
    w = table[lo:hi].astype(jnp.float32).T                  # (d, V slice)
    return _mm(hf, w, fp8)


@functools.partial(jax.jit, static_argnames=("sz",))
def _embed(tokens, table, *, sz: Sizes):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("sz",))
def _final(h, rows, scale, *, sz: Sizes):
    return _rms(h[rows], scale.astype(jnp.float32), sz.norm_eps)


def logits(weights, sz: Sizes, tokens: np.ndarray, rows: np.ndarray,
           fp8: bool = False) -> np.ndarray:
    """float32 logits (len(rows), vocab) of the reference at positions
    ``rows`` of the sequence ``tokens`` (the logits at row p predict
    token p + 1)."""
    S = len(tokens)
    pad = -(-S // BUCKET) * BUCKET
    tok = np.zeros((pad,), np.int32)
    tok[:S] = tokens
    n = len(rows)           # rows padded too, so few programs compile
    rpad = np.full((-(-n // ROWBLOCK) * ROWBLOCK,), rows[-1], np.int32)
    rpad[:n] = rows
    with jax.default_matmul_precision("highest"):
        h = _embed(jnp.asarray(tok), weights["embed"], sz=sz)
        for layer in range(sz.layers):
            h = _layer(h, weights["blocks"], jnp.int32(layer), sz=sz,
                       fp8=fp8)
        hf = _final(h, jnp.asarray(rpad),
                    weights["ln_f"]["scale"], sz=sz)
        table = weights["embed"] if sz.tied else weights["unembed"]
        parts: List[np.ndarray] = []
        for lo in range(0, sz.vocab, VBLOCK):
            hi = min(sz.vocab, lo + VBLOCK)
            parts.append(np.asarray(_logits(hf, table, sz=sz, fp8=fp8,
                                            lo=lo, hi=hi)))
    return np.concatenate(parts, axis=1)[:n]
