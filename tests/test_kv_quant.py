"""Quantized KV pages: int8 page pools with per-row f32 scales.

Four layers of coverage, mirroring how the feature is built:

  * ARITHMETIC — ``quantize_rows``/``dequantize_rows`` round-trip error is
    bounded by scale/2 per element, all-zero rows (the null page) stay
    exact, and gather-then-dequantize equals dequantize-then-gather (the
    oracle's placement of the dequant is free);
  * KERNELS — both Pallas kernels (paged decode sweep incl. multi-page
    blocking, ragged multi-token prefill) dequantize inside the page sweep
    and must match the dequantizing jnp gather oracles in interpret mode
    across GQA/MQA/MHA and ragged geometry;
  * POOL MANAGEMENT — COW privatization copies int8 rows + scale rows
    bit-exactly while shared, retained-prefix adoption re-shares frozen
    quantized pages WITH their scales, and the quantized COW copy's census
    bytes stay page-scaled and pool-size independent;
  * SERVING — the two quantized WRITE paths (prefill lane vs
    prefill-by-decode) quantize identical appended rows identically, so
    the emitted streams must be token-identical on randomized schedules.
    Drift vs bf16 pools is bounded at the attention-output level (the
    token-level comparison is measured, not gated: int8 noise flips
    near-tie argmaxes at the reduced config — see serve_bench's
    ragged_int8 scenario).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get
from repro.models import get_model
from repro.models.kv_quant import QMAX, dequantize_rows, quantize_rows
from repro.serve.engine import PagedEngine, ServeConfig


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_bound():
    x = jax.random.normal(jax.random.key(0), (5, 3, 2, 16), jnp.float32)
    q, s = quantize_rows(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert q.shape == x.shape and s.shape == x.shape[:-1] + (1,)
    err = np.abs(np.asarray(dequantize_rows(q, s)) - np.asarray(x))
    assert (err <= np.asarray(s) * 0.5 + 1e-7).all()
    # the row absmax is representable exactly (hits +-127)
    assert (np.abs(np.asarray(q)).max(axis=-1) == QMAX).all()


def test_quantize_zero_rows_exact():
    """All-zero rows -> scale 1.0 and exact zero dequant: the null page and
    never-written pool rows decode to zeros regardless of scale init."""
    q, s = quantize_rows(jnp.zeros((2, 4, 8), jnp.float32))
    assert (np.asarray(s) == 1.0).all()
    assert not np.asarray(q).any()
    assert not np.asarray(dequantize_rows(q, s)).any()


def test_bf16_rows_roundtrip_through_f32():
    """The write paths quantize bf16 activations: quantization happens in
    f32 and the bound holds against the f32 view of the input."""
    x = jax.random.normal(jax.random.key(3), (4, 2, 32),
                          jnp.float32).astype(jnp.bfloat16)
    q, s = quantize_rows(x)
    err = np.abs(np.asarray(dequantize_rows(q, s))
                 - np.asarray(x, np.float32))
    assert (err <= np.asarray(s) * 0.5 + 1e-7).all()


# ---------------------------------------------------------------------------
# kernels vs the dequantizing gather oracles (interpret mode)
# ---------------------------------------------------------------------------

def _quantized_paged_case(seed, B, H, KV, D, page, NB, L, extra_pages=3):
    """Random f32 pool quantized row-wise + distinct non-null pages per
    slot + ragged per-slot lengths (not multiples of ``page``)."""
    rng = np.random.RandomState(seed)
    P = B * NB + extra_pages
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    kq, ksc = quantize_rows(jax.random.normal(ks[1], (L, P, KV, page, D)))
    vq, vsc = quantize_rows(jax.random.normal(ks[2], (L, P, KV, page, D)))
    tbl = rng.permutation(np.arange(1, P))[:B * NB].reshape(B, NB)
    lens = rng.randint(1, NB * page + 1, size=B)
    layer = rng.randint(0, L)
    return (q, kq, vq, ksc, vsc, jnp.asarray(tbl, jnp.int32),
            jnp.asarray(lens, jnp.int32), layer)


@pytest.mark.parametrize("pps", [1, 2])
@pytest.mark.parametrize("B,H,KV,D,page,NB,L", [
    (2, 4, 2, 16, 8, 5, 2),       # GQA group 2; NB !| pps
    (3, 4, 1, 16, 8, 3, 1),       # MQA
    (1, 8, 8, 32, 8, 4, 2),       # MHA
    (2, 6, 2, 32, 16, 2, 2),      # group 3; trailing partial page
])
def test_quantized_paged_decode_matches_dequant_oracle(pps, B, H, KV, D,
                                                       page, NB, L):
    """The decode kernel dequantizes P scattered pages per loop iteration
    through the online softmax; with per-row scales threaded it must match the
    dequantizing jnp gather oracle."""
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    q, kq, vq, ksc, vsc, tbl, lens, layer = _quantized_paged_case(
        B + H + pps, B, H, KV, D, page, NB, L)
    got = paged_decode_attention(q, kq, vq, tbl, lens, layer,
                                 pages_per_step=pps, k_scale=ksc,
                                 v_scale=vsc, interpret=True)
    want = paged_decode_attention_ref(q, kq, vq, tbl, lens, layer,
                                      k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,T,H,KV,D,page,NB,L", [
    (2, 6, 4, 2, 16, 8, 3, 2),    # GQA group 2; T !| page
    (3, 8, 4, 1, 16, 4, 5, 1),    # MQA; chunk spans 2+ pages
    (1, 5, 8, 8, 32, 8, 4, 3),    # MHA; odd T
])
def test_quantized_paged_prefill_matches_dequant_oracle(B, T, H, KV, D,
                                                        page, NB, L):
    """The ragged prefill sweep with quantized pools + per-row scales vs
    the dequantizing oracle: ragged bases/grants, chunks crossing page
    boundaries."""
    from repro.kernels.decode_attention.ops import paged_prefill_attention
    from repro.kernels.decode_attention.ref import paged_prefill_attention_ref
    rng = np.random.RandomState(B + T + H)
    P = B * NB + 3
    ks = jax.random.split(jax.random.key(B + T + H), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    kq, ksc = quantize_rows(jax.random.normal(ks[1], (L, P, KV, page, D)))
    vq, vsc = quantize_rows(jax.random.normal(ks[2], (L, P, KV, page, D)))
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[:B * NB]
                      .reshape(B, NB), jnp.int32)
    base = rng.randint(0, NB * page - T + 1, size=B)
    grants = rng.randint(1, T + 1, size=B)
    base = jnp.asarray(base, jnp.int32)
    new = base + jnp.asarray(grants, jnp.int32)
    layer = rng.randint(0, L)
    got = paged_prefill_attention(q, kq, vq, tbl, base, new, layer,
                                  k_scale=ksc, v_scale=vsc, interpret=True)
    want = paged_prefill_attention_ref(q, kq, vq, tbl, base, new, layer,
                                       k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_quantized_oracle_equals_oracle_on_dequantized_pool():
    """Oracle-of-oracle: the quantized gather oracle on (int8 pool, scales)
    must equal the plain oracle on the eagerly dequantized f32 pool —
    gather-then-dequantize and dequantize-then-gather are the same map, so
    the dequant's placement inside the sweep is a pure optimization."""
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    q, kq, vq, ksc, vsc, tbl, lens, layer = _quantized_paged_case(
        11, 2, 4, 2, 16, 8, 4, 2)
    got = paged_decode_attention_ref(q, kq, vq, tbl, lens, layer,
                                     k_scale=ksc, v_scale=vsc)
    want = paged_decode_attention_ref(q, dequantize_rows(kq, ksc),
                                      dequantize_rows(vq, vsc), tbl, lens,
                                      layer)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quantized_attention_drift_bounded():
    """Drift bound vs unquantized pools: quantizing a random f32 pool
    perturbs the decode attention output by quantization noise only —
    bounded well under the logit scale, NOT zero (the test must actually
    exercise the quantizer)."""
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    rng = np.random.RandomState(5)
    B, H, KV, D, page, NB, L, P = 2, 4, 2, 32, 8, 4, 2, 12
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (L, P, KV, page, D), jnp.float32)
    vp = jax.random.normal(ks[2], (L, P, KV, page, D), jnp.float32)
    kq, ksc = quantize_rows(kp)
    vq, vsc = quantize_rows(vp)
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[:B * NB]
                      .reshape(B, NB), jnp.int32)
    lens = jnp.asarray(rng.randint(1, NB * page + 1, size=B), jnp.int32)
    exact = paged_decode_attention_ref(q, kp, vp, tbl, lens, 1)
    quant = paged_decode_attention_ref(q, kq, vq, tbl, lens, 1,
                                       k_scale=ksc, v_scale=vsc)
    drift = np.abs(np.asarray(exact) - np.asarray(quant)).max()
    assert 0 < drift < 0.15


# ---------------------------------------------------------------------------
# pool management: COW, retention, census
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def int8_harness():
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), kv_dtype="int8")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


def test_int8_pool_decls_and_page_bytes(int8_harness):
    """The cache manager's pools come up int8 with f32 per-row scale pools,
    and page_bytes derives from the ACTUAL itemsizes: page x KV x (hd int8
    bytes + 4 scale bytes) x L x 2 (K and V)."""
    model, params = int8_harness
    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=2, max_seq=32, page_size=4))
    kv = pe.kv
    assert kv.quantized
    assert kv.k.dtype == jnp.int8 and kv.v.dtype == jnp.int8
    assert kv.k_scale.dtype == jnp.float32
    assert kv.k_scale.shape == kv.k.shape[:-1] + (1,)
    L, _, KV, page, hd = kv.k.shape
    assert kv.page_bytes == 2 * L * page * KV * (hd + 4)


def test_int8_cow_preserves_quantized_rows_and_scales(int8_harness):
    """COW on quantized pools: the shared rows of the original physical
    page — int8 content AND f32 scales — are bit-identical after both
    slots append into the shared page, and the two identical requests
    emit identical streams."""
    model, params = int8_harness
    sc = ServeConfig(max_batch=2, max_seq=32, max_new_tokens=4, page_size=4,
                     prefill_chunk=2, prefill_chunk_tokens=2)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(17)
    prompt = rng.randint(0, model.cfg.vocab_size, size=6).astype(np.int32)
    rid_a = pe.submit(prompt)             # donor
    pe.step()                             # donor at 2 tokens: page PARTIAL
    rid_b = pe.submit(prompt)             # sharer: same 6-token prompt
    pe._admit()                           # shares the partial page
    n_shared = pe.shared_tokens
    assert 0 < n_shared < pe.kv.page
    shared = [p for p in range(1, pe.kv.num_pages) if pe.kv.refcount[p] > 1]
    assert shared, "admission did not map a page into both tables"
    before = {p: (np.asarray(pe.kv.k)[:, p, :, :n_shared].copy(),
                  np.asarray(pe.kv.k_scale)[:, p, :, :n_shared].copy())
              for p in shared}
    pe.step()                             # both append into the shared page
    assert pe.kv.cow_copies > 0
    after_k = np.asarray(pe.kv.k)
    after_s = np.asarray(pe.kv.k_scale)
    for p, (rows, scales) in before.items():
        np.testing.assert_array_equal(
            rows, after_k[:, p, :, :n_shared],
            err_msg=f"write into shared page {p} reached shared int8 rows")
        np.testing.assert_array_equal(
            scales, after_s[:, p, :, :n_shared],
            err_msg=f"write into shared page {p} reached shared scales")
    res = pe.run()
    pe.kv.check()
    assert res[rid_a] == res[rid_b]       # same prompt, same budget


def test_int8_retained_adoption_carries_scales(int8_harness):
    """A follower adopting a DEAD donor's retained prefix re-shares the
    frozen int8 pages by reference with their scales untouched, and emits
    the donor's exact stream (same prompt, same budget — the retained rows
    are the donor's own bits, so retention is invisible in the tokens)."""
    model, params = int8_harness
    sc = ServeConfig(max_batch=2, max_seq=48, max_new_tokens=4, page_size=4,
                     prefill_chunk=2)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(41)
    prompt = rng.randint(0, model.cfg.vocab_size, size=11).astype(np.int32)
    rid0 = pe.submit(prompt)
    res0 = pe.run()                       # donor finishes and is FREED
    assert not pe.busy and pe.kv.live_pages == 0
    assert pe.kv.retained, "finished donor left nothing retained"
    entry = pe.kv.retained[-1]
    ret_pages = list(entry.pages)
    k_before = np.asarray(pe.kv.k)[:, ret_pages].copy()
    s_before = np.asarray(pe.kv.k_scale)[:, ret_pages].copy()
    rid = pe.submit(prompt)               # donor DEAD; only digests match
    pe._admit()
    assert pe.kv.retained_hits == 1
    np.testing.assert_array_equal(
        k_before, np.asarray(pe.kv.k)[:, ret_pages],
        err_msg="adoption mutated frozen retained int8 rows")
    np.testing.assert_array_equal(
        s_before, np.asarray(pe.kv.k_scale)[:, ret_pages],
        err_msg="adoption mutated frozen retained scales")
    res = pe.run()
    pe.kv.check()
    assert res[rid] == res0[rid0]


def test_quantized_cow_copy_census_page_scaled():
    """The quantized COW copy (int8 pools + scale pools in ONE dispatch)
    stays page-scaled and pool-size independent in the census — the
    byte-accounting claim the paged cache makes, now per quantized page."""
    from repro.core.hlo_counters import census_from_compiled
    from repro.serve.cache import _copy_pages_quant
    L, page, KV, hd = 4, 16, 2, 16

    def census(P, n):
        pool = jax.ShapeDtypeStruct((L, P, KV, page, hd), jnp.int8)
        scale = jax.ShapeDtypeStruct((L, P, KV, page, 1), jnp.float32)
        idx = jax.ShapeDtypeStruct((n,), jnp.int32)
        compiled = jax.jit(_copy_pages_quant,
                           donate_argnums=(0, 1, 2, 3)).lower(
            pool, pool, scale, scale, idx, idx).compile()
        return census_from_compiled(compiled)

    c2_small, c2_big = census(33, 2), census(65, 2)
    c4 = census(65, 4)
    assert c2_big.hbm_bytes == c2_small.hbm_bytes
    assert c4.hbm_bytes == pytest.approx(2 * c2_big.hbm_bytes, rel=0.01)
    # absolute sanity: int8 page + scale rows, both pools, read + write —
    # nowhere near a whole-pool convert's worth of traffic
    page_q = L * page * KV * (hd + 4)
    assert c2_big.hbm_bytes >= 2 * 2 * 2 * page_q  # rd+wr, K+V, 2 pages
    assert c2_big.hbm_bytes < 2 * 24 * page_q


# ---------------------------------------------------------------------------
# serving: the two quantized write paths agree token-for-token
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_int8_lane_vs_decode_token_identical(int8_harness, seed):
    """Property-harness schedule on int8 pools, prefill lane ON vs OFF:
    both write paths quantize the same appended rows with the same per-row
    arithmetic, so the emitted streams must be EXACTLY token-identical —
    the within-dtype half of the correctness story (cross-dtype drift vs
    bf16 is bounded above and measured in serve_bench's ragged_int8)."""
    model, params = int8_harness
    rng = np.random.RandomState(seed)
    reqs = [(rng.randint(0, model.cfg.vocab_size,
                         size=rng.choice((3, 5, 8, 11))).astype(np.int32),
             int(rng.choice((3, 5))))
            for _ in range(8)]
    outs = {}
    for lane in (True, False):
        pe = PagedEngine(model, params,
                         ServeConfig(max_batch=3, max_seq=48,
                                     max_new_tokens=5, page_size=4,
                                     prefill_chunk=3, prefill_lane=lane))
        rids = []
        # staggered submissions: mid-flight joins exercise mixed
        # prefill/decode ticks on the quantized pools
        for i, (p, b) in enumerate(reqs):
            rids.append(pe.submit(p, b))
            if i % 3 == 2:
                pe.step()
                pe.kv.check()
        res = pe.run()
        pe.kv.check()
        assert pe.kv.live_pages == 0
        outs[lane] = [res[r] for r in rids]
    assert outs[True] == outs[False], \
        "prefill lane and prefill-by-decode diverged on int8 pools"


def test_int8_defrag_carries_scales(int8_harness):
    """Defrag permutes int8 pages and scale pages with the SAME
    permutation: mid-flight defrag on a quantized engine leaves every
    live slot's (content, scale) pairing intact — checked end-to-end by
    stream identity against a defrag-free run."""
    model, params = int8_harness
    rng = np.random.RandomState(9)
    reqs = [(rng.randint(0, model.cfg.vocab_size,
                         size=n).astype(np.int32), 4)
            for n in (6, 9, 5, 7)]
    outs = []
    for defrag in (False, True):
        pe = PagedEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=48,
                                     max_new_tokens=4, page_size=4,
                                     prefill_chunk=3))
        rids = [pe.submit(p, b) for p, b in reqs]
        while pe.busy:
            pe.step()
            if defrag and pe.steps_run % 3 == 0:
                pe.defrag()
                pe.kv.check()
        res = pe.results
        outs.append([res[r] for r in rids])
    assert outs[0] == outs[1], "defrag perturbed quantized streams"
