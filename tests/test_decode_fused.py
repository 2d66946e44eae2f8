"""Fused decode subsystem tests: decode_many vs the legacy per-token loop
(greedy AND seeded temperature must be token-identical), Pallas
decode-attention (dense AND paged) vs the jnp references in interpret mode,
per-slot stop conditions, the paged engine's continuous-batching guarantees
(mid-flight joins, first-request token-identity, outliving max_seq, zero
recompiles — migrated from the retired dense lockstep engine), and the
census-ability of the fused/paged decode programs (paged transaction count
scales with live tokens, not max_seq; COW page-copy bytes scale with pages
copied, not pool size)."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get
from repro.models import get_model
from repro.serve.engine import PagedEngine, ServeConfig, ServingEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = get("qwen2-0.5b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


def _prompts(model, n=2, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, model.cfg.vocab_size, size=ln).astype(np.int32)
            for ln in rng.randint(5, 12, size=n)]


# ---------------------------------------------------------------------------
# fused loop vs legacy loop
# ---------------------------------------------------------------------------

def test_fused_matches_legacy_greedy(small_model):
    model, params = small_model
    eng = ServingEngine(model, params,
                        ServeConfig(max_batch=2, max_seq=48,
                                    max_new_tokens=6, temperature=0.0))
    prompts = _prompts(model)
    assert eng.generate_batch(prompts, fused=True) == \
        eng.generate_batch(prompts, fused=False)


def test_fused_matches_legacy_temperature(small_model):
    """Same seed => identical key-split discipline => identical tokens."""
    model, params = small_model
    prompts = _prompts(model)
    cfg = ServeConfig(max_batch=2, max_seq=48, max_new_tokens=6,
                      temperature=0.7, seed=11)
    a = ServingEngine(model, params, cfg).generate_batch(prompts, fused=True)
    b = ServingEngine(model, params, cfg).generate_batch(prompts, fused=False)
    assert a == b


def test_decode_many_eos_freezes_slot(small_model):
    """Once a slot samples eos its output is frozen to pad_id while the
    other slots keep decoding."""
    model, params = small_model
    B, S, steps = 2, 8, 5
    cache = model.init_cache(B, S + steps + 1)
    cache["pos"] = jnp.asarray(S, jnp.int32)
    key = jax.random.key(0)
    tok = jnp.zeros((B, 1), jnp.int32)
    ref, *_ = model.decode_many(params, tok, cache, key, num_steps=steps)
    eos = int(ref[0, 0])                    # force slot 0's first sample
    cache = model.init_cache(B, S + steps + 1)
    cache["pos"] = jnp.asarray(S, jnp.int32)
    toks, _, _, done = model.decode_many(
        params, tok, cache, key, num_steps=steps, eos_id=eos, pad_id=255)
    toks = np.asarray(toks)
    assert int(toks[0, 0]) == eos
    assert all(int(t) == 255 for t in toks[1:, 0])       # frozen after eos
    assert bool(np.asarray(done)[0])
    if eos not in toks[:, 1]:
        assert not bool(np.asarray(done)[1])


def test_decode_many_advances_cache_pos(small_model):
    model, params = small_model
    cache = model.init_cache(2, 32)
    cache["pos"] = jnp.asarray(4, jnp.int32)
    toks, cache, _, _ = model.decode_many(
        params, jnp.zeros((2, 1), jnp.int32), cache, jax.random.key(0),
        num_steps=6)
    assert toks.shape == (6, 2)
    assert int(cache["pos"]) == 10


# ---------------------------------------------------------------------------
# Pallas decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,H,KV,D", [
    (2, 32, 4, 2, 16),        # GQA
    (3, 48, 4, 1, 16),        # MQA
    (1, 128, 8, 8, 64),       # MHA, aligned
    (2, 24, 6, 2, 32),        # odd T
])
def test_decode_attention_matches_ref(B, T, H, KV, D):
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KV, D), jnp.float32)
    rng = np.random.RandomState(1)
    kv_len = jnp.int32(rng.randint(1, T + 1))
    starts = jnp.asarray(rng.randint(0, int(kv_len), size=B), jnp.int32)
    got = decode_attention(q, k, v, kv_len, starts, interpret=True)
    want = decode_attention_ref(q, k, v, kv_len, starts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_no_start_mask():
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, 40, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, 40, 2, 16), jnp.float32)
    got = decode_attention(q, k, v, jnp.int32(17), None, interpret=True)
    want = decode_attention_ref(q, k, v, jnp.int32(17), None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged Pallas decode attention vs the jnp gather oracle
# ---------------------------------------------------------------------------

def _paged_case(seed, B, H, KV, D, page, NB, L, extra_pages=3):
    """Random pool + a block table of DISTINCT non-null pages per slot +
    ragged per-slot lengths (deliberately not multiples of ``page``)."""
    rng = np.random.RandomState(seed)
    P = B * NB + extra_pages
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (L, P, KV, page, D), jnp.float32)
    vp = jax.random.normal(ks[2], (L, P, KV, page, D), jnp.float32)
    tbl = rng.permutation(np.arange(1, P))[:B * NB].reshape(B, NB)
    lens = rng.randint(1, NB * page + 1, size=B)
    layer = rng.randint(0, L)
    return (q, kp, vp, jnp.asarray(tbl, jnp.int32),
            jnp.asarray(lens, jnp.int32), layer)


@pytest.mark.parametrize("B,H,KV,D,page,NB,L", [
    (2, 4, 2, 16, 8, 3, 2),       # GQA group 2, multi-layer pool
    (3, 4, 1, 16, 16, 2, 1),      # MQA (group 4)
    (1, 8, 8, 32, 8, 4, 3),       # MHA (group 1)
    (2, 6, 2, 32, 16, 2, 2),      # group 3, page !| kv_len
    (2, 4, 2, 16, 1, 5, 1),       # degenerate single-row pages
])
def test_paged_decode_attention_matches_gather_oracle(B, H, KV, D, page,
                                                      NB, L):
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    q, kp, vp, tbl, lens, layer = _paged_case(B + H, B, H, KV, D, page,
                                              NB, L)
    assert any(int(x) % page for x in lens) or page == 1, \
        "case must exercise a partially-filled page"
    got = paged_decode_attention(q, kp, vp, tbl, lens, layer,
                                 interpret=True)
    want = paged_decode_attention_ref(q, kp, vp, tbl, lens, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("NB,P,want", [
    (5, 1, 5), (5, 2, 3), (5, 4, 2), (8, 4, 2), (3, 4, 1), (1, 4, 1),
])
def test_multipage_grid_arithmetic(NB, P, want):
    """P pages per grid step -> ceil(NB / P) steps along the block axis,
    table padded to steps * P with null-page entries."""
    from repro.kernels.decode_attention.paged import grid_steps, padded_blocks
    assert grid_steps(NB, P) == want == -(-NB // P)
    assert padded_blocks(NB, P) == want * P
    assert padded_blocks(NB, P) >= NB


def test_decode_kernel_runs_one_grid_step_per_slot(monkeypatch):
    """The decode kernel's grid is (B,): one step per slot, whatever P.
    Inside it each slot loops ``block_trips(kv_len, P * page)`` times —
    the helper the kernel calls, spied on here with the block it gets
    (and ``page`` for the live-page bound)."""
    import repro.kernels.decode_attention.paged as paged_mod
    recorded = {"tokens": set()}
    orig_spec = paged_mod.pltpu.PrefetchScalarGridSpec
    orig_trips = paged_mod.block_trips

    def spec(*args, **kwargs):
        recorded["grid"] = kwargs["grid"]
        return orig_spec(*args, **kwargs)

    def trips(kv_len, block_tokens):
        recorded["tokens"].add(block_tokens)
        return orig_trips(kv_len, block_tokens)

    monkeypatch.setattr(paged_mod.pltpu, "PrefetchScalarGridSpec", spec)
    monkeypatch.setattr(paged_mod, "block_trips", trips)
    B, H, KV, D, page, NB, L = 3, 4, 2, 16, 8, 5, 1
    q, kp, vp, tbl, _, layer = _paged_case(0, B, H, KV, D, page, NB, L)
    lens = jnp.asarray([0, 17, 40], jnp.int32)
    for pps in (4, 2, 1):
        recorded["tokens"].clear()
        paged_mod.paged_decode_attention_fwd(
            q, kp, vp, tbl, lens, layer, pages_per_step=pps, interpret=True)
        assert recorded["grid"] == (B,), f"pages_per_step={pps}"
        assert recorded["tokens"] == {page, pps * page}


@pytest.mark.parametrize("kv_len,block,want", [
    (0, 64, 0), (1, 64, 1), (64, 64, 1), (65, 64, 2), (40, 8, 5), (41, 8, 6),
])
def test_block_trips_counts_blocks_overlapping_kv_len(kv_len, block, want):
    """A slot's loop covers [0, kv_len) in blocks: ceil(kv_len / block)
    iterations, none for an empty slot or past the last live token."""
    from repro.kernels.decode_attention.paged import block_trips
    assert block_trips(kv_len, block) == want == -(-kv_len // block)


@pytest.mark.parametrize("KV,page,D,dtype,quantized,want", [
    (8, 16, 128, jnp.bfloat16, False, 16),   # phi4 / granite: 256 tokens
    (8, 16, 128, jnp.int8, True, 6),         # int8 rows + 128-lane scales
    (8, 16, 128, jnp.float32, False, 8),
    (2, 8, 16, jnp.float32, False, 64),      # 16 lanes pad to 128
])
def test_block_pages_fits_the_vmem_block(KV, page, D, dtype, quantized, want):
    """The page block comes from the pool's shape and dtype: the most
    pages whose VMEM rows fit ``BLOCK_BYTES`` per pool."""
    from repro.kernels.decode_attention.paged import BLOCK_BYTES, block_pages
    got = block_pages(KV, page, D, dtype, quantized)
    assert got == want
    row = -(-D // 128) * 128 * jnp.dtype(dtype).itemsize + 512 * quantized
    assert got * KV * page * row <= BLOCK_BYTES < (got + 1) * KV * page * row


@pytest.mark.parametrize("B,H,KV,D,page,NB,P,lens,shared,int8", [
    (4, 4, 2, 16, 8, 5, 2, (0, 1, 23, 40), False, False),  # empty, 1, full
    (4, 4, 2, 16, 8, 4, 2, (8, 16, 32, 24), False, False),  # page/block edge
    (2, 4, 2, 16, 8, 5, 3, (29, 13), False, False),  # P !| kv_len, P !| NB
    (2, 4, 2, 16, 8, 5, 5, (40, 40), False, False),  # whole table, 1 block
    (3, 4, 1, 16, 8, 4, 2, (30, 19, 0), True, False),  # shared prefix pages
    (2, 4, 4, 16, 8, 4, None, (21, 32), False, False),  # group 1, derived P
    (2, 6, 2, 32, 16, 3, None, (47, 5), False, False),  # group 3, derived P
    (2, 8, 2, 16, 8, 4, 3, (31, 9), False, False),   # group 4
    (4, 4, 2, 16, 1, 9, 4, (0, 1, 5, 9), False, False),  # page-1 rows
    (4, 4, 2, 16, 8, 5, 2, (0, 1, 16, 33), False, True),  # int8 + scales
])
def test_paged_decode_reads_only_live_pages(B, H, KV, D, page, NB, P, lens,
                                            shared, int8):
    """Edge lengths against the gather oracle, with every page a slot's
    kv_len does not reach (the null page included) filled with NaN: a
    kernel that read one would return NaN.  An empty slot yields zeros.
    ``shared``: slots 1.. reuse slot 0's first pages, as the prefix
    cache maps them."""
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.models.kv_quant import quantize_rows
    q, kp, vp, tbl, _, layer = _paged_case(B * H + NB, B, H, KV, D, page,
                                           NB, 2)
    tbl = np.array(tbl)
    if shared:
        tbl[1:, :2] = tbl[0, :2]
    live = np.zeros(kp.shape[1], bool)
    for b, n in enumerate(lens):
        live[tbl[b, :-(-n // page)]] = True
    dead = jnp.asarray(~live)[None, :, None, None, None]
    lens = jnp.asarray(lens, jnp.int32)
    tbl = jnp.asarray(tbl, jnp.int32)
    if int8:
        (kp, ks), (vp, vs) = quantize_rows(kp), quantize_rows(vp)
        clean = dict(k_scale=jnp.where(dead, 0.0, ks),
                     v_scale=jnp.where(dead, 0.0, vs))
        poisoned = dict(k_scale=jnp.where(dead, jnp.nan, ks),
                        v_scale=jnp.where(dead, jnp.nan, vs))
        pools = clean_pools = (kp, vp)
    else:
        clean, poisoned = {}, {}
        pools = [jnp.where(dead, jnp.nan, x) for x in (kp, vp)]
        clean_pools = [jnp.where(dead, 0.0, x) for x in (kp, vp)]
    got = np.asarray(paged_decode_attention(
        q, *pools, tbl, lens, layer, pages_per_step=P, interpret=True,
        **poisoned))
    want = np.asarray(paged_decode_attention_ref(q, *clean_pools, tbl, lens,
                                                 layer, **clean))
    empty = np.asarray(lens) == 0
    assert (got[empty] == 0).all()
    np.testing.assert_allclose(got[~empty], want[~empty],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pps", [1, 2, 4])
@pytest.mark.parametrize("B,H,KV,D,page,NB,L", [
    (2, 4, 2, 16, 8, 5, 2),       # GQA group 2; 5 % 2 and 5 % 4 != 0
    (3, 4, 1, 16, 8, 3, 1),       # MQA; NB < P at pps=4
    (1, 8, 8, 32, 8, 4, 2),       # MHA; NB % pps == 0 at 2 and 4
    (2, 6, 2, 32, 16, 2, 2),      # group 3; trailing partial page
])
def test_multipage_paged_decode_matches_oracle(pps, B, H, KV, D, page,
                                               NB, L):
    """Multi-page blocking sweeps P physically-scattered pages per grid
    step through the online softmax; the output must match the jnp gather
    oracle bit-for-fp32 across GQA groups, ragged lengths and page counts
    not dividing kv_len OR pages_per_step."""
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    q, kp, vp, tbl, lens, layer = _paged_case(B + H + pps, B, H, KV, D,
                                              page, NB, L)
    got = paged_decode_attention(q, kp, vp, tbl, lens, layer,
                                 pages_per_step=pps, interpret=True)
    want = paged_decode_attention_ref(q, kp, vp, tbl, lens, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ragged multi-token paged PREFILL kernel vs the jnp gather oracle
# ---------------------------------------------------------------------------

def _prefill_case(seed, B, T, H, KV, D, page, NB, L, extra_pages=3,
                  base=None, grants=None):
    """Random pool + distinct non-null pages per slot + RAGGED chunk
    geometry: per-slot base lengths (tokens resident before the chunk) and
    grants (chunk tokens granted, 1..T) drawn so chunks start mid-page and
    cross page boundaries unless pinned by the caller."""
    rng = np.random.RandomState(seed)
    P = B * NB + extra_pages
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (L, P, KV, page, D), jnp.float32)
    vp = jax.random.normal(ks[2], (L, P, KV, page, D), jnp.float32)
    tbl = rng.permutation(np.arange(1, P))[:B * NB].reshape(B, NB)
    if base is None:
        base = rng.randint(0, NB * page - T + 1, size=B)
    if grants is None:
        grants = rng.randint(1, T + 1, size=B)
    layer = rng.randint(0, L)
    base = np.asarray(base, np.int32)
    new = base + np.asarray(grants, np.int32)
    return (q, kp, vp, jnp.asarray(tbl, jnp.int32), jnp.asarray(base),
            jnp.asarray(new, jnp.int32), layer)


@pytest.mark.parametrize("B,T,H,KV,D,page,NB,L", [
    (2, 6, 4, 2, 16, 8, 3, 2),    # GQA group 2; T=6 !| page=8
    (3, 8, 4, 1, 16, 4, 5, 1),    # MQA; chunk spans 2+ pages
    (1, 5, 8, 8, 32, 8, 4, 3),    # MHA; odd T
    (2, 7, 6, 2, 32, 16, 2, 2),   # group 3; T !| page
    (2, 4, 4, 2, 16, 1, 9, 1),    # degenerate single-row pages
])
def test_paged_prefill_matches_gather_oracle(B, T, H, KV, D, page, NB, L):
    """Interpret-mode equivalence of the multi-token prefill kernel vs the
    jnp gather oracle across GQA/MQA/MHA, ragged per-slot base lengths and
    grants, chunk sizes not dividing the page, and chunks crossing page
    boundaries — row-for-row, including rows past a slot's grant."""
    from repro.kernels.decode_attention.ops import paged_prefill_attention
    from repro.kernels.decode_attention.ref import paged_prefill_attention_ref
    args = _prefill_case(B + T + H, B, T, H, KV, D, page, NB, L)
    got = paged_prefill_attention(*args, interpret=True)
    want = paged_prefill_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_prefill_chunk_crosses_page_boundary():
    """Pinned geometry: base mid-page, grant spanning three pages — the
    chunk starts mid-page, fills it, crosses a whole page and ends mid-way
    into a third; ragged second slot gets a single-token grant."""
    from repro.kernels.decode_attention.ops import paged_prefill_attention
    from repro.kernels.decode_attention.ref import paged_prefill_attention_ref
    args = _prefill_case(7, 2, 8, 4, 2, 16, 4, 4, 1,
                         base=[3, 5], grants=[8, 1])
    got = paged_prefill_attention(*args, interpret=True)
    want = paged_prefill_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_prefill_empty_slot_isolated():
    """A fully EMPTY slot in the batch (base=0, grant=0 — an unoccupied
    row during a mixed prefill tick) must not perturb any other slot's
    rows; its own all-masked rows are the ONE documented kernel/oracle
    divergence (zeros vs a degenerate uniform softmax) and the engine
    never reads them."""
    from repro.kernels.decode_attention.ops import paged_prefill_attention
    from repro.kernels.decode_attention.ref import paged_prefill_attention_ref
    args = _prefill_case(9, 2, 6, 4, 2, 16, 8, 3, 1,
                         base=[5, 0], grants=[4, 0])
    got = paged_prefill_attention(*args, interpret=True)
    want = paged_prefill_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[1].any()         # empty slot: guarded zeros


def test_multipage_prefill_kernel_runs_ceil_grid_steps(monkeypatch):
    """The prefill kernel's pages_per_step blocking must RUN ceil(NB/P)
    grid steps along the block axis — asserted on the actual pallas grid
    of the PREFILL kernel (mirrors the decode-kernel spy above)."""
    import repro.kernels.decode_attention.prefill_paged as prefill_mod
    recorded = {}
    orig = prefill_mod.pltpu.PrefetchScalarGridSpec

    def spy(*args, **kwargs):
        recorded["grid"] = kwargs.get("grid", args[1] if len(args) > 1
                                      else None)
        return orig(*args, **kwargs)

    monkeypatch.setattr(prefill_mod.pltpu, "PrefetchScalarGridSpec", spy)
    B, T, H, KV, D, page, NB, L = 2, 4, 4, 2, 16, 8, 5, 1
    args = _prefill_case(0, B, T, H, KV, D, page, NB, L)
    for pps, steps in ((4, 2), (2, 3), (1, 5)):
        prefill_mod.paged_prefill_attention_fwd(
            *args, pages_per_step=pps, interpret=True)
        assert recorded["grid"] == (B, KV, steps), \
            f"pages_per_step={pps}: grid {recorded['grid']}"


@pytest.mark.parametrize("pps", [1, 2, 4])
@pytest.mark.parametrize("B,T,H,KV,D,page,NB,L", [
    (2, 6, 4, 2, 16, 8, 5, 2),    # GQA group 2; 5 % 2 and 5 % 4 != 0
    (3, 8, 4, 1, 16, 4, 3, 1),    # MQA; NB < P at pps=4; chunk spans pages
    (1, 5, 8, 8, 32, 8, 4, 2),    # MHA; odd T; NB % pps == 0 at 2 and 4
    (2, 7, 6, 2, 32, 16, 2, 2),   # group 3; trailing partial page
])
def test_multipage_paged_prefill_matches_oracle(pps, B, T, H, KV, D, page,
                                                NB, L):
    """Multi-page blocking on the RAGGED PREFILL sweep: P physically-
    scattered pages per grid step through the online softmax, output equal
    to the jnp gather oracle across GQA groups, ragged base/grant
    geometry and page counts not dividing pages_per_step — the shape a
    speculative verify chunk over a long decode history hits every
    tick."""
    from repro.kernels.decode_attention.ops import paged_prefill_attention
    from repro.kernels.decode_attention.ref import paged_prefill_attention_ref
    args = _prefill_case(B + T + H + pps, B, T, H, KV, D, page, NB, L)
    got = paged_prefill_attention(*args, pages_per_step=pps, interpret=True)
    want = paged_prefill_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_prefill_oracle_matches_dense_causal():
    """Oracle-of-oracle: hand-pack a contiguous cache into pages; the
    prefill gather oracle must equal dense causal attention with the same
    per-slot query offsets and lengths."""
    from repro.kernels.decode_attention.ref import paged_prefill_attention_ref
    from repro.models.attention import direct_attention
    B, T, H, KV, D, page, NB = 2, 5, 4, 2, 16, 8, 3
    TT = page * NB
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, TT, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, TT, KV, D), jnp.float32)
    rng = np.random.RandomState(3)
    P = 1 + B * NB
    kp = np.zeros((1, P, KV, page, D), np.float32)
    vp = np.zeros_like(kp)
    tbl = np.zeros((B, NB), np.int32)
    pages = 1 + rng.permutation(B * NB)
    for b in range(B):
        for j in range(NB):
            pg = pages[b * NB + j]
            tbl[b, j] = pg
            kp[0, pg] = np.asarray(k)[b, j * page:(j + 1) * page].swapaxes(
                0, 1)
            vp[0, pg] = np.asarray(v)[b, j * page:(j + 1) * page].swapaxes(
                0, 1)
    base = jnp.asarray([7, 2], jnp.int32)          # mid-page, ragged
    new = base + jnp.asarray([5, 3], jnp.int32)
    got = paged_prefill_attention_ref(q, jnp.asarray(kp), jnp.asarray(vp),
                                      jnp.asarray(tbl), base, new)
    want = direct_attention(q, k, v, causal=True, q_offset=base, kv_len=new)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_prefill_step_paged_matches_sequential_decode(small_model):
    """THE lane-equivalence pin: one ragged chunked-prefill step must leave
    the pool rows, the per-slot lengths AND the last-position logits
    bit-identical to feeding the same tokens one decode step at a time
    (the prefill-by-decode path it replaces)."""
    model, params = small_model
    B, page, nb, pool, T = 2, 4, 4, 9, 6
    tbl = np.zeros((B, nb), np.int32)
    tbl[0] = [1, 2, 3, 4]
    tbl[1] = [5, 6, 7, 8]

    def fresh():
        cache = model.init_paged_cache(B, nb, page, pool)
        return dict(cache, table=jnp.asarray(tbl))

    rng = np.random.RandomState(0)
    toks = rng.randint(0, model.cfg.vocab_size, size=(B, T)).astype(np.int32)
    grants = np.array([T, 3], np.int32)            # ragged: slot 1 partial

    cache = fresh()
    logits_seq = [None] * B
    for t in range(T):
        act = jnp.asarray([t < grants[0], t < grants[1]])
        logits, cache = model.decode_step_paged(
            params, jnp.asarray(toks[:, t:t + 1]), cache, act)
        for i in range(B):
            if t == grants[i] - 1:
                logits_seq[i] = np.asarray(logits[i])

    cache2 = fresh()
    logits2, cache2 = model.prefill_step_paged(
        params, jnp.asarray(toks), cache2, jnp.asarray(grants))
    np.testing.assert_array_equal(np.asarray(cache["length"]),
                                  np.asarray(cache2["length"]))
    for i in range(B):
        np.testing.assert_array_equal(np.asarray(logits2[i]), logits_seq[i])
    k_seq, k_chunk = np.asarray(cache["k"]), np.asarray(cache2["k"])
    for i in range(B):
        for t in range(grants[i]):
            pg, off = tbl[i, t // page], t % page
            np.testing.assert_array_equal(k_seq[:, pg, :, off],
                                          k_chunk[:, pg, :, off])


@pytest.mark.parametrize("lane", [True, False])
def test_prefill_lane_token_identical_to_decode_lane(small_model, lane):
    """The engine's outputs must be byte-for-byte identical with the
    prefill lane on and off (greedy): the lane changes WHEN prompt rows
    are appended (chunks vs steps), never WHAT is appended or sampled."""
    model, params = small_model
    prompts = _prompts(model, n=4, seed=21)
    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=2, max_seq=64, max_new_tokens=5,
                                 page_size=8, prefill_chunk=3,
                                 prefill_lane=lane))
    rids = [pe.submit(p) for p in prompts]
    res = pe.run()
    single = ServingEngine(model, params,
                           ServeConfig(max_batch=1, max_seq=64,
                                       max_new_tokens=5))
    for rid, p in zip(rids, prompts):
        assert res[rid] == single.generate_batch([p])[0], \
            f"lane={lane} rid={rid}"
    if lane:
        assert pe.forced_upload_bytes == 0      # prompts never rode forced
        assert pe.prefill_upload_bytes > 0
    else:
        assert pe.forced_upload_bytes > 0       # legacy path measured
        assert pe.prefill_upload_bytes == 0


def test_prefill_lane_fewer_dispatches_per_prompt(small_model):
    """The perf-shape claim behind the lane: admitting a P-token prompt
    costs ceil(P / T) prefill dispatches, not P decode steps.  A 24-token
    prompt with T=8 must fully drain in 3 prefill-lane ticks."""
    model, params = small_model
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, model.cfg.vocab_size, size=24).astype(np.int32)
    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=1, max_seq=64, max_new_tokens=2,
                                 page_size=8, prefill_chunk=2,
                                 prefill_chunk_tokens=8))
    pe.submit(prompt)
    ticks = 0
    while any(s.active and s.prompt_left for s in pe.slots) or pe.queue:
        pe.step()
        ticks += 1
    assert ticks == 3                       # ceil(24 / 8), not 24 steps
    assert int(pe.kv.length[0]) == 24       # whole prompt resident
    assert len(pe.slots[0].out) == 1        # first output sampled in-lane


def test_paged_oracle_matches_dense_on_packed_pages():
    """Oracle-of-oracle: hand-pack a contiguous (B, T, KV, D) cache into
    pages; the gather oracle must equal the dense direct attention with the
    same per-slot lengths."""
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.models.attention import direct_attention
    B, H, KV, D, page, NB = 2, 4, 2, 16, 8, 3
    T = page * NB
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KV, D), jnp.float32)
    # pack slot b's rows into pages 1 + b*NB + j (order scrambled per slot)
    rng = np.random.RandomState(3)
    P = 1 + B * NB
    kp = np.zeros((1, P, KV, page, D), np.float32)
    vp = np.zeros_like(kp)
    tbl = np.zeros((B, NB), np.int32)
    pages = 1 + rng.permutation(B * NB)
    for b in range(B):
        for j in range(NB):
            pg = pages[b * NB + j]
            tbl[b, j] = pg
            kp[0, pg] = np.asarray(k)[b, j * page:(j + 1) * page].swapaxes(
                0, 1)
            vp[0, pg] = np.asarray(v)[b, j * page:(j + 1) * page].swapaxes(
                0, 1)
    lens = jnp.asarray([T - 3, page + 1], jnp.int32)      # ragged, page !| len
    got = paged_decode_attention_ref(q, jnp.asarray(kp), jnp.asarray(vp),
                                     jnp.asarray(tbl), lens)
    want = direct_attention(q, k, v, causal=False, kv_len=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pallas_decode_path_token_identical(small_model):
    """Whole serving path with cfg.attention_impl='pallas' (kernel inside
    the layer scan inside decode_many) vs the jnp reference path."""
    model, params = small_model
    model_pl = get_model(dataclasses.replace(model.cfg,
                                             attention_impl="pallas"))
    sc = ServeConfig(max_batch=2, max_seq=48, max_new_tokens=5)
    prompts = _prompts(model)
    a = ServingEngine(model, params, sc).generate_batch(prompts)
    b = ServingEngine(model_pl, params, sc).generate_batch(prompts)
    assert a == b


# ---------------------------------------------------------------------------
# paged continuous batching (the regression guarantees migrated from the
# retired dense lockstep engine)
# ---------------------------------------------------------------------------

def test_paged_first_request_matches_generate(small_model):
    """A request admitted into an idle engine decodes exactly like
    generate_batch (chunked prefill-by-decode == prefill: same causal
    math, same request-relative positions)."""
    model, params = small_model
    prompt = _prompts(model, n=1, seed=9)[0]
    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=2, max_seq=64, max_new_tokens=6,
                                 page_size=8, prefill_chunk=3))
    rid = pe.submit(prompt)
    res = pe.run()
    single = ServingEngine(
        model, params, ServeConfig(max_batch=1, max_seq=48,
                                   max_new_tokens=6)
    ).generate_batch([prompt])[0]
    assert res[rid] == single


def test_paged_slot_release_and_join(small_model):
    """More requests than slots: finished sequences release their pages and
    queued requests join mid-flight (no recompilation, per-slot pages)."""
    model, params = small_model
    cfg = ServeConfig(max_batch=2, max_seq=128, max_new_tokens=4,
                      page_size=8, prefill_chunk=4)
    pe = PagedEngine(model, params, cfg)
    prompts = _prompts(model, n=5, seed=4)
    rids = [pe.submit(p) for p in prompts]
    res = pe.run()
    assert set(res) == set(rids)
    assert all(len(res[r]) == 4 for r in rids)
    assert pe.joins == 5                        # every request got a slot
    assert all(not s.active for s in pe.slots)
    V = model.cfg.vocab_size
    assert all(0 <= t < V for r in rids for t in res[r])
    # late joiners genuinely joined mid-flight: more joins than slots
    assert pe.joins > cfg.max_batch


def test_paged_outlives_max_seq_token_identical():
    """REGRESSION (the retired lockstep engine's wraparound guarantee): a
    long-lived engine must keep serving after total traffic far exceeds
    max_seq — pages recycle through the free list — and every request must
    stay token-identical to a fresh run.  rope_theta=0 makes attention
    position-free, so ANY leak of a previous occupant's rows changes the
    softmax and breaks exact token-identity with the oracle."""
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), rope_theta=0.0)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=2, max_seq=32, max_new_tokens=4,
                                 page_size=4, prefill_chunk=4))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size,
                           size=rng.randint(5, 10)).astype(np.int32)
               for _ in range(8)]
    rids = [pe.submit(p) for p in prompts]
    res = pe.run()
    # total token traffic (prompts + outputs) well past max_seq
    assert sum(len(p) + 4 for p in prompts) > 2 * pe.cfg.max_seq
    oracle = ServingEngine(model, params,
                           ServeConfig(max_batch=1, max_seq=32,
                                       max_new_tokens=4))
    for rid, p in zip(rids, prompts):
        assert res[rid] == oracle.generate_batch([p])[0], \
            f"rid={rid}: read rows outside its own pages"


def test_paged_zero_recompiles(small_model):
    """The whole engine lifetime — admissions, mid-flight joins, stalls,
    partial grants, evictions — reuses the compiled cells, each compiled
    exactly once.  With the prefill lane ON the universe is the ragged
    prefill cell + the forced-free decode twin (the forced decode cell
    never runs: prompt traffic moved to the lane); with the lane OFF it is
    the legacy pair (forced decode + plain twin)."""
    model, params = small_model
    rng = np.random.RandomState(2)

    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=2, max_seq=48, max_new_tokens=4,
                                 page_size=4, num_pages=13,
                                 prefill_chunk=3))
    if not hasattr(pe._many, "_cache_size"):
        pytest.skip("jit cache-size introspection unavailable")
    for n in (3, 7, 5, 9, 4, 6):
        pe.submit(rng.randint(0, model.cfg.vocab_size,
                              size=n).astype(np.int32))
    pe.run()
    assert pe._prefill_lane._cache_size() == 1   # ragged prefill cell
    assert pe._many_plain._cache_size() == 1     # pure-decode twin
    assert pe._many._cache_size() == 0           # forced cell retired

    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=2, max_seq=48, max_new_tokens=4,
                                 page_size=4, num_pages=13,
                                 prefill_chunk=3, prefill_lane=False))
    for n in (3, 7, 5, 9, 4, 6):
        pe.submit(rng.randint(0, model.cfg.vocab_size,
                              size=n).astype(np.int32))
    pe.run()
    assert pe._prefill_lane._cache_size() == 0   # lane off: never compiled
    assert pe._many._cache_size() == 1           # legacy forced cell
    assert pe._many_plain._cache_size() <= 1


def test_steady_state_tick_uploads_zero_table_bytes(small_model):
    """REGRESSION (device-resident tick state): once a slot's prompt has
    drained and no allocation/COW/admission happens, an engine tick must
    upload ZERO table/length bytes and ZERO forced-token bytes — only the
    B-int feed/grant vectors move, and the tick is exactly one device
    dispatch (the fused decode cell)."""
    model, params = small_model
    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=2, max_seq=64, max_new_tokens=12,
                                 page_size=32, prefill_chunk=2))
    pe.submit(np.arange(1, 5, dtype=np.int32))       # 4-token prompt
    while any(s.active and s.forced for s in pe.slots) or pe.queue:
        pe.step()                 # drain admission + chunked prefill
    pe.step()                     # settle residual dirty rows
    tb0, fb0 = pe.table_upload_bytes, pe.forced_upload_bytes
    d0 = pe.kv.cow_dispatches
    c0 = pe.counters()
    pe.step()                     # a pure steady-state decode tick
    c1 = pe.counters()
    assert pe.table_upload_bytes == tb0, "steady tick re-uploaded the table"
    assert pe.forced_upload_bytes == fb0, "steady tick built forced arrays"
    assert pe.kv.cow_dispatches == d0
    assert c1["dispatches"] - c0["dispatches"] == 1   # the fused decode cell
    assert c1["upload_bytes"] - c0["upload_bytes"] == \
        2 * pe.cfg.max_batch * 4                      # feed + grants


def test_decode_page_counters_follow_live_lengths(small_model):
    """``decode_pages_streamed`` adds ceil(kv_len / page) for every active
    decode step of a dispatch (kv_len = the slot's length after the step's
    append); ``decode_pages_table`` adds the table width for each."""
    model, params = small_model
    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=2, max_seq=64, max_new_tokens=12,
                                 page_size=4, prefill_chunk=3))
    pe.submit(np.arange(1, 8, dtype=np.int32))       # 7-token prompt
    while any(s.active and s.forced for s in pe.slots) or pe.queue:
        pe.step()                 # drain admission + prefill
    i = next(j for j, s in enumerate(pe.slots) if s.active)
    n0 = int(pe.kv.length[i])
    c0 = pe.counters()
    pe.step()                     # one decode dispatch of 3 steps, 1 slot
    c1 = pe.counters()
    steps = int(pe.kv.length[i]) - n0
    assert steps == 3
    assert c1["decode_pages_streamed"] - c0["decode_pages_streamed"] == \
        sum(-(-(n0 + s + 1) // 4) for s in range(steps))
    assert c1["decode_pages_table"] - c0["decode_pages_table"] == \
        steps * pe.kv.max_blocks


# ---------------------------------------------------------------------------
# paged engine: pallas path + fused-vs-stepwise + sampling discipline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pps", [1, 2])
def test_paged_pallas_path_token_identical(small_model, pps):
    """Whole paged serving path with cfg.attention_impl='pallas' (paged
    kernel inside the layer scan inside decode_many_paged) vs the jnp
    gather-oracle path — including the multi-page blocking mode, which
    must be invisible in the tokens."""
    model, params = small_model
    model_pl = get_model(dataclasses.replace(model.cfg,
                                             attention_impl="pallas",
                                             pages_per_step=pps))
    sc = ServeConfig(max_batch=2, max_seq=48, max_new_tokens=5, page_size=8,
                     prefill_chunk=3)
    prompts = _prompts(model, n=3)
    outs = []
    for m in (model, model_pl):
        pe = PagedEngine(m, params, sc)
        rids = [pe.submit(p) for p in prompts]
        res = pe.run()
        outs.append([res[r] for r in rids])
    assert outs[0] == outs[1]


def test_decode_many_paged_matches_stepwise_temperature(small_model):
    """The fused paged scan and a per-step decode_step_paged loop must be
    token-identical under seeded temperature sampling (same key-split
    discipline), including forced-token overrides."""
    from repro.models.model import sample_token
    model, params = small_model
    B, steps, page, nb, pool = 2, 5, 4, 4, 9
    forced = np.zeros((steps, B), np.int32)
    fmask = np.zeros((steps, B), bool)
    forced[0] = [7, 9]
    fmask[0] = [True, True]
    active = jnp.ones((B,), bool)
    tok0 = jnp.asarray([[3], [4]], jnp.int32)

    def fresh():
        cache = model.init_paged_cache(B, nb, page, pool)
        tbl = np.zeros((B, nb), np.int32)
        tbl[0] = [1, 2, 3, 4]
        tbl[1] = [5, 6, 7, 8]
        return dict(cache, table=jnp.asarray(tbl))

    key = jax.random.key(42)
    toks_f, cache_f, _ = model.decode_many_paged(
        params, tok0, fresh(), key, active, jnp.asarray(forced),
        jnp.asarray(fmask), num_steps=steps, temperature=0.8)

    cache = fresh()
    tok = tok0
    rows = []
    for s in range(steps):
        logits, cache = model.decode_step_paged(params, tok, cache, active)
        nxt, key = sample_token(logits, key, 0.8)
        nxt = jnp.where(jnp.asarray(fmask[s]), jnp.asarray(forced[s]), nxt)
        rows.append(np.asarray(nxt))
        tok = nxt[:, None]
    np.testing.assert_array_equal(np.asarray(toks_f), np.stack(rows))
    np.testing.assert_array_equal(np.asarray(cache_f["length"]),
                                  np.asarray(cache["length"]))
    assert list(np.asarray(cache_f["length"])) == [steps, steps]


def test_decode_many_paged_per_step_active(small_model):
    """A (num_steps, B) active mask packs PARTIAL chunks: a slot active for
    its first s steps advances exactly s tokens, its emitted stream is
    frozen from step s on (the host reads a stable value at any step >=
    s-1), and its tokens for the active prefix are identical to a full-
    chunk run."""
    model, params = small_model
    B, steps, page, nb, pool = 2, 4, 4, 3, 7

    def fresh():
        cache = model.init_paged_cache(B, nb, page, pool)
        tbl = np.zeros((B, nb), np.int32)
        tbl[0] = [1, 2, 3]
        tbl[1] = [4, 5, 6]
        return dict(cache, table=jnp.asarray(tbl))

    tok0 = jnp.asarray([[3], [4]], jnp.int32)
    key = jax.random.key(0)
    full, cache_full, _ = model.decode_many_paged(
        params, tok0, fresh(), key, jnp.ones((B,), bool), num_steps=steps)
    mask = np.ones((steps, B), bool)
    mask[2:, 1] = False                       # slot 1: only 2 of 4 steps
    part, cache_part, _ = model.decode_many_paged(
        params, tok0, fresh(), key, jnp.asarray(mask), num_steps=steps)
    full, part = np.asarray(full), np.asarray(part)
    np.testing.assert_array_equal(part[:, 0], full[:, 0])   # slot 0 untouched
    np.testing.assert_array_equal(part[:2, 1], full[:2, 1])  # active prefix
    assert all(int(t) == int(part[1, 1]) for t in part[2:, 1])  # frozen
    assert list(np.asarray(cache_part["length"])) == [steps, 2]


def test_decode_step_paged_inactive_slot_frozen(small_model):
    """An inactive slot must not advance its length and must not perturb
    any live page (its append lands on the null page 0)."""
    model, params = small_model
    B, page, nb, pool = 2, 4, 2, 5
    cache = model.init_paged_cache(B, nb, page, pool)
    tbl = np.zeros((B, nb), np.int32)
    tbl[0] = [1, 2]
    cache["table"] = jnp.asarray(tbl)
    cache["length"] = jnp.asarray([3, 0], jnp.int32)
    active = jnp.asarray([True, False])
    before_k = np.asarray(cache["k"])
    _, cache2 = jax.jit(model.decode_step_paged)(
        params, jnp.zeros((B, 1), jnp.int32), cache, active)
    assert list(np.asarray(cache2["length"])) == [4, 0]
    after_k = np.asarray(cache2["k"])
    np.testing.assert_array_equal(before_k[:, 2:], after_k[:, 2:])  # pages >= 2
    assert not np.array_equal(before_k[:, 1], after_k[:, 1])        # slot 0 wrote


# ---------------------------------------------------------------------------
# the fused decode cell is censusable (the PR's motivation)
# ---------------------------------------------------------------------------

def test_fused_decode_program_census(small_model):
    from repro.core.hlo_counters import census_from_compiled
    model, params = small_model
    B, T, steps = 2, 32, 4

    def fused(params, tok, cache, key):
        return model.decode_many(params, tok, cache, key, num_steps=steps)

    key = jax.random.key(0)
    compiled = jax.jit(fused).lower(
        model.abstract_params(), jax.ShapeDtypeStruct((B, 1), jnp.int32),
        model.abstract_cache(B, T),
        jax.ShapeDtypeStruct(key.shape, key.dtype)).compile()
    census = census_from_compiled(compiled)
    assert census.mxu_flops > 0
    assert census.total_instructions > 0
    # the token loop appears as a trip-counted while: per-layer matmul work
    # must scale with num_steps x n_layers, far above a single step's
    single = model.cfg.n_layers * 2 * model.cfg.d_model
    assert census.mxu_flops > single


def test_paged_decode_census_scales_with_live_tokens():
    """The roofline claim the paged cache exists to make measurable: the
    paged decode step's transaction count scales with LIVE tokens (block-
    table width), not with the pool / max_seq.  Two fills of each cache
    flavor, byte-count ratios asserted.  f32 config: the CPU backend wraps
    bf16 scatters in full-pool converts that would pollute the traffic
    model (TPU scatters natively)."""
    from repro.core.hlo_counters import census_from_compiled
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), dtype="float32")
    model = get_model(cfg)
    B, page = 2, 16

    def paged(nb, pool):
        cache = model.abstract_paged_cache(B, nb, page, pool)
        compiled = jax.jit(lambda p, t, c: model.decode_step_paged(p, t, c),
                           donate_argnums=(2,)).lower(
            model.abstract_params(), jax.ShapeDtypeStruct((B, 1), jnp.int32),
            cache).compile()
        return census_from_compiled(compiled)

    def dense_cache(max_seq):
        cache = model.abstract_cache(B, max_seq)
        compiled = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
            model.abstract_params(), jax.ShapeDtypeStruct((B, 1), jnp.int32),
            cache).compile()
        return census_from_compiled(compiled)

    p_small_pool = paged(2, 33)           # 2 live blocks, 512-row pool
    p_big_pool = paged(2, 65)             # 2 live blocks, 1024-row pool
    p_more_live = paged(8, 65)            # 8 live blocks, 1024-row pool
    d_512, d_1024 = dense_cache(512), dense_cache(1024)

    # fill 1 vs fill 2, paged: doubling the POOL moves zero extra bytes
    assert p_big_pool.hbm_bytes == p_small_pool.hbm_bytes
    assert p_big_pool.irregular_bytes == p_small_pool.irregular_bytes
    # more LIVE blocks do move more bytes (gather grows with the table)
    assert p_more_live.hbm_bytes > p_big_pool.hbm_bytes
    assert p_more_live.irregular_bytes > 3 * p_big_pool.irregular_bytes
    # fill 1 vs fill 2, dense: bytes track max_seq whether or not it is live
    assert d_1024.hbm_bytes > 1.5 * d_512.hbm_bytes
    # and at equal capacity the paged step moves a fraction of the dense one
    assert d_1024.hbm_bytes > 2 * p_big_pool.hbm_bytes


def test_paged_prefill_census_scales_with_chunk_and_live_tokens():
    """Mirror of ``test_paged_decode_census_scales_with_live_tokens`` for
    the ragged prefill lane: a prefill step's hbm_bytes scale with CHUNK
    tokens and LIVE pages (block-table width), never with the pool size —
    the kernel-level half of the lane's roofline claim.  f32 config: the
    CPU backend wraps bf16 scatters in full-pool converts that would
    pollute the traffic model (TPU scatters natively)."""
    from repro.core.hlo_counters import census_from_compiled
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), dtype="float32")
    model = get_model(cfg)
    B, page = 2, 16

    def prefill(T, nb, pool):
        cache = model.abstract_paged_cache(B, nb, page, pool)
        compiled = jax.jit(
            lambda p, t, c, g: model.prefill_step_paged(p, t, c, g),
            donate_argnums=(2,)).lower(
            model.abstract_params(), jax.ShapeDtypeStruct((B, T), jnp.int32),
            cache, jax.ShapeDtypeStruct((B,), jnp.int32)).compile()
        return census_from_compiled(compiled)

    p_small_pool = prefill(8, 2, 33)      # 8-tok chunk, 2 blocks, 512-row pool
    p_big_pool = prefill(8, 2, 65)        # 8-tok chunk, 2 blocks, 1024-row pool
    p_more_live = prefill(8, 8, 65)       # 8-tok chunk, 8 blocks
    p_more_chunk = prefill(32, 8, 65)     # 32-tok chunk, 8 blocks

    # doubling the POOL moves zero extra bytes (chunk scatter + page
    # gather address only granted rows and live pages)
    assert p_big_pool.hbm_bytes == p_small_pool.hbm_bytes
    assert p_big_pool.irregular_bytes == p_small_pool.irregular_bytes
    # more LIVE blocks move more bytes (the gather grows with the table)
    assert p_more_live.hbm_bytes > p_big_pool.hbm_bytes
    # more CHUNK tokens move more bytes (scatter + attention grow with T)
    assert p_more_chunk.hbm_bytes > 1.5 * p_more_live.hbm_bytes


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_cow_page_copy_census_scales_with_pages(dtype):
    """The COW page copy's census bytes scale with the pages COPIED, never
    with the pool — standalone (the engine's jitted copy) and with the
    copy fused into an append step.  bf16 exercises the dtype-bracket
    elision: the CPU backend wraps the in-place update in whole-pool
    converts that would otherwise charge 3x the pool per copy (TPU updates
    the storage dtype natively).  int8 pins the quantized page pools: the
    CPU backend scatters s8 natively (no brackets), and the page-wise
    accounting must survive at 1-byte granularity."""
    from repro.core.hlo_counters import census_from_compiled
    from repro.serve.cache import _copy_pages
    L, page, KV, hd = 4, 16, 2, 16

    def census(P, n):
        pool = jax.ShapeDtypeStruct((L, P, KV, page, hd), dtype)
        idx = jax.ShapeDtypeStruct((n,), jnp.int32)
        compiled = jax.jit(_copy_pages, donate_argnums=(0,)).lower(
            pool, idx, idx).compile()
        return census_from_compiled(compiled)

    page_bytes = L * page * KV * hd * jnp.dtype(dtype).itemsize
    page_f32 = L * page * KV * hd * 4       # compute-dtype page (CPU widens)
    c2_small, c2_big = census(33, 2), census(65, 2)
    c3, c4 = census(65, 3), census(65, 4)
    # pool-size independence: doubling the pool moves zero extra bytes
    assert c2_big.hbm_bytes == c2_small.hbm_bytes
    # page scaling: the batched-COW claim — bytes == pages_copied x
    # page_bytes regardless of the batch size the tick collected
    assert c3.hbm_bytes == pytest.approx(1.5 * c2_big.hbm_bytes, rel=0.01)
    assert c4.hbm_bytes == pytest.approx(2 * c2_big.hbm_bytes, rel=0.01)
    assert c4.irregular_bytes == pytest.approx(2 * c2_big.irregular_bytes,
                                               rel=0.01)
    # absolute sanity: a handful of page-moves per copied page (the
    # fusion-boundary model counts this lowering's intermediate page
    # materializations), nowhere near the 33-page pool per copy
    assert c2_big.hbm_bytes < 2 * 12 * page_f32
    assert c2_big.hbm_bytes >= 2 * 2 * page_bytes      # read src + write dst

    # in-fusion: the copy composed with an append into the private page
    # stays page-scaled and pool-independent
    def cow_append(pool, dst, src, kv_new, row):
        pool = _copy_pages(pool, dst, src)
        return pool.at[:, dst[0], :, row].set(kv_new)

    def fused_census(P):
        pool = jax.ShapeDtypeStruct((L, P, KV, page, hd), dtype)
        idx = jax.ShapeDtypeStruct((1,), jnp.int32)
        kvn = jax.ShapeDtypeStruct((L, KV, hd), dtype)
        row = jax.ShapeDtypeStruct((), jnp.int32)
        compiled = jax.jit(cow_append, donate_argnums=(0,)).lower(
            pool, idx, idx, kvn, row).compile()
        return census_from_compiled(compiled)

    f_small, f_big = fused_census(33), fused_census(65)
    assert f_big.hbm_bytes == f_small.hbm_bytes
    assert f_big.hbm_bytes < 12 * page_f32


def test_cow_bytes_zero_without_shared_writes(small_model):
    """The engine-level half of the COW accounting claim: a workload that
    never writes a shared page (sharing disabled entirely) performs ZERO
    copy-on-write traffic, and a shared-prefix workload's COW bytes equal
    copies x page_bytes exactly."""
    model, params = small_model
    rng = np.random.RandomState(1)
    common = rng.randint(0, model.cfg.vocab_size, size=6).astype(np.int32)
    # STAGGERED tails: sharing matches live slots only, so request
    # lifetimes must overlap for a donor to exist at admission time
    prompts = [np.concatenate([common,
                               rng.randint(0, model.cfg.vocab_size,
                                           size=n).astype(np.int32)])
               for n in (3, 6, 2, 5)]
    for sharing in (False, True):
        # prefill chunk pinned to one page so prompt drains stay slow
        # enough for request lifetimes to overlap (sharing needs a donor
        # still LIVE when the next request is admitted)
        pe = PagedEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=32,
                                     max_new_tokens=3, page_size=4,
                                     prefill_chunk=3,
                                     prefill_chunk_tokens=4,
                                     prefix_sharing=sharing))
        # budgets staggered too: equal budgets + equal chunked-prefill
        # tick counts would finish both donors in the same tick, leaving
        # no live donor for the later admissions
        for j, p in enumerate(prompts):
            pe.submit(p, 3 + 2 * (j % 2))
        pe.run()
        if sharing:
            assert pe.shared_tokens > 0
            assert pe.kv.cow_bytes == pe.kv.cow_copies * pe.kv.page_bytes
        else:
            assert pe.kv.cow_copies == 0 and pe.kv.cow_bytes == 0
            assert pe.shared_tokens == 0


# ---------------------------------------------------------------------------
# stream _grid fallback (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,block,expect", [
    (24, 64, 24), (100, 64, 50), (8, 8, 8), (7, 8, 7), (256, 64, 64),
])
def test_stream_block_rows_fallback(rows, block, expect):
    from repro.kernels.stream.stream import _block_rows
    assert _block_rows(rows, block) == expect
    assert rows % _block_rows(rows, block) == 0


def test_stream_odd_rows_no_crash():
    from repro.kernels.stream import ref, stream
    a = jax.random.normal(jax.random.key(0), (24, 128), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (24, 128), jnp.float32)
    got = stream.add(a, b, block_rows=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.add(a, b)),
                               rtol=1e-6)
