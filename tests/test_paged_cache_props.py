"""Property-test harness for the paged serving path.

Randomized admit/finish/join schedules drive the non-lockstep ``PagedEngine``
(mixed prompt lengths and budgets, staggered submissions, mid-flight joins,
random defrags, shared prompt prefixes) and assert three properties after
every engine tick:

  * SAFETY — the refcounted page pool never double-allocates or leaks:
    every page's refcount equals the number of block-table references to
    it, and the null page + referenced pages + free list cover the pool
    exactly (``PagedKVCache.check()``);
  * IMMUTABILITY — a page mapped into several block tables (prefix
    sharing) is never mutated while shared: any append into it
    copy-on-write privatizes the page first, so its content is frozen
    across ticks for as long as its refcount exceeds one, and evicting a
    sharer never frees a page another slot still references;
  * CORRECTNESS — every request's output is token-identical to a fresh
    dense-cache ``ServingEngine`` run of the same prompt (the oracle):
    per-slot positions mean a mid-flight join decodes exactly like a
    batch-of-one run from position 0, and a shared prefix references
    bit-identical K/V rows, so sharing must be invisible in the tokens.

Runs a SHORT fuzz profile (>= 200 randomized engine steps across seeds)
under tier-1; the LONG profile is ``@pytest.mark.slow``
(``pytest --runslow``).  Written as explicit seeded fuzz loops because the
container image has no hypothesis; with hypothesis present these would be
``@given`` schedules.

Prompt lengths and budgets are drawn from small sets so the oracle's
compile universe stays bounded (one prefill per distinct prompt length, one
decode_many per distinct budget).
"""
import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get
from repro.models import get_model
from repro.serve.cache import PagedKVCache
from repro.serve.engine import (PagedEngine, RequestStatus, ServeConfig,
                                ServingEngine)

PROMPT_LENS = (3, 5, 8)
BUDGETS = (3, 5)
SUFFIX_LENS = (2, 3, 5)                  # shared-prefix fuzz tails


@pytest.fixture(scope="module")
def harness():
    cfg = get("qwen2-0.5b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    oracle = ServingEngine(model, params,
                           ServeConfig(max_batch=1, max_seq=64,
                                       max_new_tokens=max(BUDGETS)))
    return model, params, oracle


@contextlib.contextmanager
def _seeded_repro(**seeds):
    """Stamp every AssertionError escaping a fuzz body with the seeds that
    reproduce it, so a CI failure is a ONE-LINE repro: paste the printed
    ``[repro: schedule_seed=N fault_seed=M]`` values back into the harness
    and the exact failing schedule (and fault plan, if any) replays.  Seeds
    passed as ``None`` are omitted (e.g. a fuzz run with no fault plan)."""
    try:
        yield
    except AssertionError as e:
        tag = " ".join(f"{k}={v}" for k, v in seeds.items() if v is not None)
        head = str(e.args[0]) if e.args else ""
        e.args = (f"{head}\n[repro: {tag}]",) + tuple(e.args[1:])
        raise


def _assert_tokens_identical(got, want, label=""):
    """EXACT token identity between the paged engine and the oracle.

    This used to be near-tie-aware (``_assert_match_or_near_tie``): the
    unembed ran at activation dtype, and bf16 logit rounding (~2^-8
    relative) could flip an argmax between two numerically-equivalent
    lanes on the ragged workload.  Root-caused and fixed — every sampled
    position (decode steps and the prefill last position) now unembeds at
    f32 (``transformer._logits_exact``), so batched prefill and
    prefill-by-decode pick the same token and any divergence is a REAL
    bug, asserted loudly."""
    got, want = list(got), list(want)
    assert got == want, f"{label}: outputs diverged: {got} vs {want}"


def _check_tick(pe):
    """Per-tick invariants beyond ``kv.check()``: the engine's host token
    history mirrors the device lengths exactly (the prefix-sharing donor
    index must never drift from the cache), and the rolling-hash prefix
    index holds entries ONLY for live slots, consistent with their real
    histories (staleness across preempt->requeue->recompute cycles would
    surface here)."""
    pe.kv.check()
    for i, slot in enumerate(pe.slots):
        if slot.active:
            assert len(slot.history) == int(pe.kv.length[i]), \
                f"slot {i}: history/length drift"
    pe._pindex.check(pe.slots)


def _assert_drained_clean(pe):
    """Post-drain pool accounting, retention-aware: no live references,
    every non-retained page free — and after flushing the retained pool,
    the free list is the ENTIRE pool (nothing leaked through retention)."""
    assert pe.kv.live_pages == 0
    assert (pe.kv.refcount[1:] == 0).all()
    retained_only = pe.kv.retained_pages
    assert len(pe.kv.free) == pe.kv.num_pages - 1 - retained_only
    pe.kv.flush_retained()
    pe.kv.check()
    assert len(pe.kv.free) == pe.kv.num_pages - 1


def _snapshot_shared(pe):
    """Content snapshot of every page with refcount > 1 (k-pool rows)."""
    k = np.asarray(pe.kv.k)
    return {int(p): k[:, p].copy()
            for p in range(1, pe.kv.num_pages) if pe.kv.refcount[p] > 1}


def _assert_shared_frozen(pe, before):
    """IMMUTABILITY: a page that was shared at the last tick and is STILL
    shared now must be bit-identical — COW never mutates a page another
    slot can still see."""
    k = np.asarray(pe.kv.k)
    for p, rows in before.items():
        if pe.kv.refcount[p] > 1:
            np.testing.assert_array_equal(
                rows, k[:, p],
                err_msg=f"shared page {p} mutated while refcount > 1")


def _fuzz_schedule(model, params, oracle, seed: int, min_ticks: int,
                   n_requests: int, **kw) -> dict:
    """Seeded-repro wrapper: any assertion out of the fuzz body carries
    ``[repro: schedule_seed=N]`` for a one-line replay."""
    with _seeded_repro(schedule_seed=seed):
        return _fuzz_schedule_impl(model, params, oracle, seed, min_ticks,
                                   n_requests, **kw)


def _fuzz_schedule_impl(model, params, oracle, seed: int, min_ticks: int,
                        n_requests: int, *, max_batch=3, page_size=4,
                        prefill_chunk=3, prefill_lane=True,
                        prefill_chunk_tokens=0, defrag_every=0, prefixes=(),
                        check_frozen=False) -> dict:
    """One randomized schedule; returns engine stats.  Asserts the
    refcount/free-list invariants every tick and oracle token-identity at
    the end.  ``prefixes``: pool of common prompt prefixes — when set,
    every prompt is prefix + short suffix, exercising sharing and COW.
    The ragged prefill lane is ON by default (the production path);
    ``prefill_lane=False`` fuzzes the legacy prefill-by-decode route."""
    rng = np.random.RandomState(seed)
    cfg = model.cfg
    pe = PagedEngine(model, params,
                     ServeConfig(max_batch=max_batch, max_seq=48,
                                 max_new_tokens=max(BUDGETS),
                                 page_size=page_size,
                                 prefill_chunk=prefill_chunk,
                                 prefill_lane=prefill_lane,
                                 prefill_chunk_tokens=prefill_chunk_tokens))
    submitted = {}

    def make_prompt():
        if prefixes and rng.rand() < 0.85:
            pre = prefixes[rng.randint(len(prefixes))]
            suf = rng.randint(0, cfg.vocab_size,
                              size=rng.choice(SUFFIX_LENS)).astype(np.int32)
            return np.concatenate([pre, suf])
        return rng.randint(0, cfg.vocab_size,
                           size=rng.choice(PROMPT_LENS)).astype(np.int32)

    shared_snap = {}
    for it in range(10 * min_ticks + 10 * n_requests + 100):
        # keep the schedule alive until BOTH the request count and the tick
        # count are met — late submissions are exactly the mid-flight joins
        # the harness exists to fuzz
        want_more = (len(submitted) < n_requests
                     or pe.steps_run < min_ticks)
        if want_more and rng.rand() < 0.6:
            for _ in range(rng.randint(1, 3)):
                p = make_prompt()
                b = int(rng.choice(BUDGETS))
                submitted[pe.submit(p, b)] = (p, b)
        if pe.busy:
            cow_disp0 = pe.kv.cow_dispatches
            pe.step()
            # batched COW: however many pages a tick privatizes, it
            # issues at most ONE copy dispatch
            assert pe.kv.cow_dispatches - cow_disp0 <= 1
            _check_tick(pe)                   # refcounts, no leak, no drift
            if check_frozen:
                _assert_shared_frozen(pe, shared_snap)
                shared_snap = _snapshot_shared(pe)
        if defrag_every and pe.steps_run and \
                pe.steps_run % defrag_every == 0:
            pe.defrag()
            pe.kv.check()
            shared_snap = _snapshot_shared(pe)    # defrag renumbers pages
        if len(submitted) >= n_requests and not pe.busy \
                and pe.steps_run >= min_ticks:
            break
    res = pe.run()
    pe.kv.check()
    # eviction returns every page: nothing live, nothing leaked after drain
    # — and no page was ever freed while another slot still referenced it
    # (a premature free would surface as a refcount/partition violation in
    # the per-tick check above).  Finished requests' prefixes legitimately
    # outlive them in the RETAINED pool; flushing it must restore the full
    # free list.
    _assert_drained_clean(pe)
    assert set(res) == set(submitted)
    assert pe.joins == len(submitted)
    for rid, (p, b) in submitted.items():
        want = oracle.generate_batch([p], max_new_tokens=b)[0]
        _assert_tokens_identical(
            res[rid], want,
            label=f"seed={seed} rid={rid} (paged vs dense-cache oracle)")
    return {"ticks": pe.steps_run, "shared": pe.shared_tokens,
            "cow": pe.kv.cow_copies}


# ---------------------------------------------------------------------------
# short profile (tier-1): >= 200 randomized engine steps across seeds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,defrag_every", [(0, 0), (1, 5), (2, 3)])
def test_fuzz_schedule_token_identical(harness, seed, defrag_every):
    model, params, oracle = harness
    stats = _fuzz_schedule(model, params, oracle, seed, min_ticks=67,
                           n_requests=12, defrag_every=defrag_every)
    assert stats["ticks"] >= 67               # 3 seeds x 67 >= 200 steps


def test_fuzz_shared_prefix_token_identical(harness):
    """The sharing fuzz: prompts drawn from common-prefix families, so
    admissions share resident pages and appends into the shared trailing
    page exercise COW — outputs must stay oracle-identical and shared
    pages bit-frozen (checked tick by tick)."""
    model, params, oracle = harness
    rng = np.random.RandomState(100)
    prefixes = tuple(rng.randint(0, model.cfg.vocab_size,
                                 size=n).astype(np.int32) for n in (6, 9))
    stats = _fuzz_schedule(model, params, oracle, seed=3, min_ticks=40,
                           n_requests=10, prefixes=prefixes,
                           check_frozen=True)
    assert stats["shared"] > 0, "schedule never shared a prefix"
    assert stats["cow"] > 0, "schedule never exercised copy-on-write"


def test_fuzz_shared_prefix_with_defrag(harness):
    """Sharing + periodic defrag: renumbering must preserve refcounts and
    multi-table references (one physical move, all tables rewritten)."""
    model, params, oracle = harness
    rng = np.random.RandomState(200)
    prefixes = (rng.randint(0, model.cfg.vocab_size,
                            size=7).astype(np.int32),)
    stats = _fuzz_schedule(model, params, oracle, seed=5, min_ticks=30,
                           n_requests=8, prefixes=prefixes, defrag_every=4)
    assert stats["shared"] > 0


def test_fuzz_single_slot_chunked(harness):
    """max_batch=1 with chunk > prompt: the pure chunked-prefill path."""
    model, params, oracle = harness
    _fuzz_schedule(model, params, oracle, seed=7, min_ticks=20,
                   n_requests=6, max_batch=1, prefill_chunk=6)


def test_fuzz_prefill_lane_odd_chunk(harness):
    """Prefill-lane chunk NOT dividing the page (T=5, page=4): every
    mid-prompt chunk is clipped to a page boundary and the final chunk
    carries the ragged tail — outputs must stay oracle-identical with the
    refcount invariants intact every tick."""
    model, params, oracle = harness
    rng = np.random.RandomState(500)
    prefixes = (rng.randint(0, model.cfg.vocab_size,
                            size=6).astype(np.int32),)
    _fuzz_schedule(model, params, oracle, seed=13, min_ticks=30,
                   n_requests=8, prefill_chunk_tokens=5, prefixes=prefixes)


def test_fuzz_legacy_prefill_by_decode(harness):
    """REGRESSION: the legacy forced-token route (lane off) must keep its
    guarantees — it is the measured baseline the lane is gated against."""
    model, params, oracle = harness
    stats = _fuzz_schedule(model, params, oracle, seed=17, min_ticks=25,
                           n_requests=6, prefill_lane=False)
    assert stats["ticks"] >= 25


def test_fuzz_page_size_one(harness):
    """page_size=1 maximizes allocation churn (one page per token; every
    shared page is full, so sharing never needs COW)."""
    model, params, oracle = harness
    _fuzz_schedule(model, params, oracle, seed=11, min_ticks=25,
                   n_requests=5, max_batch=2, page_size=1, prefill_chunk=2)


# ---------------------------------------------------------------------------
# long profile (manual): pytest --runslow
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("seed", [101, 202])
def test_fuzz_schedule_long(harness, seed):
    model, params, oracle = harness
    stats = _fuzz_schedule(model, params, oracle, seed, min_ticks=500,
                           n_requests=60, defrag_every=7)
    assert stats["ticks"] >= 500


@pytest.mark.slow
def test_fuzz_shared_prefix_long(harness):
    model, params, oracle = harness
    rng = np.random.RandomState(300)
    prefixes = tuple(rng.randint(0, model.cfg.vocab_size,
                                 size=n).astype(np.int32) for n in (5, 9))
    stats = _fuzz_schedule(model, params, oracle, seed=303, min_ticks=400,
                           n_requests=50, prefixes=prefixes,
                           defrag_every=9, check_frozen=True)
    assert stats["shared"] > 0 and stats["cow"] > 0


# ---------------------------------------------------------------------------
# targeted edge cases
# ---------------------------------------------------------------------------

def test_eos_truncates_like_oracle(harness):
    """eos sampled mid-stream finishes the slot with the same truncation
    rule as the dense oracle."""
    model, params, _ = harness
    prompt = np.random.RandomState(5).randint(
        0, model.cfg.vocab_size, size=5).astype(np.int32)
    # pick an eos the model actually emits: the 2nd greedy token
    probe = ServingEngine(model, params,
                          ServeConfig(max_batch=1, max_seq=32,
                                      max_new_tokens=4))
    eos = probe.generate_batch([prompt])[0][1]
    sc = ServeConfig(max_batch=2, max_seq=48, max_new_tokens=6, eos_id=eos,
                     page_size=4, prefill_chunk=3)
    pe = PagedEngine(model, params, sc)
    rid = pe.submit(prompt)
    res = pe.run()
    want = ServingEngine(model, params,
                         ServeConfig(max_batch=1, max_seq=32,
                                     max_new_tokens=6, eos_id=eos)
                         ).generate_batch([prompt])[0]
    assert res[rid] == want
    assert res[rid][-1] == eos and len(res[rid]) == 2


def test_stall_recovers_via_eviction(harness):
    """A slot that cannot get step capacity stalls (zero granted steps for
    the tick) and resumes after another slot finishes and its pages are
    evicted — no deadlock, outputs still oracle-identical."""
    model, params, oracle = harness
    # 3 allocatable pages, two slots each eventually needing 2 pages
    sc = ServeConfig(max_batch=2, max_seq=8, max_new_tokens=5, page_size=4,
                     num_pages=4, prefill_chunk=2)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, model.cfg.vocab_size, size=3).astype(np.int32)
               for _ in range(2)]
    rids = [pe.submit(p) for p in prompts]
    res = pe.run()
    assert pe.stalls > 0
    for rid, p in zip(rids, prompts):
        assert res[rid] == oracle.generate_batch([p], max_new_tokens=5)[0]


def test_scheduler_partial_grant_budget_fairness(harness):
    """The tick scheduler's packing policies, deterministically: a slot
    short on pages gets a PARTIAL grant (prefix of the tick's steps)
    instead of stalling outright; ``tick_budget`` caps total fresh tokens
    per tick; least-served fairness hands pages to the slot with the
    fewest tokens appended."""
    from repro.serve.cache import PagedKVCache
    from repro.serve.engine import _Slot
    from repro.serve.scheduler import TickScheduler
    model, params, _ = harness

    def slots(served=(0, 0)):
        return [_Slot(rid=i, forced=list(range(9)), budget=3, served=s,
                      active=True) for i, s in enumerate(served)]

    # 5 allocatable single-row pages, two slots wanting chunk 4 each:
    # first-in-order takes its full chunk, the other packs the 1 left
    kv = PagedKVCache(model, 2, 16, page_size=1, num_pages=6)
    plan = TickScheduler().plan(slots(), kv, chunk=4)
    assert list(plan.steps) == [4, 1]
    assert plan.active[:, 0].all()
    assert plan.active[0, 1] and not plan.active[1:, 1].any()
    assert plan.stalled == 0

    # the budget knob caps the tick's total fresh tokens
    kv = PagedKVCache(model, 2, 16, page_size=1, num_pages=12)
    plan = TickScheduler(tick_budget=5).plan(slots(), kv, chunk=4)
    assert int(plan.steps.sum()) == 5

    # least-served fairness: the starved slot allocates first
    kv = PagedKVCache(model, 2, 16, page_size=1, num_pages=6)
    plan = TickScheduler().plan(slots(served=(10, 0)), kv, chunk=4)
    assert list(plan.steps) == [1, 4]
    # legacy slot-order: first slot wins regardless of service
    kv = PagedKVCache(model, 2, 16, page_size=1, num_pages=6)
    plan = TickScheduler(fairness="slot-order").plan(
        slots(served=(10, 0)), kv, chunk=4)
    assert list(plan.steps) == [4, 1]


def test_scheduler_prefill_grants_page_aligned(harness):
    """Prefill-lane grants: a chunk that does not drain the prompt is
    clipped to end on a PAGE BOUNDARY (appends never leave a partially
    written page mid-prompt); the final chunk keeps its ragged tail; a
    slot whose prompt has drained gets decode steps instead; the tick
    budget caps both lanes together."""
    from repro.serve.cache import PagedKVCache
    from repro.serve.engine import _Slot
    from repro.serve.scheduler import TickScheduler
    model, params, _ = harness

    def slot(prompt_left, forced_n=None, budget=3, served=0):
        n = prompt_left - 1 if forced_n is None else forced_n
        return _Slot(rid=0, forced=list(range(max(0, n))), budget=budget,
                     served=served, prompt_left=prompt_left, active=True)

    # mid-prompt chunk clipped to the page boundary: T=6, page=4, base=0,
    # prompt_left=20 -> grant 4 (not 6); a draining chunk keeps its tail:
    # prompt_left=5 <= T -> grant 5
    kv = PagedKVCache(model, 2, 32, page_size=4, num_pages=20)
    plan = TickScheduler().plan([slot(20), slot(5)], kv, chunk=3,
                                prefill_tokens=6)
    assert list(plan.prefill) == [4, 5]
    assert list(plan.steps) == [0, 0]           # no decode while prefilling
    assert plan.any_work

    # base mid-page (prefix share at 2 tokens): the clip lands the chunk
    # end on the boundary — T=5 from base 2 would end at 7 mid-page, so
    # the grant clips to 2 (base+grant = 4 = one page); with T=6 the
    # un-clipped end (8) is already a boundary and the full 6 is granted
    for T, want in ((5, 2), (6, 6)):
        kv = PagedKVCache(model, 1, 32, page_size=4, num_pages=20)
        assert kv.ensure(0, 2)
        kv.length[0] = 2
        plan = TickScheduler().plan([slot(20)], kv, chunk=3,
                                    prefill_tokens=T)
        assert list(plan.prefill) == [want], T

    # drained prompt -> decode lane; budget caps prefill + decode together
    kv = PagedKVCache(model, 2, 32, page_size=4, num_pages=20)
    plan = TickScheduler(tick_budget=5).plan(
        [slot(8), slot(0, forced_n=0)], kv, chunk=3, prefill_tokens=4)
    assert int(plan.prefill.sum()) + int(plan.steps.sum()) == 5
    assert list(plan.prefill) == [4, 0]
    assert list(plan.steps) == [0, 1]

    # prefill_tokens=0 (lane off): prompts ride the decode cell as before
    kv = PagedKVCache(model, 1, 32, page_size=4, num_pages=20)
    plan = TickScheduler().plan([slot(20)], kv, chunk=3, prefill_tokens=0)
    assert list(plan.prefill) == [0]
    assert list(plan.steps) == [3]


def test_scheduler_prefill_partial_grant_under_pool_pressure(harness):
    """A prefill chunk that does not fit the free list is granted the
    largest feasible prefix (alignment yields to pool pressure) instead of
    stalling outright."""
    from repro.serve.cache import PagedKVCache
    from repro.serve.engine import _Slot
    from repro.serve.scheduler import TickScheduler
    model, params, _ = harness
    kv = PagedKVCache(model, 1, 32, page_size=4, num_pages=2)  # 1 page free
    s = _Slot(rid=0, forced=list(range(15)), budget=3, prompt_left=16,
              active=True)
    plan = TickScheduler().plan([s], kv, chunk=3, prefill_tokens=12)
    assert list(plan.prefill) == [4]            # one page's worth
    assert plan.stalled == 0


def test_scheduler_cow_before_ensure(harness):
    """REGRESSION: with ONE free page and an append landing in a shared
    partial page, the scheduler must spend the page on the COW copy (and
    advance within existing pages), not on extending the table — the old
    ensure-first order consumed the page, failed the COW, granted 0 to a
    completable slot, and the engine raised pool-exhausted."""
    from repro.serve.cache import PagedKVCache
    from repro.serve.engine import _Slot
    from repro.serve.scheduler import TickScheduler
    model, params, _ = harness
    kv = PagedKVCache(model, 2, 8, page_size=4, num_pages=3)  # 2 allocatable
    # donor wrote 2 tokens into page A; sharer references it at length 2
    assert kv.ensure(0, 2)
    kv.length[0] = 2
    kv.share(1, 0, 2)
    assert len(kv.free) == 1 and kv.refcount[kv.owned[0][0]] == 2
    slots = [_Slot(rid=0, forced=[1, 2, 3], budget=3, active=True), _Slot()]
    plan = TickScheduler().plan(slots, kv, chunk=4)
    assert kv.cow_copies == 1                # the free page went to the COW
    assert int(plan.steps[0]) == 2           # advances within the new page
    assert plan.stalled == 0


def test_cow_many_one_dispatch_refcount_fuzz(harness):
    """Batched COW at the cache level: randomized share topologies, then
    one ``cow_many`` over a random (slot, blk) set — N privatizations must
    cost exactly ONE device dispatch, counters must track pages (bytes ==
    copies x page_bytes), and the refcount/free-list/table invariants must
    hold after every batch."""
    from repro.serve.cache import PagedKVCache
    model, params, _ = harness
    for seed in range(4):
        rng = np.random.RandomState(40 + seed)
        kv = PagedKVCache(model, 4, 32, page_size=4, num_pages=40)
        n_tok = int(rng.randint(8, 17))
        assert kv.ensure(0, n_tok)
        kv.length[0] = n_tok
        for dst in (1, 2, 3):
            kv.share(dst, 0, int(rng.randint(1, n_tok + 1)))
        kv.check()
        items = [(i, b) for i in range(4) for b in range(len(kv.owned[i]))
                 if rng.rand() < 0.5]
        d0, c0, b0 = kv.cow_dispatches, kv.cow_copies, kv.cow_bytes
        # expected copies: each privatization drains one reference, and
        # the LAST referent of a page keeps the original (no copy)
        rc = kv.refcount.copy()
        expected = 0
        for i, b in items:
            pg = kv.owned[i][b]
            if rc[pg] > 1:
                rc[pg] -= 1
                expected += 1
        copied = kv.cow_many(items)
        assert copied == expected            # exclusive pages skipped
        assert kv.cow_dispatches - d0 == (1 if copied else 0)
        assert kv.cow_copies - c0 == copied
        assert kv.cow_bytes - b0 == copied * kv.page_bytes
        kv.check()


def test_tick_batches_cow_into_one_dispatch(harness):
    """Engine-level half of the batched-COW claim: a tick whose appends
    privatize SEVERAL shared pages (two sharers forking off one donor in
    the same tick) issues exactly ONE copy dispatch for all of them."""
    model, params, oracle = harness
    sc = ServeConfig(max_batch=3, max_seq=32, max_new_tokens=4, page_size=4,
                     prefill_chunk=2)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(31)
    prompt = rng.randint(0, model.cfg.vocab_size, size=6).astype(np.int32)
    rids = [pe.submit(prompt)]            # donor
    pe.step()                             # donor resident mid-page
    rids += [pe.submit(prompt), pe.submit(prompt)]
    pe._admit()                           # both sharers reference the page
    shared = [p for p in range(1, pe.kv.num_pages) if pe.kv.refcount[p] > 1]
    assert shared and pe.kv.refcount[shared[0]] == 3
    d0, c0 = pe.kv.cow_dispatches, pe.kv.cow_copies
    pe.step()                             # both sharers append -> 2 COWs
    assert pe.kv.cow_copies - c0 == 2, "tick should privatize two pages"
    assert pe.kv.cow_dispatches - d0 == 1, \
        "N privatizations in one tick must be ONE copy dispatch"
    res = pe.run()                        # outputs stay oracle-identical
    want = oracle.generate_batch([prompt], max_new_tokens=4)[0]
    for rid in rids:
        assert res[rid] == want


def test_identity_helper_rejects_any_divergence(harness):
    """The exact-identity comparison (which RETIRED the bf16 near-tie
    workaround — sampled positions now unembed at f32) accepts only
    token-for-token equality: any swap or length mismatch fails."""
    model, params, oracle = harness
    rng = np.random.RandomState(77)
    prompt = rng.randint(0, model.cfg.vocab_size, size=5).astype(np.int32)
    want = oracle.generate_batch([prompt], max_new_tokens=4)[0]
    _assert_tokens_identical(want, want)                       # passes
    forged = [(want[0] + 1) % model.cfg.vocab_size] + want[1:]
    with pytest.raises(AssertionError, match="diverged"):
        _assert_tokens_identical(forged, want)
    with pytest.raises(AssertionError, match="diverged"):
        _assert_tokens_identical(want[:-1], want)              # truncation


def test_cow_preserves_shared_rows(harness):
    """Copy-on-write never mutates rows another slot can still see: share
    a PARTIAL page between two slots, let both append into it on the next
    tick (COW must fire for whoever writes while the page is shared), and
    verify the shared token rows of the original physical page are
    bit-identical afterwards — the surviving owner may only have written
    rows past the shared prefix."""
    model, params, oracle = harness
    # prefill chunk pinned to 2 tokens so the donor's first tick leaves a
    # PARTIAL page for the sharer to reference
    sc = ServeConfig(max_batch=2, max_seq=32, max_new_tokens=4, page_size=4,
                     prefill_chunk=2, prefill_chunk_tokens=2)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(17)
    prompt = rng.randint(0, model.cfg.vocab_size, size=6).astype(np.int32)
    rid_a = pe.submit(prompt)             # donor
    pe.step()                             # donor at 2 tokens: page 0 PARTIAL
    rid_b = pe.submit(prompt)             # sharer: same 6-token prompt
    pe._admit()                           # shares the partial page
    n_shared = pe.shared_tokens
    assert 0 < n_shared < pe.kv.page      # partial-page share
    shared = [p for p in range(1, pe.kv.num_pages) if pe.kv.refcount[p] > 1]
    assert shared, "admission did not map a page into both tables"
    before = {p: np.asarray(pe.kv.k)[:, p, :, :n_shared].copy()
              for p in shared}
    pe.step()                             # both append into the shared page
    assert pe.kv.cow_copies > 0
    after = np.asarray(pe.kv.k)
    for p, rows in before.items():
        np.testing.assert_array_equal(
            rows, after[:, p, :, :n_shared],
            err_msg=f"write into shared page {p} reached shared rows")
    res = pe.run()                        # drain: outputs stay exact
    for rid in (rid_a, rid_b):
        assert res[rid] == oracle.generate_batch([prompt],
                                                 max_new_tokens=4)[0]


def test_sharer_survives_donor_eviction(harness):
    """Evicting the donor must not free pages the sharer still references:
    the donor finishes first, its exclusive pages return to the free list,
    the shared ones stay live until the sharer finishes."""
    model, params, oracle = harness
    sc = ServeConfig(max_batch=2, max_seq=32, max_new_tokens=2, page_size=4,
                     prefill_chunk=4)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(23)
    prompt = rng.randint(0, model.cfg.vocab_size, size=6).astype(np.int32)
    rid_a = pe.submit(prompt, 2)          # donor: short budget
    pe.step()                             # donor live at 4 prompt tokens
    rid_b = pe.submit(prompt, 5)          # sharer: outlives the donor
    res = pe.run()
    pe.kv.check()
    assert pe.shared_tokens > 0
    assert res[rid_a] == oracle.generate_batch([prompt],
                                               max_new_tokens=2)[0]
    assert res[rid_b] == oracle.generate_batch([prompt],
                                               max_new_tokens=5)[0]


def test_chunk_reservation_capped_at_remaining_work(harness):
    """REGRESSION: the scheduler must reserve pages for the slot's
    REMAINING work, not the whole prefill_chunk — a fitting request
    (1 page of real work) with chunk 8 on a 1-page pool must complete,
    not raise pool-exhausted.  The chunk overshoot lands on the null page
    and is discarded."""
    model, params, oracle = harness
    sc = ServeConfig(max_batch=1, max_seq=16, max_new_tokens=1, page_size=4,
                     num_pages=2, prefill_chunk=8)   # 1 allocatable page
    pe = PagedEngine(model, params, sc)
    prompt = np.arange(3, dtype=np.int32)
    rid = pe.submit(prompt)
    res = pe.run()
    assert res[rid] == oracle.generate_batch([prompt],
                                             max_new_tokens=1)[0]


def test_inadmissible_request_rejected_at_submit(harness):
    """REGRESSION: a request no eviction can ever serve (prompt + budget
    exceed the whole pool) used to spin until the deep-tick pool-exhausted
    raise; now it is typed-REJECTED at submit() and the engine stays
    usable."""
    model, params, _ = harness
    sc = ServeConfig(max_batch=1, max_seq=8, max_new_tokens=5, page_size=4,
                     num_pages=2, prefill_chunk=4)   # 1 allocatable page
    pe = PagedEngine(model, params, sc)
    rid = pe.submit(np.arange(3, dtype=np.int32))    # 3 + 5 > 4 tokens
    assert pe.status[rid] is RequestStatus.REJECTED
    assert "pool" in pe.reject_reason[rid]
    assert pe.results[rid] == []
    assert not pe.busy                               # nothing queued/stalled
    pe.run()                                         # no-op, no raise


def test_oversize_request_rejected_at_submit(harness):
    """A prompt+budget wider than the slot's block table used to raise
    ``max_blocks`` from deep inside a tick; now submit() rejects it."""
    model, params, _ = harness
    sc = ServeConfig(max_batch=1, max_seq=8, max_new_tokens=12, page_size=4)
    pe = PagedEngine(model, params, sc)          # max_blocks = 2 (8 tokens)
    rid = pe.submit(np.arange(5, dtype=np.int32))    # 5 + 12 > 8
    assert pe.status[rid] is RequestStatus.REJECTED
    assert "max_blocks" in pe.reject_reason[rid]
    assert not pe.busy


def test_pool_exhaustion_raises_only_without_preemption(harness):
    """The legacy pool-exhausted RuntimeError survives ONLY behind
    ``preempt=False``: two individually-admissible requests that jointly
    wedge a 2-page pool raise on the baseline config and complete via
    preempt-and-recompute on the default config (that regression lives in
    tests/test_overload_props.py)."""
    model, params, _ = harness
    sc = ServeConfig(max_batch=2, max_seq=8, max_new_tokens=5, page_size=4,
                     num_pages=3, prefill_chunk=2, preempt=False)
    pe = PagedEngine(model, params, sc)
    pe.submit(np.arange(3, dtype=np.int32), 5)    # 8 tokens = 2 blocks each:
    pe.submit(np.arange(3, 6, dtype=np.int32), 5)  # admissible alone, wedged
    with pytest.raises(RuntimeError, match="page pool exhausted"):
        pe.run()


def test_paged_rejects_empty_prompt(harness):
    model, params, _ = harness
    pe = PagedEngine(model, params, ServeConfig(max_batch=1, max_seq=16))
    with pytest.raises(ValueError):
        pe.submit(np.array([], np.int32))


def test_paged_rejects_ssm():
    cfg = get("falcon-mamba-7b").reduced()
    model = get_model(cfg)
    with pytest.raises(ValueError):
        PagedEngine(model, None, ServeConfig(max_batch=2, max_seq=32))


def test_scheduler_rejects_unknown_fairness(harness):
    model, params, _ = harness
    with pytest.raises(ValueError, match="fairness"):
        PagedEngine(model, params,
                    ServeConfig(max_batch=1, max_seq=16, fairness="lifo"))


def test_defrag_compacts_to_prefix(harness):
    """After defrag the kept pages occupy the contiguous pool prefix —
    [null | live | retained-only] — and the free list is exactly the tail
    (shared pages counted once).  Requests that finished during the churn
    leave page-aligned prefixes in the RETAINED pool; defrag renumbers
    those entries alongside the live mappings."""
    model, params, _ = harness
    sc = ServeConfig(max_batch=3, max_seq=32, max_new_tokens=5, page_size=2,
                     prefill_chunk=2)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(13)
    for _ in range(5):
        pe.submit(rng.randint(0, model.cfg.vocab_size,
                              size=4).astype(np.int32))
    for _ in range(4):                           # churn: some finish, some join
        if pe.busy:
            pe.step()
    pe.defrag()
    pe.kv.check()
    live = pe.kv.live_pages
    distinct = sorted({p for o in pe.kv.owned for p in o})
    assert distinct == list(range(1, live + 1))
    kept = live + len(pe.kv._retained_only())
    assert sorted(pe.kv.free) == list(range(kept + 1, pe.kv.num_pages))
    res = pe.run()                               # still drains correctly
    assert len(res) == 5


# ---------------------------------------------------------------------------
# pending-COW rollback / cancellation (the mid-plan dry-pool path)
# ---------------------------------------------------------------------------

def test_cow_rollback_restores_sharing(harness):
    """REGRESSION (mid-plan dry pool): a COW reservation undone by
    ``cow_rollback`` must restore the shared mapping exactly — source
    refcount bumped back, table/owned rewired, the reserved destination
    page returned to the free list — leaving no trace in the partition
    invariant."""
    model, _, _ = harness
    kv = PagedKVCache(model, 2, 32, page_size=4, num_pages=6)
    kv.ensure(0, 8)                       # donor: 2 pages
    kv.length[0] = 8
    kv.share(1, 0, 8)                     # both pages shared
    free0 = sorted(kv.free)
    assert kv.cow_reserve(1, 0) and kv.cow_reserve(1, 1)
    assert len(kv._pending_cow) == 2
    kv.check(allow_pending=True)          # mid-plan state is legal
    cow0 = kv.cow_copies
    # roll back only the SECOND reservation (a shrunken grant)
    assert kv.cow_rollback(1, from_blk=1) == 1
    assert len(kv._pending_cow) == 1
    assert kv.owned[1][1] == kv.owned[0][1]      # sharing restored
    assert kv.refcount[kv.owned[0][1]] == 2
    kv.check(allow_pending=True)
    # roll back the rest: the pool is exactly as before the reservations
    assert kv.cow_rollback(1) == 1
    assert not kv._pending_cow
    assert kv.owned[1] == kv.owned[0]
    assert sorted(kv.free) == free0
    assert kv.cow_copies == cow0 - 2      # counters unwound too
    kv.check()


def test_free_slot_cancels_pending_cow(harness):
    """REGRESSION: freeing a slot with a PENDING COW reservation must
    cancel the reservation, not leave a queued device copy into a page
    that just returned to the free list (whoever allocates it next would
    be silently corrupted by the late flush)."""
    model, _, _ = harness
    kv = PagedKVCache(model, 2, 32, page_size=4, num_pages=6)
    kv.ensure(0, 4)
    kv.length[0] = 4
    kv.share(1, 0, 4)
    assert kv.cow_reserve(1, 0)           # pending copy into a fresh page
    dst = kv.owned[1][0]
    kv.free_slot(1)                       # evict the sharer mid-plan
    assert not kv._pending_cow            # the copy was cancelled...
    assert dst in kv.free                 # ...and its page is free again
    kv.check()
    assert kv.cow_flush() == 0            # nothing queued for the device


def test_grant_dry_pool_leaves_no_stray_reservation(harness):
    """Scheduler-level pin for the mid-plan dry-pool path: a grant clipped
    (or refused) by pool pressure must leave the pending-COW queue holding
    ONLY reservations the granted appends actually reach — a zero grant
    holds zero pages hostage, and a clipped multi-block grant keeps
    exactly the reservations below the clip."""
    from repro.serve.scheduler import TickScheduler
    model, _, _ = harness
    sched = TickScheduler()
    # appends into the shared trailing block with ONE free page: the COW
    # takes the spare, the grant lands inside the privatized page
    kv = PagedKVCache(model, 2, 32, page_size=4, num_pages=4)
    kv.ensure(0, 8)
    kv.length[0] = 8
    kv.share(1, 0, 8)
    kv.length[1] = 6
    granted, cows = sched._grant(kv, 1, 6, 2)
    assert granted == 2 and cows == 1
    assert len(kv._pending_cow) == 1      # exactly the reachable block
    kv.check(allow_pending=True)
    kv.cow_flush()
    kv.check()
    # a grant that CANNOT advance (block 2 needed, pool dry) must not
    # leave any reservation behind
    granted, cows = sched._grant(kv, 1, 8, 2)
    assert granted == 0 and cows == 0
    assert not kv._pending_cow
    kv.check()


def test_fuzz_pending_cow_never_targets_free_page(harness):
    """Fuzz pin for the rollback/cancellation machinery: random share /
    reserve / rollback / free-slot churn on a bare pool, asserting after
    every operation that pending copies only ever reference live pages
    (``check(allow_pending=True)``) and that a full rollback + free drains
    the pool leak-free."""
    model, _, _ = harness
    rng = np.random.RandomState(11)
    for trial in range(20):
        kv = PagedKVCache(model, 3, 32, page_size=4, num_pages=8)
        kv.ensure(0, rng.randint(1, 3) * 4)
        kv.length[0] = 4 * len(kv.owned[0])
        for _ in range(rng.randint(4, 12)):
            op = rng.randint(4)
            if op == 0 and not kv.owned[1]:
                n = int(kv.length[0])
                if n:
                    kv.share(1, 0, rng.randint(1, n + 1))
            elif op == 1 and kv.owned[1]:
                blk = rng.randint(len(kv.owned[1]))
                kv.cow_reserve(1, blk)
            elif op == 2 and kv.owned[1]:
                kv.cow_rollback(1, rng.randint(0, len(kv.owned[1]) + 1))
            elif op == 3 and kv.owned[1]:
                kv.free_slot(1)
            kv.check(allow_pending=True)
        kv.cow_flush()
        for i in range(3):
            if kv.owned[i]:
                kv.free_slot(i)
        kv.check()
        assert kv.live_pages == 0, f"trial {trial} leaked"
        assert len(kv.free) == kv.num_pages - 1

# ---------------------------------------------------------------------------
# cross-lifetime retained prefix pool (serve/cache.py RetainedPrefix)
# ---------------------------------------------------------------------------

def test_retained_reshare_bit_identical_after_donor_death(harness):
    """The tentpole property: a follower submitted AFTER its donor fully
    drained adopts the donor's frozen pages BY REFERENCE — same physical
    page ids, bit-identical K/V rows — and its output is token-identical
    to a cold oracle run (request-relative rope makes the frozen rows
    exact for any adopter)."""
    model, params, oracle = harness
    sc = ServeConfig(max_batch=2, max_seq=48, max_new_tokens=4, page_size=4,
                     prefill_chunk=2)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(41)
    prompt = rng.randint(0, model.cfg.vocab_size, size=11).astype(np.int32)
    pe.submit(prompt)
    pe.run()                               # donor finishes and is FREED
    assert not pe.busy and pe.kv.live_pages == 0
    assert pe.kv.retained, "finished donor left nothing in the retained pool"
    # the donor retains its FULL history prefix (prompt + emitted); the
    # follower's prompt only reaches the prompt's page-aligned part
    entry = pe.kv.retained[-1]
    ret_pages = list(entry.pages)
    rows_before = np.asarray(pe.kv.k)[:, ret_pages].copy()
    rid = pe.submit(prompt)                # donor is DEAD; only digests match
    pe._admit()
    assert pe.kv.retained_hits == 1
    n_hit = (len(prompt) // 4) * 4
    assert pe.kv.retained_hit_tokens == n_hit
    # adoption is by reference: the follower's table maps the SAME pages
    slot = next(i for i, s in enumerate(pe.slots) if s.active)
    assert pe.kv.owned[slot][:n_hit // 4] == ret_pages[:n_hit // 4]
    np.testing.assert_array_equal(
        rows_before, np.asarray(pe.kv.k)[:, ret_pages],
        err_msg="adoption mutated frozen retained rows")
    res = pe.run()
    want = oracle.generate_batch([prompt], max_new_tokens=4)[0]
    _assert_tokens_identical(res[rid], want,
                             label="retained re-share vs oracle")
    _assert_drained_clean(pe)


def test_reclaim_never_touches_adopted_pages(harness):
    """Reclamation under pressure must skip entries whose pages a live
    slot just re-shared (adoption bumps refcount, so the entry frees
    nothing) and drop only genuinely idle entries."""
    model, _, _ = harness
    kv = PagedKVCache(model, 3, 32, page_size=4, num_pages=10, retain=True)
    toks_a = list(range(8))
    kv.ensure(0, 8); kv.length[0] = 8
    kv.free_slot(0, retain_tokens=toks_a)          # entry A: 2 pages
    toks_b = list(range(100, 108))
    kv.ensure(0, 8); kv.length[0] = 8
    kv.free_slot(0, retain_tokens=toks_b)          # entry B: 2 pages
    kv.check()
    assert len(kv.retained) == 2
    entry_a, n = kv.match_retained(np.asarray(toks_a, np.int32), 32)
    assert entry_a is not None and n == 8
    kv.adopt_retained(1, entry_a, 8)               # A's pages live again
    kv.check()
    freed = kv.reclaim_retained(100)               # demand the whole pool
    assert freed == 2                              # only B's pages freed
    assert entry_a in kv.retained                  # A survived: adopted
    assert (kv.refcount[entry_a.pages] == 1).all()
    kv.check()
    # once the adopter dies, A's pages are retained-only again and A is
    # reclaimable
    kv.free_slot(1)
    assert kv.reclaim_retained(100) == 2
    assert not kv.retained
    kv.check()
    assert len(kv.free) == kv.num_pages - 1


def test_seize_drains_warm_retained_pool(harness):
    """A fault-plan squeeze deeper than the free list must seize straight
    through the retained pool without corrupting the digest map: entries
    are dropped cleanly (later lookups miss), seized pages release back to
    the free list whole."""
    model, _, _ = harness
    kv = PagedKVCache(model, 2, 32, page_size=4, num_pages=8, retain=True)
    toks = list(range(12))
    kv.ensure(0, 12); kv.length[0] = 12
    kv.free_slot(0, retain_tokens=toks)            # 3 retained pages
    kv.check()
    n_free = len(kv.free)
    seized = kv.seize_pages(n_free + 2)            # MUST drain retention
    assert len(seized) == n_free + 2
    assert kv.retained_reclaimed_pages >= 2
    kv.check()                                     # digest map consistent
    entry, n = kv.match_retained(np.asarray(toks, np.int32), 32)
    assert entry is None and n == 0                # dropped entries miss
    kv.release_pages(seized)
    kv.check()
    assert len(kv.free) == kv.num_pages - 1


def test_retain_policies_order_reclamation(harness):
    """"lru" evicts the oldest-touched entry first; "popularity" evicts
    the fewest-adoptions entry first even when it is the youngest."""
    model, _, _ = harness
    for policy, survivor in (("lru", "young"), ("popularity", "popular")):
        kv = PagedKVCache(model, 2, 64, page_size=4, num_pages=12,
                          retain=True, retain_policy=policy)
        toks_old = list(range(4))
        toks_young = list(range(50, 54))
        kv.ensure(0, 4); kv.length[0] = 4
        kv.free_slot(0, retain_tokens=toks_old)
        if policy == "popularity":
            # make the OLD entry popular: adopt + release it once
            e, n = kv.match_retained(np.asarray(toks_old, np.int32), 64)
            kv.adopt_retained(1, e, 4)
            kv.free_slot(1)
        kv.ensure(0, 4); kv.length[0] = 4
        kv.free_slot(0, retain_tokens=toks_young)
        kv.check()
        assert kv.reclaim_retained(1) == 1         # drop exactly one entry
        kept = kv.retained[0].tokens
        if survivor == "young":
            assert kept == toks_young, "lru must drop the oldest entry"
        else:
            assert kept == toks_old, \
                "popularity must keep the adopted (popular) entry"
        kv.check()


def test_retain_cap_bounds_idle_pages(harness):
    """``retain_cap`` bounds retained-ONLY pages: retaining past the cap
    evicts older entries instead of growing the idle set."""
    model, _, _ = harness
    kv = PagedKVCache(model, 2, 64, page_size=4, num_pages=16,
                      retain=True, retain_cap=2)
    for base in (0, 100, 200):
        kv.ensure(0, 8); kv.length[0] = 8
        kv.free_slot(0, retain_tokens=list(range(base, base + 8)))
        kv.check()
        assert len(kv._retained_only()) <= 2
    # the newest entry is the survivor
    assert kv.retained and kv.retained[-1].tokens == list(range(200, 208))


def test_retained_survives_defrag(harness):
    """Defrag renumbers retained entries' pages alongside live mappings:
    the digest lookup still hits afterwards and the adopted content is
    bit-identical to the pre-defrag rows."""
    model, params, oracle = harness
    sc = ServeConfig(max_batch=2, max_seq=48, max_new_tokens=4, page_size=4,
                     prefill_chunk=2)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(43)
    prompt = rng.randint(0, model.cfg.vocab_size, size=9).astype(np.int32)
    pe.submit(prompt)
    pe.run()
    assert pe.kv.retained
    entry = pe.kv.retained[-1]
    rows_before = np.asarray(pe.kv.k)[:, entry.pages].copy()
    pe.defrag()
    pe.kv.check()
    np.testing.assert_array_equal(
        rows_before, np.asarray(pe.kv.k)[:, entry.pages],
        err_msg="defrag lost retained page content")
    rid = pe.submit(prompt)
    pe._admit()
    assert pe.kv.retained_hits == 1, "digest lookup broken after defrag"
    res = pe.run()
    want = oracle.generate_batch([prompt], max_new_tokens=4)[0]
    _assert_tokens_identical(res[rid], want,
                             label="post-defrag retained re-share")


def test_retention_off_restores_legacy_drain(harness):
    """``retain_prefixes=False`` keeps the pre-retention contract: a
    finished slot's pages go straight back to the free list."""
    model, params, _ = harness
    sc = ServeConfig(max_batch=2, max_seq=48, max_new_tokens=4, page_size=4,
                     prefill_chunk=2, retain_prefixes=False)
    pe = PagedEngine(model, params, sc)
    rng = np.random.RandomState(44)
    pe.submit(rng.randint(0, model.cfg.vocab_size, size=9).astype(np.int32))
    pe.run()
    assert not pe.kv.retained
    assert len(pe.kv.free) == pe.kv.num_pages - 1
    pe.kv.check()
