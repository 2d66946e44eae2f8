"""Serving engines: fused device-resident decode and the paged
continuous-batching production path.

The decode hot path is ONE compiled HLO module (``Model.decode_many`` /
``Model.decode_many_paged``: a ``lax.scan`` over decode steps with on-device
sampling), jitted with ``donate_argnums`` so the KV cache and sampler key
are updated in place instead of re-materialized every token.  That makes the
decode cell a single program `core.hlo_counters` can census and place on the
instruction roofline — and removes the per-token host round-trip the legacy
loop pays (kept as ``fused=False`` for the measured comparison in
``benchmark_decode`` / benchmarks/serve_bench.py).

Two engines, one compiled-cell discipline (no recompiles, ever):

  * ``ServingEngine`` — whole-batch generation: prefill once, decode all
    sequences in lockstep.  Also the token-identity ORACLE the paged
    property harness fuzzes against.
  * ``PagedEngine`` — THE production path: non-lockstep continuous
    batching over a ``PagedKVCache`` (serve/cache.py: refcounted page pool
    + per-slot block tables + per-slot lengths) driven by a
    ``TickScheduler`` (serve/scheduler.py: prefill-lane + decode grants,
    partial grants, fairness, per-tick budget).  Every slot decodes at
    its own position on its own pages (rope is request-relative by
    construction), prompts stream through the RAGGED MULTI-TOKEN PREFILL
    LANE (``Model.prefill_many_paged``: one compiled kernel step appends
    and causally attends a page-aligned chunk of up to T prompt tokens
    per slot, so admitting a P-token prompt costs ceil(P / T) dispatches
    instead of P decode steps), and a request admitted with a prompt
    prefix already resident in a live slot's pages SHARES those pages
    (refcount bump, no recompute; the donor is found through a
    rolling-hash prefix index, not a linear LCP scan) — appends into a
    shared page copy-on-write privatize it first, all of a tick's copies
    batched into ONE device dispatch.  A request can outlive ``max_seq``
    total traffic (pages recycle), mid-flight joins reuse the compiled
    cells, and the decode kernel's transaction count scales with live
    tokens, not pool size — the engine's regression suite pins all three
    guarantees, migrated from the retired dense lockstep engine (its
    row-wraparound machinery is gone; per-slot pages make it
    unnecessary).

    The TICK is host-side as thin as the kernel: at most two compiled
    cells per tick (the ragged prefill lane for prompt chunks, the
    forced-token-free decode twin for generation — each compiled once; a
    legacy forced-token decode cell remains only for the measured
    ``prefill_lane=False`` baseline), a device-resident block table /
    length state patched only at DIRTY rows (a steady-state decode tick
    uploads zero table bytes and runs one dispatch), per-slot grants
    uploaded as B ints, prompt chunks uploaded as ONE ragged (B, T) token
    block (the per-step (chunk, B) forced-token/mask uploads are retired
    for prompt traffic — ``forced_upload_bytes`` stays 0 and verify.sh
    gates it), a per-tick host-cost trace (host ms) and cumulative
    counters (dispatches, upload bytes: ``PagedEngine.counters``) feeding
    BENCH_serve.json's tick_overhead section.

    The tick is instrumented where the work happens: ``step`` opens JAX
    profiler spans (``engine.step`` and one per phase: ``engine.admit``,
    ``engine.plan``, ``engine.cow_flush``, ``engine.sync_dirty``,
    ``engine.dispatch_prefill``, ``engine.dispatch_decode``,
    ``engine.readback``, ``engine.bookkeep``, ``engine.preempt``,
    ``engine.snapshot``) on the host timeline the device planes share,
    and builds their attributes (request ids, the tick's dispatches and
    upload bytes) only while a trace is being recorded.  Each request is
    stamped when it is first admitted and when the tick that sampled its
    first output ends (``request_times``).

CPU-runnable end-to-end (examples/serve_demo.py); the same step functions are
what launch/serve.py lowers for the production mesh.
"""
from __future__ import annotations

import dataclasses
import enum
import time
from typing import Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model, sample_token
from repro.serve.cache import COUNTERS as CACHE_COUNTERS
from repro.serve.cache import PagedKVCache, digest_step
from repro.serve.faults import EngineKilled
from repro.serve.scheduler import TickScheduler


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 4
    max_seq: int = 128
    max_new_tokens: int = 16
    temperature: float = 0.0          # 0 = greedy
    seed: int = 0
    eos_id: int = -1                  # < 0: no stop condition
    pad_id: int = 0                   # emitted by finished slots
    fused: bool = True                # decode_many scan vs per-token loop
    # --- paged engine ------------------------------------------------------
    page_size: int = 16               # tokens per KV page
    max_blocks: int = 0               # block-table width (0: ceil(max_seq/page))
    num_pages: int = 0                # pool size incl. null page (0: fit all slots)
    prefill_chunk: int = 4            # fused decode steps per PagedEngine tick
    # --- ragged multi-token prefill lane ------------------------------------
    prefill_lane: bool = True         # prompts go through the multi-token
                                      # prefill kernel (one compiled step
                                      # per chunk); False = legacy
                                      # prefill-by-decode (one step/token)
    prefill_chunk_tokens: int = 0     # prompt tokens per prefill-lane chunk
                                      # (0 = ArchConfig.prefill_chunk_tokens,
                                      # then auto: 2 x page_size; keep it a
                                      # multiple of page_size so chunk
                                      # grants stay page-aligned)
    # --- prefix sharing / scheduling ---------------------------------------
    prefix_sharing: bool = True       # share resident prompt prefixes on admit
    share_min_tokens: int = 1         # smallest common prefix worth sharing
    # --- cross-lifetime retention (needs prefix_sharing) ---------------------
    retain_prefixes: bool = True      # keep finished/evicted slots' page-
                                      # aligned prefix pages for digest-keyed
                                      # re-sharing after the donor is gone
    retain_pool_pages: int = 0        # max retained-ONLY pages held idle
                                      # (0: pool-bounded — pressure reclaims)
    retain_policy: str = "lru"        # reclamation order: "lru" |
                                      # "popularity" (fewest adoptions first)
    fairness: str = "least-served"    # page-grant order ("slot-order": legacy)
    tick_budget: int = 0              # max fresh tokens per tick (0: uncapped)
    trace_pool: bool = True           # record the per-tick util trace
                                      # (benchmarks measuring the thin
                                      # tick disable it)
    trace_ticks: bool = True          # record the per-tick host-ms trace
                                      # (a cheap scalar, but unbounded — a
                                      # long-lived server disables it;
                                      # counters stay on)
    # --- overload safety (preemption / admission / fault tolerance) ---------
    preempt: bool = True              # preempt-and-recompute when no slot
                                      # can get step capacity; False keeps
                                      # the legacy pool-exhausted raise
                                      # (measured/regression baseline only)
    preempt_policy: str = "fewest-tokens"  # victim selection (scheduler:
                                      # "fewest-tokens" | "most-pages")
    max_queue: int = 0                # submit() queue-depth bound (0:
                                      # unbounded); overflow -> REJECTED
    deadline_ticks: int = 0           # default per-request tick budget
                                      # (0: no deadline); overrun ->
                                      # DEADLINE_EXCEEDED with partial output
    quarantine_ticks: int = 2         # ticks a slot sits out after emitting
                                      # a poisoned (out-of-vocab) token
    wedge_ticks: int = 10_000         # consecutive idle-but-busy ticks
                                      # before the engine declares itself
                                      # wedged and raises (bookkeeping-bug
                                      # tripwire; fuzz harnesses shrink it
                                      # so a wedge fails in seconds)
    # --- crash consistency (serve/snapshot.py) -------------------------------
    snapshot_every_ticks: int = 0     # write a full-state snapshot every
                                      # N ticks (0 = off); restore via
                                      # snapshot.restore_engine — the
                                      # continuation is bit-identical to
                                      # the uninterrupted run
    snapshot_dir: str = ""            # where snap-<tick>.bin files land
                                      # (required when snapshotting)
    snapshot_keep: int = 2            # newest snapshots retained on disk
                                      # (>= 2 keeps a fallback if the
                                      # newest file is damaged; < 1
                                      # keeps everything)
    # --- speculative decoding (draft-and-verify) -----------------------------
    spec_k: int = 0                   # draft proposals per decode tick
                                      # (0 = off).  > 0 needs a draft
                                      # model/params handed to PagedEngine,
                                      # greedy serving (temperature == 0 —
                                      # a proposal is accepted iff it
                                      # equals the target argmax) and the
                                      # prefill lane (the target verifies
                                      # the ragged [feed, p_1..p_k] block
                                      # in ONE prefill-lane dispatch)


class RequestStatus(enum.Enum):
    """Request lifecycle.  Every submitted rid ends in a TERMINAL status —
    overload shows up as typed outcomes (REJECTED / DEADLINE_EXCEEDED /
    PREEMPTED_RESUMED), never as a hang or an engine raise."""
    QUEUED = "queued"                  # waiting for a slot (incl. requeued)
    RUNNING = "running"                # occupies a slot
    FINISHED = "finished"              # completed, never preempted
    PREEMPTED_RESUMED = "preempted_resumed"  # completed after >= 1 preemption
    REJECTED = "rejected"              # failed admission at submit()
    CANCELLED = "cancelled"            # explicit cancel(); partial output kept
    DEADLINE_EXCEEDED = "deadline_exceeded"  # tick budget ran out


TERMINAL_STATUSES = frozenset({
    RequestStatus.FINISHED, RequestStatus.PREEMPTED_RESUMED,
    RequestStatus.REJECTED, RequestStatus.CANCELLED,
    RequestStatus.DEADLINE_EXCEEDED})

# PagedEngine's cumulative counters (``PagedEngine.counters`` snapshots
# them with the page cache's; serve/snapshot.py carries them over a restore)
COUNTERS = (
    "steps_run", "ticks", "no_progress_ticks", "snapshots_written",
    "preemptions", "recompute_tokens", "rejected", "cancelled",
    "deadline_exceeded", "quarantines", "dropped_grants", "tokens_out",
    "tokens_appended", "shared_tokens", "joins", "stalls", "dispatches",
    "spec_proposed", "spec_accepted", "spec_trunc_tokens",
    "draft_dispatches", "verify_dispatches", "table_upload_bytes",
    "forced_upload_bytes", "prefill_upload_bytes", "upload_bytes",
    "decode_pages_streamed", "decode_pages_table")

# host spans on the JAX profiler's timeline; with no trace recording, a
# span costs one native call and its attributes are never built
_span = jax.profiler.TraceAnnotation
_recording = jax.profiler.TraceAnnotation.is_enabled


def _ids(rids) -> str:
    """Request ids as one span attribute (a TraceMe value holds no comma)."""
    return " ".join(str(r) for r in rids)


@dataclasses.dataclass
class Request:
    """One submitted request.  ``prompt`` is the ORIGINAL prompt and never
    changes; ``emitted`` accumulates output tokens across preemptions (on
    re-admission they are replayed as forced prompt through the prefill
    lane, so the resumed request is token-identical to an uninterrupted
    run); ``max_new_tokens`` is the TOTAL output budget across resumes."""
    rid: int
    prompt: np.ndarray                # (S,) int32
    max_new_tokens: int
    deadline_tick: int = -1           # absolute engine tick; -1 = none
    emitted: List[int] = dataclasses.field(default_factory=list)
    preempts: int = 0                 # times this request lost its slot
    # host clock (time.perf_counter), kept across preemption and requeue
    admitted_t: Optional[float] = None     # its first admission
    first_token_t: Optional[float] = None  # end of its first output's tick


class ServingEngine:
    """Greedy/temperature sampling over a shared batched KV cache."""

    def __init__(self, model: Model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        # donate the cache through BOTH decode paths: XLA aliases the input
        # buffer to the output, so each step updates the cache in place
        # instead of allocating a full copy per token
        self._decode = jax.jit(model.decode_step, donate_argnums=(2,))
        self._prefill = jax.jit(model.prefill)
        self._decode_many = jax.jit(
            model.decode_many,
            static_argnames=("num_steps", "temperature", "eos_id", "pad_id"),
            donate_argnums=(2, 3))          # cache + sampler key
        self._key = jax.random.key(cfg.seed)

    # -- sampling ---------------------------------------------------------------

    def _sample(self, logits: jax.Array, key: jax.Array):
        """One sampling step (models.model.sample_token, the shared helper,
        so legacy and fused paths are token-identical for a given seed)."""
        return sample_token(logits, key, self.cfg.temperature)

    # -- prefill ---------------------------------------------------------------

    def _prefill_cache(self, prompts: List[np.ndarray], mnt: int):
        """Left-pads prompts to a common length, prefills once, scatters the
        prefill KV into a fresh (donatable) decode cache."""
        B = len(prompts)
        S = max(len(p) for p in prompts)
        toks = np.zeros((B, S), np.int32)
        for i, p in enumerate(prompts):            # right-align
            toks[i, S - len(p):] = p
        batch = {"tokens": jnp.asarray(toks)}
        last_logits, cache_parts = self._prefill(self.params, batch)

        cache = self.model.init_cache(B, S + mnt)
        for k in cache_parts or {}:
            src = cache_parts[k]
            dst = cache[k]
            pad = [(0, d - s) for d, s in zip(dst.shape, src.shape)]
            cache[k] = jnp.pad(src.astype(dst.dtype), pad)
        cache["pos"] = jnp.asarray(S, jnp.int32)
        return last_logits, cache

    # -- generation ---------------------------------------------------------------

    def generate_batch(self, prompts: List[np.ndarray],
                       max_new_tokens: Optional[int] = None,
                       fused: Optional[bool] = None) -> List[List[int]]:
        """Prefill once, then decode all sequences in lockstep (the
        decode_32k cell's shape).  ``fused=True`` (default) runs the whole
        token loop on device; ``fused=False`` is the legacy per-token host
        loop (same tokens, one dispatch + sync per step)."""
        cfg = self.cfg
        mnt = max_new_tokens or cfg.max_new_tokens
        fused = cfg.fused if fused is None else fused
        B = len(prompts)

        last_logits, cache = self._prefill_cache(prompts, mnt)
        key = self._key
        first, key = self._sample(last_logits, key)

        if fused:
            toks, cache, key, _done = self._decode_many(
                self.params, first[:, None], cache, key,
                num_steps=mnt - 1, temperature=cfg.temperature,
                eos_id=cfg.eos_id, pad_id=cfg.pad_id)
            all_toks = np.concatenate(
                [np.asarray(first)[None], np.asarray(toks)], axis=0)
        else:
            tok = first[:, None]
            rows = [np.asarray(first)]
            for _ in range(mnt - 1):
                logits, cache = self._decode(self.params, tok, cache)
                t, key = self._sample(logits, key)
                tok = t[:, None]
                rows.append(np.asarray(t))         # per-token host sync
            all_toks = np.stack(rows, axis=0)
        self._key = key

        outs: List[List[int]] = []
        for i in range(B):
            col = [int(t) for t in all_toks[:, i]]
            if cfg.eos_id >= 0 and cfg.eos_id in col:
                col = col[: col.index(cfg.eos_id) + 1]
            outs.append(col)
        return outs

    # -- benchmarking ---------------------------------------------------------------

    def benchmark_decode(self, batch: int, seq: int, steps: int = 8
                         ) -> Dict[str, float]:
        """Wall-clock decode throughput on this host (CPU here; the TPU
        numbers come from the dry-run roofline): the fused device-resident
        loop vs the legacy per-step loop, both with donated caches."""
        assert seq // 2 + 2 * steps + 2 <= seq, \
            f"steps={steps} overruns the cache (seq={seq})"

        def fresh_cache():
            cache = self.model.init_cache(batch, seq)
            cache["pos"] = jnp.asarray(seq // 2, jnp.int32)
            return cache

        tok0 = jnp.zeros((batch, 1), jnp.int32)

        # legacy: one dispatch + argmax + host sync per token
        cache = fresh_cache()
        logits, cache = self._decode(self.params, tok0, cache)  # compile
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        jax.block_until_ready(tok)
        t0 = time.perf_counter()
        for _ in range(steps):
            logits, cache = self._decode(self.params, tok, cache)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            np.asarray(tok)                        # the per-token round-trip
        dt_loop = (time.perf_counter() - t0) / steps

        # fused: one dispatch for the whole token loop
        key = jax.random.key(self.cfg.seed)
        cache = fresh_cache()
        toks, cache, key, _ = self._decode_many(   # compile
            self.params, tok0, cache, key, num_steps=steps,
            temperature=0.0, eos_id=-1, pad_id=0)
        jax.block_until_ready(toks)
        t0 = time.perf_counter()
        toks, cache, key, _ = self._decode_many(
            self.params, tok0, cache, key, num_steps=steps,
            temperature=0.0, eos_id=-1, pad_id=0)
        jax.block_until_ready(toks)
        dt_fused = (time.perf_counter() - t0) / steps

        return {
            "s_per_step": dt_fused,
            "tokens_per_s": batch / dt_fused,
            "s_per_step_fused": dt_fused,
            "tokens_per_s_fused": batch / dt_fused,
            "s_per_step_loop": dt_loop,
            "tokens_per_s_loop": batch / dt_loop,
            "fused_speedup": dt_loop / dt_fused,
        }


# ---------------------------------------------------------------------------
# paged continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Slot:
    """One schedulable slot: ``forced`` holds the prompt tokens queued
    behind the feed token (consumed by the prefill lane in chunks, or
    forced into the decode stream one per step when the lane is off);
    ``prompt_left`` counts prompt tokens not yet appended (feed + forced
    while prefilling, 0 once the first output is sampled — the scheduler's
    lane selector); ``history`` mirrors the tokens whose K/V rows are
    resident in the slot's pages (the prefix-sharing donor index —
    ``len(history) == kv.length[i]`` always); ``served`` counts fresh
    tokens appended (the fairness key)."""
    rid: int = -1
    forced: List[int] = dataclasses.field(default_factory=list)
    out: List[int] = dataclasses.field(default_factory=list)
    history: List[int] = dataclasses.field(default_factory=list)
    budget: int = 0
    served: int = 0
    prompt_left: int = 0
    active: bool = False


def _lcp(a: List[int], b: List[int]) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def _patch_rows(table, length, rows, t_rows, l_rows):
    """Patch the device table/length mirrors at ``rows`` (donated, so the
    update is in place — the upload cost is the DIRTY rows, never the whole
    (B, max_blocks) table)."""
    return table.at[rows].set(t_rows), length.at[rows].set(l_rows)


class _PrefixIndex:
    """Rolling-hash index over every live slot's token-history PREFIXES.

    Admission donor lookup used to be an O(slots x prompt) LCP scan per
    request; this index makes it O(matched prefix): each live slot
    registers the rolling digest of history[:n] for every n (extended
    incrementally, a few entries per appended token), and a lookup walks
    the prompt's own rolling digest outward, stopping at the FIRST length
    with no registered match — a prompt sharing nothing with any live slot
    costs one probe, independent of its length.  Digest collisions are
    survivable: the engine verifies the winning (slot, n) against the real
    token history and falls back to the exact scan on a mismatch.

    The digest recurrence is shared with the RETAINED pool
    (serve/cache.py ``digest_step``/``prefix_digests``): a prefix hashes
    identically whether its donor is live or long dead."""

    def __init__(self):
        self._map: Dict[tuple, set] = {}      # (n, digest) -> slot ids
        self._keys: Dict[int, List[tuple]] = {}
        self._digest: Dict[int, int] = {}
        self._len: Dict[int, int] = {}

    def add(self, slot: int, tokens) -> None:
        """Extend slot's indexed history by ``tokens`` (incremental)."""
        h = self._digest.get(slot, 0)
        n = self._len.get(slot, 0)
        keys = self._keys.setdefault(slot, [])
        for t in tokens:
            h = digest_step(h, t)
            n += 1
            key = (n, h)
            self._map.setdefault(key, set()).add(slot)
            keys.append(key)
        self._digest[slot] = h
        self._len[slot] = n

    def drop(self, slot: int) -> None:
        for key in self._keys.pop(slot, ()):
            owners = self._map.get(key)
            if owners is not None:
                owners.discard(slot)
                if not owners:
                    del self._map[key]
        self._digest.pop(slot, None)
        self._len.pop(slot, None)

    def lookup(self, prompt: List[int], cap: int):
        """Longest n <= cap with a live slot whose indexed history starts
        with prompt[:n]; returns (slot, n) or (-1, 0).  Walks outward and
        stops at the first unmatched length (a slot matching n+1 tokens
        also matches n, so no longer match can exist past a miss)."""
        h, best, donor = 0, 0, -1
        for n in range(1, cap + 1):
            h = digest_step(h, prompt[n - 1])
            owners = self._map.get((n, h))
            if not owners:
                break
            best, donor = n, next(iter(owners))
        return donor, best

    def check(self, slots) -> None:
        """Index/engine consistency (fuzz-asserted every tick by the
        property harness): every indexed slot is LIVE, its registered
        length equals its real history, its digest chain recomputes from
        that history, and every (n, digest) key's owner set round-trips —
        the staleness a preempt->requeue->recompute cycle could introduce
        if drop/add ever ran twice or not at all."""
        for slot_id, n in self._len.items():
            s = slots[slot_id]
            assert s.active, \
                f"prefix index holds entries for inactive slot {slot_id}"
            assert n == len(s.history), \
                f"slot {slot_id}: indexed length {n} != history " \
                f"{len(s.history)}"
            h = 0
            keys = self._keys.get(slot_id, [])
            assert len(keys) == n, \
                f"slot {slot_id}: {len(keys)} keys for {n} indexed tokens"
            for j, t in enumerate(s.history):
                h = digest_step(h, t)
                assert keys[j] == (j + 1, h), \
                    f"slot {slot_id}: key {j} drifted from history"
                assert slot_id in self._map.get((j + 1, h), ()), \
                    f"slot {slot_id}: key {(j + 1, h)} unregistered"
            assert self._digest.get(slot_id, 0) == h, \
                f"slot {slot_id}: digest accumulator drifted"
        for key, owners in self._map.items():
            assert owners, f"empty owner set left behind for {key}"
            for s_id in owners:
                assert s_id in self._len and self._len[s_id] >= key[0], \
                    f"key {key} names slot {s_id} beyond its indexed length"


class PagedEngine:
    """Non-lockstep continuous batching over the paged KV cache.

    Every engine tick runs at most TWO fused cells planned by the
    ``TickScheduler``:

      * the RAGGED PREFILL LANE (``prefill_many_paged``) — slots with
        unfed prompt tokens advance by a page-aligned chunk of up to
        ``prefill_chunk_tokens`` of them in ONE compiled kernel step
        (append + causal attention over history and the in-flight chunk),
        so admission latency scales with ceil(prompt / T) dispatches, not
        with prompt length; the chunk's single sampled token seeds the
        request's first output when the prompt drains;
      * the DECODE cell (``decode_many_paged``) — generating slots run
        ``cfg.prefill_chunk`` compiled scan steps under a per-step active
        mask: slot ``i`` advances for its granted ``steps[i] <= chunk``
        steps and idles for the rest (null-page appends, frozen length) —
        a slot short on pages runs a PARTIAL chunk instead of sitting out
        the tick.

    Each slot advances at its OWN position (per-slot ``length``), so a
    request admitted mid-flight starts at position 0 of its own pages and
    rope is request-relative by construction: outputs are token-identical
    to a fresh single-request run (property-fuzzed, lane on AND off),
    total traffic can outlive ``max_seq`` (pages recycle through the free
    list), and the jitted cells never recompile (regression-tested via
    their compile-cache sizes).

    PREFIX SHARING: admission matches the new prompt against the token
    history of every live slot; the longest common prefix (capped so at
    least one prompt token is always fed — its logits seed the first
    output) is mapped into the new slot's block table by reference
    (``PagedKVCache.share``).  Shared pages are immutable — the scheduler
    copy-on-write privatizes a shared block before any append touches it —
    and eviction only returns a page once its refcount drains.

    With ``prefill_lane=False`` prompts ride the decode cell as forced
    tokens (prefill-by-decode, one sequential step per prompt token) —
    the measured baseline the lane is benchmarked and gated against.
    Page lifecycle: admission allocates from the free list (or references
    shared pages), finished slots' references are dropped on finish, a
    slot that cannot get capacity STALLS until eviction frees pages, and
    ``defrag()`` compacts the pool.

    OVERLOAD SAFETY: the engine survives any admissible load by
    construction.  ``submit()`` validates capacity and queue depth (typed
    ``REJECTED``, never a stall), every request carries an optional tick
    deadline and ends in a typed terminal ``RequestStatus``, and a tick
    where NO slot can get step capacity preempts victims (fewest tokens
    generated, then most pages held) instead of raising — the victim
    requeues with its emitted output as forced prompt, recomputes through
    the ragged prefill lane, and finishes token-identical to an
    uninterrupted run (``PREEMPTED_RESUMED``).  A seeded ``FaultPlan``
    (serve/faults.py) can inject pool pressure, forced evictions, dropped
    grants and poisoned logits; the always-on out-of-vocab output guard
    quarantines a poisoned slot and requeues its request.  The legacy
    pool-exhausted ``RuntimeError`` survives only behind
    ``preempt=False``.

    SPECULATIVE DECODING (``cfg.spec_k > 0``, greedy-only): a small DRAFT
    model with its own page pool proposes up to k tokens per granted slot
    per tick (one forced-token decode dispatch; a slot the draft has not
    caught up with replays its history through the draft's prefill lane
    first), and the TARGET verifies the whole ragged [feed, p_1..p_k]
    block in ONE prefill-lane dispatch.  A proposal is accepted iff it
    equals the target's greedy argmax at its position, so the emitted
    stream is BIT-IDENTICAL to plain greedy decode while a tick emits up
    to k+1 tokens per slot.  Rejected rows roll back by length truncation
    on both caches (pages stay owned; nothing past a slot's length is
    read or shared), preemption rebuilds the draft by catch-up, and
    deadlines stay tick-denominated.

    Decoder-only attention LMs only (a joining SSM slot would inherit the
    previous occupant's state; whisper needs per-request cross caches).
    """

    def __init__(self, model: Model, params, cfg: ServeConfig,
                 draft_model: Optional[Model] = None, draft_params=None):
        if model.cfg.is_encoder_decoder or model.cfg.mamba_version:
            raise ValueError("paged serving requires a decoder-only "
                             "attention LM (per-slot page tables)")
        self.model = model
        self.params = params
        self.cfg = cfg
        B = cfg.max_batch
        # --- speculative decoding: draft-and-verify ----------------------
        self._spec = cfg.spec_k > 0
        if self._spec:
            if draft_model is None:
                raise ValueError(
                    "spec_k > 0 needs a draft model: "
                    "PagedEngine(model, params, cfg, draft_model=, "
                    "draft_params=)")
            if cfg.temperature != 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only: a proposal is "
                    "accepted iff it equals the target argmax "
                    "(set temperature=0)")
            if not cfg.prefill_lane:
                raise ValueError(
                    "speculative decoding verifies through the ragged "
                    "prefill lane (set prefill_lane=True)")
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    "draft and target must share a tokenizer: vocab "
                    f"{draft_model.cfg.vocab_size} != "
                    f"{model.cfg.vocab_size}")
        self.draft_model = draft_model
        self.draft_params = draft_params
        # decode chunk per tick: a speculative tick verifies up to
        # spec_k proposals plus the feed token in one ragged dispatch
        self._chunk = (cfg.spec_k + 1) if self._spec \
            else max(1, cfg.prefill_chunk)
        self._many = jax.jit(model.decode_many_paged,
                             static_argnames=("num_steps", "temperature"),
                             donate_argnums=(2, 3))   # cache + key
        # the forced-token-free twin: pure-decode ticks (no prompt in
        # flight) skip building and uploading the (chunk, B) forced
        # arrays entirely — a second compiled cell, compiled once
        def decode_many_paged(params, tok, cache, key, steps, *, num_steps,
                              temperature):
            return model.decode_many_paged(
                params, tok, cache, key, steps, None, None,
                num_steps=num_steps, temperature=temperature)

        self._many_plain = jax.jit(
            decode_many_paged, static_argnames=("num_steps", "temperature"),
            donate_argnums=(2, 3))
        # the ragged multi-token PREFILL LANE: one compiled step appends
        # and attends a (B, T) chunk of prompt tokens — a prompt costs
        # ceil(prompt / T) dispatches instead of `prompt` decode steps,
        # and prompt traffic stops paying the (chunk, B) forced-token
        # uploads entirely.  T = 0 disables the lane (legacy
        # prefill-by-decode through the forced decode cell).
        self._chunk_tokens = 0
        if cfg.prefill_lane:
            self._chunk_tokens = (cfg.prefill_chunk_tokens
                                  or model.cfg.prefill_chunk_tokens
                                  or 2 * cfg.page_size)
        self._prefill_lane = jax.jit(model.prefill_many_paged,
                                     static_argnames=("temperature",),
                                     donate_argnums=(2, 3))  # cache + key
        # dirty-row patcher for the device table/length mirrors
        self._patch = jax.jit(_patch_rows, donate_argnums=(0, 1))
        # cross-lifetime retention rides the sharing machinery: without
        # prefix_sharing nothing could ever adopt a retained page
        self._retain = bool(cfg.prefix_sharing and cfg.retain_prefixes)
        self.kv = PagedKVCache(model, B, cfg.max_seq,
                               page_size=cfg.page_size,
                               max_blocks=cfg.max_blocks,
                               num_pages=cfg.num_pages,
                               retain=self._retain,
                               retain_cap=cfg.retain_pool_pages,
                               retain_policy=cfg.retain_policy)
        # DEVICE-RESIDENT tick state: the block table and lengths live on
        # device across ticks; the host patches only rows the cache marked
        # dirty (admission/COW/eviction/defrag) instead of re-uploading the
        # whole (B, max_blocks) table every tick
        self._table_dev = jnp.zeros((B, self.kv.max_blocks), jnp.int32)
        self._length_dev = jnp.zeros((B,), jnp.int32)
        self.kv.dirty.clear()            # mirrors start in sync (all zero)
        # pre-compile every power-of-two patch variant (dirty-row batches
        # are pow2-padded) so a dirty-row sync never compiles mid-tick —
        # log2(B)+1 tiny programs, warmed with zero-on-zero patches
        n = 1
        while True:
            self._table_dev, self._length_dev = self._patch(
                self._table_dev, self._length_dev,
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n, self.kv.max_blocks), jnp.int32),
                jnp.zeros((n,), jnp.int32))
            if n >= B:
                break
            n = min(2 * n, 1 << (B - 1).bit_length())
        if cfg.prefix_sharing:
            # pre-compile the COW flush for every batch size up to the
            # per-tick bound (capped at 8; rarer, larger bursts compile
            # lazily once) so a COW tick never pays an XLA compile
            chunk = max(self._chunk, self._chunk_tokens)
            bound = B * (-(-chunk // self.kv.page) + 1)
            self.kv.warm_copy(tuple(range(1, min(bound, 8) + 1)))
        # --- draft-side state (speculative mode) --------------------------
        self.dkv: Optional[PagedKVCache] = None
        if self._spec:
            # the target's VERIFY cell: all k+1 positions unembedded at
            # f32, the accepted prefix reduced on device (no PRNG)
            self._verify = jax.jit(model.verify_many_paged,
                                   donate_argnums=(2,))
            # draft cells: the forced-token decode twin (PROPOSE — the
            # steady-state <=1-token history deficit replays as a forced
            # step 0) and the prefill lane (CATCH-UP after a fresh admit,
            # a prefix-share adoption, or a preempt-resume)
            self._draft_many = jax.jit(
                draft_model.decode_many_paged,
                static_argnames=("num_steps", "temperature"),
                donate_argnums=(2, 3))
            self._draft_prefill = jax.jit(
                draft_model.prefill_many_paged,
                static_argnames=("temperature",),
                donate_argnums=(2, 3))
            # the draft keeps its own page pool: no sharing and no
            # retention (rejected rows roll back by length truncation;
            # a preempted slot rebuilds through catch-up)
            self.dkv = PagedKVCache(draft_model, B, cfg.max_seq,
                                    page_size=cfg.page_size,
                                    max_blocks=cfg.max_blocks,
                                    num_pages=cfg.num_pages)
            self._dtable_dev = jnp.zeros((B, self.dkv.max_blocks),
                                         jnp.int32)
            self._dlength_dev = jnp.zeros((B,), jnp.int32)
            self.dkv.dirty.clear()       # mirrors start in sync (all zero)
            self._dkey = jax.random.key(cfg.seed + 1)
        self._pindex = _PrefixIndex()
        self.scheduler = TickScheduler(fairness=cfg.fairness,
                                       tick_budget=cfg.tick_budget,
                                       preempt_policy=cfg.preempt_policy)
        self.key = jax.random.key(cfg.seed)
        self.slots = [_Slot() for _ in range(B)]
        self.queue: List[Request] = []
        self.results: Dict[int, List[int]] = {}
        self._feed = np.full((B,), cfg.pad_id, np.int32)
        self._next_rid = 0
        self.steps_run = 0                # engine ticks (chunks)
        # --- request lifecycle / overload state --------------------------
        self.ticks = 0                    # step() calls, incl. idle ticks
                                          # (the deadline / fault clock)
        self._idle = 0                    # consecutive no-work busy ticks
        self.no_progress_ticks = 0        # CUMULATIVE idle-but-busy ticks
                                          # (the wedge detector resets
                                          # _idle on progress; this one
                                          # survives as a health stat)
        self.snapshots_written = 0        # crash-consistency snapshots
        self._last_snapshot_tick = -1     # dedupe guard for the hook
        self._reqs: Dict[int, Request] = {}
        self._unstamped: set = set()      # admitted, no first output yet
        self.status: Dict[int, RequestStatus] = {}
        self.reject_reason: Dict[int, str] = {}
        self.preemptions = 0              # capacity preemptions + forced
                                          # evictions (fault-injected)
        self.recompute_tokens = 0         # tokens re-appended on resume
        self.rejected = 0
        self.cancelled = 0
        self.deadline_exceeded = 0
        self.quarantines = 0              # poison-triggered slot requeues
        self.dropped_grants = 0           # granted tokens a fault dropped
        self.fault_counts: Dict[str, int] = {}
        self._quarantined: Dict[int, int] = {}  # slot -> usable-again tick
        self._squeezed: List[Tuple[int, List[int]]] = []  # (release, pages)
        self._faults = None               # armed serve/faults.py FaultPlan
        self._drop_slots: Set[int] = set()
        self._poison_slots: Set[int] = set()
        self.tokens_out = 0               # kept (non-discarded) tokens
        self.tokens_appended = 0          # fresh K/V rows written (physical)
        # --- speculative decoding counters -------------------------------
        self.spec_proposed = 0            # draft tokens offered to verify
        self.spec_accepted = 0            # proposals the target accepted
        self.spec_trunc_tokens = 0        # target K/V rows rolled back
        self.draft_dispatches = 0         # draft catch-up + propose calls
        self.verify_dispatches = 0        # target verify calls
        self.shared_tokens = 0            # prompt tokens served by reference
        self.joins = 0
        self.stalls = 0
        self.dispatches = 0               # device calls of working ticks
        # pages the decode kernel copies per layer, summed over the decode
        # lane's active (step, slot) pairs: ceil(kv_len / page) each, and
        # the whole table width each (their ratio: the share of the table
        # the kernel still reads)
        self.decode_pages_streamed = 0
        self.decode_pages_table = 0
        self.util_trace: List[float] = []        # per-tick page utilization
        # --- tick-overhead accounting (the host side the roofline can't
        # see: BENCH_serve.json's tick_overhead section reads these) ------
        self.table_upload_bytes = 0       # dirty-row table/length patches
        self.forced_upload_bytes = 0      # forced-token arrays (legacy
                                          # prefill-by-decode only: stays 0
                                          # while the prefill lane routes
                                          # all prompt traffic — gated)
        self.prefill_upload_bytes = 0     # (B, T) chunk tokens + grants
        self.upload_bytes = 0             # all per-tick host->device bytes
        self.host_ms_trace: List[float] = []     # host work per tick (ms)

    # -- request lifecycle -----------------------------------------------------

    def _admissible(self, prompt: np.ndarray, mnt: int) -> Optional[str]:
        """None if the request can complete on this engine; otherwise the
        typed rejection reason.  Validated at submit() — an inadmissible
        request used to stall forever or raise deep inside a tick."""
        total = int(prompt.size) + mnt
        blocks = -(-total // self.kv.page)
        if blocks > self.kv.max_blocks:
            return (f"prompt+output needs {blocks} blocks > max_blocks="
                    f"{self.kv.max_blocks} (max_seq={self.cfg.max_seq})")
        if blocks > self.kv.num_pages - 1:
            return (f"prompt+output needs {blocks} blocks > pool of "
                    f"{self.kv.num_pages - 1} allocatable pages")
        if self.cfg.max_queue > 0 and len(self.queue) >= self.cfg.max_queue:
            return f"queue full ({self.cfg.max_queue} requests waiting)"
        return None

    def submit(self, prompt: np.ndarray,
               max_new_tokens: Optional[int] = None,
               deadline_ticks: Optional[int] = None) -> int:
        """Queue a request.  Admission is BOUNDED: a prompt+output that can
        never fit the slot table or the page pool, or a submit past
        ``max_queue`` depth, gets a typed ``REJECTED`` status (reason in
        ``reject_reason[rid]``) instead of a stall or a deep-tick raise.
        ``deadline_ticks`` (default ``cfg.deadline_ticks``; 0 = none)
        bounds the engine ticks the request may stay live."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.size == 0:
            raise ValueError("empty prompt: a slot needs at least one "
                             "token to feed the decode step")
        rid = self._next_rid
        self._next_rid += 1
        mnt = max_new_tokens or self.cfg.max_new_tokens
        reason = self._admissible(prompt, mnt)
        if reason is not None:
            self.status[rid] = RequestStatus.REJECTED
            self.reject_reason[rid] = reason
            self.results[rid] = []
            self.rejected += 1
            return rid
        dl = self.cfg.deadline_ticks if deadline_ticks is None \
            else deadline_ticks
        req = Request(rid, prompt, mnt,
                      deadline_tick=self.ticks + dl if dl > 0 else -1)
        self._reqs[rid] = req
        self.status[rid] = RequestStatus.QUEUED
        self.queue.append(req)
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or running request: partial output is kept in
        ``results[rid]``, status becomes ``CANCELLED``, slot/pages are
        released.  False if the rid is unknown or already terminal."""
        st = self.status.get(rid)
        if st is None or st in TERMINAL_STATUSES:
            return False
        req = self._reqs[rid]
        if st is RequestStatus.QUEUED:
            self.queue.remove(req)
        else:
            i = next((j for j, s in enumerate(self.slots)
                      if s.active and s.rid == rid), -1)
            if i >= 0:
                req.emitted.extend(self.slots[i].out)
                self._release_slot(i)
        self.results[rid] = list(req.emitted)
        self.status[rid] = RequestStatus.CANCELLED
        self.cancelled += 1
        return True

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(s.active for s in self.slots)

    def request_times(self, rid: int
                      ) -> Tuple[Optional[float], Optional[float]]:
        """(admitted_t, first_token_t) of a request on the
        ``time.perf_counter`` clock: its first admission to a slot, and
        the end of the tick that sampled its first output; None where it
        has not happened (a rejected request has neither).  Preemption
        and quarantine requeues keep both."""
        req = self._reqs.get(rid)
        if req is None:
            return None, None
        return req.admitted_t, req.first_token_t

    def cell_hlo(self) -> Dict[str, str]:
        """The compiled HLO text of the cells a plain tick runs (the
        prefill lane and the decode cell), keyed by XLA module name.  A
        device trace names each op by its instruction; this text gives
        the instruction's ``op_name``, whose named scope says which part
        of the model step the op is (bench/spans.py).  Lowered at the
        engine's own shapes, so the compiles are the ones the ticks ran
        (cache hits where a persistent compile cache is on)."""
        B = self.cfg.max_batch
        cache = {"k": self.kv.k, "v": self.kv.v,
                 "table": self._table_dev, "length": self._length_dev}
        if self.kv.quantized:
            cache["k_scale"] = self.kv.k_scale
            cache["v_scale"] = self.kv.v_scale
        grants = jnp.zeros((B,), jnp.int32)
        temp = self.cfg.temperature
        cells = [self._many_plain.lower(
            self.params, jnp.zeros((B, 1), jnp.int32), cache, self.key,
            grants, num_steps=self._chunk, temperature=temp)]
        if self._chunk_tokens:
            cells.append(self._prefill_lane.lower(
                self.params, jnp.zeros((B, self._chunk_tokens), jnp.int32),
                cache, self.key, grants, temperature=temp))
        out = {}
        for lowered in cells:
            text = lowered.compile().as_text()
            out[text.split(",", 1)[0].split()[1]] = text
        return out

    def _count_decode_pages(self, steps, pgr, chunk: int) -> None:
        """Decode step s of slot i attends over kv_len = (length after the
        tick's prefill) + s + 1 tokens; count its pages."""
        s = np.arange(chunk)[:, None]
        kv_len = (self.kv.length + pgr)[None, :] + s + 1
        act = s < steps[None, :]
        pages = -(-kv_len // self.kv.page)
        self.decode_pages_streamed += int(pages[act].sum())
        self.decode_pages_table += int(act.sum()) * self.kv.max_blocks

    def counters(self) -> Dict[str, int]:
        """A snapshot of every cumulative counter, the engine's and its
        page cache's: the difference of two snapshots is what the ticks
        between them did."""
        out = {c: int(getattr(self, c)) for c in COUNTERS}
        out.update((c, int(getattr(self.kv, c))) for c in CACHE_COUNTERS)
        return out

    def run(self) -> Dict[int, List[int]]:
        """Drain queue + slots; returns {rid: generated tokens}."""
        while self.busy:
            self.step()
        return self.results

    # -- fault injection (serve/faults.py) --------------------------------------

    def install_faults(self, plan) -> None:
        """Arm a ``FaultPlan``; its events fire at the top of their tick.
        Squeezed pages auto-release when their duration elapses (``step()``
        keeps processing releases even while idle, so a squeeze can starve
        ticks but never deadlock the engine)."""
        self._faults = plan

    def _apply_faults(self) -> None:
        now = self.ticks
        if self._squeezed:                # releases first: a squeeze never
            keep = []                     # outlives its scheduled duration
            for until, pages in self._squeezed:
                if until <= now:
                    self.kv.release_pages(pages)
                else:
                    keep.append((until, pages))
            self._squeezed = keep
        if self._faults is None:
            return
        events = self._faults.events_at(now)
        for ev in events:
            if ev.kind == "kill":         # process death pre-empts the
                self.fault_counts["kill"] = \
                    self.fault_counts.get("kill", 0) + 1
                raise EngineKilled(now)   # WHOLE tick: no state advanced
        for ev in events:
            self.fault_counts[ev.kind] = self.fault_counts.get(ev.kind, 0) + 1
            if ev.kind == "squeeze":      # pool pressure: free list shrinks
                pages = self.kv.seize_pages(ev.pages)
                if pages:
                    self._squeezed.append((now + max(1, ev.duration), pages))
            elif ev.kind == "evict":      # forced eviction -> requeue
                i = ev.slot
                if not (0 <= i < len(self.slots) and self.slots[i].active):
                    i = next((j for j, s in enumerate(self.slots)
                              if s.active), -1)
                if i >= 0:
                    self._preempt(i)
            elif ev.kind == "drop":       # this tick's grant vanishes
                self._drop_slots.update(
                    range(len(self.slots)) if ev.slot < 0 else (ev.slot,))
            elif ev.kind == "poison":     # nonfinite logits: the sampled
                self._poison_slots.update(  # token comes back out-of-vocab
                    range(len(self.slots)) if ev.slot < 0 else (ev.slot,))

    # -- preemption / expiry -----------------------------------------------------

    def _release_slot(self, i: int) -> None:
        """Return slot ``i`` to the pool: pages freed refcount-aware
        (shared pages survive for their other referents), prefix index
        dropped, feed reset.  With retention on, the slot's page-aligned
        token-history prefix moves to the RETAINED pool instead of the
        free list — finish and eviction alike (an evicted victim's resume
        is the hottest possible re-share)."""
        history = self.slots[i].history
        self.slots[i] = _Slot()
        self._feed[i] = self.cfg.pad_id
        self._pindex.drop(i)
        self.kv.free_slot(i, retain_tokens=history if self._retain else None)
        if self.dkv is not None:          # draft pages never retain
            self.dkv.free_slot(i)

    def _preempt(self, i: int, quarantine: bool = False) -> None:
        """Evict slot ``i`` and requeue its request AT THE FRONT with all
        output emitted so far: on re-admission the emitted tokens replay as
        forced prompt through the ragged prefill lane (recompute), so the
        resumed request finishes token-identical to an uninterrupted run
        (greedy decode is deterministic and the lane is pinned
        bit-identical to stepwise decode)."""
        slot = self.slots[i]
        req = self._reqs[slot.rid]
        with _span("engine.preempt") as span:
            if _recording():
                span.set_metadata(rids=_ids([req.rid]),
                                  quarantine=int(quarantine))
            req.emitted.extend(slot.out)
            req.preempts += 1
            self.status[req.rid] = RequestStatus.QUEUED
            self.queue.insert(0, req)
            self._release_slot(i)
        if quarantine:
            self._quarantined[i] = self.ticks \
                + max(1, self.cfg.quarantine_ticks)
            self.quarantines += 1
        else:
            self.preemptions += 1

    def _preempt_for_capacity(self) -> bool:
        """Victim selection when no slot can get step capacity.  Requires
        >= 2 active slots: preempting the survivors' blocker strictly
        advances total generated tokens, so the overload loop terminates; a
        LONE stuck slot is only possible under fault-injected pool pressure
        (its pages release on schedule — wait, don't thrash it)."""
        active = [i for i, s in enumerate(self.slots) if s.active]
        if len(active) < 2:
            return False
        gen = {i: len(self._reqs[self.slots[i].rid].emitted)
               + len(self.slots[i].out) for i in active}
        victim = self.scheduler.pick_victim(self.slots, self.kv,
                                            generated=gen)
        if victim < 0:
            return False
        self._preempt(victim)
        return True

    def _expire_deadlines(self) -> None:
        """Requests past their tick budget terminate as DEADLINE_EXCEEDED
        with whatever output they produced — queued and running alike (a
        preempted request's deadline keeps ticking while it waits)."""
        now = self.ticks
        for req in [r for r in self.queue if 0 <= r.deadline_tick < now]:
            self.queue.remove(req)
            self.results[req.rid] = list(req.emitted)
            self.status[req.rid] = RequestStatus.DEADLINE_EXCEEDED
            self.deadline_exceeded += 1
        for i, slot in enumerate(self.slots):
            if not slot.active:
                continue
            req = self._reqs[slot.rid]
            if 0 <= req.deadline_tick < now:
                req.emitted.extend(slot.out)
                self.results[req.rid] = list(req.emitted)
                self.status[req.rid] = RequestStatus.DEADLINE_EXCEEDED
                self.deadline_exceeded += 1
                self._release_slot(i)

    def _find_donor(self, prompt: List[int]):
        """Longest-common-prefix match of ``prompt`` against (a) the LIVE
        slots' resident token histories via the rolling-hash prefix index
        (O(matched prefix), not O(slots x prompt)) and (b) the RETAINED
        pool of dead donors' page-aligned prefixes (same digests, via
        ``kv.match_retained``).  Returns (kind, ref, n_shared) where kind
        is "live" (ref = slot index), "retained" (ref = RetainedPrefix)
        or None when nothing clears ``share_min_tokens``.  Live matches
        win ties: they can extend past page boundaries and keep feeding
        the index.  The cap at ``len(prompt) - 1`` keeps the last prompt
        token always fed (its logits seed the first sampled output)."""
        cap = len(prompt) - 1
        donor, best = self._pindex.lookup(prompt, cap)
        if donor >= 0 and not (self.slots[donor].active
                               and self.slots[donor].history[:best]
                               == prompt[:best]):
            # digest collision (or index drift): exact-scan fallback
            best, donor = 0, -1
            for j, s in enumerate(self.slots):
                if not s.active:
                    continue
                n = min(_lcp(prompt, s.history), cap)
                if n > best:
                    best, donor = n, j
        entry, n_ret = (None, 0)
        if self._retain:
            entry, n_ret = self.kv.match_retained(prompt, cap)
        min_share = max(1, self.cfg.share_min_tokens)
        if best >= n_ret and best >= min_share:
            return "live", donor, best
        if entry is not None and n_ret >= min_share:
            return "retained", entry, n_ret
        return None, None, 0

    def _admit(self) -> List[int]:
        """Give free slots to the queue's head requests; returns their
        ids."""
        admitted: List[int] = []
        for i, slot in enumerate(self.slots):
            if slot.active or not self.queue:
                continue
            if self._quarantined.get(i, 0) > self.ticks:
                continue                   # poisoned slot sits out
            head = self.queue[0]
            # a resumed request replays its emitted output as forced
            # prompt: recompute rides the ragged prefill lane, and greedy
            # decode continues token-identically from where it left off
            prompt = [int(t) for t in head.prompt] + list(head.emitted)
            kind, ref, n_shared = (None, None, 0)
            if self.cfg.prefix_sharing:
                kind, ref, n_shared = self._find_donor(prompt)
            if n_shared == 0 and self.kv.allocatable == 0:
                break                      # pool dry: wait for eviction
            req = self.queue.pop(0)
            if kind == "live":
                self.kv.share(i, ref, n_shared)
                self.shared_tokens += n_shared
            elif kind == "retained":
                # cross-lifetime hit: the donor is gone, its pages are not
                self.kv.adopt_retained(i, ref, n_shared)
                self.shared_tokens += n_shared
            # no donor: the slot's length row is already 0 (free_slot
            # zeroed and dirty-marked it; a fresh engine starts at 0)
            # best-effort first page; a dry pool stalls (not deadlocks):
            # the scheduler re-tries every tick as evictions refill the list
            self.kv.ensure(i, n_shared + 1)
            self.slots[i] = _Slot(rid=req.rid, forced=prompt[n_shared + 1:],
                                  out=[], history=prompt[:n_shared],
                                  budget=req.max_new_tokens
                                  - len(req.emitted),
                                  prompt_left=len(prompt) - n_shared,
                                  active=True)
            if self.cfg.prefix_sharing:
                self._pindex.add(i, prompt[:n_shared])
            self._feed[i] = prompt[n_shared]
            self.status[req.rid] = RequestStatus.RUNNING
            if req.admitted_t is None:
                req.admitted_t = time.perf_counter()
                self._unstamped.add(req.rid)
            admitted.append(req.rid)
            if req.preempts:
                # re-appended work (prefix-shared tokens cost nothing) —
                # the bench's recompute-overhead fraction reads this
                self.recompute_tokens += len(prompt) - n_shared
            self.joins += 1
        return admitted

    def _finish(self, i: int) -> None:
        slot = self.slots[i]
        req = self._reqs[slot.rid]
        self.results[slot.rid] = req.emitted + slot.out
        self.status[slot.rid] = (RequestStatus.PREEMPTED_RESUMED
                                 if req.preempts else RequestStatus.FINISHED)
        self._release_slot(i)             # drop the slot's page references

    # -- stepping ---------------------------------------------------------------

    def defrag(self) -> None:
        self.kv.defrag()

    def _sync_dirty(self, kv: PagedKVCache, table_dev, length_dev):
        """Patch a device table/length mirror pair at ``kv``'s dirty rows.
        The row batch is padded to a power of two (repeating the first
        dirty row — an idempotent scatter) so the patcher's compile
        universe is log2(B)-bounded, not one program per distinct count.
        Returns the updated mirrors plus the bytes uploaded (0 = the
        mirrors were already in sync, no dispatch)."""
        if not kv.dirty:
            return table_dev, length_dev, 0
        rows = sorted(kv.dirty)
        kv.dirty.clear()
        pad = 1 << (len(rows) - 1).bit_length()
        rows = np.asarray(rows + rows[:1] * (pad - len(rows)), np.int32)
        table_dev, length_dev = self._patch(
            table_dev, length_dev, jnp.asarray(rows),
            jnp.asarray(kv.table[rows]), jnp.asarray(kv.length[rows]))
        return table_dev, length_dev, \
            int(rows.size) * (kv.max_blocks + 1) * 4

    # -- speculative decoding ----------------------------------------------------

    @property
    def accept_rate(self) -> float:
        """Fraction of draft proposals the target accepted (1.0 until the
        first speculative tick)."""
        return self.spec_accepted / max(1, self.spec_proposed)

    def _spec_decode(self, steps, chunk: int, cache):
        """One speculative decode tick over the granted slots:

          1. CATCH-UP — a slot whose draft cache is missing more than one
             history token (fresh admit, prefix-share adoption,
             preempt-resume: the draft never shares pages, it recomputes)
             replays the gap through the DRAFT prefill lane in fixed-width
             chunks until at most one token trails;
          2. PROPOSE — one draft forced-token decode dispatch, ``num_steps
             = spec_k + 1`` static: a slot with a 1-token deficit feeds
             the missing history token and forces the target feed in as
             step 0, so the deficit never costs an extra dispatch;
          3. host-sync the proposals (a device wait, reported as such);
          4. VERIFY — ONE ragged prefill-lane dispatch on the TARGET over
             [feed, p_1..p_k] per slot, all positions unembedded at f32,
             the accepted prefix reduced on device.

        Returns (greedy, accept, vgr, cache, upload_bytes, draft_disp,
        verify_disp, wait_s).  ``greedy``/``accept`` are the verify cell's
        device outputs (the caller syncs them with the tick's other
        outputs); ``vgr`` is the (B,) int32 K/V rows the verify actually
        appended — 1 + proposals, which drops below the planned grant only
        when the draft pool capped a slot (it then advances one verified
        token per tick until pages free up)."""
        cfg = self.cfg
        B = len(self.slots)
        dkv = self.dkv
        upload = 0
        draft_disp = 0
        rows = [i for i in range(B)
                if self.slots[i].active and steps[i] > 0]

        def dcache():
            c = {"k": dkv.k, "v": dkv.v, "table": self._dtable_dev,
                 "length": self._dlength_dev}
            if dkv.quantized:
                c["k_scale"] = dkv.k_scale
                c["v_scale"] = dkv.v_scale
            return c

        def writeback(c):
            dkv.k, dkv.v = c["k"], c["v"]
            if dkv.quantized:
                dkv.k_scale, dkv.v_scale = c["k_scale"], c["v_scale"]
            self._dtable_dev = c["table"]
            self._dlength_dev = c["length"]

        # --- catch-up: stream missing history through the draft lane -----
        Tc = max(self._chunk_tokens, chunk)
        while True:
            cg = np.zeros((B,), np.int32)
            tok_c = np.full((B, Tc), cfg.pad_id, np.int32)
            for i in rows:
                hist = self.slots[i].history
                dlen = int(dkv.length[i])
                miss = len(hist) - dlen
                if miss <= 1:
                    continue
                take = min(miss, Tc)
                if not dkv.ensure(i, dlen + take):
                    # draft pool dry: partial catch-up — the slot keeps
                    # verifying at grant 1 until draft pages free up
                    take = min(take,
                               len(dkv.owned[i]) * dkv.page - dlen)
                if take <= 0:
                    continue
                cg[i] = take
                tok_c[i, :take] = hist[dlen:dlen + take]
            if not cg.any():
                break
            self._dtable_dev, self._dlength_dev, b = self._sync_dirty(
                dkv, self._dtable_dev, self._dlength_dev)
            upload += b + B * (Tc + 1) * 4
            draft_disp += bool(b) + 1
            _, c, self._dkey = self._draft_prefill(
                self.draft_params, jnp.asarray(tok_c), dcache(),
                self._dkey, jnp.asarray(cg), temperature=0.0)
            writeback(c)
            dkv.length += cg

        # --- propose: grant = deficit (<= 1) + k proposals per slot ------
        off = np.zeros((B,), np.int32)
        dgr = np.zeros((B,), np.int32)
        k_prop = np.zeros((B,), np.int32)
        feed_d = np.full((B,), cfg.pad_id, np.int32)
        forced_tok = np.full((chunk, B), cfg.pad_id, np.int32)
        forced_mask = np.zeros((chunk, B), bool)
        for i in rows:
            hist = self.slots[i].history
            d = len(hist) - int(dkv.length[i])
            if d > 1:
                continue                  # still catching up: no proposals
            k = int(steps[i]) - 1
            if k and not dkv.ensure(i, len(hist) + k):
                k = max(0, len(dkv.owned[i]) * dkv.page - len(hist))
            if d == 0 and k == 0:
                continue                  # nothing for the draft to do
            off[i], k_prop[i], dgr[i] = d, k, d + k
            if d:                         # replay the missing history
                feed_d[i] = hist[-1]      # token, force the feed in as
                forced_tok[0, i] = self._feed[i]   # the step-0 output
                forced_mask[0, i] = True
            else:
                feed_d[i] = self._feed[i]
        toks_d = None
        if dgr.any():
            self._dtable_dev, self._dlength_dev, b = self._sync_dirty(
                dkv, self._dtable_dev, self._dlength_dev)
            # feed + grants + forced tok/mask — DRAFT-side traffic (the
            # gated forced_upload_bytes tracks prompt traffic only)
            upload += b + 2 * B * 4 + chunk * B * (4 + 1)
            draft_disp += bool(b) + 1
            toks_d, c, self._dkey = self._draft_many(
                self.draft_params, jnp.asarray(feed_d)[:, None], dcache(),
                self._dkey, jnp.asarray(dgr), jnp.asarray(forced_tok),
                jnp.asarray(forced_mask), num_steps=chunk,
                temperature=0.0)
            writeback(c)
            dkv.length += dgr

        # --- host-sync the proposals (device wait, not host work) --------
        w0 = time.perf_counter()
        toks_d_np = np.array(toks_d) if toks_d is not None else None
        wait = time.perf_counter() - w0

        # --- verify: ONE ragged prefill-lane dispatch on the target ------
        vocab = self.model.cfg.vocab_size
        vgr = np.zeros((B,), np.int32)
        tok_v = np.full((B, chunk), cfg.pad_id, np.int32)
        for i in rows:
            tok_v[i, 0] = self._feed[i]
            k = int(k_prop[i])
            for s in range(k):
                t = int(toks_d_np[int(off[i]) + s, i])
                # clamp: an out-of-range draft sample must not index past
                # the target embedding (it just gets rejected)
                tok_v[i, 1 + s] = min(max(t, 0), vocab - 1)
            vgr[i] = 1 + k
            self.spec_proposed += k
        upload += B * (chunk + 1) * 4
        greedy, accept, cache = self._verify(
            self.params, jnp.asarray(tok_v), cache, jnp.asarray(vgr))
        return greedy, accept, vgr, cache, upload, draft_disp, 1, wait

    def _spec_bookkeep(self, vgr, greedy_np, accept_np,
                       poisoned: Set[int]) -> None:
        """Post-verify bookkeeping for a speculative tick: emit the
        accepted prefix plus the bonus token per slot, TRUNCATE the
        target's rejected K/V rows (length rollback — the pages stay
        owned and the garbage rows rewrite on the next append; nothing
        past a slot's length is ever read or shared), and roll the draft
        cache back to the accepted frontier."""
        cfg = self.cfg
        for i, slot in enumerate(self.slots):
            v = int(vgr[i])
            if not slot.active or v == 0 or i in poisoned:
                continue
            a = int(accept_np[i])
            kept = a + 1                  # accepted proposals + bonus
            L = len(slot.history)
            if kept < v:                  # rejected rows roll back
                self.kv.length[i] -= v - kept
                self.kv.dirty.add(i)
                self.spec_trunc_tokens += v - kept
            fed = [int(self._feed[i])] \
                + [int(greedy_np[i, s]) for s in range(kept - 1)]
            slot.history.extend(fed)
            if cfg.prefix_sharing:
                self._pindex.add(i, fed)
            slot.served += kept
            self.spec_accepted += a
            # draft rollback: the propose dispatch appended [feed,
            # p_1..p_{k-1}] past the shared history — keep the prefix the
            # target accepted.  All-k accepted leaves a 1-token deficit
            # (p_k was sampled, never appended) that next tick's forced
            # replay absorbs.
            cur = int(self.dkv.length[i])
            dvalid = min(cur, L + min(kept, v - 1))
            if dvalid != cur:
                self.dkv.length[i] = dvalid
                self.dkv.dirty.add(i)
            finished = False
            for s in range(kept):
                tok = int(greedy_np[i, s])
                slot.out.append(tok)
                self.tokens_out += 1
                if (cfg.eos_id >= 0 and tok == cfg.eos_id) \
                        or len(slot.out) >= slot.budget:
                    finished = True
                    break
            if finished:
                self._finish(i)
            else:
                self._feed[i] = greedy_np[i, kept - 1]

    def step(self) -> None:
        """One engine tick: admit, plan (prefill-lane + decode grants /
        partial grants / batched COW / fairness), sync the dirty rows of
        the device-resident table state, then advance every granted slot —
        prompt chunks through the RAGGED PREFILL LANE (one compiled kernel
        step appends and attends up to T prompt tokens per slot; a prompt
        costs ceil(prompt / T) dispatches instead of ``prompt`` decode
        steps) and decode grants through the fused decode cell.

        The tick is kept as thin as the kernel: the tick's COW copies are
        ONE batched dispatch (flushed inside ``plan``), the block table and
        lengths live on device and only dirty rows are patched (a
        steady-state decode tick uploads zero table bytes), the per-slot
        grants go up as B ints (the per-step decode mask is built on
        device), prompt traffic moves as ONE ragged (B, T) token block per
        prefill chunk (the per-step (chunk, B) forced-token/mask uploads
        are retired for prompts — ``forced_upload_bytes`` stays 0 and is
        gated), and a pure-decode tick runs the forced-token-free twin
        cell.

        The tick runs inside the profiler span ``engine.step`` and each
        phase inside its own (module docstring); the step span carries
        the tick's dispatches and upload bytes while a trace records."""
        with _span("engine.step") as span:
            recording = _recording()
            work = self._tick(recording)
            if recording and work is not None:
                span.set_metadata(**work)

    def _tick(self, recording: bool) -> Optional[Dict[str, int]]:
        """``step``'s body; returns the working tick's device calls and
        upload bytes (None for a tick with no work)."""
        cfg = self.cfg
        chunk = self._chunk
        T = self._chunk_tokens
        t0 = time.perf_counter()
        self.ticks += 1
        self._apply_faults()
        self._expire_deadlines()
        with _span("engine.admit") as span:
            admitted = self._admit()
            if recording and admitted:
                span.set_metadata(rids=_ids(admitted))
        cow_disp0 = self.kv.cow_dispatches
        with _span("engine.plan"):
            plan = self.scheduler.plan(self.slots, self.kv, chunk,
                                       prefill_tokens=T)
        self.stalls += plan.stalled
        # PREEMPT-AND-RECOMPUTE: when no slot can get step capacity, evict
        # victims (fewest tokens generated, then most pages held) until the
        # survivors can advance — the victims requeue with their emitted
        # output as forced prompt and finish token-identical later.  Each
        # iteration drops one active slot, so the loop is bounded by B.
        while not plan.any_work and cfg.preempt \
                and self._preempt_for_capacity():
            with _span("engine.plan"):
                plan = self.scheduler.plan(self.slots, self.kv, chunk,
                                           prefill_tokens=T)
            self.stalls += plan.stalled
        # dropped-grant fault: the victims' granted work vanishes AFTER
        # planning (a dropped grant must look like lost work, not trigger
        # preemption) — the scheduler simply re-grants next tick
        if self._drop_slots:
            for i in self._drop_slots:
                if 0 <= i < len(self.slots):
                    d = int(plan.steps[i]) + int(plan.prefill[i])
                    if d:
                        self.dropped_grants += d
                        plan.steps[i] = 0
                        plan.prefill[i] = 0
            self._drop_slots.clear()
        if not plan.any_work:
            self._poison_slots.clear()
            if self.busy:
                if not cfg.preempt:
                    raise RuntimeError(
                        f"page pool exhausted: {len(self.kv.free)} free "
                        f"pages cannot give any slot step capacity "
                        f"(num_pages={self.kv.num_pages}, "
                        f"page={self.kv.page})")
                # idle-but-busy ticks are BOUNDED: every queued request is
                # admissible, lone-slot stalls only ride out fault squeezes
                # (which release on schedule), so sustained idling means a
                # bookkeeping bug — fail loudly instead of spinning
                self._idle += 1
                self.no_progress_ticks += 1
                if self._idle > cfg.wedge_ticks:
                    raise RuntimeError(
                        f"engine wedged: {cfg.wedge_ticks} consecutive "
                        "idle ticks with work pending (queue="
                        f"{len(self.queue)}, active="
                        f"{sum(s.active for s in self.slots)}, free="
                        f"{len(self.kv.free)}, seized="
                        f"{len(self.kv.seized)})")
            self._maybe_snapshot()
            return None
        self._idle = 0
        B = len(self.slots)
        steps = plan.steps
        pgr = plan.prefill
        dispatches = self.kv.cow_dispatches - cow_disp0   # batched COW: <= 1
        tick_upload = 0

        # dirty-row sync of the device table/length mirrors: only rows
        # admission/COW/eviction/defrag/truncation touched; nothing in
        # steady state.
        with _span("engine.sync_dirty"):
            self._table_dev, self._length_dev, row_bytes = self._sync_dirty(
                self.kv, self._table_dev, self._length_dev)
        if row_bytes:
            self.table_upload_bytes += row_bytes
            tick_upload += row_bytes
            dispatches += 1

        cache = {"k": self.kv.k, "v": self.kv.v,
                 "table": self._table_dev, "length": self._length_dev}
        if self.kv.quantized:            # scale pools ride the cache pytree
            cache["k_scale"] = self.kv.k_scale
            cache["v_scale"] = self.kv.v_scale

        # --- prefill lane: one ragged (B, T) chunk of prompt tokens ------
        nxt = None
        if pgr.any():
            with _span("engine.dispatch_prefill"):
                tok_mat = np.full((B, T), cfg.pad_id, np.int32)
                for i, slot in enumerate(self.slots):
                    g = int(pgr[i])
                    if g:
                        tok_mat[i, 0] = self._feed[i]
                        if g > 1:
                            tok_mat[i, 1:g] = slot.forced[:g - 1]
                pbytes = B * (T + 1) * 4          # token block + grant vector
                self.prefill_upload_bytes += pbytes
                tick_upload += pbytes
                nxt, cache, self.key = self._prefill_lane(
                    self.params, jnp.asarray(tok_mat), cache, self.key,
                    jnp.asarray(pgr), temperature=cfg.temperature)
                dispatches += 1

        # --- decode lane: the fused scan over decode grants, or (spec
        # mode) the draft-propose + target-verify pipeline ----------------
        toks = None
        greedy = accept = None
        vgr = steps                       # K/V rows the lane appends
        d_disp = v_disp = 0
        spec_wait = 0.0
        if steps.any():
            with _span("engine.dispatch_decode"):
                if self._spec:
                    (greedy, accept, vgr, cache, d_up, d_disp, v_disp,
                     spec_wait) = self._spec_decode(steps, chunk, cache)
                    tick_upload += d_up
                    dispatches += d_disp + v_disp
                    self.draft_dispatches += d_disp
                    self.verify_dispatches += v_disp
                else:
                    self._count_decode_pages(steps, pgr, chunk)
                    tick_upload += 2 * B * 4      # feed tokens + step grants
                    feed = jnp.asarray(self._feed)[:, None]
                    steps_dev = jnp.asarray(steps)
                    prompt_in_flight = any(s.active and s.forced and steps[i]
                                           for i, s in enumerate(self.slots))
                    if prompt_in_flight:
                        # legacy prefill-by-decode (lane disabled): prompts
                        # ride the decode cell as forced tokens
                        forced_tok = np.full((chunk, B), cfg.pad_id, np.int32)
                        forced_mask = np.zeros((chunk, B), bool)
                        for i, slot in enumerate(self.slots):
                            for s in range(min(len(slot.forced),
                                               int(steps[i]))):
                                forced_tok[s, i] = slot.forced[s]
                                forced_mask[s, i] = True
                        forced_bytes = chunk * B * (4 + 1)
                        self.forced_upload_bytes += forced_bytes
                        tick_upload += forced_bytes
                        toks, cache, self.key = self._many(
                            self.params, feed, cache, self.key, steps_dev,
                            jnp.asarray(forced_tok), jnp.asarray(forced_mask),
                            num_steps=chunk, temperature=cfg.temperature)
                    else:
                        toks, cache, self.key = self._many_plain(
                            self.params, feed, cache, self.key, steps_dev,
                            num_steps=chunk, temperature=cfg.temperature)
                    dispatches += 1
        self.kv.k = cache["k"]
        self.kv.v = cache["v"]
        if self.kv.quantized:
            self.kv.k_scale = cache["k_scale"]
            self.kv.v_scale = cache["v_scale"]
        self._table_dev = cache["table"]
        self._length_dev = cache["length"]    # device already advanced it
        self.kv.length += vgr + pgr           # host mirror of the increment
        self.tokens_appended += int(vgr.sum()) + int(pgr.sum())
        self.steps_run += 1
        if cfg.trace_pool:
            self.util_trace.append(self.kv.utilization())

        t1 = time.perf_counter()
        with _span("engine.readback"):                  # device wait
            toks_np = np.array(toks) if toks is not None else None
            nxt_np = np.array(nxt) if nxt is not None else None
            greedy_np = np.array(greedy) if greedy is not None else None
            accept_np = np.array(accept) if accept is not None else None
        t2 = time.perf_counter()
        with _span("engine.bookkeep") as span:
            live = [s.rid for s in self.slots if s.active] if recording \
                else ()
            self._bookkeep(pgr, steps, vgr, toks_np, nxt_np, greedy_np,
                           accept_np)
            if recording:
                done = [r for r in live
                        if self.status[r] in TERMINAL_STATUSES]
                if done:
                    span.set_metadata(rids=_ids(done))
        t3 = time.perf_counter()
        if cfg.trace_ticks:
            # host cost of the tick = everything but the device waits
            # (the mid-tick proposal sync in spec mode is a device wait)
            self.host_ms_trace.append(
                ((t1 - t0 - spec_wait) + (t3 - t2)) * 1e3)
        self.dispatches += dispatches
        self.upload_bytes += tick_upload
        self._maybe_snapshot()
        t_end = time.perf_counter()
        for rid in tuple(self._unstamped):
            if self._output_len(rid) > 0:
                self._reqs[rid].first_token_t = t_end
                self._unstamped.discard(rid)
            elif self.status[rid] in TERMINAL_STATUSES:
                self._unstamped.discard(rid)
        if not recording:
            return None
        work = {"dispatches": dispatches, "upload_bytes": tick_upload}
        if self._spec:
            work.update(draft_dispatches=d_disp, verify_dispatches=v_disp)
        return work

    def _output_len(self, rid: int) -> int:
        """Output tokens a request holds (kept across preemption)."""
        if rid in self.results:
            return len(self.results[rid])
        held = sum(len(s.out) for s in self.slots
                   if s.active and s.rid == rid)
        return len(self._reqs[rid].emitted) + held

    def _bookkeep(self, pgr, steps, vgr, toks_np, nxt_np, greedy_np,
                  accept_np) -> None:
        """A working tick's host bookkeeping over the tokens read back:
        the poison fault and output guard, then the prefill lane's and
        the decode lane's emits, finishes and feeds, then quarantine."""
        cfg = self.cfg
        B = len(self.slots)
        # poison fault: nonfinite logits make the sampler return garbage —
        # modeled as an out-of-vocab sentinel overwriting the slot's
        # sampled tokens for this tick (in spec mode the WHOLE verified
        # window poisons — every kept token is garbage, not just one)
        if self._poison_slots:
            for i in self._poison_slots:
                if 0 <= i < B:
                    if toks_np is not None and steps[i]:
                        toks_np[:, i] = -1
                    if greedy_np is not None and vgr[i]:
                        greedy_np[i, :] = -1
                    if nxt_np is not None and pgr[i]:
                        nxt_np[i] = -1
            self._poison_slots.clear()
        # ALWAYS-ON output guard (not fault-plan-gated): a sampled token
        # outside the vocabulary means the slot's logits were garbage —
        # quarantine the slot and requeue the request with its PRE-TICK
        # output, skipping this tick's bookkeeping for it entirely.  A
        # speculative tick emits up to k+1 tokens per slot, so the guard
        # inspects EVERY kept token (accepted prefix + bonus), not one.
        vocab = self.model.cfg.vocab_size
        poisoned: Set[int] = set()
        for i, slot in enumerate(self.slots):
            if not slot.active:
                continue
            g, si = int(pgr[i]), int(vgr[i])
            if g and slot.prompt_left - g <= 0:   # sampled token is kept
                t = int(nxt_np[i])
                if t < 0 or t >= vocab:
                    poisoned.add(i)
            if si and i not in poisoned:
                if greedy_np is not None:         # speculative tick
                    for s in range(int(accept_np[i]) + 1):
                        t = int(greedy_np[i, s])
                        if t < 0 or t >= vocab:
                            poisoned.add(i)
                            break
                else:
                    for s in range(si):
                        t = int(toks_np[s, i])
                        if t < 0 or t >= vocab:
                            poisoned.add(i)
                            break
        # prefill-lane bookkeeping: the chunk's appended tokens are known
        # on the host (feed + forced prefix) — only the ONE sampled token
        # per slot came back, and it matters only when the prompt drained
        for i, slot in enumerate(self.slots):
            g = int(pgr[i])
            if not slot.active or g == 0 or i in poisoned:
                continue
            fed = [int(self._feed[i])] + [int(t) for t in slot.forced[:g - 1]]
            slot.history.extend(fed)
            if cfg.prefix_sharing:          # the index only feeds donor
                self._pindex.add(i, fed)    # lookup, gated the same way
            slot.served += g
            del slot.forced[:g - 1]
            slot.prompt_left -= g
            if slot.prompt_left > 0:
                # mid-prompt: the sampled token is a known prompt token's
                # prediction — discard it, feed the next prompt token
                self._feed[i] = slot.forced.pop(0)
                continue
            tok = int(nxt_np[i])            # the request's FIRST output
            slot.out.append(tok)
            self.tokens_out += 1
            if (cfg.eos_id >= 0 and tok == cfg.eos_id) \
                    or len(slot.out) >= slot.budget:
                self._finish(i)
            else:
                self._feed[i] = tok
        # decode-lane bookkeeping (legacy forced-prefill rides here too;
        # a speculative tick's multi-token emit/truncate/rollback lives in
        # _spec_bookkeep)
        if greedy_np is not None:
            self._spec_bookkeep(vgr, greedy_np, accept_np, poisoned)
        for i, slot in (enumerate(self.slots) if greedy_np is None
                        else ()):
            si = int(steps[i])
            if not slot.active or si == 0 or i in poisoned:
                continue
            # tokens fed this tick = this tick's K/V rows (donor index)
            fed = [int(self._feed[i])] \
                + [int(toks_np[s, i]) for s in range(si - 1)]
            slot.history.extend(fed)
            if cfg.prefix_sharing:
                self._pindex.add(i, fed)
            slot.served += si
            n_forced = min(len(slot.forced), si)
            del slot.forced[:n_forced]
            slot.prompt_left = max(0, slot.prompt_left - si)
            finished = False
            for s in range(n_forced, si):
                if finished:
                    break                      # chunk overshoot: discarded
                tok = int(toks_np[s, i])
                slot.out.append(tok)
                self.tokens_out += 1
                if (cfg.eos_id >= 0 and tok == cfg.eos_id) \
                        or len(slot.out) >= slot.budget:
                    finished = True
            if finished:
                self._finish(i)
            else:
                self._feed[i] = toks_np[si - 1, i]
        # quarantine poisoned slots: pages freed, request requeued with its
        # pre-tick output (the garbage tokens never reach results), the
        # slot index sits out cfg.quarantine_ticks admissions
        for i in sorted(poisoned):
            if self.slots[i].active:
                self._preempt(i, quarantine=True)

    # -- crash consistency (serve/snapshot.py) -----------------------------------

    def _maybe_snapshot(self) -> None:
        """Write a full-state snapshot at the END of every
        ``cfg.snapshot_every_ticks``-th tick (idle ticks included — the
        fault/deadline clock advanced, so the state did).  The write is
        atomic and old files are pruned to ``cfg.snapshot_keep``; lazy
        import keeps the engine importable without the snapshot layer."""
        cfg = self.cfg
        if cfg.snapshot_every_ticks <= 0 or not cfg.snapshot_dir \
                or self.ticks % cfg.snapshot_every_ticks != 0 \
                or self.ticks == self._last_snapshot_tick:
            return
        from repro.serve import snapshot as _snap
        with _span("engine.snapshot"):
            _snap.save_snapshot(
                self, _snap.snapshot_path(cfg.snapshot_dir, self.ticks))
            _snap.prune_snapshots(cfg.snapshot_dir, cfg.snapshot_keep)
        self.snapshots_written += 1
        self._last_snapshot_tick = self.ticks

    # -- bookkeeping -------------------------------------------------------------

    @property
    def logical_tokens(self) -> int:
        """Tokens logically resident over the run: fresh appends plus
        prompt tokens served by page reference."""
        return self.tokens_appended + self.shared_tokens

    @property
    def logical_physical_ratio(self) -> float:
        """Prefix-sharing win: logical tokens per physically-written token
        (1.0 when nothing was shared)."""
        return self.logical_tokens / max(1, self.tokens_appended)
