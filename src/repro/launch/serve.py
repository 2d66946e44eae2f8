"""Serving launcher: production mesh + the paged continuous-batching engine.

On a CPU host run --local-smoke (reduced config, real engine).  At full
width the default granite-8b (~16.5 GB of bf16 weights) does not fit one
16 GB TPU v5e; phi4-mini-3.8b (~7.7 GB) does, and ``chip_smoke.py`` at the
checkout root serves it through ``build_engine``.  The
production path is the ``PagedEngine`` (refcounted page pool with prefix
sharing + copy-on-write, tick scheduler with partial grants, chunked
prefill through the one fused decode cell); --whole-batch falls back to
lockstep whole-batch generation (``ServingEngine``), --legacy-loop to the
per-token host loop, both kept for measured comparison.
"""
import argparse
import os
import sys
from pathlib import Path

import numpy as np

# the checkout root (src/repro/launch/serve.py -> three levels up)
REPO_ROOT = Path(__file__).resolve().parents[3]


class SpecCapacityError(ValueError):
    """``--spec-k`` asks the draft pool for more rows than its block
    tables can hold: the draft cache must fit every request's full span
    PLUS k in-flight proposals, and ``PagedKVCache.ensure`` raising
    mid-run (after minutes of serving) is the failure mode this
    startup check replaces."""


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache before the first
    compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and nothing else is set here; otherwise the cache lives at the
    fixed path ``<checkout>/.jax_cache`` (the path is part of the cache
    key, so it must not move between runs)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))


def build_engine(cfg, scfg, *, draft_cfg=None, whole_batch: bool = False):
    """The model for ``cfg`` with seeded random weights, and the serving
    engine over it: the ``PagedEngine`` (with a draft model when
    ``draft_cfg`` is given), or the lockstep ``ServingEngine`` when
    ``whole_batch``.  Weights are initialised in one jitted program — an
    eager init materializes each stacked leaf in f32 before the cast, a
    transient that a 3.8B model cannot afford next to its weights on one
    16 GB chip."""
    import jax
    from repro.models import get_model
    from repro.serve.engine import PagedEngine, ServingEngine

    model = get_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    if whole_batch:
        return ServingEngine(model, params, scfg)
    draft_model = draft_params = None
    if draft_cfg is not None:
        draft_model = get_model(draft_cfg)
        draft_params = jax.jit(draft_model.init)(jax.random.key(1))
    return PagedEngine(model, params, scfg, draft_model=draft_model,
                       draft_params=draft_params)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--local-smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--whole-batch", action="store_true",
                    help="lockstep whole-batch generation instead of the "
                         "paged continuous-batching engine")
    ap.add_argument("--legacy-loop", action="store_true",
                    help="with --whole-batch: per-token host loop instead "
                         "of fused decode_many")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable prompt-prefix page sharing on admission "
                         "(implies no cross-lifetime retention)")
    ap.add_argument("--no-retain-prefixes", action="store_true",
                    help="disable cross-lifetime prefix retention: a "
                         "finished/evicted request's page-aligned prefix "
                         "pages return to the free list immediately "
                         "instead of staying adoptable by digest after "
                         "the donor is gone")
    ap.add_argument("--retain-policy", default="lru",
                    choices=("lru", "popularity"),
                    help="retained-pool reclamation order under pool "
                         "pressure: least-recently-touched entries first "
                         "(lru) or fewest-adoptions first (popularity — "
                         "keeps hot system prompts alive longest)")
    ap.add_argument("--retain-pool-pages", type=int, default=0,
                    help="cap on retained-ONLY pages held idle (0 = "
                         "pool-bounded: retention uses whatever the free "
                         "list spares and pressure reclaims it lazily)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=4)
    ap.add_argument("--prefill-chunk-tokens", type=int, default=0,
                    help="ragged prefill lane: prompt tokens per chunked-"
                         "prefill kernel step (0 = auto: 2x --page-size; "
                         "a prompt costs ceil(prompt/T) dispatches "
                         "instead of one decode step per token)")
    ap.add_argument("--no-prefill-lane", action="store_true",
                    help="route prompts through the decode cell one "
                         "token per step (legacy prefill-by-decode, kept "
                         "for measured comparison)")
    ap.add_argument("--pages-per-step", type=int, default=1,
                    help="ragged paged-prefill kernel page-list "
                         "blocking: pages swept per grid step (cuts grid "
                         "steps by P for long slots; only meaningful with "
                         "the pallas attention impl; the decode kernel "
                         "derives its page block from the pool)")
    ap.add_argument("--kv-dtype", default="bf16", choices=("bf16", "int8"),
                    help="paged KV page pool storage: bf16 (model compute "
                         "dtype) or int8 (quantized pools + per-row f32 "
                         "scales dequantized inside the page sweep — "
                         "~halves the sweep's HBM bytes and ~doubles "
                         "resident tokens per HBM byte, at a bounded "
                         "logit drift)")
    ap.add_argument("--draft-arch", default="",
                    help="speculative decoding: registry id of the DRAFT "
                         "model (must share the target's tokenizer / "
                         "vocab size); proposes --spec-k tokens per "
                         "decode tick from its own paged KV pool, the "
                         "target verifies the window in ONE ragged "
                         "prefill-lane dispatch")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft proposals per decode tick (0 = off). A "
                         "tick keeps the accepted prefix plus one bonus "
                         "token, so a slot advances 1..k+1 tokens per "
                         "verify dispatch; greedy output is BIT-IDENTICAL "
                         "to plain decode regardless of accept rate")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission: reject submits once this many "
                         "requests are waiting (0 = unbounded); rejected "
                         "requests get a typed REJECTED status, never an "
                         "engine crash")
    ap.add_argument("--deadline-ticks", type=int, default=0,
                    help="per-request tick deadline (0 = none): a request "
                         "still unfinished this many engine ticks after "
                         "submit is retired DEADLINE_EXCEEDED with its "
                         "partial output")
    ap.add_argument("--preempt-policy", default="fewest-tokens",
                    choices=("fewest-tokens", "most-pages"),
                    help="victim choice when the page pool wedges: evict "
                         "the request with the fewest generated tokens "
                         "(least recompute work lost) or the one holding "
                         "the most pages (frees the most pool per "
                         "eviction); preempted requests requeue and "
                         "recompute to a token-identical result")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable preempt-and-recompute: a wedged page "
                         "pool raises 'page pool exhausted' (the "
                         "pre-overload-safety behavior, kept for measured "
                         "comparison)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="crash consistency: write a full-state snapshot "
                         "(both page pools verbatim, tables, queue, "
                         "request lifecycle, RNG keys) every N engine "
                         "ticks (0 = off); atomic write-then-rename, "
                         "checksummed, pruned to the newest few files")
    ap.add_argument("--snapshot-dir", default="",
                    help="directory for snap-<tick>.bin files (required "
                         "with --snapshot-every)")
    ap.add_argument("--restore-from", default="",
                    help="restore the engine from a snapshot file and "
                         "resume its in-flight work instead of submitting "
                         "the demo workload; the snapshot's config "
                         "fingerprint must match the launch flags (typed "
                         "fast-fail BEFORE the engine builds)")
    ap.add_argument("--wedge-ticks", type=int, default=10_000,
                    help="consecutive idle-but-busy ticks before the "
                         "engine declares itself wedged and raises (a "
                         "bookkeeping-bug tripwire, not a tuning knob)")
    ap.add_argument("--sys-prompt-tokens", type=int, default=16,
                    help="shared system-prompt length for the demo "
                         "workload; keep it a MULTIPLE of --page-size — a "
                         "page-aligned shared prefix needs zero "
                         "copy-on-write (every shared page is full), a "
                         "mid-page prefix copies one page per sharer "
                         "(measured ~15%% tokens/s on the smoke config)")
    args = ap.parse_args()
    if args.legacy_loop and not args.whole_batch:
        ap.error("--legacy-loop only applies to --whole-batch generation "
                 "(the paged engine always runs the fused decode cell)")
    if args.page_size < 1:
        ap.error("--page-size must be >= 1 (tokens per KV page)")
    if args.pages_per_step < 1:
        ap.error("--pages-per-step must be >= 1 (pages swept per grid "
                 "step)")
    if args.prefill_chunk_tokens < 0:
        ap.error("--prefill-chunk-tokens must be >= 0 (0 = auto)")
    if args.max_queue < 0:
        ap.error("--max-queue must be >= 0 (0 = unbounded admission)")
    if args.deadline_ticks < 0:
        ap.error("--deadline-ticks must be >= 0 (0 = no deadline)")
    if args.retain_pool_pages < 0:
        ap.error("--retain-pool-pages must be >= 0 (0 = pool-bounded)")
    if args.spec_k < 0:
        ap.error("--spec-k must be >= 0 (draft proposals per decode tick)")
    if args.spec_k and not args.draft_arch:
        ap.error("--spec-k needs --draft-arch (a draft model proposes the "
                 "tokens the target verifies)")
    if args.draft_arch and not args.spec_k:
        ap.error("--draft-arch without --spec-k does nothing; pass "
                 "--spec-k >= 1 to enable speculative decoding")
    if args.spec_k and args.whole_batch:
        ap.error("speculative decoding is a paged-engine mode (draft pages "
                 "+ ragged verify); drop --whole-batch")
    if args.spec_k and args.no_prefill_lane:
        ap.error("speculative verify rides the ragged prefill lane; drop "
                 "--no-prefill-lane")
    if args.spec_k and args.temperature != 0.0:
        ap.error("speculative decoding is greedy-only (acceptance compares "
                 "argmax tokens); use --temperature 0")
    if args.snapshot_every < 0:
        ap.error("--snapshot-every must be >= 0 (0 = no snapshots)")
    if args.snapshot_every and not args.snapshot_dir:
        ap.error("--snapshot-every needs --snapshot-dir (where the "
                 "snap-<tick>.bin files land)")
    if args.restore_from and args.whole_batch:
        ap.error("--restore-from restores the PAGED engine's state; the "
                 "whole-batch path has no snapshot format — drop "
                 "--whole-batch")
    if args.wedge_ticks < 1:
        ap.error("--wedge-ticks must be >= 1 (idle ticks before the "
                 "wedge tripwire fires)")
    if args.kv_dtype == "int8" and args.whole_batch:
        ap.error("--kv-dtype int8 quantizes the PAGED page pools (the "
                 "Pallas/reference paged attention path); the whole-batch "
                 "dense cache has no page pool to quantize — drop "
                 "--whole-batch or use --kv-dtype bf16")
    if args.no_prefix_sharing and not args.no_retain_prefixes:
        print("[launch.serve] NOTE: --no-prefix-sharing disables the "
              "donor index, so cross-lifetime retention is off too "
              "(retention is digest-keyed prefix sharing)")
    if args.deadline_ticks and args.deadline_ticks < args.new_tokens:
        print(f"[launch.serve] NOTE: --deadline-ticks "
              f"({args.deadline_ticks}) is below --new-tokens "
              f"({args.new_tokens}) — a decode tick emits at most one "
              f"token per request, so most requests will retire "
              f"DEADLINE_EXCEEDED with partial output")
    if args.no_preempt and args.max_queue == 0:
        print("[launch.serve] NOTE: --no-preempt with unbounded admission "
              "restores the crashing overload behavior — an oversubscribed "
              "pool raises 'page pool exhausted' instead of preempting")
    if not args.no_prefill_lane and args.prefill_chunk_tokens % args.page_size:
        print(f"[launch.serve] NOTE: --prefill-chunk-tokens "
              f"({args.prefill_chunk_tokens}) is not a multiple of "
              f"--page-size ({args.page_size}) — prefill chunk grants are "
              f"clipped to page boundaries, so a non-aligned chunk wastes "
              f"its tail rows on every mid-prompt chunk; pick a multiple "
              f"of the page size (the same alignment guidance as "
              f"--sys-prompt-tokens below)")

    from repro import configs
    from repro.serve.engine import ServeConfig

    cfg = configs.get(args.arch)
    if args.local_smoke:
        cfg = cfg.reduced()
    if (args.pages_per_step != 1 or args.kv_dtype != "bf16"
            or args.draft_arch):
        import dataclasses
        cfg = dataclasses.replace(cfg, pages_per_step=args.pages_per_step,
                                  kv_dtype=args.kv_dtype,
                                  draft_arch=args.draft_arch)
    if args.sys_prompt_tokens % args.page_size:
        print(f"[launch.serve] NOTE: sys prompt ({args.sys_prompt_tokens} "
              f"tokens) is not page-aligned (page {args.page_size}) — every "
              f"sharer will copy-on-write the partial trailing page; align "
              f"shared system prompts to the page size for zero-copy "
              f"sharing")
    dcfg = None
    if args.spec_k:
        dcfg = configs.get(args.draft_arch)
        if args.local_smoke:
            dcfg = dcfg.reduced()
        if dcfg.vocab_size != cfg.vocab_size:
            ap.error(f"--draft-arch {args.draft_arch!r} has vocab "
                     f"{dcfg.vocab_size}, target has {cfg.vocab_size} — "
                     f"speculation needs a shared tokenizer")
    # 2x batch requests of (prompt<=16 + new_tokens) tokens each; the paged
    # engine recycles pages across requests so max_seq only bounds ONE
    # request's span, not the engine's lifetime
    # worst-case request span: prompt (sys + tail <= 8) + the largest
    # staggered budget (new_tokens + 2*(batch-1)) + chunk-overshoot margin
    max_seq = max(64, args.sys_prompt_tokens + 8 + args.new_tokens
                  + 2 * (args.batch - 1) + 16)
    if args.spec_k:
        # DRAFT-POOL CAPACITY FAST-FAIL: the draft cache must hold a
        # request's full span plus k in-flight proposals — past this
        # bound ``dkv.ensure`` raises deep inside a tick, potentially
        # minutes into a run.  Same block-table geometry as the target
        # (max_seq rows), so the check is pure arithmetic.
        span_max = (args.sys_prompt_tokens + 8 + args.new_tokens
                    + 2 * (args.batch - 1))
        if span_max + args.spec_k > max_seq:
            raise SpecCapacityError(
                f"--spec-k {args.spec_k} overflows the draft pool: the "
                f"worst-case request span is {span_max} tokens and the "
                f"draft block tables hold max_seq={max_seq} rows, so up "
                f"to {max_seq - span_max} proposals fit in flight; "
                f"lower --spec-k or --new-tokens/--sys-prompt-tokens")
    if args.restore_from:
        # FINGERPRINT FAST-FAIL: compare the snapshot header against the
        # launch flags BEFORE paying for engine construction — a
        # mismatched restore must die with a typed error naming the
        # divergent knob, not a shape error mid-restore
        from repro.serve.snapshot import SnapshotMismatchError, load_header
        fp = load_header(args.restore_from)["fingerprint"]
        want = {"arch": cfg.name, "kv_dtype": cfg.kv_dtype,
                "max_batch": args.batch, "max_seq": max_seq,
                "page_size": args.page_size, "spec_k": args.spec_k,
                "temperature": args.temperature,
                "prefill_lane": not args.no_prefill_lane}
        diff = {k: (fp.get(k), v) for k, v in want.items()
                if fp.get(k) != v}
        if diff:
            raise SnapshotMismatchError(
                f"{args.restore_from}: snapshot was taken from a "
                f"different serving config (snapshot vs launch flags): "
                f"{diff}")
    scfg = ServeConfig(max_batch=args.batch, max_seq=max_seq,
                       max_new_tokens=args.new_tokens,
                       temperature=args.temperature,
                       fused=not args.legacy_loop,
                       page_size=args.page_size,
                       prefill_chunk=args.prefill_chunk,
                       prefill_lane=not args.no_prefill_lane,
                       prefill_chunk_tokens=args.prefill_chunk_tokens,
                       prefix_sharing=not args.no_prefix_sharing,
                       retain_prefixes=not args.no_retain_prefixes,
                       retain_pool_pages=args.retain_pool_pages,
                       retain_policy=args.retain_policy,
                       preempt=not args.no_preempt,
                       preempt_policy=args.preempt_policy,
                       max_queue=args.max_queue,
                       deadline_ticks=args.deadline_ticks,
                       wedge_ticks=args.wedge_ticks,
                       snapshot_every_ticks=args.snapshot_every,
                       snapshot_dir=args.snapshot_dir,
                       spec_k=args.spec_k)
    rng = np.random.RandomState(0)
    enable_compile_cache()
    engine = build_engine(cfg, scfg, draft_cfg=dcfg,
                          whole_batch=args.whole_batch)

    if args.whole_batch:
        prompts = [rng.randint(0, cfg.vocab_size, size=rng.randint(4, 16)
                               ).astype(np.int32) for _ in range(args.batch)]
        outs = engine.generate_batch(prompts)
        mode = ("legacy per-token loop" if args.legacy_loop
                else "fused decode_many")
        print(f"[launch.serve] generated {sum(len(o) for o in outs)} tokens "
              f"across {len(outs)} requests ({mode})")
        return 0

    # pool capacity banner: resident tokens per HBM byte is the quantized-
    # pool payoff (int8 + per-row f32 scales vs 2-byte bf16 rows); a draft
    # pool, when speculating, is extra HBM the speedup has to pay for
    tok_bytes = engine.kv.page_bytes / engine.kv.page
    pool_bytes = engine.kv.num_pages * engine.kv.page_bytes
    draft_bytes = (engine.dkv.num_pages * engine.dkv.page_bytes
                   if engine.dkv is not None else 0)
    print(f"[launch.serve] pool: kv_dtype={args.kv_dtype}, "
          f"{engine.kv.num_pages} pages x {args.page_size} tokens, "
          f"{engine.kv.page_bytes} B/page ({tok_bytes:.1f} B/token, "
          f"{1.0 / tok_bytes:.4f} resident tokens per HBM byte, "
          f"{pool_bytes / 1e6:.2f} MB pool"
          + (f" + {draft_bytes / 1e6:.2f} MB draft pool" if draft_bytes
             else "") + ")")
    if args.spec_k:
        print(f"[launch.serve] speculative: draft={args.draft_arch} "
              f"k={args.spec_k} (a decode tick verifies k+1 = "
              f"{args.spec_k + 1} positions in one ragged dispatch)")
    if args.restore_from:
        # resume the snapshot's in-flight work instead of submitting the
        # demo workload: queued requests re-admit through the prefill
        # lane, running slots keep decoding from their restored feed
        import time
        from repro.serve.snapshot import restore_engine
        t0 = time.perf_counter()
        restore_engine(engine, args.restore_from)
        restore_ms = (time.perf_counter() - t0) * 1e3
        rids = sorted(engine.status)
        print(f"[launch.serve] restored tick {engine.ticks} from "
              f"{args.restore_from} in {restore_ms:.1f} ms "
              f"({len(engine.queue)} queued, "
              f"{sum(s.active for s in engine.slots)} running, "
              f"{sum(1 for r in rids if engine.status[r].value in ('finished', 'preempted_resumed'))} "
              f"already terminal)")
    else:
        # shared system prompt + per-request tail: the prefix-sharing
        # showcase.  Budgets are STAGGERED so early slots outlive late
        # admissions — a joiner only shares pages while a donor is still
        # resident
        sys_prompt = rng.randint(0, cfg.vocab_size,
                                 size=args.sys_prompt_tokens
                                 ).astype(np.int32)
        rids = [engine.submit(
            np.concatenate(
                [sys_prompt,
                 rng.randint(0, cfg.vocab_size, size=rng.randint(2, 8)
                             ).astype(np.int32)]),
            max_new_tokens=args.new_tokens + (i % args.batch) * 2)
            for i in range(2 * args.batch)]
    results = engine.run()
    util = engine.util_trace
    print(f"[launch.serve] paged: {len(results)} requests, "
          f"{sum(len(results[r]) for r in rids)} tokens, "
          f"{engine.joins} joins over {args.batch} slots in "
          f"{engine.steps_run} ticks; "
          f"shared {engine.shared_tokens} prefix tokens "
          f"(logical/physical x{engine.logical_physical_ratio:.2f}, "
          f"{engine.kv.cow_copies} COW copies), page util "
          f"mean={np.mean(util) if util else 0:.2f} "
          f"max={np.max(util) if util else 0:.2f}")
    print(f"[launch.serve] retention: {engine.kv.retained_hits} retained "
          f"adoptions ({engine.kv.retained_hit_tokens} tokens re-shared "
          f"from dead donors), {engine.kv.retained_pages} pages retained, "
          f"{engine.kv.retained_reclaimed_pages} reclaimed under pressure")
    from repro.serve.engine import RequestStatus
    n_status = {s.value: sum(1 for r in rids if engine.status[r] == s)
                for s in RequestStatus}
    print(f"[launch.serve] overload: {engine.preemptions} preemptions "
          f"({engine.recompute_tokens} recomputed tokens), "
          f"{engine.rejected} rejected, "
          f"{engine.deadline_exceeded} deadline-exceeded, "
          f"{engine.no_progress_ticks} no-progress ticks; statuses "
          + ", ".join(f"{k}={v}" for k, v in n_status.items() if v))
    if args.snapshot_every:
        print(f"[launch.serve] crash consistency: "
              f"{engine.snapshots_written} snapshots written to "
              f"{args.snapshot_dir} (every {args.snapshot_every} ticks, "
              f"newest at tick {engine._last_snapshot_tick})")
    if args.spec_k:
        print(f"[launch.serve] speculative: accept rate "
              f"{engine.accept_rate:.2f} ({engine.spec_accepted}/"
              f"{engine.spec_proposed} proposals), "
              f"{engine.draft_dispatches} draft + "
              f"{engine.verify_dispatches} verify dispatches, "
              f"{engine.spec_trunc_tokens} rejected K/V rows truncated")
    return 0


if __name__ == "__main__":
    sys.exit(main())
