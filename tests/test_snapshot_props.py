"""Crash-consistency properties for serve/snapshot.py.

The snapshot subsystem serializes the COMPLETE paged serving state —
both page pools verbatim (bf16 raw, int8 + per-row scales), block
tables, free list, refcounts, pending COW reservations, the retained
pool, scheduler queues, per-request lifecycle state and RNG keys — so a
killed engine can be rebuilt from disk and finish EXACTLY the run it
was going to produce.  This module asserts that headline end to end:

  * ROUNDTRIP — restore(save(engine)) and the original engine, both
    drained to completion, emit bit-identical tokens with identical
    terminal statuses;
  * KILL-AND-RECOVER — a seeded fuzz drives an engine under a random
    recoverable fault plan (squeeze/evict/drop/poison) PLUS injected
    ``kill`` events; every kill is recovered by restoring the latest
    on-disk snapshot, resubmitting the not-yet-snapshotted requests
    (rids realign deterministically) and re-arming the plan with the
    fired kills filtered out.  The recovered run's outputs and statuses
    must be bit-identical to an uninterrupted oracle engine driven by
    the same schedule and the same recoverable plan — across int8
    pools, speculation, prefix sharing on/off, and cold recovery (kill
    before the first snapshot lands);
  * ATOMICITY — a truncated snapshot file is detected by checksum
    (``SnapshotCorruptError``) and ``latest_snapshot`` falls back to
    the previous intact file, so a crash DURING a snapshot write can
    never poison recovery;
  * TYPED MISMATCH — restoring into an engine whose architecture or
    serving geometry differs from the snapshot's fingerprint raises
    ``SnapshotMismatchError`` naming every differing field;
  * WEDGE DETECTOR — ``ServeConfig.wedge_ticks`` bounds consecutive
    idle-but-busy ticks, and ``no_progress_ticks`` surfaces the count.

Explicit seeded fuzz loops (no hypothesis in the container image);
assertion messages carry ``[repro: schedule_seed=N fault_seed=M]``.
"""
import dataclasses
import json
import os

import numpy as np
import jax
import pytest

from repro.configs import get
from repro.models import get_model
from repro.serve.engine import (PagedEngine, RequestStatus, ServeConfig,
                                TERMINAL_STATUSES)
from repro.serve.faults import (EngineKilled, FaultEvent, FaultPlan,
                                RECOVERABLE_KINDS)
from repro.serve import snapshot as snap

from test_paged_cache_props import (_assert_drained_clean,
                                    _assert_tokens_identical, _check_tick,
                                    _seeded_repro)

PROMPT_LENS = (3, 5, 8)
BUDGETS = (3, 5)
MAX_TICKS = 3000


@pytest.fixture(scope="module")
def harness():
    cfg = get("qwen2-0.5b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


@pytest.fixture(scope="module")
def int8_harness(harness):
    """Same weights, int8 page pools: kv_dtype only changes the paged
    cache, so the bf16 harness params transfer verbatim."""
    model, params = harness
    icfg = dataclasses.replace(model.cfg, kv_dtype="int8")
    return get_model(icfg), params


@pytest.fixture(scope="module")
def int8_draft(int8_harness):
    """1-layer slice of the int8 target as the draft — the draft pool is
    quantized too, so the snapshot carries int8 pages + scales for BOTH
    pools."""
    model, params = int8_harness
    dcfg = dataclasses.replace(model.cfg, n_layers=1)
    dparams = dict(params)
    dparams["blocks"] = jax.tree.map(lambda x: x[:1], params["blocks"])
    return get_model(dcfg), dparams


# ---------------------------------------------------------------------------
# deterministic driver: submissions gated on the engine's OWN tick counter,
# so a restored engine replays the exact schedule the dead one was running
# ---------------------------------------------------------------------------

def _make_items(model, seed, n=8, last_tick=18):
    """The schedule: ``[(at_tick, prompt, budget), ...]`` submitted in
    order when the engine's tick counter reaches ``at_tick``.  Pure
    function of the seed — both the oracle run and every recovery replay
    rebuild it identically."""
    rng = np.random.RandomState(seed)
    ats = sorted(int(t) for t in rng.randint(0, last_tick, size=n))
    return [(at,
             rng.randint(0, model.cfg.vocab_size,
                         size=int(rng.choice(PROMPT_LENS))).astype(np.int32),
             int(rng.choice(BUDGETS)))
            for at in ats]


def _run_schedule(pe, items):
    """Drive the engine to completion, submitting ``items`` when their
    tick gate passes.  ``pe._next_rid`` doubles as the submission cursor:
    rids are sequential from 0 (no admission rejection in these configs),
    so after a restore the cursor lands exactly on the first request the
    snapshot does NOT contain and the replay resubmits from there."""
    t = 0
    while True:
        while pe._next_rid < len(items) and items[pe._next_rid][0] <= pe.ticks:
            want_rid = pe._next_rid
            _, p, b = items[want_rid]
            assert pe.submit(p, b) == want_rid, "rid realignment broke"
        if not pe.busy and pe._next_rid >= len(items) and not pe._squeezed:
            return
        pe.step()
        _check_tick(pe)
        t += 1
        assert t < MAX_TICKS, "schedule failed to terminate"


def _drive_with_recovery(mk_engine, items, plan, snap_dir):
    """The recovery protocol under test: on ``EngineKilled``, restore the
    newest intact snapshot into a FRESH engine (or start cold if none
    landed yet), re-arm the plan with fired kills filtered out, and keep
    driving.  Returns (engine, kills, restores)."""
    pe = mk_engine()
    if plan is not None:
        pe.install_faults(plan)
    kills = restores = 0
    while True:
        try:
            _run_schedule(pe, items)
            return pe, kills, restores
        except EngineKilled as e:
            kills += 1
            assert kills < 10, "kill storm: recovery never converged"
            latest = snap.latest_snapshot(snap_dir)
            pe = mk_engine()
            if latest is not None:
                snap.restore_engine(pe, latest)
                restores += 1
            plan = plan.without_kills_through(e.tick)
            pe.install_faults(plan)


def _assert_runs_identical(got, want, label):
    """Full bit-identity between two drained engines: same rid universe,
    same terminal status per rid, same tokens per rid."""
    assert set(got.results) == set(want.results), f"{label}: rid sets differ"
    for rid in sorted(want.results):
        gs, ws = got.status[rid], want.status[rid]
        assert gs in TERMINAL_STATUSES and gs is ws, \
            f"{label} rid={rid}: status {gs} vs oracle {ws}"
        _assert_tokens_identical(got.results[rid], want.results[rid],
                                 label=f"{label} rid={rid}")


def _mk(model, params, snap_dir, *, every=2, spec=None,
        prefix_sharing=True):
    spec_k, dm, dp = spec if spec else (0, None, None)
    return PagedEngine(model, params, ServeConfig(
        max_batch=3, max_seq=48, page_size=4, num_pages=8,
        prefill_chunk=3, max_new_tokens=max(BUDGETS), spec_k=spec_k,
        prefix_sharing=prefix_sharing,
        snapshot_every_ticks=every if snap_dir else 0,
        snapshot_dir=snap_dir or ""),
        draft_model=dm, draft_params=dp)


def _kill_restore_case(model, params, seed, snap_dir, *, spec=None,
                       prefix_sharing=True, kill_ticks=(8,),
                       with_faults=True, every=2):
    """One seeded kill-and-recover drill vs its uninterrupted oracle."""
    with _seeded_repro(schedule_seed=seed,
                       fault_seed=seed if with_faults else None):
        items = _make_items(model, seed)
        recoverable = (FaultPlan.random(seed, n_events=4, max_tick=20,
                                        max_batch=3, max_pages=3,
                                        max_duration=3,
                                        kinds=RECOVERABLE_KINDS).events
                       if with_faults else ())
        oracle, _, _ = _drive_with_recovery(
            lambda: _mk(model, params, None, spec=spec,
                        prefix_sharing=prefix_sharing),
            items, FaultPlan(list(recoverable)), snap_dir)
        plan = FaultPlan(list(recoverable)
                         + [FaultEvent(t, "kill") for t in kill_ticks])
        pe, kills, restores = _drive_with_recovery(
            lambda: _mk(model, params, snap_dir, every=every, spec=spec,
                        prefix_sharing=prefix_sharing),
            items, plan, snap_dir)
        assert kills == len(kill_ticks), "a scheduled kill never fired"
        _assert_runs_identical(pe, oracle, f"seed={seed}")
        pe.kv.check()
        if pe.dkv is not None:
            pe.dkv.check()
        _assert_drained_clean(pe)
        return pe, restores


# ---------------------------------------------------------------------------
# roundtrip: restore(save(engine)) continues bit-identically
# ---------------------------------------------------------------------------

def test_snapshot_roundtrip_continue_identical(harness, tmp_path):
    """Mid-flight snapshot, then BOTH the original engine and a fresh
    restore drain to completion: tokens and statuses bit-identical, and
    the restored pool passes every per-tick invariant on the way."""
    model, params = harness
    items = _make_items(model, seed=42, n=5, last_tick=1)
    pe = _mk(model, params, None)
    for _, p, b in items:
        pe.submit(p, b)
    for _ in range(4):
        pe.step()
        _check_tick(pe)
    path = snap.snapshot_path(str(tmp_path), pe.ticks)
    snap.save_snapshot(pe, path)
    assert os.path.exists(path)

    fresh = _mk(model, params, None)
    snap.restore_engine(fresh, path)
    assert fresh.ticks == pe.ticks
    assert fresh._next_rid == pe._next_rid
    _check_tick(fresh)                     # pool sane immediately on restore

    _run_schedule(pe, items)
    _run_schedule(fresh, items)
    _assert_runs_identical(fresh, pe, "roundtrip")
    _assert_drained_clean(fresh)


# ---------------------------------------------------------------------------
# kill-and-recover fuzz: int8 x speculation x prefix sharing x fault plans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_kill_restore_fuzz(harness, tmp_path, seed):
    """bf16, prefix sharing on, random recoverable plan + one kill late
    enough that a snapshot exists — recovery must restore from disk (not
    just cold-start) and still match the oracle bit for bit."""
    model, params = harness
    _, restores = _kill_restore_case(model, params, seed, str(tmp_path),
                                     kill_ticks=(9,))
    assert restores == 1, "kill fired but recovery never restored a snapshot"


def test_kill_restore_int8_speculative(int8_harness, int8_draft, tmp_path):
    """The hard quadrant: int8 target AND draft pools (pages + per-row
    scales snapshotted verbatim), speculation in flight, double kill —
    the second kill lands on the RESTORED engine, so recovery must be
    re-entrant."""
    model, params = int8_harness
    dm, dp = int8_draft
    pe, restores = _kill_restore_case(model, params, 5, str(tmp_path),
                                      spec=(2, dm, dp),
                                      kill_ticks=(7, 13))
    assert pe.kv.quantized and pe.dkv.quantized
    assert restores >= 1


def test_kill_restore_no_prefix_sharing(harness, tmp_path):
    """Sharing off: the restored prefix index must stay empty instead of
    being rebuilt from histories, and recovery still matches the oracle."""
    model, params = harness
    _kill_restore_case(harness[0], harness[1], 3, str(tmp_path),
                       prefix_sharing=False, kill_ticks=(8,))


def test_kill_before_first_snapshot_cold_recovery(harness, tmp_path):
    """Kill at tick 1 with snapshot cadence 50: no snapshot exists, so
    recovery cold-starts a fresh engine and resubmits EVERYTHING — the
    degenerate case must still be oracle-identical."""
    model, params = harness
    _, restores = _kill_restore_case(model, params, 7, str(tmp_path),
                                     kill_ticks=(1,), with_faults=False,
                                     every=50)
    assert restores == 0, "no snapshot could exist, yet restore ran"


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(8, 16)))
def test_kill_restore_fuzz_long(harness, tmp_path, seed):
    model, params = harness
    _kill_restore_case(model, params, seed, str(tmp_path),
                       kill_ticks=(int(5 + seed % 9),),
                       with_faults=bool(seed % 2))


# ---------------------------------------------------------------------------
# atomicity: truncation is detected, recovery falls back
# ---------------------------------------------------------------------------

def test_truncated_snapshot_detected_and_skipped(harness, tmp_path):
    """A crash mid-write leaves either no file (atomic rename) or — if
    the filesystem is ruder — a short/garbled one.  Every truncation
    point must raise ``SnapshotCorruptError`` on load, and
    ``latest_snapshot`` must fall back to the previous intact file."""
    model, params = harness
    pe = _mk(model, params, None)
    for _, p, b in _make_items(model, seed=9, n=3, last_tick=1):
        pe.submit(p, b)
    for _ in range(2):
        pe.step()
    good = snap.snapshot_path(str(tmp_path), 1)
    newer = snap.snapshot_path(str(tmp_path), 2)
    snap.save_snapshot(pe, good)
    snap.save_snapshot(pe, newer)
    assert snap.latest_snapshot(str(tmp_path)) == newer

    blob = open(newer, "rb").read()
    # representative truncation points: inside the magic, the header,
    # the state JSON, and the raw array bytes (checksum tail cut off)
    for cut in (4, 24, len(blob) // 2, len(blob) - 3):
        with open(newer, "wb") as f:
            f.write(blob[:cut])
        with pytest.raises(snap.SnapshotCorruptError):
            snap.load_snapshot(newer)
        assert snap.latest_snapshot(str(tmp_path)) == good, \
            f"truncation at byte {cut} not skipped"
    # the fallback file actually restores
    fresh = _mk(model, params, None)
    snap.restore_engine(fresh, good)
    assert fresh.ticks == pe.ticks


def test_prune_keeps_newest(harness, tmp_path):
    model, params = harness
    pe = _mk(model, params, None)
    pe.submit(np.arange(3, dtype=np.int32), 3)
    pe.step()
    for t in (1, 2, 3, 4):
        snap.save_snapshot(pe, snap.snapshot_path(str(tmp_path), t))
    removed = snap.prune_snapshots(str(tmp_path), keep=2)
    assert [os.path.basename(r) for r in removed] == \
        ["snap-00000001.bin", "snap-00000002.bin"]
    assert sorted(os.listdir(tmp_path)) == \
        ["snap-00000003.bin", "snap-00000004.bin"]


# ---------------------------------------------------------------------------
# fingerprint mismatch: typed, named fields
# ---------------------------------------------------------------------------

def test_fingerprint_mismatch_typed(harness, tmp_path):
    model, params = harness
    pe = _mk(model, params, None)
    pe.submit(np.arange(3, dtype=np.int32), 3)
    pe.step()
    path = snap.snapshot_path(str(tmp_path), pe.ticks)
    snap.save_snapshot(pe, path)
    other = PagedEngine(model, params, ServeConfig(
        max_batch=2, max_seq=64, page_size=4, num_pages=8,
        prefill_chunk=3, max_new_tokens=max(BUDGETS)))
    with pytest.raises(snap.SnapshotMismatchError) as ei:
        snap.restore_engine(other, path)
    msg = str(ei.value)
    assert "max_batch" in msg and "max_seq" in msg


def test_token_major_version_refused(harness, tmp_path):
    """A version-1 file holds token-major pools: the reader refuses it
    with the typed mismatch error before touching its arrays."""
    model, params = harness
    pe = _mk(model, params, None)
    pe.submit(np.arange(3, dtype=np.int32), 3)
    pe.step()
    path = snap.save_snapshot(pe, snap.snapshot_path(str(tmp_path), 1))
    blob = open(path, "rb").read()
    off = len(snap.MAGIC)
    hlen = int.from_bytes(blob[off:off + 8], "little")
    header = json.loads(blob[off + 8:off + 8 + hlen])
    header["version"] = 1
    old = json.dumps(header).encode("utf-8")
    with open(path, "wb") as f:
        f.write(snap.MAGIC + len(old).to_bytes(8, "little") + old
                + blob[off + 8 + hlen:])
    with pytest.raises(snap.SnapshotMismatchError, match="version 1"):
        snap.restore_engine(_mk(model, params, None), path)


def test_counter_missing_from_an_older_file_reads_zero(harness, tmp_path):
    """A version-2 file written before the engine counted ``dispatches``
    restores with that counter at 0 and the rest verbatim, and the
    restored engine drains bit-identically to the one that wrote it."""
    import zlib
    model, params = harness
    items = _make_items(model, seed=7, n=4, last_tick=1)
    pe = _mk(model, params, None)
    for _, p, b in items:
        pe.submit(p, b)
    for _ in range(3):
        pe.step()
    assert pe.dispatches > 0
    path = snap.save_snapshot(pe, snap.snapshot_path(str(tmp_path), 3))
    blob = open(path, "rb").read()
    off = len(snap.MAGIC)
    hlen = int.from_bytes(blob[off:off + 8], "little")
    header = json.loads(blob[off + 8:off + 8 + hlen])
    body = blob[off + 8 + hlen:]
    state = json.loads(body[:header["state_len"]])
    del state["engine"]["counters"]["dispatches"]
    state_b = json.dumps(state).encode("utf-8")
    body = state_b + body[header["state_len"]:]
    header.update(state_len=len(state_b), body_len=len(body),
                  body_crc32=zlib.crc32(body) & 0xFFFFFFFF)
    header_b = json.dumps(header).encode("utf-8")
    with open(path, "wb") as f:
        f.write(snap.MAGIC + len(header_b).to_bytes(8, "little") + header_b
                + body)
    fresh = snap.restore_engine(_mk(model, params, None), path)
    assert fresh.dispatches == 0
    assert fresh.steps_run == pe.steps_run
    assert fresh.tokens_appended == pe.tokens_appended
    _run_schedule(pe, items)
    _run_schedule(fresh, items)
    _assert_runs_identical(fresh, pe, "old file")


# ---------------------------------------------------------------------------
# wedge detector: configurable threshold, surfaced counter
# ---------------------------------------------------------------------------

def test_wedge_ticks_configurable(harness):
    """A squeeze that outlives any admissible progress trips the wedge
    detector after ``wedge_ticks`` consecutive idle-but-busy ticks — at
    the CONFIGURED threshold, not the 10k default — and the
    ``no_progress_ticks`` counter records the idle span."""
    model, params = harness
    pe = PagedEngine(model, params, ServeConfig(
        max_batch=1, max_seq=32, page_size=4, num_pages=4,
        prefill_chunk=2, max_new_tokens=3, wedge_ticks=5))
    pe.submit(np.arange(5, dtype=np.int32), 3)
    pe.install_faults(FaultPlan([FaultEvent(1, "squeeze", pages=3,
                                            duration=500)]))
    with pytest.raises(RuntimeError, match="wedged"):
        for _ in range(50):
            pe.step()
    assert pe.no_progress_ticks >= 5
