"""Crash-consistent snapshot/restore of the COMPLETE paged serving state.

The paper's discipline — when the platform cannot observe a behavior,
build the measurement yourself — extends across process lifetimes: a
production engine that loses every in-flight request, every retained
prefix page, and every draft cache on a process death is not
production-scale, and no benchmark of the live tick can show what a
restart costs.  This module makes the engine's full state a durable,
verifiable artifact:

  * ``save_snapshot(engine, path)`` serializes EVERYTHING the next tick
    depends on — the target and draft ``PagedKVCache`` pools verbatim
    (bf16 rows, or int8 rows + f32 scale pools), block tables, lengths,
    free lists, refcounts, pending-COW reservations, the retained pool
    (tokens + pages + stamps + hit counts; digests recompute), the
    seized set with its release schedule, every slot (feed token, forced
    queue, output, history, budget), the request table (status, emitted
    tokens, deadlines, preempt counts), the queue order, both RNG keys,
    and the tick/idle/stat counters — to ONE file with a versioned
    header and a CRC32 over the body.  The write is ATOMIC
    (temp file + fsync + ``os.replace``): a crash mid-write leaves the
    previous snapshot intact, and a truncated or bit-flipped file fails
    the checksum instead of restoring garbage.

  * ``restore_engine(engine, path)`` rebuilds a FRESHLY CONSTRUCTED
    engine (weights are the caller's; a snapshot carries state, not
    parameters) into the snapshotted tick: pools re-upload via one host
    array per pool, every table row is marked dirty so the existing
    dirty-row patcher rebuilds the device mirrors on the next tick, the
    live prefix-sharing index and the retained digest index are
    RECOMPUTED from the restored token histories (indexes are derived
    state — recomputing them is self-validating), and in-flight
    requests simply resume: a queued request re-admits through the
    prefill lane, a running slot keeps decoding from its restored feed
    token.  Greedy decode is deterministic and the restore is verbatim,
    so the continuation is bit-identical to the uninterrupted run — the
    property suite pins exactly that, under int8 pools, speculation,
    prefix sharing, retained-page adoption, and random fault plans.

  * A ``fingerprint`` in the header names every shape-determining knob
    (arch dims, kv dtype, pool geometry, spec_k).  Restoring into an
    engine built from a different config raises a typed
    ``SnapshotMismatchError`` at load time, not a shape error deep in a
    tick.

File layout (all integers little-endian)::

    MAGIC "RPSNAP01" | u64 header_len | header JSON | body
    body = state JSON (state_len bytes) | raw array bytes, manifest order
    header = {version, tick, state_len, body_len, body_crc32, fingerprint}

Array bytes are raw ``tobytes()`` with dtype NAME + shape in the
manifest — bfloat16 pools round-trip through ``ml_dtypes`` without a
float32 detour, int8 pools and their f32 scales byte-verbatim.

Directory management (``snapshot_path`` / ``latest_snapshot`` /
``prune_snapshots``) keeps ``snap-<tick>.bin`` files under a configured
dir; ``latest_snapshot`` SKIPS corrupt files, so the kill-and-recover
drill falls back to the newest snapshot that checks out.
"""
from __future__ import annotations

import json
import os
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.cache import COUNTERS as CACHE_COUNTERS
from repro.serve.cache import (PagedKVCache, RetainedPrefix,
                               prefix_digests)
from repro.serve.engine import COUNTERS as ENGINE_COUNTERS

MAGIC = b"RPSNAP01"
# 2: head-major pools (L, num_pages, KV, page, hd) and scales
# (L, num_pages, KV, page, 1); a version-1 file holds token-major pools
# and is refused with SnapshotMismatchError, never misread
VERSION = 2

# engine scalars restored verbatim: its counters and the page cache's
# (CACHE_COUNTERS) ride along so a restored engine's stats and bench
# sections stay continuous across the restart (none affect output), and
# the idle streak and next request id
_ENGINE_COUNTERS = ("_idle", "_next_rid") + ENGINE_COUNTERS


class SnapshotError(RuntimeError):
    """Base for snapshot failures (all typed, none a bare crash)."""


class SnapshotCorruptError(SnapshotError):
    """Bad magic, truncated file, or checksum mismatch — the file is not
    a usable snapshot (a mid-write crash lands here, never in a partial
    restore)."""


class SnapshotMismatchError(SnapshotError):
    """The snapshot is intact but was taken from an engine whose
    shape-determining config differs from the restore target."""


# -- fingerprint --------------------------------------------------------------

def fingerprint(engine) -> Dict[str, Any]:
    """Every knob that determines the SHAPES of the serialized state.
    Two engines with equal fingerprints can exchange snapshots; anything
    else is a typed mismatch at load time."""
    acfg, scfg = engine.model.cfg, engine.cfg
    fp = {
        "arch": acfg.name,
        "n_layers": acfg.n_layers,
        "d_model": acfg.d_model,
        "n_heads": acfg.n_heads,
        "n_kv_heads": acfg.n_kv_heads,
        "d_head": acfg.d_head,
        "vocab_size": acfg.vocab_size,
        "kv_dtype": acfg.kv_dtype,
        "max_batch": scfg.max_batch,
        "max_seq": scfg.max_seq,
        "page_size": engine.kv.page,
        "max_blocks": engine.kv.max_blocks,
        "num_pages": engine.kv.num_pages,
        "spec_k": scfg.spec_k,
        "prefill_lane": bool(scfg.prefill_lane),
        "temperature": scfg.temperature,
        "seed": scfg.seed,
        "draft_arch": engine.draft_model.cfg.name
        if engine.draft_model is not None else None,
    }
    return fp


# -- array codec --------------------------------------------------------------

def _np_dtype(name: str) -> np.dtype:
    """dtype NAME -> dtype, routing the ml_dtypes extension types (e.g.
    "bfloat16") that ``np.dtype`` alone does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _put(arrays: Dict[str, np.ndarray], name: str, arr) -> None:
    arrays[name] = np.ascontiguousarray(np.asarray(arr))


def _encode_arrays(arrays: Dict[str, np.ndarray]):
    """(manifest, concatenated raw bytes) in insertion order."""
    manifest: List[Dict[str, Any]] = []
    blobs: List[bytes] = []
    for name, arr in arrays.items():
        raw = arr.tobytes()
        manifest.append({"name": name, "dtype": arr.dtype.name,
                         "shape": list(arr.shape), "nbytes": len(raw)})
        blobs.append(raw)
    return manifest, b"".join(blobs)


def _decode_arrays(manifest, raw: bytes) -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}
    off = 0
    for ent in manifest:
        n = int(ent["nbytes"])
        if off + n > len(raw):
            raise SnapshotCorruptError(
                f"array {ent['name']!r} runs past the body "
                f"({off + n} > {len(raw)} bytes)")
        dt = _np_dtype(ent["dtype"])
        # frombuffer views are read-only; copy so restored host mirrors
        # (table/length/refcount) stay writable
        out[ent["name"]] = np.frombuffer(
            raw, dtype=dt, count=n // dt.itemsize,
            offset=off).copy().reshape(ent["shape"])
        off += n
    return out


# -- cache (de)serialization ---------------------------------------------------

def _cache_state(kv: PagedKVCache, tag: str,
                 arrays: Dict[str, np.ndarray]) -> Dict[str, Any]:
    _put(arrays, f"{tag}.k", kv.k)
    _put(arrays, f"{tag}.v", kv.v)
    if kv.quantized:
        _put(arrays, f"{tag}.k_scale", kv.k_scale)
        _put(arrays, f"{tag}.v_scale", kv.v_scale)
    _put(arrays, f"{tag}.table", kv.table)
    _put(arrays, f"{tag}.length", kv.length)
    _put(arrays, f"{tag}.refcount", kv.refcount)
    _put(arrays, f"{tag}.retained_refs", kv.retained_refs)
    return {
        "quantized": bool(kv.quantized),
        "owned": [[int(p) for p in o] for o in kv.owned],
        "free": [int(p) for p in kv.free],
        "seized": sorted(int(p) for p in kv.seized),
        # pending COW reservations restore VERBATIM: the source pages are
        # snapshotted pre-flush, so re-running the flush after restore
        # performs the exact copies the dead process never issued
        "pending_cow": [[int(x) for x in t] for t in kv._pending_cow],
        "retain_clock": int(kv._retain_clock),
        # digest keys recompute from tokens on restore (derived state)
        "retained": [{"tokens": [int(t) for t in e.tokens],
                      "pages": [int(p) for p in e.pages],
                      "stamp": int(e.stamp), "hits": int(e.hits)}
                     for e in kv.retained],
        "counters": {c: int(getattr(kv, c)) for c in CACHE_COUNTERS},
    }


def _restore_cache(kv: PagedKVCache, tag: str, state: Dict[str, Any],
                   arrays: Dict[str, np.ndarray]) -> None:
    if bool(state["quantized"]) != bool(kv.quantized):
        raise SnapshotMismatchError(
            f"{tag}: snapshot quantized={state['quantized']} but engine "
            f"pool quantized={kv.quantized}")
    kv.k = jnp.asarray(arrays[f"{tag}.k"])
    kv.v = jnp.asarray(arrays[f"{tag}.v"])
    if kv.quantized:
        kv.k_scale = jnp.asarray(arrays[f"{tag}.k_scale"])
        kv.v_scale = jnp.asarray(arrays[f"{tag}.v_scale"])
    kv.table = arrays[f"{tag}.table"].astype(np.int32)
    kv.length = arrays[f"{tag}.length"].astype(np.int32)
    kv.refcount = arrays[f"{tag}.refcount"].astype(np.int32)
    kv.retained_refs = arrays[f"{tag}.retained_refs"].astype(np.int32)
    kv.owned = [list(o) for o in state["owned"]]
    kv.free = [int(p) for p in state["free"]]
    kv.seized = set(int(p) for p in state["seized"])
    kv._pending_cow = [tuple(int(x) for x in t)
                       for t in state["pending_cow"]]
    kv._retain_clock = int(state["retain_clock"])
    kv.retained = []
    kv._retained_keys = {}
    for ent in state["retained"]:
        toks = [int(t) for t in ent["tokens"]]
        digests = prefix_digests(toks, kv.page)
        entry = RetainedPrefix(
            tokens=toks, pages=[int(p) for p in ent["pages"]],
            keys=[(j + 1, d) for j, d in enumerate(digests)],
            stamp=int(ent["stamp"]), hits=int(ent["hits"]))
        kv.retained.append(entry)
        for key in entry.keys:
            kv._retained_keys.setdefault(key, []).append(entry)
    for c in CACHE_COUNTERS:
        setattr(kv, c, int(state["counters"][c]))
    # every device mirror row rebuilds through the existing dirty-row
    # patcher on the next tick — restore never grows a second upload path
    kv.dirty = set(range(kv.table.shape[0]))


# -- engine (de)serialization --------------------------------------------------

def _engine_state(engine,
                  arrays: Dict[str, np.ndarray]) -> Dict[str, Any]:
    _put(arrays, "engine.feed", engine._feed)
    _put(arrays, "engine.key", jax.random.key_data(engine.key))
    if engine.dkv is not None:
        _put(arrays, "engine.dkey", jax.random.key_data(engine._dkey))
    st = {
        "slots": [{"rid": s.rid, "forced": [int(t) for t in s.forced],
                   "out": [int(t) for t in s.out],
                   "history": [int(t) for t in s.history],
                   "budget": s.budget, "served": s.served,
                   "prompt_left": s.prompt_left, "active": s.active}
                  for s in engine.slots],
        "queue": [r.rid for r in engine.queue],
        "reqs": {str(rid): {"prompt": [int(t) for t in r.prompt],
                            "max_new_tokens": r.max_new_tokens,
                            "deadline_tick": r.deadline_tick,
                            "emitted": [int(t) for t in r.emitted],
                            "preempts": r.preempts}
                 for rid, r in engine._reqs.items()},
        "status": {str(rid): s.value for rid, s in engine.status.items()},
        "reject_reason": {str(rid): r
                          for rid, r in engine.reject_reason.items()},
        "results": {str(rid): [int(t) for t in toks]
                    for rid, toks in engine.results.items()},
        "quarantined": {str(i): t
                        for i, t in engine._quarantined.items()},
        "squeezed": [[until, [int(p) for p in pages]]
                     for until, pages in engine._squeezed],
        "fault_counts": dict(engine.fault_counts),
        "counters": {c: int(getattr(engine, c))
                     for c in _ENGINE_COUNTERS},
    }
    return st


def _restore_engine_state(engine, state: Dict[str, Any],
                          arrays: Dict[str, np.ndarray]) -> None:
    from repro.serve.engine import RequestStatus, Request, _Slot

    engine._feed = arrays["engine.feed"].astype(np.int32)
    engine.key = jax.random.wrap_key_data(
        jnp.asarray(arrays["engine.key"]))
    if engine.dkv is not None:
        if "engine.dkey" not in arrays:
            raise SnapshotMismatchError(
                "speculative engine cannot restore from a snapshot "
                "without a draft RNG key (spec_k mismatch)")
        engine._dkey = jax.random.wrap_key_data(
            jnp.asarray(arrays["engine.dkey"]))
    engine.slots = [
        _Slot(rid=s["rid"], forced=list(s["forced"]), out=list(s["out"]),
              history=list(s["history"]), budget=s["budget"],
              served=s["served"], prompt_left=s["prompt_left"],
              active=s["active"])
        for s in state["slots"]]
    engine._reqs = {
        int(rid): Request(int(rid), np.asarray(r["prompt"], np.int32),
                          r["max_new_tokens"],
                          deadline_tick=r["deadline_tick"],
                          emitted=list(r["emitted"]),
                          preempts=r["preempts"])
        for rid, r in state["reqs"].items()}
    engine.queue = [engine._reqs[rid] for rid in state["queue"]]
    engine.status = {int(rid): RequestStatus(v)
                     for rid, v in state["status"].items()}
    engine.reject_reason = {int(rid): r
                            for rid, r in state["reject_reason"].items()}
    engine.results = {int(rid): list(toks)
                      for rid, toks in state["results"].items()}
    engine._quarantined = {int(i): int(t)
                           for i, t in state["quarantined"].items()}
    engine._squeezed = [(int(until), [int(p) for p in pages])
                        for until, pages in state["squeezed"]]
    engine.fault_counts = {str(k): int(v)
                           for k, v in state["fault_counts"].items()}
    engine._drop_slots = set()
    engine._poison_slots = set()
    # a counter newer than the file's writer reads as 0: version-2 files
    # written before the engine counted ``dispatches`` lack it
    for c in _ENGINE_COUNTERS:
        setattr(engine, c, int(state["counters"].get(c, 0)))
    # the live prefix index is DERIVED state: rebuild it from the
    # restored histories exactly as the ticks that built it would have
    engine._pindex.__init__()
    if engine.cfg.prefix_sharing:
        for i, slot in enumerate(engine.slots):
            if slot.active and slot.history:
                engine._pindex.add(i, slot.history)


# -- container ----------------------------------------------------------------

def save_snapshot(engine, path: str) -> str:
    """Serialize ``engine`` to ``path`` ATOMICALLY (temp + fsync +
    rename): readers only ever see the previous complete snapshot or the
    new complete snapshot, never a partial write.  Returns ``path``."""
    arrays: Dict[str, np.ndarray] = {}
    state: Dict[str, Any] = {
        "engine": _engine_state(engine, arrays),
        "kv": _cache_state(engine.kv, "kv", arrays),
        "dkv": _cache_state(engine.dkv, "dkv", arrays)
        if engine.dkv is not None else None,
    }
    manifest, blob = _encode_arrays(arrays)
    state["arrays"] = manifest
    state_b = json.dumps(state).encode("utf-8")
    body = state_b + blob
    header = {
        "version": VERSION,
        "tick": int(engine.ticks),
        "state_len": len(state_b),
        "body_len": len(body),
        "body_crc32": zlib.crc32(body) & 0xFFFFFFFF,
        "fingerprint": fingerprint(engine),
    }
    header_b = json.dumps(header).encode("utf-8")
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".{os.path.basename(path)}.tmp")
    with open(tmp, "wb") as f:
        f.write(MAGIC)
        f.write(len(header_b).to_bytes(8, "little"))
        f.write(header_b)
        f.write(body)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def _read_container(path: str) -> Tuple[Dict[str, Any], bytes]:
    """(header, body) with magic/length/checksum fully validated —
    truncation and bit flips land in ``SnapshotCorruptError`` here, never
    in a partially-applied restore."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError as e:
        raise SnapshotError(f"cannot read snapshot {path}: {e}") from e
    if data[:len(MAGIC)] != MAGIC:
        raise SnapshotCorruptError(
            f"{path}: bad magic {data[:len(MAGIC)]!r} "
            f"(want {MAGIC!r})")
    off = len(MAGIC)
    if len(data) < off + 8:
        raise SnapshotCorruptError(f"{path}: truncated before header")
    hlen = int.from_bytes(data[off:off + 8], "little")
    off += 8
    if len(data) < off + hlen:
        raise SnapshotCorruptError(f"{path}: truncated header")
    try:
        header = json.loads(data[off:off + hlen])
    except ValueError as e:
        raise SnapshotCorruptError(f"{path}: header is not JSON") from e
    if header.get("version") != VERSION:
        raise SnapshotMismatchError(
            f"{path}: snapshot version {header.get('version')} != "
            f"reader version {VERSION}")
    off += hlen
    body = data[off:]
    if len(body) != int(header["body_len"]):
        raise SnapshotCorruptError(
            f"{path}: body is {len(body)} bytes, header says "
            f"{header['body_len']} (truncated write)")
    crc = zlib.crc32(body) & 0xFFFFFFFF
    if crc != int(header["body_crc32"]):
        raise SnapshotCorruptError(
            f"{path}: body checksum {crc:#010x} != recorded "
            f"{int(header['body_crc32']):#010x}")
    return header, body


def load_header(path: str) -> Dict[str, Any]:
    """Validated header only (cheap relative to a restore) — the launch
    fast-fail compares ``header['fingerprint']`` before building an
    engine."""
    header, _ = _read_container(path)
    return header


def load_snapshot(path: str):
    """(header, state, arrays) — fully decoded and checksum-verified."""
    header, body = _read_container(path)
    state_len = int(header["state_len"])
    try:
        state = json.loads(body[:state_len])
    except ValueError as e:
        raise SnapshotCorruptError(f"{path}: state is not JSON") from e
    arrays = _decode_arrays(state["arrays"], body[state_len:])
    return header, state, arrays


def restore_engine(engine, path: str):
    """Restore ``engine`` (freshly constructed, same configs as the
    snapshotting engine) to the snapshotted tick.  Fingerprints must
    match exactly; pools, tables, slots, queue, requests, RNG keys and
    counters come back verbatim; derived indexes (live prefix index,
    retained digest keys, device mirrors) rebuild from the restored
    state.  Returns ``engine``."""
    header, state, arrays = load_snapshot(path)
    want, got = fingerprint(engine), header["fingerprint"]
    if want != got:
        diff = {k: (got.get(k), want.get(k))
                for k in set(want) | set(got)
                if got.get(k) != want.get(k)}
        raise SnapshotMismatchError(
            f"{path}: snapshot fingerprint does not match this engine "
            f"(snapshot vs engine): {diff}")
    _restore_cache(engine.kv, "kv", state["kv"], arrays)
    if engine.dkv is not None:
        if state["dkv"] is None:
            raise SnapshotMismatchError(
                f"{path}: engine has a draft pool but the snapshot "
                "carries none")
        _restore_cache(engine.dkv, "dkv", state["dkv"], arrays)
    _restore_engine_state(engine, state["engine"], arrays)
    engine._last_snapshot_tick = int(header["tick"])
    return engine


# -- snapshot directories ------------------------------------------------------

def snapshot_path(snap_dir: str, tick: int) -> str:
    return os.path.join(snap_dir, f"snap-{tick:08d}.bin")


def list_snapshots(snap_dir: str) -> List[str]:
    """All snapshot files under ``snap_dir``, oldest tick first."""
    try:
        names = os.listdir(snap_dir)
    except OSError:
        return []
    return [os.path.join(snap_dir, n) for n in sorted(names)
            if n.startswith("snap-") and n.endswith(".bin")]


def latest_snapshot(snap_dir: str) -> Optional[str]:
    """Newest snapshot that passes checksum validation, or None.  A
    truncated newest file (mid-write crash on a filesystem without
    atomic rename, or operator damage) is SKIPPED — recovery falls back
    to the previous complete snapshot instead of failing."""
    for path in reversed(list_snapshots(snap_dir)):
        try:
            _read_container(path)
        except SnapshotError:
            continue
        return path
    return None


def prune_snapshots(snap_dir: str, keep: int) -> List[str]:
    """Drop all but the newest ``keep`` snapshots; returns removed
    paths.  ``keep`` < 1 keeps everything (a retention floor of one live
    snapshot is the point of the exercise)."""
    removed: List[str] = []
    if keep < 1:
        return removed
    snaps = list_snapshots(snap_dir)
    for path in snaps[:-keep] if len(snaps) > keep else []:
        try:
            os.remove(path)
            removed.append(path)
        except OSError:
            pass
    return removed
