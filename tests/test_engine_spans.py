"""The measurement inside the serving path: named device work and the
engine tick's host spans, request stamps and counters.

  * every matmul and custom call of the two compiled lanes carries exactly
    one of the step's eight named scopes in its ``op_name``;
  * the plain decode cell compiles as ``jit_decode_many_paged`` and the two
    Pallas kernels carry their names (``PagedEngine.cell_hlo``);
  * ``request_times`` stamps survive a preemption and a quarantine requeue;
  * with no trace recording, a tick builds no span attributes;
  * ``counters()`` deltas reproduce the per-tick dispatch and upload
    record of a scripted run with faults (the values the engine's retired
    per-tick lists held for the same run).
"""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.configs import get
from repro.models import get_model
from repro.serve import engine as engine_mod
from repro.serve.engine import PagedEngine, ServeConfig
from repro.serve.faults import FaultEvent, FaultPlan

SCOPES = ("embed", "qkv", "kv_write", "attn", "out_proj", "ffn", "head",
          "sample")
KERNELS = {"prefill": "ragged_prefill_attention",
           "decode": "paged_decode_attention"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = .*? ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

# the scripted run's per working tick device calls and upload bytes, as
# the engine's per-tick lists recorded them before they were folded into
# counters
SCRIPT_DISPATCHES = [2, 3, 2, 3, 3, 2, 2, 3, 1, 1, 2]
SCRIPT_UPLOADS = [144, 124, 52, 124, 124, 144, 144, 124, 16, 16, 52]


@pytest.fixture(scope="module")
def pallas_engine():
    """A tiny phi4-shaped engine on the Pallas kernels (interpret mode)."""
    cfg = dataclasses.replace(
        get("phi4-mini-3.8b"), attention_impl="pallas", n_layers=2,
        d_model=64, n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
        vocab_size=256)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    return PagedEngine(model, params, ServeConfig(
        max_batch=4, max_seq=256, page_size=16, num_pages=65,
        prefill_chunk=4, prefill_chunk_tokens=32, trace_pool=False))


@pytest.fixture(scope="module")
def compiled(pallas_engine):
    """lane -> compiled HLO text of the engine's two cells."""
    hlo = pallas_engine.cell_hlo()
    return {lane: hlo[f"jit_{lane}_many_paged"]
            for lane in ("prefill", "decode")}


def _instructions(text):
    """(name, opcode, op_name path components) of every HLO instruction."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            yield m.group(1), m.group(2), (op.group(1).split("/") if op
                                           else [])


@pytest.mark.parametrize("lane", ["prefill", "decode"])
def test_lane_matmuls_and_custom_calls_carry_one_scope(compiled, lane):
    """Also: the lanes' only scatters are the K/V row writes (a trace
    reader counts a scatter XLA left without an ``op_name`` as one)."""
    checked = scatters = 0
    for name, code, path in _instructions(compiled[lane]):
        scopes = [c for c in path if c in SCOPES]
        if code in ("dot", "convolution", "custom-call"):
            assert len(scopes) == 1, (lane, name, code, "/".join(path))
            checked += 1
        if code == "scatter":
            assert scopes == ["kv_write"], (lane, name, "/".join(path))
            scatters += 1
    assert checked and scatters


@pytest.mark.parametrize("lane", ["prefill", "decode"])
def test_lane_module_and_kernel_names(compiled, lane):
    module = re.search(r"HloModule (\S+?),", compiled[lane]).group(1)
    assert module == f"jit_{lane}_many_paged"
    # interpret mode runs the kernel as HLO under the kernel's name, in
    # the attention scope
    kernel_paths = [path for _, _, path in _instructions(compiled[lane])
                    if KERNELS[lane] in path]
    assert kernel_paths
    assert all("attn" in p for p in kernel_paths)


# ---------------------------------------------------------------------------
# host spans, stamps and counters on a scripted run with faults
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    cfg = get("qwen2-0.5b").reduced()
    model = get_model(cfg)
    return model, model.init(jax.random.key(0))


def _scripted(small):
    """Two slots, four prompts, a forced eviction, a poisoned slot and a
    dropped grant."""
    model, params = small
    pe = PagedEngine(model, params, ServeConfig(
        max_batch=2, max_seq=64, max_new_tokens=6, page_size=8,
        prefill_chunk=2, prefill_chunk_tokens=8))
    pe.install_faults(FaultPlan([FaultEvent(4, "evict", slot=0),
                                 FaultEvent(6, "poison", slot=1),
                                 FaultEvent(8, "drop", slot=-1)]))
    rng = np.random.RandomState(5)
    rids = [pe.submit(rng.randint(0, model.cfg.vocab_size, size=n)
                      .astype(np.int32)) for n in (5, 11, 19, 3)]
    return pe, rids


class _Recorder:
    """Stands in for the profiler span: records names, constructor
    attributes and ``set_metadata`` calls."""
    log = []

    def __init__(self, name, **kw):
        self.entry = [name, dict(kw)]
        _Recorder.log.append(self.entry)

    def set_metadata(self, **kw):
        self.entry[1].update(kw)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _record(monkeypatch, recording: bool):
    _Recorder.log = []
    monkeypatch.setattr(engine_mod, "_span", _Recorder)
    monkeypatch.setattr(engine_mod, "_recording", lambda: recording)
    return _Recorder.log


def test_counter_deltas_match_the_per_tick_record(small):
    pe, _ = _scripted(small)
    disp, up = [], []
    while pe.busy:
        c0, s0 = pe.counters(), pe.steps_run
        pe.step()
        c1 = pe.counters()
        if pe.steps_run > s0:                       # a working tick
            disp.append(c1["dispatches"] - c0["dispatches"])
            up.append(c1["upload_bytes"] - c0["upload_bytes"])
    assert (disp, up) == (SCRIPT_DISPATCHES, SCRIPT_UPLOADS)
    c = pe.counters()
    assert (c["preemptions"], c["quarantines"]) == (1, 1)
    assert c["recompute_tokens"] > 0 and c["tokens_appended"] > 0
    assert c["cow_dispatches"] == pe.kv.cow_dispatches


def test_profiler_off_builds_no_span_attributes(small, monkeypatch):
    log = _record(monkeypatch, recording=False)
    pe, _ = _scripted(small)
    pe.run()
    assert log and all(not attrs for _, attrs in log)
    names = {n for n, _ in log}
    assert {"engine.step", "engine.admit", "engine.plan",
            "engine.sync_dirty", "engine.dispatch_prefill",
            "engine.dispatch_decode", "engine.readback", "engine.bookkeep",
            "engine.preempt"} <= names
    assert all(n.startswith("engine.") for n in names)
    assert not names & {"tick", "plan", "traced_window"}


def test_recorded_spans_carry_request_ids_and_tick_work(small, monkeypatch):
    log = _record(monkeypatch, recording=True)
    pe, rids = _scripted(small)
    pe.run()
    steps = [a for n, a in log if n == "engine.step" and a]
    assert [a["dispatches"] for a in steps] == SCRIPT_DISPATCHES
    assert [a["upload_bytes"] for a in steps] == SCRIPT_UPLOADS

    def ids(name):
        return sorted(int(r) for n, a in log if n == name and "rids" in a
                      for r in a["rids"].split())
    assert set(ids("engine.admit")) == set(rids)
    assert ids("engine.bookkeep") == sorted(rids)       # each finishes once
    assert len(ids("engine.preempt")) == 2              # evict + quarantine


def test_request_times_survive_preemption_and_quarantine(small):
    pe, rids = _scripted(small)
    seen, requeued = {}, {}
    while pe.busy:
        before = {r: pe._reqs[r].preempts for r in rids}
        pe.step()
        for r in rids:
            t = pe.request_times(r)
            for k in (0, 1):                        # a stamp is set once
                if seen.get(r, (None, None))[k] is not None:
                    assert t[k] == seen[r][k]
            if pe._reqs[r].preempts > before[r]:
                requeued[r] = t
            seen[r] = t
    assert len(requeued) == 2                       # evicted, quarantined
    for r, (admitted, first) in requeued.items():
        assert admitted is not None
        end = pe.request_times(r)
        assert end[0] == admitted and (first is None or end[1] == first)
    # the evicted request had sampled before it lost its slot
    assert any(first is not None for _, first in requeued.values())
    for r in rids:
        admitted, first = pe.request_times(r)
        assert admitted is not None and first is not None
        assert admitted <= first
    assert pe.request_times(10_000) == (None, None)
