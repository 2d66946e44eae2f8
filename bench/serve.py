"""Builds the system under test and drives it through the measured window.

The engine is the program's ``PagedEngine`` over its model, built with the
config file's sizes and serving settings and the benchmark's own weights.
The window drives ``PagedEngine.submit`` / ``PagedEngine.step`` from the
mix's schedule (bench/generator.py): a request is submitted once it is
due, a session's next turn is due ``think_s`` after its previous turn's
last token (at once, for a closed-loop client), and the engine steps
while it has work.  The same schedule runs for the mix's ``prime_s``
before the window opens, so the window opens on slots, pages and
retained prefixes in the state the traffic keeps them in; the requests
due then are set-up and not measured.  Everything the metrics read is
recorded from outside the engine: per request its due, admission, first and last
token times on the host clock; per tick its host interval and the plan's
grants; and the engine's own counters (``PagedEngine.counters()``), taken
between ticks where the window and its traced part open and where the
window closes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import generator, weights
from bench.spec import Sizes

clock = time.perf_counter


@dataclasses.dataclass
class Req:
    rid: int
    session: int
    turn: int
    due: float                      # host clock
    prompt: np.ndarray
    admit_t: Optional[float] = None  # start of the tick that gave it a slot
    first_t: Optional[float] = None  # end of the tick of its first token
    last_t: Optional[float] = None   # end of the tick of its last token
    n: int = 0                       # tokens emitted in the window
    ok: Optional[bool] = None        # terminal: finished (True) or failed


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    host_ms: Optional[float]        # the engine's own host-cost trace
    lengths: Optional[np.ndarray]   # plan: tokens resident before appends
    prefill: Optional[np.ndarray]   # plan: prefill-lane grants
    steps: Optional[np.ndarray]     # plan: decode steps
    waiting: int = 0                # requests due and without a slot after


@dataclasses.dataclass
class Window:
    t0: float                       # the window opens (end of set-up)
    t1: float
    requests: List[Req]             # those due in the window
    ticks: List[Tick]
    shared_tokens: int              # engine.shared_tokens over the window
    decode_steps: int               # scan steps per decode dispatch
    slots: int                      # the engine's max_batch
    opened: float                   # first tick boundary at or after t0
    tokens: int                     # tokens emitted in ticks after opened
    traced: Optional[tuple] = None  # (first, last + 1) tick index traced
    primed: List[Req] = dataclasses.field(default_factory=list)  # set-up
    # engine.counters() deltas, every key the engine gives: from ``opened``
    # to the close, and from the traced part's opening to the close ({}
    # where nothing was traced)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    counters_traced: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a config file: its registry entry,
    then the file's published sizes, then its ``program_settings`` last,
    so that the program's own name for a width wins (``d_ff`` is a routed
    expert's width, onto which the published ``intermediate_size`` maps)."""
    from repro import configs
    fields = {**Sizes.from_config(conf).arch_overrides(),
              **conf["program_settings"]}
    return dataclasses.replace(configs.get(conf["registry_id"]), **fields)


def build(conf: dict, seed: int):
    """(engine, weights, sizes) for a config file, weights from ``seed``."""
    from repro.models import get_model
    from repro.serve.engine import PagedEngine, ServeConfig

    model = get_model(arch_config(conf))
    params = weights.make(model.abstract_params(), seed)
    scfg = ServeConfig(max_seq=conf["max_context"], temperature=0.0,
                       eos_id=-1, **conf["serving"])
    return PagedEngine(model, params, scfg), params, Sizes.from_config(conf)


def warm(engine, vocab: int) -> None:
    """Compile every program the window runs: the prefill lane and the
    decode cell (one request that fills two prefill chunks and decodes
    one tick), and the batched page copy for every count a tick can ask
    for, with the conversion of its page lists.  The warm-up request's
    retained pages are dropped after."""
    import jax.numpy as jnp
    B = engine.cfg.max_batch
    engine.kv.warm_copy(tuple(range(9, B + 1)))
    for n in range(1, B + 1):        # cow_flush uploads its pages as lists
        jnp.asarray([0] * n, jnp.int32)
    T = engine.cfg.prefill_chunk_tokens
    chunk = engine.cfg.prefill_chunk
    rng = np.random.default_rng(12345)
    engine.submit(rng.integers(0, vocab, T + 1, dtype=np.int32),
                  max_new_tokens=chunk + 1)
    engine.run()
    engine.kv.flush_retained()


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def instrument(engine, rec: dict) -> None:
    """Record each tick's plan (lengths before the appends, the grants),
    and mark the plan on the trace's host timeline."""
    plan = engine.scheduler.plan

    def recorded(slots, kv, chunk, prefill_tokens=0):
        with _annotate("plan"):
            p = plan(slots, kv, chunk, prefill_tokens=prefill_tokens)
        rec["plan"] = (kv.length.copy(), p.prefill.copy(), p.steps.copy())
        return p

    engine.scheduler.plan = recorded


class Driver:
    """One window of traffic over ``engine``: open-loop sessions, or
    closed-loop clients (sessions whose turns carry no history and are
    due the moment the previous one finishes)."""

    def __init__(self, engine):
        self.engine = engine
        self.plan: Dict[str, tuple] = {}
        instrument(engine, self.plan)

    def run(self, sessions: List[generator.Session], seconds: float,
            prime_s: float = 0.0,
            trace: Optional[Callable[[str], contextlib.AbstractContextManager]]
            = None, trace_at: float = 0.0,
            at_open: Optional[Callable[[], None]] = None) -> Window:
        """Drive ``sessions`` (``arrival_s`` from the window's opening,
        negative for those due while priming) for ``prime_s`` and then
        the window of ``seconds``.  ``at_open`` is called at the first
        tick boundary after the window opens.  With ``trace``, the
        context it returns is opened at the first tick boundary
        ``trace_at`` seconds into the window and closed when the window
        ends, so no trace is written while the window runs.  The
        engine's counters are snapshot at those two boundaries and at
        the close."""
        from repro.serve.engine import RequestStatus, TERMINAL_STATUSES
        eng = self.engine
        done_ok = (RequestStatus.FINISHED, RequestStatus.PREEMPTED_RESUMED)
        t0 = clock() + prime_s
        t_end = t0 + seconds
        heap = [(t0 + s.arrival_s, i, 0) for i, s in enumerate(sessions)]
        heapq.heapify(heap)
        reqs: Dict[int, Req] = {}
        queued, running = set(), set()
        base: Dict[int, int] = {}
        ticks: List[Tick] = []
        shared0 = None
        traced = tracing = None
        at = {}                             # engine.counters() snapshots

        def submit(due, si, ti):
            s = sessions[si]
            t = s.turns[ti]
            prev = [r for r in reqs.values() if r.session == si
                    and r.turn == ti - 1]
            history = (np.concatenate([prev[0].prompt,
                                       np.asarray(eng.results[prev[0].rid],
                                                  np.int32)])
                       if prev and s.carry else s.prefix)
            prompt = np.concatenate([history, t.user]).astype(np.int32)
            rid = eng.submit(prompt, max_new_tokens=t.out_budget)
            reqs[rid] = Req(rid, si, ti, due, prompt)
            queued.add(rid)

        def observe(ts, te):
            """Update the requests from the engine after a tick; returns
            the tokens they emitted in it."""
            new = 0
            for rid in list(queued):
                st = eng.status[rid]
                if st is not RequestStatus.QUEUED:
                    queued.discard(rid)
                    running.add(rid)
                    if st is not RequestStatus.REJECTED:
                        reqs[rid].admit_t = ts
            counts = {s.rid: base.get(s.rid, 0) + len(s.out)
                      for s in eng.slots if s.active}
            for rid in list(running):
                r = reqs[rid]
                st = eng.status[rid]
                if st in TERMINAL_STATUSES:
                    n = len(eng.results[rid])
                elif rid in counts:
                    n = counts[rid]
                else:                       # preempted: requeued
                    base[rid] = r.n
                    if st is RequestStatus.QUEUED:
                        running.discard(rid)
                        queued.add(rid)
                    continue
                if n > r.n:
                    if r.first_t is None:
                        r.first_t = te
                    r.last_t = te
                    new += n - r.n
                    r.n = n
                if st in TERMINAL_STATUSES:
                    running.discard(rid)
                    r.ok = st in done_ok
                    s = sessions[r.session]
                    if r.ok and r.turn + 1 < len(s.turns):
                        nt = s.turns[r.turn + 1]
                        heapq.heappush(heap, (te + nt.think_s, r.session,
                                              r.turn + 1))
            return new

        tokens = 0
        while True:
            now = clock()
            if shared0 is None and now >= t0:
                opened = now
                shared0 = eng.shared_tokens
                at["open"] = eng.counters()
                if at_open is not None:
                    at_open()
            if trace is not None and traced is None \
                    and now >= t0 + trace_at:
                tracing = trace(WINDOW)
                tracing.__enter__()
                traced = len(ticks)
                at["traced"] = eng.counters()
            if now >= t_end:
                break
            while heap and heap[0][0] <= now:
                due, si, ti = heapq.heappop(heap)
                submit(due, si, ti)
            if not eng.busy:
                nxt = min(heap[0][0] if heap else t_end, t_end,
                          t0 if shared0 is None else t_end)
                time.sleep(max(0.0, nxt - clock()))
                continue
            self.plan.clear()
            n_host = len(eng.host_ms_trace)
            ts = clock()
            with _annotate("tick"):
                eng.step()
            te = clock()
            new = observe(ts, te)
            if shared0 is None:             # still priming
                continue
            tokens += new
            host_ms = (eng.host_ms_trace[-1]
                       if len(eng.host_ms_trace) > n_host else None)
            lengths, pre, steps = self.plan.get("plan", (None, None, None))
            ticks.append(Tick(ts, te, host_ms, lengths, pre, steps,
                              len(queued)))
        t1 = clock()
        close = eng.counters()
        if tracing is not None:
            tracing.__exit__(None, None, None)

        def since(snap):
            return {k: v - snap[k] for k, v in close.items()} if snap else {}

        return Window(t0, t1, [r for r in reqs.values() if r.due >= t0],
                      ticks, eng.shared_tokens - shared0,
                      eng.cfg.prefill_chunk, eng.cfg.max_batch, opened,
                      tokens,
                      None if traced is None else (traced, len(ticks)),
                      [r for r in reqs.values() if r.due < t0],
                      since(at.get("open")), since(at.get("traced")))


WINDOW = "traced_window"
