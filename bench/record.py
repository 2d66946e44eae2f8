"""What one run hands the metric readers (``bench/metrics/<name>.py``).

A reader takes a ``Record`` and returns a number, or None when the run
holds nothing for it to read (the harness then leaves the metric out).
A reader never returns 0 for a share of a roofline or of a peak that it
could not read.  What a ``Record`` offers, and when each part is empty:

  * ``window`` (bench/serve.py ``Window``), in every run: the requests due
    in the window with their host-clock stamps, each tick's host interval
    and plan grants (``lengths``, ``prefill``, ``steps``), the tokens
    emitted, and ``traced``, the (first, last + 1) ticks of the traced
    part of a ``--trace 1`` run (None in a ``--trace 0`` run);
  * ``counters``, in every run: the deltas of every key of
    ``PagedEngine.counters()`` (``tokens_out``, ``recompute_tokens``,
    ``decode_pages_streamed``, ...) from the window's first tick boundary
    to its close, keyed by the engine's own names, so a counter the
    program adds reaches a reader with no edit here;
  * ``counters_traced``: the same deltas over the traced ticks; empty in
    a ``--trace 0`` run;
  * ``trace``, with ``--trace 1`` only (else None): ``trace.reduce`` of
    the traced part, per lane (``lane(name)``) its module runs and
    seconds and its attention kernel's seconds, and the device's busy
    and window seconds; a Mosaic call named under the config's
    ``trace_kernels`` is left out of a lane's kernel time;
  * ``spans``, with ``--trace 1`` only (else None): ``spans.reduce`` of
    the same trace (``span_lane(name)``), per lane its device seconds in
    each named scope and kernel, the config's ``trace_scopes`` and
    ``trace_kernels`` among them, and the idle time by engine span.

A trace with no TPU plane (the CPU) holds no lane in ``trace`` or
``spans``: ``lane`` and ``span_lane`` give None there, and so do the
readers that need them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from bench import costs
from bench.peaks import Peaks
from bench.serve import Req, Tick, Window
from bench.spec import Sizes


def percentile(values, q: float) -> Optional[float]:
    values = list(values)
    return float(np.percentile(values, q)) if values else None


@dataclasses.dataclass
class Record:
    sizes: Sizes
    peaks: Peaks
    window: Window
    setup_s: float
    trace: Optional[Dict[str, object]] = None   # trace.reduce() of a run
    spans: Optional[Dict[str, object]] = None   # spans.reduce() of a run

    @property
    def counters(self) -> Dict[str, int]:
        """The engine's counters over the window (``Window.counters``)."""
        return self.window.counters

    @property
    def counters_traced(self) -> Dict[str, int]:
        """The engine's counters over the traced ticks; {} untraced."""
        return self.window.counters_traced

    @property
    def requests(self) -> List[Req]:
        return self.window.requests

    def ttft_s(self) -> List[float]:
        """Per request due in the window: due to first token, or to the
        window's end for one that has none."""
        return [(r.first_t if r.first_t is not None else self.window.t1)
                - r.due for r in self.requests]

    def tpot_s(self) -> List[float]:
        """Per request with two or more tokens in the window: the mean gap
        between its first and last token."""
        return [(r.last_t - r.first_t) / (r.n - 1)
                for r in self.requests if r.n >= 2]

    def traced_ticks(self) -> List[Tick]:
        if self.trace is None or self.window.traced is None:
            return []
        a, b = self.window.traced
        return [t for t in self.window.ticks[a:b] if t.lengths is not None]

    def lane(self, name: str) -> Optional[Dict[str, float]]:
        if self.trace is None:
            return None
        lane = self.trace["lanes"].get(name)
        return lane if lane and lane["runs"] else None

    def span_lane(self, name: str) -> Optional[Dict[str, object]]:
        """A lane of ``spans``: runs, module seconds, and device seconds
        by scope and by kernel; None where the run holds none."""
        if self.spans is None:
            return None
        lane = self.spans["lanes"].get(name)
        return lane if lane and lane["runs"] else None

    def prefill_work(self, fn) -> float:
        """``fn`` (a function of bench/costs.py) summed over the traced
        ticks' prefill dispatches."""
        return _sum(fn, self.sizes, self.traced_ticks(), "prefill")

    def decode_work(self, fn) -> float:
        return _sum(fn, self.sizes, self.traced_ticks(), "steps")


def _sum(fn, sz, ticks, field):
    tot = None
    for t in ticks:
        g = getattr(t, field)
        if g.any():
            v = fn(sz, t.lengths, g)
            tot = v if tot is None else (
                tuple(a + b for a, b in zip(tot, v)) if isinstance(v, tuple)
                else tot + v)
    return tot


def roofline_share(rec: Record, lane: str, fn) -> Optional[float]:
    """Percent of the roofline a lane's kernel reached over the traced
    window: the least time the chip needs for the kernel's operations
    and bytes (bench/costs.py) over the kernel's device time."""
    d = rec.lane(lane)
    if d is None or not d["kernel_s"]:
        return None
    work = (rec.prefill_work(fn) if lane == "prefill"
            else rec.decode_work(fn))
    if work is None:
        return None
    ops, nbytes = work
    bound = max(ops / rec.peaks.bf16_flops, nbytes / rec.peaks.hbm_bw)
    return 100.0 * bound / d["kernel_s"]


def step_mfu(rec: Record, lane: str) -> Optional[float]:
    """Percent of the bf16 peak that a lane's dispatches reached: model
    FLOPs of the work they did over their device time."""
    d = rec.lane(lane)
    if d is None or not d["module_s"]:
        return None
    flops = (rec.prefill_work(costs.prefill_model_flops) if lane == "prefill"
             else rec.decode_work(costs.decode_model_flops))
    if flops is None:
        return None
    return 100.0 * flops / (d["module_s"] * rec.peaks.bf16_flops)


def decode_step_ms(rec: Record) -> Optional[float]:
    """Device time of the decode-cell module per scan step (one token per
    active slot) in the traced window."""
    d = rec.lane("decode")
    if d is None:
        return None
    return 1e3 * d["module_s"] / (d["runs"] * rec.window.decode_steps)


def tick_host_ms(rec: Record) -> Optional[float]:
    """Mean over the window's working ticks of the engine's own
    ``host_ms_trace``: the tick's host time less its wait for results."""
    v = [t.host_ms for t in rec.window.ticks if t.host_ms is not None]
    return sum(v) / len(v) if v else None


def idle_share(rec: Record) -> Optional[float]:
    """100 x (1 - the union of the intervals in which an op ran on the
    device / the traced window); nothing where the trace holds no device
    op (no TPU plane)."""
    if rec.trace is None or not rec.trace["busy_s"]:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])


def whole_step_mfu(rec: Record) -> Optional[float]:
    """Percent of the bf16 peak over the traced window: model FLOPs of
    all the work the ticks did (granted prompt rows and active decode
    steps) over the window's length; nothing where the trace holds no
    device op."""
    if rec.trace is None or not rec.trace["busy_s"]:
        return None
    flops = [rec.prefill_work(costs.prefill_model_flops),
             rec.decode_work(costs.decode_model_flops)]
    flops = [f for f in flops if f is not None]
    if not flops:
        return None
    return 100.0 * sum(flops) / (rec.trace["window_s"] *
                                 rec.peaks.bf16_flops)
