"""What one run hands the metric readers (``bench/metrics/<name>.py``).

A reader takes a ``Record`` and returns a number, or None when the run
holds nothing for it to read (the harness then leaves the metric out).
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
