"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: device busy time, per-lane module and kernel time, the device ops
that took most time, and idle gaps attributed to what the host was doing.

The trace is read with ``jax.profiler.ProfileData`` into three lists of
``Span``: device ops (the "XLA Ops" line of each TPU plane, each op tagged
with the XLA module that ran it), device module runs ("XLA Modules"), and
the benchmark's own host annotations (``TraceAnnotation`` around each
``engine.step()``, the scheduler's plan and the traced window).  The
lists are plain data, so the reduction is checked on a small recorded
trace (bench/tests/data/) without a chip.

On a TPU v5e the "XLA Ops" events carry no module name and are named by
their HLO text ("%closed_call.28 = bf16[16,8,3,128]{...} custom-call(...)").
So an op belongs to the module run whose interval holds its start, and
its opcode is read from the text.  Control-flow ops (the layer scan's
``while``) span their bodies on the same line: they count towards busy
time (a union) but not in the per-op breakdown.

A lane is found by the name of the XLA module that runs it, and a
kernel as a Mosaic custom call (``custom_call_target="tpu_custom_call"``
in the op's text) inside that lane's modules: the prefill lane holds only
the ragged prefill kernel and the decode cell only the paged decode
kernel (checked by hand on a v5e trace of phi4-mini: 32 Mosaic calls per
prefill run and 32 x 4 per decode run; the lanes' other custom calls are
XLA's own, ConcatBitcast and AllocateBuffer, of about no time).  A lane's
kernel time is its attention kernel's: a configuration that puts another
Pallas kernel in a lane (a grouped matmul) names it under ``trace_kernels``
in its file, and ``reduce(..., other_kernels=)`` leaves the Mosaic calls
named after it out of the lane's kernel time and count.  They stay in the
lane's module time, in busy time and in the breakdown.  The attention
kernels are found by that exclusion and not by their names, since a
trace from before the kernels were named (data/trace_v5e.json) calls
them ``%closed_call.N``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# XLA module names of the engine's two compiled cells (jit names, before
# the module's fingerprint in parentheses).  The decode cell runs as
# ``jit__lambda`` (``PagedEngine._many_plain``), a name any jitted lambda
# takes (the page cache's compaction gather is one), so a module joins a
# lane only when it also holds a Mosaic kernel.
LANES = {"prefill": ("jit_prefill_many_paged",),
         "decode": ("jit_decode_many_paged", "jit__lambda")}
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
PARENT_OPCODES = ("while", "conditional", "call")
WINDOW_SPAN = "traced_window"
TICK_SPAN = "tick"
HOST_SPANS = (WINDOW_SPAN, TICK_SPAN, "plan")


@dataclasses.dataclass
class Span:
    name: str
    start: float               # ns, on the trace's clock
    end: float
    module: str = ""           # ops: the XLA module that ran them

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: List[Span]
    modules: List[Span]
    host: List[Span]
    chips: int

    def to_json(self) -> dict:
        return {"chips": self.chips,
                **{k: [dataclasses.astuple(s) for s in getattr(self, k)]
                   for k in ("ops", "modules", "host")}}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        return cls(**{k: [Span(*s) for s in data[k]]
                      for k in ("ops", "modules", "host")},
                   chips=data["chips"])


def _is_device(name: str) -> bool:
    parts = name.split(":")
    return name.startswith("/device:TPU:") and parts[-1].isdigit()


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    ops, modules, host, chips = [], [], [], 0
    for plane in data.planes:
        if _is_device(plane.name):
            chips += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [Span(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [Span(e.name, e.start_ns, e.end_ns,
                                 dict(e.stats).get("hlo_module", ""))
                            for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [Span(e.name, e.start_ns, e.end_ns)
                         for e in line.events if e.name in HOST_SPANS]
    return Trace(ops, modules, host, chips)


def _union(spans: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _lane(module: str, kernel_modules) -> Optional[str]:
    """The lane of a module run, by its jit name and the Mosaic kernel it
    holds (``kernel_modules``: the module names that hold one)."""
    if module not in kernel_modules:
        return None
    name = module.split("(")[0]
    for lane, names in LANES.items():
        if name in names:
            return lane
    return None


_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of an op named by its instruction text ("" when the
    name is not HLO text)."""
    m = _OPCODE.search(name)
    return m.group(1) if m else ""


def _short(name: str) -> str:
    """"%closed_call.28 custom-call" from the op's HLO text."""
    return f"{name.split(' ', 1)[0]} {opcode(name)}".strip()


def attribute(ops: List[Span], modules: List[Span]) -> List[Span]:
    """Ops with ``module`` filled in from the module run holding their
    start, where the trace did not name it."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for o in ops:
        mod = o.module
        if not mod:
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and o.start < mods[i].end:
                mod = mods[i].name
        out.append(dataclasses.replace(o, module=mod))
    return out


def _host_label(host: Sequence[Span], t: float) -> str:
    """The innermost benchmark host span open at time t."""
    inner = [s for s in host if s.name != WINDOW_SPAN and s.start <= t < s.end]
    if not inner:
        return "between_ticks"
    return "/".join(s.name for s in sorted(inner, key=lambda s: -s.dur))


def reduce(tr: Trace, top: int = 10,
           other_kernels: Sequence[str] = ()) -> Dict[str, object]:
    """The per-layer numbers of one traced window (times in seconds).
    ``other_kernels``: Pallas kernels, by name, whose Mosaic calls are no
    lane's attention kernel."""
    win = [s for s in tr.host if s.name == WINDOW_SPAN]
    if win:
        w0, w1 = win[0].start, win[0].end
    else:
        w0 = min(s.start for s in tr.ops)
        w1 = max(s.end for s in tr.ops)
    attributed = attribute(tr.ops, tr.modules)
    kernel_modules = {o.module for o in attributed if KERNEL_TARGET in o.name}
    ops = [Span(o.name, max(o.start, w0), min(o.end, w1), o.module)
           for o in attributed if o.end > w0 and o.start < w1]
    busy = _union((o.start, o.end) for o in ops)
    chips = max(1, tr.chips)
    busy_s = sum(e - s for s, e in busy) / chips / 1e9
    other = tuple(other_kernels)
    lanes: Dict[str, Dict[str, float]] = {}
    for m in tr.modules:
        lane = _lane(m.name, kernel_modules)
        if lane and w0 <= m.start < w1:
            d = lanes.setdefault(lane, {"runs": 0, "module_s": 0.0,
                                        "kernel_s": 0.0, "kernel_ops": 0})
            d["runs"] += 1
            d["module_s"] += m.dur / 1e9
    by_op: Dict[str, float] = {}
    for o in ops:
        code = opcode(o.name)
        lane = _lane(o.module, kernel_modules)
        if lane in lanes and KERNEL_TARGET in o.name \
                and not o.name.lstrip("%").startswith(other):
            lanes[lane]["kernel_s"] += o.dur / 1e9
            lanes[lane]["kernel_ops"] += 1
        if code not in PARENT_OPCODES:
            key = f"{o.module.split('(')[0]}:{_short(o.name)}"
            by_op[key] = by_op.get(key, 0.0) + o.dur / 1e9
    gaps = []
    if chips == 1:
        edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_host_label(tr.host, (s + e) / 2), (e - s) / 1e9))
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_s,
        "lanes": lanes,
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top],
        "idle_by_host": _sum_by(gaps),
    }


def _sum_by(gaps) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for label, s in gaps:
        out[label] = out.get(label, 0.0) + s
    return out


def summary(tr: Trace) -> dict:
    """What a trace holds, for reading one by hand: module names and op
    names with their counts and times."""
    mods: Dict[str, List[float]] = {}
    for m in tr.modules:
        mods.setdefault(m.name, []).append(m.dur / 1e9)
    ops: Dict[str, List[float]] = {}
    for o in attribute(tr.ops, tr.modules):
        ops.setdefault(f"{o.module.split('(')[0]} | {_short(o.name)}",
                       []).append(o.dur / 1e9)
    return {"chips": tr.chips,
            "modules": {k: [len(v), sum(v)] for k, v in mods.items()},
            "ops": dict(sorted(((k, [len(v), sum(v)]) for k, v in
                                ops.items()), key=lambda kv: -kv[1][1])[:60]),
            "host": [[s.name, s.start, s.end] for s in tr.host[:20]],
            "first_op": [tr.ops[0].start, tr.ops[0].end] if tr.ops else None}


if __name__ == "__main__":
    import sys
    print(json.dumps(summary(load(sys.argv[1])), indent=1))
