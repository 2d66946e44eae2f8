"""The program's own names in a profiler trace, read per layer.

The serving path names its work where it happens:

  * device ops carry one of the model step's named scopes (``SCOPES``) in
    their HLO ``op_name`` (models/transformer.py, models/model.py), and the
    two Pallas kernels carry their own names (``KERNELS``);
  * the engine tick's phases are host spans ``engine.*`` on the profiler's
    host plane (serve/engine.py), on the clock the device planes use, with
    the tick's dispatches and upload bytes and the request ids of admits,
    preemptions and finishes as attributes.

``load`` reads a profiler directory into a ``Spans`` of plain data (device
ops with their lane module and scope, module runs, host spans with their
attributes), so the reduction is checked on a small recorded trace
(bench/tests/data/) without a chip.  ``reduce`` gives, per lane, the
device seconds in each scope and kernel, and the traced window's idle
time put down to the innermost ``engine.*`` span open across each gap.

On a TPU v5e an "XLA Ops" event is named by its HLO text
("%fusion.133 = bf16[...] fusion(...)") and carries no ``op_name``: its
stats are the device offset and duration alone (checked on a v5e trace).
So an op's scope comes from the compiled HLO text of its module, which
``PagedEngine.cell_hlo()`` gives for both lanes (``hlo``: module name ->
text) and which maps each instruction's name to its ``op_name``.

A configuration may add names of its own: its file's ``trace_scopes`` are
read as scopes beside ``SCOPES`` (``load(..., scopes=)``), and its
``trace_kernels`` as kernels beside ``KERNELS`` (``reduce(...,
kernels=)``), for that configuration's cells only.  An op takes a scope
only where exactly one known name is in its ``op_name`` path, so a
configuration's scope must not nest inside one of ``SCOPES`` (a
``moe_experts`` inside ``ffn`` would leave its ops in no scope): it takes
the place of the scope it would nest in.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace
from bench.trace import PARENT_OPCODES, WINDOW_SPAN, _union

# the model step's named scopes, and those of them that are the dense
# weight sweep (everything but the embedding gather, the K/V row write
# and the attention kernel)
SCOPES = ("embed", "qkv", "kv_write", "attn", "out_proj", "ffn", "head",
          "sample")
DENSE = ("qkv", "out_proj", "ffn", "head", "sample")
KERNELS = ("paged_decode_attention", "ragged_prefill_attention")
# XLA module (jit) names of the engine's two compiled cells
LANES = {"prefill": "jit_prefill_many_paged",
         "decode": "jit_decode_many_paged"}
ENGINE = "engine."
NO_SPAN = "no_span"


@dataclasses.dataclass
class Op:
    text: str                  # HLO text, cut after the opcode (``cut``)
    start: float               # ns, on the trace's clock
    end: float
    module: str = ""           # XLA module (with its fingerprint)
    scope: str = ""            # one of SCOPES, or "" where it has none

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def name(self) -> str:
        return instruction(self.text)[0]

    @property
    def opcode(self) -> str:
        return instruction(self.text)[1]


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float
    end: float
    attrs: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Spans:
    ops: List[Op]
    modules: List[HostSpan]    # module runs ("XLA Modules"), no attrs
    host: List[HostSpan]       # engine.* spans and the benchmark's own
    chips: int

    def to_json(self) -> dict:
        return {"chips": self.chips,
                "ops": [dataclasses.astuple(o) for o in self.ops],
                "modules": [[m.name, m.start, m.end] for m in self.modules],
                "host": [[s.name, s.start, s.end, s.attrs]
                         for s in self.host]}

    @classmethod
    def from_json(cls, data: dict) -> "Spans":
        return cls([Op(*o) for o in data["ops"]],
                   [HostSpan(*m) for m in data["modules"]],
                   [HostSpan(*s) for s in data["host"]], data["chips"])


# -- reading ------------------------------------------------------------------

_TEXT = re.compile(r"^%?([^\s=]+) = .*? ([a-z][a-z0-9-]*)\(")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = .*? ([a-z][a-z0-9-]*)\(")
_CALLS = re.compile(r"calls=%?([^\s,]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="[^"]*"')


def scope_of(op_name: str, names: Sequence[str] = SCOPES) -> str:
    """The one scope among an ``op_name``'s path components ("" where
    there is none, or more than one)."""
    found = [c for c in op_name.split("/") if c in names]
    return found[0] if len(found) == 1 else ""


def instruction(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an op named by its HLO text."""
    m = _TEXT.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


def cut(text: str) -> str:
    """An op's HLO text up to its opcode, plus a custom call's target
    ("%paged_decode_attention.15 = bf16[...] custom-call( ...
    custom_call_target="tpu_custom_call"")."""
    m = _TEXT.match(text)
    head = text[:m.end()] if m else text
    target = _TARGET.search(text)
    return f"{head} ... {target.group(0)}" if target else head


def hlo_scopes(text: str, names: Sequence[str] = SCOPES) -> Dict[str, str]:
    """Instruction name -> scope, from a compiled module's HLO text.  A
    fusion with no ``op_name`` of its own takes its root instruction's
    scope, else the one scope its fused instructions share.  A scatter
    with no ``op_name`` is the K/V row write: the lanes' only scatters
    are the ``kv_write`` appends into the page pools, and XLA drops the
    ``op_name`` where it rewrites the prefill lane's row scatter into one
    over the pool viewed as rows."""
    scopes: Dict[str, set] = {}            # computation -> scopes inside
    roots: Dict[str, Tuple[str, str]] = {}  # computation -> (opcode, scope)
    own: Dict[str, Tuple[str, str]] = {}   # instruction -> (opcode, scope)
    calls: Dict[str, str] = {}
    comp = ""
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()
            comp = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        sc = scope_of(op.group(1), names) if op else ""
        if not op and m.group(2) == "scatter":
            sc = "kv_write"
        own[m.group(1)] = (m.group(2), sc)
        if sc:
            scopes.setdefault(comp, set()).add(sc)
        if line.lstrip().startswith("ROOT "):
            roots[comp] = (m.group(2), sc)
        c = _CALLS.search(line)
        if c:
            calls[m.group(1)] = c.group(1)
    out = {}
    for name, (_, sc) in own.items():
        if not sc and name in calls:
            sc = roots.get(calls[name], ("", ""))[1]
            inner = scopes.get(calls[name], set())
            if not sc and len(inner) == 1:
                sc = next(iter(inner))
        out[name] = sc
    return out


def _jit_name(module: str) -> str:
    return module.split("(")[0]


def load(log_dir: str, hlo: Dict[str, str],
         scopes: Sequence[str] = ()) -> Spans:
    """Read the newest ``.xplane.pb`` under ``log_dir``: its device ops and
    module runs as ``trace.load`` reads them, and the host plane's spans
    with their attributes.  ``hlo`` maps a module's jit name to its
    compiled HLO text (``PagedEngine.cell_hlo``), which names each op's
    scope among ``SCOPES`` and a configuration's own ``scopes``."""
    from jax.profiler import ProfileData
    tr = trace.load(log_dir)
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    host = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [HostSpan(e.name, e.start_ns, e.end_ns,
                                  dict(e.stats)) for e in line.events
                         if e.name.startswith(ENGINE)
                         or e.name in trace.HOST_SPANS]
    modules = [HostSpan(m.name, m.start, m.end) for m in tr.modules]
    return Spans(build_ops(tr.ops, modules, hlo, scopes), modules, host,
                 tr.chips)


def build_ops(ops: Sequence[trace.Span], modules: Sequence[HostSpan],
              hlo: Dict[str, str], scopes: Sequence[str] = ()) -> List[Op]:
    """``Op``s from ``trace.load``'s device ops: each op's module is the
    one ``trace.attribute`` gives it, and its scope is its instruction's
    in that module's HLO text, among ``SCOPES`` and ``scopes``."""
    known = SCOPES + tuple(scopes)
    maps = {k: hlo_scopes(v, known) for k, v in hlo.items()}
    return [Op(cut(o.name), o.start, o.end, o.module,
               maps.get(_jit_name(o.module), {}).get(instruction(o.name)[0],
                                                    ""))
            for o in trace.attribute(list(ops), list(modules))]


# -- reducing -----------------------------------------------------------------

def _lane(module: str) -> Optional[str]:
    name = _jit_name(module)
    for lane, jit in LANES.items():
        if name == jit:
            return lane
    return None


def kernel_of(op: Op, kernels: Sequence[str] = KERNELS) -> str:
    """The Pallas kernel an op is ("" for any other op)."""
    base = op.name.split(".")[0]
    return base if base in kernels else ""


def innermost(host: Sequence[HostSpan], t: float) -> str:
    """The shortest ``engine.*`` span open at time t, or ``NO_SPAN``."""
    open_ = [s for s in host
             if s.name.startswith(ENGINE) and s.start <= t < s.end]
    return min(open_, key=lambda s: s.dur).name if open_ else NO_SPAN


def window(sp: Spans) -> Tuple[float, float]:
    win = [s for s in sp.host if s.name == WINDOW_SPAN]
    if win:
        return win[0].start, win[0].end
    return (min(o.start for o in sp.ops), max(o.end for o in sp.ops))


def reduce(sp: Spans, kernels: Sequence[str] = ()) -> Dict[str, object]:
    """Per lane: module runs and seconds, device seconds per scope, per
    kernel (``KERNELS`` and a configuration's own ``kernels``) and
    without a scope, and the scoped share of module time; the window's
    idle seconds by the innermost engine span open across each gap; and
    each engine span's count and host seconds."""
    known = KERNELS + tuple(kernels)
    w0, w1 = window(sp)
    lanes: Dict[str, Dict[str, object]] = {}
    for m in sp.modules:
        lane = _lane(m.name)
        if lane and w0 <= m.start < w1:
            d = lanes.setdefault(lane, {
                "runs": 0, "module_s": 0.0, "scopes": {}, "kernels": {},
                "unscoped_s": 0.0, "unscoped_ops": {}})
            d["runs"] += 1
            d["module_s"] += m.dur / 1e9
    ops = [o for o in sp.ops if o.end > w0 and o.start < w1]
    for o in ops:
        lane = lanes.get(_lane(o.module))
        if lane is None or o.opcode in PARENT_OPCODES:
            continue
        s = o.dur / 1e9
        if o.scope:
            lane["scopes"][o.scope] = lane["scopes"].get(o.scope, 0.0) + s
        else:
            lane["unscoped_s"] += s
            key = o.name.rstrip("0123456789").rstrip(".")
            lane["unscoped_ops"][key] = lane["unscoped_ops"].get(key, 0.0) + s
        k = kernel_of(o, known)
        if k:
            lane["kernels"][k] = lane["kernels"].get(k, 0.0) + s
    for d in lanes.values():
        scoped = sum(d["scopes"].values())
        d["scoped_share"] = scoped / d["module_s"] if d["module_s"] else None
        d["unscoped_ops"] = dict(sorted(d["unscoped_ops"].items(),
                                        key=lambda kv: -kv[1])[:8])
    busy = _union((max(o.start, w0), min(o.end, w1)) for o in ops)
    gaps: Dict[str, float] = {}
    if sp.chips <= 1:
        edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = innermost(sp.host, (s + e) / 2)
                gaps[label] = gaps.get(label, 0.0) + (e - s) / 1e9
    phases: Dict[str, List[float]] = {}
    for s in sp.host:
        if s.name.startswith(ENGINE) and w0 <= s.start < w1:
            c = phases.setdefault(s.name, [0, 0.0])
            c[0] += 1
            c[1] += s.dur / 1e9
    return {"window_s": (w1 - w0) / 1e9,
            "lanes": lanes,
            "idle_gaps_by_span": dict(sorted(gaps.items(),
                                             key=lambda kv: -kv[1])),
            "engine_spans": phases}


def dense_s(lane: Dict[str, object]) -> float:
    """Device seconds of a lane in the dense scopes."""
    return sum(lane["scopes"].get(s, 0.0) for s in DENSE)


