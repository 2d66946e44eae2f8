"""hlo_counters — a "rocProf for XLA": instruction & traffic census over
post-optimization, post-SPMD-partitioning HLO text.

The paper's central move is extracting an instruction roofline from the small
set of counters a constrained profiler exposes (FETCH_SIZE / WRITE_SIZE /
SQ_INSTS_VALU / SQ_INSTS_SALU).  XLA's AOT interface is constrained in an
analogous way: ``compiled.cost_analysis()`` gives total flops / bytes (and
counts ``while`` bodies ONCE, ignoring trip counts), and nothing reports
per-unit instruction mixes or collective traffic.  This module recovers them
by parsing ``compiled.as_text()``:

  * per-opcode / per-class instruction census (MXU, VPU, scalar, layout,
    irregular-memory, collective, flow) — the SQ_INSTS_{VALU,SALU} analogue;
  * trip-count-aware scaling of ``while`` bodies (reads
    ``backend_config={"known_trip_count":{"n":...}}``), so scan-over-layers
    models are costed correctly;
  * MXU *issue* estimation per dot: ceil-div tiling over (M, N, K) by the
    128x128x128 systolic pass — this exposes padding / alignment waste the
    FLOP roofline hides (the TPU analogue of the paper's transaction-level
    strided-access insight);
  * VPU issue estimation with (8,128)-vreg padding;
  * HBM traffic model at fusion boundaries, slice-aware (a fusion parameter
    consumed only by (dynamic-)slice ops contributes the slice bytes, not the
    full buffer — critical for stacked scan weights);
  * collective census: operand bytes and ring-model wire bytes per kind,
    with replica-group sizes parsed from the op attributes.

Everything here is plain-text parsing on one device's SPMD module, i.e. all
quantities are **per device** unless noted.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import Counter
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

DTYPE_BYTES = {
    "pred": 1, "s2": 0.25, "u2": 0.25, "s4": 0.5, "u4": 0.5,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8,
    "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e4m3": 1, "f8e8m0fnu": 1,
    "f4e2m1fn": 0.5,
    "c64": 8, "c128": 16,
    "token": 0, "opaque": 0, "tuple": 0,
}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")


@dataclasses.dataclass(frozen=True)
class Shape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def elements(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def bytes(self) -> float:
        return self.elements * DTYPE_BYTES.get(self.dtype, 4)

    def padded_vreg_issues(self, sublane: int = 8, lane: int = 128) -> int:
        """Number of (sublane x lane) vector-register issues needed to touch
        every element, including layout padding of the two minor dims."""
        if not self.dims:
            return 1
        if len(self.dims) == 1:
            return max(1, math.ceil(self.dims[0] / lane))
        lead = 1
        for d in self.dims[:-2]:
            lead *= d
        return max(1, lead * math.ceil(self.dims[-2] / sublane)
                   * math.ceil(self.dims[-1] / lane))


@lru_cache(maxsize=65536)
def _parse_shapes_cached(text: str) -> Tuple[Shape, ...]:
    """Type strings repeat heavily across a module (every scan iteration,
    every fusion parameter re-states the same tuple type) — cache the parse
    instead of re-running the regex + int conversion per use."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype in ("token", "opaque"):
            out.append(Shape(dtype, ()))
            continue
        if dtype not in DTYPE_BYTES:
            continue
        dims_t = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        out.append(Shape(dtype, dims_t))
    return tuple(out)


def parse_shapes(text: str) -> List[Shape]:
    """All shapes appearing in a type string (handles tuples)."""
    return list(_parse_shapes_cached(text))


def shapes_bytes(shapes: Sequence[Shape]) -> float:
    return float(sum(s.bytes for s in shapes))


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    shapes: Tuple[Shape, ...]          # result shape(s); tuples flattened
    operands: Tuple[str, ...]          # operand instruction names
    attrs: str                         # raw attribute tail
    args_raw: str = ""                 # raw text inside the operand parens
    is_root: bool = False


@dataclasses.dataclass
class Computation:
    name: str
    instructions: Dict[str, Instruction]
    order: List[Instruction]
    _users: Optional[Dict[str, List[Instruction]]] = \
        dataclasses.field(default=None, repr=False)
    _params: Optional[Dict[str, Instruction]] = \
        dataclasses.field(default=None, repr=False)

    @property
    def root(self) -> Optional[Instruction]:
        for inst in self.order:
            if inst.is_root:
                return inst
        return self.order[-1] if self.order else None

    def users_of(self, name: str) -> List[Instruction]:
        """Downstream users, via a lazily-built one-pass index (the naive
        per-query scan is O(insts) and the fusion byte accounting queries
        it per parameter)."""
        if self._users is None:
            users: Dict[str, List[Instruction]] = {}
            for inst in self.order:
                for op in inst.operands:
                    users.setdefault(op, []).append(inst)
            self._users = users
        return self._users.get(name, [])

    def param_named(self, index: int) -> Optional[Instruction]:
        if self._params is None:
            self._params = {i.args_raw.strip(): i for i in self.order
                            if i.opcode == "parameter"}
        return self._params.get(str(index))


_COMP_HEADER_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-~]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_INST_RE = re.compile(r"^\s*(ROOT\s+)?%([\w.\-~]+)\s*=\s*(.+)$")
_OPCODE_RE = re.compile(r"([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%([\w.\-~]+)")
_TRIP_RE = re.compile(r'"known_trip_count"\s*:\s*\{\s*"n"\s*:\s*"?(\d+)"?')
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_CALLS_RE = re.compile(r"calls=%?([\w.\-~]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-~]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-~]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w.\-~]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_LHS_BATCH_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")


_TYPE_TOKEN_RE = re.compile(r"[a-z]\w*(\[[^\]]*\])?(\{[^}]*\})?")


def _split_type(rest: str) -> Tuple[str, str]:
    """Split 'TYPE opcode(...)' into (type_str, remainder)."""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    return rest[: i + 1], rest[i + 1:].lstrip()
        return rest, ""
    m = _TYPE_TOKEN_RE.match(rest)
    if not m:
        return "", rest
    return m.group(0), rest[m.end():].lstrip()


def _match_paren(text: str) -> Tuple[str, str]:
    """text starts at '('; return (inside, after)."""
    depth = 0
    for i, c in enumerate(text):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return text[1:i], text[i + 1:]
    return text[1:], ""


def parse_module(text: str) -> Tuple[Dict[str, Computation], str]:
    """Parse HLO module text -> ({computation name: Computation}, entry)."""
    comps: Dict[str, Computation] = {}
    entry_name = ""
    cur: Optional[Computation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            m = _COMP_HEADER_RE.match(stripped)
            if m and ("(" in stripped):
                cur = Computation(m.group(2), {}, [])
                if m.group(1):
                    entry_name = m.group(2)
            continue
        if stripped == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _INST_RE.match(line)
        if not m:
            continue
        is_root, name, rest = bool(m.group(1)), m.group(2), m.group(3)
        type_str, remainder = _split_type(rest)
        om = _OPCODE_RE.match(remainder)
        if not om:
            continue
        opcode = om.group(1)
        args, after = _match_paren(remainder[om.end() - 1:])
        operands = tuple(_OPERAND_RE.findall(args))
        inst = Instruction(
            name=name, opcode=opcode,
            shapes=_parse_shapes_cached(type_str),
            operands=operands, attrs=after, args_raw=args,
            is_root=is_root)
        cur.instructions[name] = inst
        cur.order.append(inst)
    if cur is not None:                      # unterminated (defensive)
        comps[cur.name] = cur
    return comps, entry_name


# ---------------------------------------------------------------------------
# opcode classification
# ---------------------------------------------------------------------------

MXU_OPS = {"dot", "convolution", "ragged-dot"}

COLLECTIVE_OPS = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
}

LAYOUT_OPS = {
    "copy", "transpose", "reshape", "pad", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "reverse", "copy-start",
    "copy-done",
}

IRREGULAR_OPS = {"gather", "scatter", "sort", "select-and-scatter"}

REDUCE_OPS = {"reduce", "reduce-window"}

FLOW_OPS = {"while", "conditional", "call", "fusion", "custom-call",
            "after-all", "async-start", "async-done", "async-update",
            "optimization-barrier", "infeed", "outfeed", "send", "recv",
            "send-done", "recv-done", "domain", "partition-id", "replica-id",
            "rng-get-and-update-state"}

NO_WORK_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "bitcast-convert"}

# everything else (add/multiply/exp/convert/select/compare/broadcast/iota/...)
# is treated as a VPU elementwise op.


def classify(opcode: str) -> str:
    base = opcode[:-6] if opcode.endswith("-start") else (
        opcode[:-5] if opcode.endswith("-done") else opcode)
    if base in MXU_OPS:
        return "mxu"
    if base in COLLECTIVE_OPS:
        return "collective"
    if base in LAYOUT_OPS:
        return "layout"
    if base in IRREGULAR_OPS:
        return "irregular"
    if base in REDUCE_OPS:
        return "reduce"
    if base in FLOW_OPS or opcode in FLOW_OPS:
        return "flow"
    if base in NO_WORK_OPS:
        return "none"
    return "vpu"


# ---------------------------------------------------------------------------
# census
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CollectiveStat:
    kind: str
    count: float = 0.0
    operand_bytes: float = 0.0       # payload size (result for all-gather)
    wire_bytes: float = 0.0          # ring-model bytes on the wire per device


@dataclasses.dataclass
class Census:
    """Per-device instruction/traffic census (all fields trip-count scaled)."""

    flops: float = 0.0               # mxu_flops + vpu_flops
    mxu_flops: float = 0.0
    vpu_flops: float = 0.0           # 1 flop per elementwise output element
    hbm_bytes: float = 0.0           # fusion-boundary traffic model
    layout_bytes: float = 0.0        # subset of hbm_bytes moved by layout ops
    irregular_bytes: float = 0.0     # gather/scatter traffic
    mxu_issues: float = 0.0          # 128^3 systolic passes (ceil-tiled)
    mxu_flops_padded: float = 0.0    # issues x flops-per-issue
    vpu_issues: float = 0.0          # (8,128) vreg issues (ceil-tiled)
    scalar_ops: float = 0.0          # scalar-result + flow ops (SALU analogue)
    opcode_counts: Counter = dataclasses.field(default_factory=Counter)
    class_counts: Counter = dataclasses.field(default_factory=Counter)
    collectives: Dict[str, CollectiveStat] = dataclasses.field(
        default_factory=dict)

    @property
    def collective_wire_bytes(self) -> float:
        return sum(c.wire_bytes for c in self.collectives.values())

    @property
    def collective_operand_bytes(self) -> float:
        return sum(c.operand_bytes for c in self.collectives.values())

    @property
    def total_instructions(self) -> float:
        """Eq. 1 analogue: issue-scaled vector instructions + scalar ones."""
        return self.mxu_issues + self.vpu_issues + self.scalar_ops

    def merge_scaled(self, other: "Census", mult: float) -> None:
        self.flops += other.flops * mult
        self.mxu_flops += other.mxu_flops * mult
        self.vpu_flops += other.vpu_flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        self.layout_bytes += other.layout_bytes * mult
        self.irregular_bytes += other.irregular_bytes * mult
        self.mxu_issues += other.mxu_issues * mult
        self.mxu_flops_padded += other.mxu_flops_padded * mult
        self.vpu_issues += other.vpu_issues * mult
        self.scalar_ops += other.scalar_ops * mult
        for k, v in other.opcode_counts.items():
            self.opcode_counts[k] += v * mult
        for k, v in other.class_counts.items():
            self.class_counts[k] += v * mult
        for kind, stat in other.collectives.items():
            dst = self.collectives.setdefault(kind, CollectiveStat(kind))
            dst.count += stat.count * mult
            dst.operand_bytes += stat.operand_bytes * mult
            dst.wire_bytes += stat.wire_bytes * mult


def _group_size(attrs: str, num_partitions: int) -> int:
    m = _GROUPS_RE.search(attrs)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(attrs)
    if m:
        groups = [g for g in m.group(1).split("},{") if g.strip()]
        if groups:
            first = groups[0].strip("{}")
            ids = [x for x in first.split(",") if x.strip()]
            return max(1, len(ids))
    return max(1, num_partitions)


def _dot_census(inst: Instruction, comp: Computation) -> Tuple[float, float]:
    """Returns (flops, mxu_issues) for a dot instruction."""
    result = inst.shapes[0]
    lhs_shape: Optional[Shape] = None
    if inst.operands:
        op0 = comp.instructions.get(inst.operands[0])
        if op0 is not None and op0.shapes:
            lhs_shape = op0.shapes[0]
    cm = _CONTRACT_RE.search(inst.attrs)
    contract = 1
    if cm and lhs_shape is not None:
        for idx in (int(i) for i in cm.group(1).split(",") if i):
            if idx < len(lhs_shape.dims):
                contract *= lhs_shape.dims[idx]
    bm = _LHS_BATCH_RE.search(inst.attrs)
    n_batch = len([i for i in bm.group(1).split(",") if i]) if bm else 0
    flops = 2.0 * result.elements * contract
    # tile census: result = (batch..., M..., N) — treat minor dim as N, the
    # rest of the non-batch dims as M.
    dims = result.dims
    batch = 1
    for d in dims[:n_batch]:
        batch *= d
    rest = dims[n_batch:]
    if rest:
        n_dim = rest[-1]
        m_dim = 1
        for d in rest[:-1]:
            m_dim *= d
    else:
        n_dim, m_dim = 1, 1
    tiles = (batch * math.ceil(max(1, m_dim) / 128)
             * math.ceil(max(1, n_dim) / 128) * math.ceil(contract / 128))
    return flops, float(tiles)


def _conv_census(inst: Instruction, comp: Computation) -> Tuple[float, float]:
    """Rough convolution cost: 2 * output_elems * (kernel spatial * in-ch)."""
    result = inst.shapes[0]
    kernel: Optional[Shape] = None
    if len(inst.operands) > 1:
        op1 = comp.instructions.get(inst.operands[1])
        if op1 is not None and op1.shapes:
            kernel = op1.shapes[0]
    k_elems = kernel.elements if kernel is not None else 1
    out_ch = result.dims[-1] if result.dims else 1
    per_out = k_elems / max(1, out_ch)
    flops = 2.0 * result.elements * per_out
    issues = flops / (2.0 * 128 ** 3)
    return flops, max(1.0, math.ceil(issues))


_SLICE_LIKE = {"slice", "dynamic-slice"}
# single-operand ops that preserve the access pattern; fused interiors of
# these are register-resident, so byte accounting sees through them
_TRANSPARENT = {"convert", "bitcast", "copy", "reshape", "negate"}


def _through_users(fcomp: Computation, name: str):
    """BFS downstream through transparent ops (which may fan out to several
    users, e.g. ``convert -> {dynamic-slice, dynamic-update-slice}`` in the
    decode-cache pattern); returns the non-transparent terminal users."""
    out = []
    frontier = list(fcomp.users_of(name))
    seen = set()
    while frontier:
        u = frontier.pop()
        if u.name in seen:
            continue
        seen.add(u.name)
        if u.opcode in _TRANSPARENT:
            nxt = fcomp.users_of(u.name)
            if not nxt:
                out.append((u, u))
            else:
                frontier.extend(nxt)
        else:
            out.append((u, u))
    return out


def _through_operand(fcomp: Computation, inst: Instruction,
                     idx: int) -> Optional[Instruction]:
    """Follow operand `idx` upstream through transparent ops."""
    if idx >= len(inst.operands):
        return None
    cur = fcomp.instructions.get(inst.operands[idx])
    while cur is not None and cur.opcode in _TRANSPARENT and cur.operands:
        cur = fcomp.instructions.get(cur.operands[0])
    return cur


def _fusion_param_read_bytes(fcomp: Computation, param_index: int,
                             full: Shape) -> float:
    """Slice-aware read size of one fusion parameter (sees through
    convert/bitcast/copy chains)."""
    pinst = fcomp.param_named(param_index)
    pname = pinst.name if pinst is not None else None
    if pname is None:
        return full.bytes
    finals = _through_users(fcomp, pname)
    if not finals:
        return 0.0
    # every use is either a slice read or an in-place dynamic-update-slice
    # whose destination chain starts at this param (XLA fuses these in
    # place on TPU: only the slice regions are touched, the rest aliases) —
    # e.g. the decode-cache pattern  kc = slice(K, l); K' = dus(K, kc', l)
    total = 0.0
    for _, f in finals:
        if f.opcode in _SLICE_LIKE:
            total += f.shapes[0].bytes
            continue
        if f.opcode == "gather":
            src = _through_operand(fcomp, f, 0)
            if src is not None and src.name == pname:
                # table operand of a gather: only the gathered rows are
                # read (paged-KV pools — see the irregular-class model)
                total += f.shapes[0].bytes
                continue
        if f.opcode == "scatter":
            dest = _through_operand(fcomp, f, 0)
            if dest is not None and dest.name == pname and \
                    len(f.operands) >= 3:
                upd = _through_operand(fcomp, f, 2)
                if upd is not None and upd.shapes:
                    total += upd.shapes[0].bytes
                    continue
        if f.opcode == "dynamic-update-slice":
            dest = _through_operand(fcomp, f, 0)
            if dest is not None and dest.name == pname:
                upd = _through_operand(fcomp, f, 1)
                if upd is not None and upd.shapes:
                    total += upd.shapes[0].bytes
                    continue
        return full.bytes                       # some other use: full read
    return float(total)


def _fusion_write_bytes(fcomp: Computation) -> float:
    """Slice-aware write size of a fusion root (sees through transparent
    chains: a root convert(dus(...)) writes only the updated slice when XLA
    fuses it in place)."""
    root = fcomp.root
    if root is None:
        return 0.0
    roots = [root]
    if root.opcode == "tuple":
        roots = [fcomp.instructions[o] for o in root.operands
                 if o in fcomp.instructions]
    total = 0.0
    for r in roots:
        cur = r
        while cur.opcode in _TRANSPARENT and cur.operands:
            nxt = fcomp.instructions.get(cur.operands[0])
            if nxt is None:
                break
            cur = nxt
        if cur.opcode == "dynamic-update-slice" and len(cur.operands) >= 2:
            upd = _through_operand(fcomp, cur, 1)
            if upd is not None and upd.shapes:
                total += upd.shapes[0].bytes
                continue
        if cur.opcode == "scatter" and len(cur.operands) >= 3:
            # in-place row scatter: only the update rows are written
            upd = _through_operand(fcomp, cur, 2)
            if upd is not None and upd.shapes:
                total += upd.shapes[0].bytes
                continue
        total += shapes_bytes(r.shapes)
    return total


def _is_convert_like(inst: Instruction, comps: Dict[str, Computation]) -> bool:
    """A ``convert``, or a ``call``/``fusion`` whose callee is nothing but
    one root convert of its parameter (the CPU backend's sharded
    "parallel_convert" call and its "wrapped_convert" fusion around large
    buffers)."""
    if inst.opcode == "convert":
        return True
    if inst.opcode in ("call", "fusion"):
        m = (_TO_APPLY_RE if inst.opcode == "call" else _CALLS_RE).search(
            inst.attrs)
        callee = comps.get(m.group(1)) if m else None
        if callee is not None:
            body = [i for i in callee.order if i.opcode != "parameter"]
            return len(body) == 1 and body[0].opcode == "convert"
    return False


def _dtype_bracket_elisions(comp: Computation,
                            comps: Dict[str, Computation]) -> set:
    """Names of standalone ``convert`` pairs (or single-convert ``call``
    wrappers) that only BRACKET a donated in-place update in a wider
    compute dtype: an upcast straight off a parameter / loop state matched
    with a downcast of the SAME shape back to the SAME dtype feeding the
    root.  Backends without native narrow-dtype scatter (CPU) materialize
    these as whole-buffer converts around the update — e.g. the paged-KV
    COW page copy on a bf16 pool compiles to
    ``convert(pool) -> scatter -> convert`` and the brackets alone would
    charge 3x the POOL per copy, erasing the page-wise accounting the
    paged cache exists to create.  XLA:TPU updates the storage dtype in
    place (or fuses the converts), so the census elides matched bracket
    pairs; a genuine one-way cast (weight upcast, output quantization) has
    no same-shape partner and is still counted."""
    root = comp.root
    root_feeds = set()
    if root is not None:
        root_feeds.add(root.name)
        if root.opcode == "tuple":
            root_feeds.update(root.operands)
    ups: List[Instruction] = []
    # (src_shape, res_shape, name-to-elide or None): the downcast may be a
    # standalone convert (elide it too) or live INSIDE a root-feeding
    # fusion as its interior root (the fusion stays counted — only the
    # orphaned standalone upcast is the artifact then)
    downs: List[Tuple[Shape, Shape, Optional[str]]] = []
    for inst in comp.order:
        if not inst.operands or not inst.shapes:
            continue
        if _is_convert_like(inst, comps):
            src = comp.instructions.get(inst.operands[0])
            if src is None or not src.shapes:
                continue
            if src.opcode in ("parameter", "get-tuple-element"):
                ups.append(inst)
            if inst.name in root_feeds:
                downs.append((src.shapes[0], inst.shapes[0], inst.name))
        elif inst.opcode in ("fusion", "call") and inst.name in root_feeds:
            # follow nested fusion/call roots to a final interior convert
            # (the CPU backend nests its sharded wrapper around the update
            # fusion): the fusion stays counted — only the orphaned
            # standalone upcast is the artifact
            cur = inst
            for _ in range(3):
                cm = (_CALLS_RE.search(cur.attrs) if cur.opcode == "fusion"
                      else _TO_APPLY_RE.search(cur.attrs)
                      if cur.opcode == "call" else None)
                fcomp = comps.get(cm.group(1)) if cm else None
                froot = fcomp.root if fcomp is not None else None
                if froot is None:
                    break
                if froot.opcode == "convert" and froot.operands \
                        and froot.shapes:
                    fsrc = fcomp.instructions.get(froot.operands[0])
                    if fsrc is not None and fsrc.shapes:
                        downs.append((fsrc.shapes[0], froot.shapes[0],
                                      None))
                    break
                cur = froot
    elide: set = set()
    used_downs: set = set()
    for u in ups:
        u_src = comp.instructions[u.operands[0]].shapes[0]
        u_res = u.shapes[0]
        if DTYPE_BYTES.get(u_res.dtype, 0) <= DTYPE_BYTES.get(u_src.dtype, 0):
            continue                                   # not an upcast
        for di, (d_src, d_res, d_name) in enumerate(downs):
            if di in used_downs or d_name == u.name:
                continue
            if (d_res.dtype == u_src.dtype and d_res.dims == u_src.dims
                    and d_src.dtype == u_res.dtype
                    and d_src.dims == u_res.dims):
                elide.add(u.name)
                if d_name is not None:
                    elide.add(d_name)
                used_downs.add(di)
                break
    return elide


class ModuleCensus:
    """Walks the computation graph of a parsed module, scaling by while trip
    counts, producing a Census."""

    def __init__(self, comps: Dict[str, Computation], entry: str,
                 num_partitions: int = 1, default_trip: int = 1):
        self.comps = comps
        self.entry = entry
        self.num_partitions = num_partitions
        self.default_trip = default_trip
        self._cache: Dict[Tuple[str, bool], Census] = {}

    def run(self) -> Census:
        return self._census(self.entry, count_bytes=True)

    # -- internals ----------------------------------------------------------

    def _census(self, comp_name: str, count_bytes: bool) -> Census:
        key = (comp_name, count_bytes)
        if key in self._cache:
            return self._cache[key]
        comp = self.comps.get(comp_name)
        out = Census()
        if comp is None:
            self._cache[key] = out
            return out
        elide = _dtype_bracket_elisions(comp, self.comps)
        for inst in comp.order:
            if inst.name in elide:
                continue                   # backend dtype-bracket artifact
            self._one(inst, comp, out, count_bytes)
        self._cache[key] = out
        return out

    def _operand_shapes(self, inst: Instruction,
                        comp: Computation) -> List[Shape]:
        out: List[Shape] = []
        for name in inst.operands:
            op = comp.instructions.get(name)
            if op is not None:
                out.extend(op.shapes)
        return out

    def _one(self, inst: Instruction, comp: Computation, out: Census,
             count_bytes: bool) -> None:
        op = inst.opcode
        cls = classify(op)
        if op.endswith("-done") or op in ("async-update",):
            return                                  # counted at -start
        if cls == "none":
            return
        out.opcode_counts[op] += 1
        out.class_counts[cls] += 1
        res_bytes = shapes_bytes(inst.shapes)
        opnd_shapes = self._operand_shapes(inst, comp)
        opnd_bytes = shapes_bytes(opnd_shapes)

        if op == "while":
            trip = self.default_trip
            m = _TRIP_RE.search(inst.attrs)
            if m:
                trip = int(m.group(1))
            bm = _BODY_RE.search(inst.attrs)
            cm = _COND_RE.search(inst.attrs)
            if bm:
                out.merge_scaled(self._census(bm.group(1), count_bytes), trip)
            if cm:
                out.merge_scaled(self._census(cm.group(1), count_bytes),
                                 trip + 1)
            out.scalar_ops += 1
            return

        if op == "conditional":
            bm = _BRANCHES_RE.search(inst.attrs)
            names = []
            if bm:
                names = [n.strip().lstrip("%") for n in bm.group(1).split(",")]
            else:
                tm = _TO_APPLY_RE.search(inst.attrs)
                if tm:
                    names = [tm.group(1)]
            for n in names:                          # upper bound: all branches
                out.merge_scaled(self._census(n, count_bytes), 1.0)
            out.scalar_ops += 1
            return

        if op == "call":
            tm = _TO_APPLY_RE.search(inst.attrs)
            if tm:
                out.merge_scaled(self._census(tm.group(1), count_bytes), 1.0)
            out.scalar_ops += 1
            return

        if op == "fusion":
            cm2 = _CALLS_RE.search(inst.attrs)
            if cm2:
                fname = cm2.group(1)
                # interior census for instruction/flop counts (no bytes —
                # fused intermediates stay on-chip)
                out.merge_scaled(self._census(fname, count_bytes=False), 1.0)
                if count_bytes:
                    fcomp = self.comps.get(fname)
                    if fcomp is not None:
                        reads = 0.0
                        for i, sh in enumerate(opnd_shapes):
                            reads += _fusion_param_read_bytes(fcomp, i, sh)
                        out.hbm_bytes += reads + _fusion_write_bytes(fcomp)
                    else:
                        out.hbm_bytes += opnd_bytes + res_bytes
            return

        base = op[:-6] if op.endswith("-start") else op

        if cls == "collective":
            g = _group_size(inst.attrs, self.num_partitions)
            stat = out.collectives.setdefault(base, CollectiveStat(base))
            stat.count += 1
            if base == "all-gather":
                payload = res_bytes
                wire = res_bytes * (g - 1) / g
            elif base == "all-reduce":
                payload = res_bytes
                wire = 2.0 * res_bytes * (g - 1) / g
            elif base == "reduce-scatter":
                payload = opnd_bytes
                wire = opnd_bytes * (g - 1) / g
            elif base in ("all-to-all", "ragged-all-to-all"):
                payload = opnd_bytes
                wire = opnd_bytes * (g - 1) / g
            elif base == "collective-broadcast":
                payload = res_bytes
                wire = res_bytes
            else:                                    # collective-permute
                payload = res_bytes
                wire = res_bytes
            stat.operand_bytes += payload
            stat.wire_bytes += wire
            if count_bytes:
                out.hbm_bytes += opnd_bytes + res_bytes
            return

        if cls == "mxu":
            if base == "dot" or base == "ragged-dot":
                flops, issues = _dot_census(inst, comp)
            else:
                flops, issues = _conv_census(inst, comp)
            out.mxu_flops += flops
            out.flops += flops
            out.mxu_issues += issues
            out.mxu_flops_padded += issues * 2.0 * 128 ** 3
            if count_bytes:
                out.hbm_bytes += opnd_bytes + res_bytes
            return

        # --- scalar / flow ---------------------------------------------------
        is_scalar = all(len(s.dims) == 0 for s in inst.shapes)
        if cls == "flow" or is_scalar:
            out.scalar_ops += 1
            if count_bytes and cls != "flow":
                out.hbm_bytes += opnd_bytes + res_bytes
            if count_bytes and op == "custom-call":
                out.hbm_bytes += opnd_bytes + res_bytes
            return

        # --- layout / irregular / reduce / vpu -------------------------------
        if cls == "layout":
            if op == "copy" and inst.operands:
                # loop-carry pass-through copies (copy of a parameter /
                # get-tuple-element of the loop state) and ROOT copies that
                # move a FUSION/CALL result into the donated output buffer
                # are aliasing artifacts — XLA:TPU elides both via buffer
                # donation (the producer writes the aliased buffer
                # directly).  Shape equality cannot distinguish a genuine
                # layout-converting root copy (Shape drops layouts), so
                # the root case is gated on the producer opcode; a
                # layout-change root copy of a fusion is still elided —
                # acceptable for a TPU traffic model where the relayout
                # folds into the producer.
                src = comp.instructions.get(inst.operands[0])
                if src is not None and (
                        src.opcode in ("parameter", "get-tuple-element")
                        or (inst.is_root
                            and src.opcode in ("fusion", "call"))):
                    out.opcode_counts[op] -= 1
                    out.class_counts[cls] -= 1
                    return
            if base in _SLICE_LIKE:
                moved = 2.0 * res_bytes
            elif base == "dynamic-update-slice":
                upd = (opnd_shapes[1].bytes if len(opnd_shapes) > 1
                       else res_bytes)
                moved = 2.0 * upd
            elif base == "pad":
                moved = opnd_bytes + res_bytes
            else:
                moved = opnd_bytes + res_bytes
            out.layout_bytes += moved
            if count_bytes:
                out.hbm_bytes += moved
            # layout movement still costs vreg issues
            out.vpu_issues += inst.shapes[0].padded_vreg_issues()
            return

        if cls == "irregular":
            if base == "gather" and opnd_shapes:
                # the memory system touches the gathered rows (read) + the
                # result (write) + the index stream — NOT the whole table
                # operand (the paged-KV block-table gather reads live pages
                # only; counting the full pool would erase exactly the
                # transaction scaling the paged cache exists to create)
                idx = opnd_shapes[1].bytes if len(opnd_shapes) > 1 else 0.0
                moved = 2.0 * res_bytes + idx
            elif base == "scatter" and len(opnd_shapes) >= 3:
                # in-place row update: read+write the update rows + the
                # index stream; the untouched operand aliases (same
                # convention as dynamic-update-slice above)
                moved = 2.0 * opnd_shapes[2].bytes + opnd_shapes[1].bytes
            else:
                moved = opnd_bytes + res_bytes
            out.irregular_bytes += moved
            if count_bytes:
                out.hbm_bytes += moved
            out.vpu_issues += inst.shapes[0].padded_vreg_issues()
            out.vpu_flops += inst.shapes[0].elements
            out.flops += inst.shapes[0].elements
            return

        if cls == "reduce":
            in_elems = sum(s.elements for s in opnd_shapes[:1]) or 1
            in_issues = (opnd_shapes[0].padded_vreg_issues()
                         if opnd_shapes else 1)
            out.vpu_flops += in_elems
            out.flops += in_elems
            out.vpu_issues += in_issues
            if count_bytes:
                out.hbm_bytes += opnd_bytes + res_bytes
            return

        # vpu elementwise
        elems = sum(s.elements for s in inst.shapes)
        out.vpu_flops += elems
        out.flops += elems
        out.vpu_issues += sum(s.padded_vreg_issues() for s in inst.shapes)
        if count_bytes:
            if base == "broadcast" or base == "iota":
                out.hbm_bytes += res_bytes
            else:
                out.hbm_bytes += opnd_bytes + res_bytes


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")


def census_from_text(hlo_text: str) -> Census:
    comps, entry = parse_module(hlo_text)
    if not entry:
        # fall back: the largest computation
        entry = max(comps, key=lambda n: len(comps[n].order)) if comps else ""
    m = _NUM_PARTITIONS_RE.search(hlo_text[:2000])
    nparts = int(m.group(1)) if m else 1
    return ModuleCensus(comps, entry, num_partitions=nparts).run()


def census_from_compiled(compiled) -> Census:
    return census_from_text(compiled.as_text())
