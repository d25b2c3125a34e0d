"""Post-SPMD HLO cost walker — the dry-run "profiler" (no hardware needed).

``compiled.cost_analysis()`` counts while-loop bodies **once** (verified in
EXPERIMENTS.md §Dry-run), which under-reports scanned-layer models by ~num
layers; and it reports nothing about collectives.  This walker parses
``compiled.as_text()`` (the post-SPMD, per-partition module) and computes:

* ``flops``       — dot/convolution/elementwise/reduce FLOPs, **x while
                    trip counts** (``known_trip_count`` where XLA annotates
                    it; else read off the loop's condition, as the TPU
                    backend leaves it out);
* ``bytes``       — fusion-boundary traffic (operands+outputs of top-level
                    ops; fusion internals excluded, matching XLA's model);
* ``collective_bytes`` — assignment definition: sum of *operand* sizes of
  every all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute, x trip counts;
* ``wire_bytes``  — algorithm-aware refinement (ring all-reduce counts 2x
  (g-1)/g, all-gather (g-1)/g x output, ...), used for the collective
  roofline term;
* per-collective-type breakdowns and the trip-count table.

All quantities are **per device** (the SPMD module is one partition's
program); multiply by ``num_partitions`` for global numbers.
"""

from __future__ import annotations

import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*?)\s+([a-z][a-z0-9\-]*)\(")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONST_RE = re.compile(r"constant\((-?\d+)\)")
_GTE_INDEX_RE = re.compile(r"index=(\d+)")
_DIRECTION_RE = re.compile(r"direction=(\w+)")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_WINDOW_RE = re.compile(r"window=\{([^}]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
# a Pallas kernel's declared ``pl.CostEstimate``, as the TPU backend embeds
# it in the ``tpu_custom_call`` backend config
_KERNEL_COST_RE = re.compile(
    r'"cost_estimate":\{"flops":"(\d+)","transcendentals":"(\d+)"')

_TRANSCENDENTAL = {"exponential", "log", "tanh", "power", "rsqrt", "sqrt",
                   "logistic", "sine", "cosine", "erf", "exponential-minus-one",
                   "log-plus-one", "atan2", "cbrt"}
_ZERO_FLOP = {"parameter", "constant", "get-tuple-element", "tuple",
              "bitcast", "copy", "reshape", "transpose", "broadcast",
              "slice", "concatenate", "dynamic-slice",
              "dynamic-update-slice", "iota", "pad", "reverse", "gather",
              "scatter", "copy-start", "copy-done", "partition-id",
              "replica-id", "after-all", "custom-call", "optimization-barrier",
              "infeed", "outfeed", "rng-bit-generator", "convert",
              "bitcast-convert", "all-gather", "all-reduce", "reduce-scatter",
              "all-to-all", "collective-permute", "select-and-scatter"}
_NO_BYTES = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "partition-id", "replica-id", "after-all",
             "while", "conditional", "call", "optimization-barrier"}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

# unary ops the fusion-bytes model traces through (layout/dtype wrappers the
# CPU backend inserts around in-place updates; free or fused on TPU)
_UNARY_THRU = {"convert", "bitcast", "copy", "reshape", "transpose"}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_elems(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _shape_dims(type_str: str) -> list:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    line: str


@dataclass
class CollectiveRecord:
    opcode: str
    operand_bytes: int
    output_bytes: int
    group_size: int
    count: float = 1.0          # trip multiplier


@dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    bytes_fused: float = 0.0     # TPU-fusion model: elementwise chains fuse
    transcendentals: float = 0.0
    collective_operand_bytes: float = 0.0
    collective_wire_bytes: float = 0.0
    by_collective: dict = field(default_factory=dict)
    collectives: list = field(default_factory=list)
    trip_counts: dict = field(default_factory=dict)
    num_partitions: int = 1

    def add(self, other: "HloCost", factor: float = 1.0):
        self.flops += other.flops * factor
        self.bytes += other.bytes * factor
        self.bytes_fused += other.bytes_fused * factor
        self.transcendentals += other.transcendentals * factor
        self.collective_operand_bytes += \
            other.collective_operand_bytes * factor
        self.collective_wire_bytes += other.collective_wire_bytes * factor
        for k, v in other.by_collective.items():
            self.by_collective[k] = self.by_collective.get(k, 0.0) \
                + v * factor


def parse_computations(hlo_text: str):
    """-> (computations: name -> [Instr], num_partitions)."""
    num_partitions = 1
    m = re.search(r"num_partitions=(\d+)", hlo_text)
    if m:
        num_partitions = int(m.group(1))
    comps: dict = {}
    cur: Optional[list] = None
    for line in hlo_text.splitlines():
        cm = _COMP_RE.match(line)
        if cm and line.rstrip().endswith("{"):
            cur = []
            comps[cm.group(2)] = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        im = _INSTR_RE.match(line)
        if im:
            cur.append(Instr(im.group(1), im.group(2), im.group(3),
                             line.strip()))
    return comps, num_partitions


def _group_size(line: str, num_partitions: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_V1_RE.search(line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return max(len(ids), 1)
    return num_partitions


def _dot_flops(instr: Instr, shapes: dict) -> float:
    out_elems = _shape_elems(instr.type_str)
    ops = _OPERAND_RE.findall(instr.line.split("(", 1)[1])
    lhs_shape = shapes.get(ops[0], []) if ops else []
    m = _LHS_CONTRACT_RE.search(instr.line)
    contract = 1
    if m and lhs_shape:
        for d in m.group(1).split(","):
            if d and int(d) < len(lhs_shape):
                contract *= lhs_shape[int(d)]
    return 2.0 * out_elems * contract


def _valid_taps(n_in: int, n_out: int, k: int, stride: int, pad_lo: int,
                lhs_dilate: int, rhs_dilate: int) -> int:
    """(output position, kernel tap) pairs of one spatial dimension that
    land on a real input element: not padding, not a hole of the input's
    dilation."""
    last = (n_in - 1) * lhs_dilate
    count = 0
    for tap in range(k):
        c = tap * rhs_dilate - pad_lo
        for o in range(n_out):
            pos = o * stride + c
            if 0 <= pos <= last and pos % lhs_dilate == 0:
                count += 1
    return count


def _conv_flops(instr: Instr, shapes: dict) -> float:
    """2 x the multiply-adds of a convolution: for each output batch and
    feature element, the kernel's input features over every valid tap.
    The TPU backend lowers every matmul to one; a batched matmul becomes a
    convolution over the batch dims with ``lhs_dilate`` = window size, of
    which one tap per output position is real."""
    labels = _DIM_LABELS_RE.search(instr.line)
    ops = _OPERAND_RE.findall(instr.line.split("(", 1)[1])
    if not labels or len(ops) < 2:
        return 0.0
    lhs_l, rhs_l, out_l = labels.groups()
    lhs, rhs = shapes.get(ops[0], []), shapes.get(ops[1], [])
    out = _shape_dims(instr.type_str)
    if (len(lhs), len(rhs), len(out)) != (len(lhs_l), len(rhs_l),
                                          len(out_l)):
        return 0.0
    win = {}
    w = _WINDOW_RE.search(instr.line)
    for field in (w.group(1).split() if w else []):
        key, _, val = field.partition("=")
        win[key] = val.split("x")
    macs = out[out_l.index("b")] * out[out_l.index("f")] \
        * rhs[rhs_l.index("i")]
    for i in range(len(out_l) - 2):
        d = str(i)

        def attr(key, default, i=i):
            vals = win.get(key)
            return vals[i] if vals else default
        pad_lo = int(attr("pad", "0_0").split("_")[0])
        macs *= _valid_taps(lhs[lhs_l.index(d)], out[out_l.index(d)],
                            rhs[rhs_l.index(d)], int(attr("stride", "1")),
                            pad_lo, int(attr("lhs_dilate", "1")),
                            int(attr("rhs_dilate", "1")))
    return 2.0 * macs


class HloAnalyzer:
    def __init__(self, hlo_text: str):
        self.comps, self.num_partitions = parse_computations(hlo_text)
        self._shapes: dict = {}
        self._instrs: dict = {}
        for instrs in self.comps.values():
            for i in instrs:
                self._shapes[i.name] = _shape_dims(i.type_str)
                self._instrs[i.name] = i
        self._memo: dict = {}
        self.trip_counts: dict = {}

    # -- entry ------------------------------------------------------------

    def analyze(self, entry: Optional[str] = None) -> HloCost:
        if entry is None:
            entry = self._find_entry()
        cost = self._comp_cost(entry)
        return HloCost(cost.flops, cost.bytes, cost.bytes_fused,
                       cost.transcendentals,
                       cost.collective_operand_bytes,
                       cost.collective_wire_bytes, dict(cost.by_collective),
                       list(cost.collectives), dict(self.trip_counts),
                       self.num_partitions)

    def _find_entry(self) -> str:
        # the ENTRY computation is the one no other computation references
        referenced = set()
        for instrs in self.comps.values():
            for i in instrs:
                for rx in (_CALLS_RE, _BODY_RE, _COND_RE):
                    for m in rx.finditer(i.line):
                        referenced.add(m.group(1))
        unref = [n for n in self.comps if n not in referenced]
        for name in unref:
            if "main" in name:
                return name
        if unref:
            return unref[0]
        return next(iter(self.comps))

    # -- recursive costing ---------------------------------------------------

    def _comp_cost(self, name: str) -> HloCost:
        if name in self._memo:
            return self._memo[name]
        cost = HloCost()
        self._memo[name] = cost          # cycle guard (shouldn't happen)
        for instr in self.comps.get(name, []):
            self._instr_cost(instr, cost)
        return cost

    @staticmethod
    def _operand_text(line: str) -> str:
        """Text inside the opcode's operand parens (balance-aware).  The
        search starts at the opcode: a TPU layout in the result type
        (``{1,0:T(8,128)(2,1)}``) has parens of its own."""
        m = _INSTR_RE.match(line)
        if m is None:
            return ""
        start = m.end() - 1
        depth = 0
        for i in range(start, len(line)):
            if line[i] == "(":
                depth += 1
            elif line[i] == ")":
                depth -= 1
                if depth == 0:
                    return line[start + 1:i]
        return line[start + 1:]

    def _operand_bytes(self, instr: Instr) -> int:
        total = 0
        for op in _OPERAND_RE.findall(self._operand_text(instr.line)):
            total += self._def_bytes(op)
        return total

    def _update_operand_bytes(self, instr: Instr) -> int:
        """Bytes of the *update* operand (2nd) of a DUS/scatter."""
        ops = _OPERAND_RE.findall(self._operand_text(instr.line))
        if len(ops) >= 2:
            return self._def_bytes(ops[1])
        return _shape_bytes(instr.type_str)

    def _fusion_effective_operand_bytes(self, instr: Instr,
                                        called: str) -> int:
        """Effective HBM reads of a fusion: a parameter consumed *only* by
        dynamic-slice (or as the in-place target of dynamic-update-slice)
        inside the fusion contributes the sliced sizes, not its full size —
        the layer-scan + gradient-accumulation pattern."""
        usage = self._param_usage(called)
        ops = _OPERAND_RE.findall(self._operand_text(instr.line))
        total = 0
        for i, opname in enumerate(ops):
            eff = usage.get(i)
            if eff is None:
                total += self._def_bytes(opname)
            else:
                total += eff
        return total

    def _fusion_effective_out_bytes(self, called: str,
                                    out_bytes: int) -> int:
        """Fusions whose ROOT is a dynamic-update-slice on a parameter
        alias the buffer in place — the written bytes are the update region,
        not the whole (e.g. layer-stacked gradient) buffer."""
        instrs = self.comps.get(called, [])
        params = {i.name for i in instrs if i.opcode == "parameter"}
        by_name = {i.name: i for i in instrs}
        root = None
        for i in instrs:
            if i.line.startswith("ROOT "):
                root = i
                break
        if root is None:
            root = instrs[-1] if instrs else None
        if root is None:
            return out_bytes

        def unwrap(instr):
            """Follow unary convert/bitcast/copy/reshape wrappers down."""
            seen = 0
            while instr is not None and instr.opcode in _UNARY_THRU \
                    and seen < 8:
                ops = _OPERAND_RE.findall(self._operand_text(instr.line))
                instr = by_name.get(ops[0]) if ops else None
                seen += 1
            return instr

        def dus_eff(instr) -> Optional[int]:
            instr = unwrap(instr)
            if instr is None or instr.opcode != "dynamic-update-slice":
                return None
            ops = _OPERAND_RE.findall(self._operand_text(instr.line))
            tgt = unwrap(by_name.get(ops[0])) if ops else None
            tgt_name = ops[0] if ops else ""
            # target must trace back to a parameter (possibly via wrappers)
            if tgt_name in params or (
                    tgt is not None and tgt.opcode == "parameter"):
                return self._update_operand_bytes(instr)
            return None

        e = dus_eff(root)
        if e is not None:
            return e
        if root.opcode == "tuple":
            total = 0
            for opname in _OPERAND_RE.findall(
                    self._operand_text(root.line)):
                sub = by_name.get(opname)
                se = dus_eff(sub) if sub is not None else None
                total += se if se is not None else self._def_bytes(opname)
            return total
        return out_bytes

    def _passes_through(self, comp_name: str, index: int) -> bool:
        """Parameter ``index`` reaches the computation's root through
        single-consumer unary wrappers only."""
        instrs = self.comps.get(comp_name, [])
        cur = next((i for i in instrs if i.opcode == "parameter" and
                    f"parameter({index})" in i.line), None)
        while cur is not None:
            if cur.line.startswith("ROOT"):
                return True
            users = [i for i in instrs if cur.name in
                     _OPERAND_RE.findall(self._operand_text(i.line))]
            if len(users) != 1 or users[0].opcode not in _UNARY_THRU:
                return False
            cur = users[0]
        return False

    def _param_usage(self, comp_name: str) -> dict:
        """param index -> effective bytes (None = read fully)."""
        if not hasattr(self, "_param_usage_cache"):
            self._param_usage_cache: dict = {}
        if comp_name in self._param_usage_cache:
            return self._param_usage_cache[comp_name]
        out: dict = {}
        instrs = self.comps.get(comp_name, [])
        params = {}
        for i in instrs:
            if i.opcode == "parameter":
                m = re.search(r"parameter\((\d+)\)", i.line)
                if m:
                    params[i.name] = int(m.group(1))
        # consumer map
        consumers: dict = {}
        for i in instrs:
            if i.opcode == "parameter":
                continue
            for opname in _OPERAND_RE.findall(self._operand_text(i.line)):
                consumers.setdefault(opname, []).append(i)

        def eff_bytes(name: str, depth: int = 0) -> Optional[int]:
            """Sliced-traffic of value ``name``; None = read fully."""
            if depth > 8:
                return None
            total = 0
            for c in consumers.get(name, []):
                ops = _OPERAND_RE.findall(self._operand_text(c.line))
                if c.opcode == "dynamic-slice" and ops and ops[0] == name:
                    total += _shape_bytes(c.type_str)
                elif c.opcode == "dynamic-update-slice" and ops and \
                        ops[0] == name:
                    total += self._update_operand_bytes(c)
                elif c.opcode == "gather" and ops and ops[0] == name:
                    total += _shape_bytes(c.type_str)
                elif c.opcode in _UNARY_THRU:
                    sub = eff_bytes(c.name, depth + 1)
                    if sub is None:
                        return None
                    total += sub
                elif c.opcode == "fusion" and _CALLS_RE.search(c.line):
                    # a nested fusion (the TPU backend's slice inside the
                    # matmul's fusion) reads what its parameter reads; one
                    # that only passes it on (a bitcast) reads what its
                    # consumers here read
                    called = _CALLS_RE.search(c.line).group(1)
                    inner = self._param_usage(called)
                    for k in (k for k, o in enumerate(ops) if o == name):
                        sub = eff_bytes(c.name, depth + 1) \
                            if self._passes_through(called, k) \
                            else inner.get(k)
                        if sub is None:
                            return None
                        total += sub
                else:
                    return None
            return total

        for pname, pidx in params.items():
            e = eff_bytes(pname)
            if e is not None and consumers.get(pname):
                out[pidx] = e
        self._param_usage_cache[comp_name] = out
        return out

    def _def_bytes(self, opname: str) -> int:
        return self._def_bytes_cache.setdefault(
            opname, _shape_bytes(self._def_types.get(opname, "")))

    def _build_def_types(self):
        self._def_types = {}
        self._def_bytes_cache: dict = {}
        for instrs in self.comps.values():
            for i in instrs:
                self._def_types[i.name] = i.type_str

    def _instr_cost(self, instr: Instr, cost: HloCost):
        if not hasattr(self, "_def_types"):
            self._build_def_types()
        op = instr.opcode
        out_bytes = _shape_bytes(instr.type_str)
        out_elems = _shape_elems(instr.type_str)

        if op == "while":
            body = _BODY_RE.search(instr.line)
            cond = _COND_RE.search(instr.line)
            m = _TRIP_RE.search(instr.line)
            trip = float(m.group(1)) if m else \
                self._trip_from_condition(instr, cond and cond.group(1))
            inner = HloCost()
            if body:
                inner.add(self._comp_cost(body.group(1)))
            if cond:
                inner.add(self._comp_cost(cond.group(1)))
            self.trip_counts[instr.name] = trip
            cost.add(inner, trip)
            return

        if op in ("call", "fusion"):
            m = _CALLS_RE.search(instr.line)
            eff_operands = self._operand_bytes(instr)
            eff_out = out_bytes
            if m:
                sub = self._comp_cost(m.group(1))
                # fusion: interior bytes don't touch HBM; flops do count
                cost.flops += sub.flops
                cost.transcendentals += sub.transcendentals
                cost.collective_operand_bytes += sub.collective_operand_bytes
                cost.collective_wire_bytes += sub.collective_wire_bytes
                for k, v in sub.by_collective.items():
                    cost.by_collective[k] = cost.by_collective.get(k, 0) + v
                eff_operands = self._fusion_effective_operand_bytes(
                    instr, m.group(1))
                eff_out = self._fusion_effective_out_bytes(
                    m.group(1), out_bytes)
            cost.bytes += out_bytes + self._operand_bytes(instr)
            cost.bytes_fused += eff_out + eff_operands
            return

        if op == "conditional":
            subs = [self._comp_cost(n) for n in
                    _CALLS_RE.findall(instr.line)] or [HloCost()]
            biggest = max(subs, key=lambda c: c.flops)
            cost.add(biggest)
            cost.bytes += out_bytes
            return

        if op in COLLECTIVE_OPS:
            operand_bytes = self._operand_bytes(instr)
            g = _group_size(instr.line, self.num_partitions)
            frac = (g - 1) / g if g > 1 else 0.0
            if op == "all-reduce":
                wire = 2.0 * frac * operand_bytes
            elif op == "all-gather":
                wire = frac * out_bytes
            elif op == "reduce-scatter":
                wire = frac * operand_bytes
            elif op == "all-to-all":
                wire = frac * operand_bytes
            else:                       # collective-permute
                wire = float(operand_bytes)
            cost.collective_operand_bytes += operand_bytes
            cost.collective_wire_bytes += wire
            cost.by_collective[op] = cost.by_collective.get(op, 0.0) \
                + operand_bytes
            cost.collectives.append(CollectiveRecord(
                op, operand_bytes, out_bytes, g))
            cost.bytes += out_bytes + operand_bytes
            cost.bytes_fused += out_bytes + operand_bytes
            return

        # ---- plain ops ----------------------------------------------------
        # hbm_real: ops that necessarily move HBM traffic even after TPU
        # producer-consumer fusion (matmuls, reductions, data reshuffles);
        # bare elementwise/copy/layout ops at the top level are artifacts of
        # the CPU backend's weaker fusion and are excluded from bytes_fused.
        hbm_real = op in ("dot", "reduce", "reduce-window", "sort", "gather",
                          "scatter", "dynamic-slice", "dynamic-update-slice",
                          "concatenate", "pad", "rng-bit-generator",
                          "convolution")
        if op == "custom-call":
            # a Mosaic kernel is opaque to the walk: its flops are the ones
            # the kernel declares; its operands and results cross HBM
            m = _KERNEL_COST_RE.search(instr.line)
            if m:
                cost.flops += float(m.group(1))
                cost.transcendentals += float(m.group(2))
                hbm_real = True
        elif op == "dot":
            cost.flops += _dot_flops(instr, self._shapes)
        elif op == "convolution":
            cost.flops += _conv_flops(instr, self._shapes)
        elif op in ("reduce", "reduce-window"):
            cost.flops += self._operand_elems_first(instr)
        elif op == "sort":
            n = self._operand_elems_first(instr)
            cost.flops += n * max(n.bit_length(), 1)
        elif op in _ZERO_FLOP:
            pass
        elif op in _TRANSCENDENTAL:
            cost.flops += 5.0 * out_elems
            cost.transcendentals += out_elems
        else:                           # generic elementwise
            cost.flops += float(out_elems)

        if op not in _NO_BYTES:
            io = out_bytes + self._operand_bytes(instr)
            cost.bytes += io
            if hbm_real:
                # in-place models: DS/DUS/gather/scatter touch only the
                # sliced region (XLA aliases the big operand in place); the
                # naive operand sum charges e.g. a layer-stacked (L, d, d)
                # weight buffer for every per-layer slice — a 40-96x
                # overcount on scanned models.
                if op == "dynamic-slice":
                    io = 2 * out_bytes
                elif op == "dynamic-update-slice":
                    io = 2 * self._update_operand_bytes(instr)
                elif op == "gather":
                    io = 2 * out_bytes
                elif op == "scatter":
                    io = 3 * self._update_operand_bytes(instr)
                cost.bytes_fused += io

    def _operands(self, instr: Instr) -> list:
        return _OPERAND_RE.findall(self._operand_text(instr.line))

    def _int_constant(self, name: str):
        """The integer a value holds, followed through copies and
        bitcasts to its ``constant``; ``None`` if it is not one."""
        i = self._instrs.get(name)
        while i is not None and i.opcode in ("copy", "bitcast"):
            ops = self._operands(i)
            i = self._instrs.get(ops[0]) if ops else None
        if i is None or i.opcode != "constant":
            return None
        m = _CONST_RE.search(i.line)
        return int(m.group(1)) if m else None

    def _trip_from_condition(self, loop: Instr, cond: Optional[str]) -> float:
        """A counted loop's trips from ``counter < N`` (or ``<=``) at the
        root of its condition, the counter's start read from the loop's
        initial tuple (0 if it is not a constant) and a step of 1, as a
        ``scan`` makes it; 1 for any other loop."""
        instrs = self.comps.get(cond or "", [])
        root = next((i for i in instrs if i.line.startswith("ROOT")), None)
        if root is None or root.opcode != "compare":
            return 1.0
        direction = _DIRECTION_RE.search(root.line)
        ops = self._operands(root)
        if not direction or len(ops) != 2 or \
                direction.group(1) not in ("LT", "LE"):
            return 1.0
        counter = self._instrs.get(ops[0])
        limit = self._int_constant(ops[1])
        if counter is None or counter.opcode != "get-tuple-element" or \
                limit is None:
            return 1.0
        index = int(_GTE_INDEX_RE.search(counter.line).group(1))
        start = 0
        init = self._instrs.get((self._operands(loop) or [""])[0])
        if init is not None and init.opcode == "tuple":
            elems = self._operands(init)
            if index < len(elems):
                start = self._int_constant(elems[index]) or 0
        trips = limit - start + (direction.group(1) == "LE")
        return float(max(trips, 0))

    def _operand_elems_first(self, instr: Instr) -> int:
        ops = _OPERAND_RE.findall(self._operand_text(instr.line))
        if not ops:
            return 0
        dims = self._shapes.get(ops[0], [])
        n = 1
        for d in dims:
            n *= d
        return n


def analyze_hlo(hlo_text: str) -> dict:
    """-> JSON-able per-device cost dict."""
    an = HloAnalyzer(hlo_text)
    c = an.analyze()
    return {
        "num_partitions": c.num_partitions,
        "per_device": {
            "flops": c.flops,
            "bytes": c.bytes,
            "bytes_fused": c.bytes_fused,
            "transcendentals": c.transcendentals,
            "collective_operand_bytes": c.collective_operand_bytes,
            "collective_wire_bytes": c.collective_wire_bytes,
            "by_collective": c.by_collective,
        },
        "global": {
            "flops": c.flops * c.num_partitions,
            "bytes": c.bytes * c.num_partitions,
            "bytes_fused": c.bytes_fused * c.num_partitions,
            "collective_operand_bytes":
                c.collective_operand_bytes * c.num_partitions,
            "collective_wire_bytes":
                c.collective_wire_bytes * c.num_partitions,
        },
        "trip_counts": c.trip_counts,
    }
