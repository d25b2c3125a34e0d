"""LIKWID performance groups, TPU-native (paper §V; hardware adaptation §2).

LIKWID abstracts HPM portability behind named *performance groups*: a group
lists the raw counter events to program and formulas for derived metrics.
TPUs expose no user MSRs; the raw "events" here come from the compiled XLA
artifact (cost/memory analysis, HLO collective parse) plus step wall-times —
see DESIGN.md §2 for the full source mapping.

Groups are defined in a LIKWID-like text format::

    GROUP FLOPS
    EVENTSET
      hlo_flops
      step_time_s
    METRICS
      gflops_per_s  hlo_flops / step_time_s / 1e9
      mfu           model_flops / step_time_s / PEAK_FLOPS

and evaluated with a tiny safe arithmetic evaluator (no eval()).
"""

from __future__ import annotations

import ast
import functools
import operator
from dataclasses import dataclass, field
from typing import Optional

from repro.core.rollup import quantile_of

# --------------------------------------------------------------------------
# Hardware constants: one TPU v5e chip
# --------------------------------------------------------------------------

# ``jax.Device.device_kind`` of the chip these peaks describe.  Source:
# Google Cloud documentation, "TPU v5e" page — 197 TFLOP/s bf16, 819 GB/s
# HBM, 1,600 Gbit/s of interchip interconnect over 4 links (50 GB/s each).
PEAKS_DEVICE_KIND = "TPU v5 lite"
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~ per chip per direction)

HW_CONSTANTS = {
    "PEAK_FLOPS": PEAK_FLOPS,
    "HBM_BW": HBM_BW,
    "ICI_BW": ICI_BW,
}


def check_device_peaks(device) -> None:
    """Refuse a TPU whose peaks are not the ones above: every rate formula
    divides by them, so a job on another chip would report shares of the
    wrong peak.  Non-TPU devices (the CPU test path) report no device
    shares and pass."""
    if device.platform == "tpu" and device.device_kind != PEAKS_DEVICE_KIND:
        raise ValueError(
            f"no peaks for TPU kind {device.device_kind!r}; "
            f"core/perf_groups.py holds {PEAKS_DEVICE_KIND!r} only")


# --------------------------------------------------------------------------
# Safe formula evaluation (compiled once, applied many times)
# --------------------------------------------------------------------------

_BINOPS = {ast.Add: operator.add, ast.Sub: operator.sub,
           ast.Mult: operator.mul, ast.Div: operator.truediv,
           ast.Pow: operator.pow, ast.Mod: operator.mod}
_UNOPS = {ast.USub: operator.neg, ast.UAdd: operator.pos}
_FUNCS = {"min": min, "max": max, "abs": abs}


def _build(node, names: list):
    """AST node -> ``fn(env) -> float`` closure (no AST walking at eval
    time).  Only the whitelisted arithmetic subset compiles; anything else
    raises ValueError at *compile* time.  ``names`` collects every bare
    identifier the formula references (first-seen order, deduplicated) —
    what the query planner turns into input columns."""
    if isinstance(node, ast.Expression):
        return _build(node.body, names)
    if isinstance(node, ast.Constant):
        if isinstance(node.value, (int, float)) and \
                not isinstance(node.value, bool):
            c = float(node.value)
            return lambda env: c
        raise ValueError(f"bad constant {node.value!r}")
    if isinstance(node, ast.Name) or (
            isinstance(node, ast.Attribute) and
            isinstance(node.value, ast.Name)):
        # a bare identifier, or the query engine's cross-measurement
        # reference ``measurement.field`` (one dotted level) — both look
        # up ``env`` by their full spelling
        ident = node.id if isinstance(node, ast.Name) \
            else f"{node.value.id}.{node.attr}"
        if ident not in names:
            names.append(ident)

        def name_fn(env, ident=ident):
            if ident in env:
                return float(env[ident])
            if ident in HW_CONSTANTS:
                return HW_CONSTANTS[ident]
            raise KeyError(ident)
        return name_fn
    if isinstance(node, ast.BinOp) and type(node.op) in _BINOPS:
        op = _BINOPS[type(node.op)]
        left, right = _build(node.left, names), _build(node.right, names)
        return lambda env: op(left(env), right(env))
    if isinstance(node, ast.UnaryOp) and type(node.op) in _UNOPS:
        op = _UNOPS[type(node.op)]
        operand = _build(node.operand, names)
        return lambda env: op(operand(env))
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        if node.func.id in _FUNCS:
            func = _FUNCS[node.func.id]
            args = [_build(a, names) for a in node.args]
            return lambda env: func(*[a(env) for a in args])
        if quantile_of(node.func.id) is not None and len(node.args) == 1 \
                and not node.keywords:
            # a quantile call over one identifier — p95(flops),
            # p99(hpm.step_time_s) — compiles to a *synthetic identifier*
            # "pNN(ident)".  The query planner reduces that input's
            # mergeable partials with the quantile agg and feeds the
            # result back through env; there is no constant fallback
            # (a quantile is data, never a HW constant).
            arg = node.args[0]
            if isinstance(arg, ast.Name):
                inner = arg.id
            elif isinstance(arg, ast.Attribute) and \
                    isinstance(arg.value, ast.Name):
                inner = f"{arg.value.id}.{arg.attr}"
            else:
                raise ValueError(
                    f"{node.func.id}() takes one field or "
                    f"measurement.field identifier")
            ident = f"{node.func.id}({inner})"
            if ident not in names:
                names.append(ident)

            def quantile_fn(env, ident=ident):
                if ident in env:
                    return float(env[ident])
                raise KeyError(ident)
            return quantile_fn
    raise ValueError(f"disallowed syntax: {ast.dump(node)}")


class CompiledFormula:
    """One parsed + compiled formula: a closure tree built once from the
    AST, then applied per evaluation — no re-parse, no AST walk.

    ``eval`` reproduces the historical ``eval_formula`` semantics exactly
    (env lookup first, then ``HW_CONSTANTS``, else ``KeyError``).
    ``eval_columns`` is the query engine's vectorized form: the same
    compiled closure applied across aligned window columns, with a ``None``
    hole wherever the scalar evaluation would have raised ``KeyError`` /
    ``ZeroDivisionError`` (missing input or domain error for that window).
    """

    __slots__ = ("expr", "names", "_fn")

    def __init__(self, expr: str):
        self.expr = expr
        names: list = []
        self._fn = _build(ast.parse(expr, mode="eval"), names)
        self.names = tuple(names)

    def eval(self, env: dict) -> float:
        return self._fn(env)

    def eval_columns(self, cols: dict, n: int) -> list:
        """Apply across ``n`` aligned windows.  ``cols`` maps input name ->
        value list of length ``n`` (``None`` holes where the window has no
        value for that input; names absent from ``cols`` entirely fall back
        to ``HW_CONSTANTS`` exactly like scalar evaluation).

        A window whose evaluation is unanswerable yields ``None``:
        missing input (KeyError) and domain errors — division by zero,
        overflow, or a complex result (``(a-b) ** 0.5`` with a < b) —
        must skip the window, never leak a non-float into query results
        or threshold comparisons."""
        fn = self._fn
        series = [(k, cols.get(k)) for k in self.names]
        out = []
        for i in range(n):
            env = {}
            for k, col in series:
                if col is not None:
                    v = col[i]
                    if v is not None:
                        env[k] = v
            try:
                v = fn(env)
            except (KeyError, ZeroDivisionError, OverflowError):
                v = None
            else:
                if isinstance(v, complex):
                    v = None
            out.append(v)
        return out


# Module-level parse cache: every PerfGroup.derive / query-engine plan
# compiles a given formula text exactly once per process.  Bounded (a
# remote /query/v2 spec carries caller-written formula text, so an
# unbounded cache would be a remote-fillable leak), thread-safe and
# LRU-by-recency — sustained distinct-formula traffic cannot evict the
# hot built-in group formulas that every collection tick derives.
# Parse errors are not cached, so a bad formula raises on every call,
# exactly like direct construction.
compile_formula = functools.lru_cache(maxsize=4096)(CompiledFormula)


def eval_formula(expr: str, env: dict) -> float:
    """Evaluate an arithmetic expression over ``env`` (names -> numbers).

    Compiles through the module-level cache, so repeated evaluation of the
    same formula (every collection tick, every query window) pays the
    parse exactly once."""
    return compile_formula(expr).eval(env)


# --------------------------------------------------------------------------
# Group definitions
# --------------------------------------------------------------------------


@dataclass
class PerfGroup:
    name: str
    events: list                       # required raw event names
    metrics: list                      # (metric name, formula) pairs
    description: str = ""

    def derive(self, raw_events: dict, strict: bool = False,
               skipped: Optional[list] = None) -> dict:
        """raw events -> derived metrics; missing events skip the metric.

        With ``strict=False`` a skipped metric is *recorded*, not silently
        swallowed: pass ``skipped`` (a list) to receive ``(metric_name,
        reason)`` pairs — ``reason`` names the missing event or the
        division by zero.  Formulas are compiled once per process
        (module-level parse cache in :func:`compile_formula`).
        """
        out = {}
        for mname, formula in self.metrics:
            try:
                out[mname] = compile_formula(formula).eval(raw_events)
            except KeyError as e:
                if strict:
                    raise
                if skipped is not None:
                    skipped.append((mname, f"missing event {e.args[0]!r}"))
            except ZeroDivisionError:
                if strict:
                    raise
                if skipped is not None:
                    skipped.append((mname, "division by zero"))
        return out


def parse_group(text: str) -> PerfGroup:
    """Parse the LIKWID-like group format (GROUP/EVENTSET/METRICS)."""
    name, desc = "", ""
    events, metrics = [], []
    section = None
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        if line.startswith("GROUP"):
            name = line.split(None, 1)[1].strip()
        elif line == "EVENTSET":
            section = "events"
        elif line == "METRICS":
            section = "metrics"
        elif line.startswith("DESC"):
            desc = line.split(None, 1)[1].strip()
        elif section == "events":
            events.append(line.split()[0])
        elif section == "metrics":
            parts = line.split(None, 1)
            if len(parts) == 2:
                metrics.append((parts[0], parts[1]))
    if not name:
        raise ValueError("group text missing GROUP header")
    return PerfGroup(name, events, metrics, desc)


# ROOFLINE: per-region roofline placement over marker work counters
# (repro.core.marker).  The template is shared with the calibrated
# re-registration path: without measured peaks the formulas reference the
# symbolic PEAK_FLOPS / HBM_BW names (HW_CONSTANTS fallback at eval
# time); with peaks they are baked in as numeric literals, so the
# resolved formula text itself carries the calibration inside any
# QuerySpec that references @ROOFLINE.* metrics.
_ROOFLINE_TEMPLATE = """
GROUP ROOFLINE
DESC marker-region roofline placement from work counters ({why})
EVENTSET
  flops
  bytes
  time_s
METRICS
  intensity           flops / bytes
  achieved_gflops     flops / time_s / 1e9
  attainable_gflops   min({pf}, {bw} * flops / bytes) / 1e9
  roofline_frac       flops / time_s / min({pf}, {bw} * flops / bytes)
"""


def roofline_group_text(peak_flops: Optional[float] = None,
                        peak_bw: Optional[float] = None) -> str:
    """The ROOFLINE group text, with measured peaks baked in when given."""
    if peak_flops is None and peak_bw is None:
        return _ROOFLINE_TEMPLATE.format(pf="PEAK_FLOPS", bw="HBM_BW",
                                         why="hardware-constant peaks")
    pf = float(PEAK_FLOPS if peak_flops is None else peak_flops)
    bw = float(HBM_BW if peak_bw is None else peak_bw)
    return _ROOFLINE_TEMPLATE.format(pf=repr(pf), bw=repr(bw),
                                     why="calibrated peaks")


# The built-in groups (TPU analogues of the paper's §V metric list).
_GROUP_TEXTS = [
    """
    GROUP FLOPS
    DESC floating point throughput and machine utilization (IPC analogue)
    EVENTSET
      hlo_flops
      model_flops
      step_time_s
    METRICS
      gflops_per_s        hlo_flops / step_time_s / 1e9
      hw_flops_util       hlo_flops / step_time_s / PEAK_FLOPS
      mfu                 model_flops / step_time_s / PEAK_FLOPS
      useful_flop_ratio   model_flops / hlo_flops
    """,
    """
    GROUP MEM
    DESC memory bandwidth and footprint
    EVENTSET
      hlo_bytes
      step_time_s
      hbm_bytes_in_use
    METRICS
      mem_gb_per_s        hlo_bytes / step_time_s / 1e9
      hbm_bw_util         hlo_bytes / step_time_s / HBM_BW
      hbm_used_gb         hbm_bytes_in_use / 1e9
    """,
    """
    GROUP ICI
    DESC interconnect (collective) traffic — the QPI/network analogue
    EVENTSET
      collective_bytes
      wire_bytes
      step_time_s
    METRICS
      ici_gb_per_s        collective_bytes / step_time_s / 1e9
      ici_bw_util         collective_bytes / step_time_s / ICI_BW
      ici_wire_gb_per_s   wire_bytes / step_time_s / 1e9
      ici_wire_bw_util    wire_bytes / step_time_s / ICI_BW
    """,
    """
    GROUP GOODPUT
    DESC end-to-end job progress (the "CPU load" analogue for a TPU job)
    EVENTSET
      step_time_s
      tokens_per_step
      data_wait_s
    METRICS
      tokens_per_s        tokens_per_step / step_time_s
      data_stall_frac     data_wait_s / step_time_s
      steps_per_s         1.0 / step_time_s
    """,
    roofline_group_text(),
]

GROUPS = {g.name: g for g in (parse_group(t) for t in _GROUP_TEXTS)}


def available_groups() -> list:
    return sorted(GROUPS)


def register_group(text: str) -> PerfGroup:
    """Parse and register a deployment-specific group (LIKWID drops group
    files into a directory; here the text registers in-process).  Its
    metrics immediately become resolvable by :func:`formula_for`, i.e.
    answerable by the query engine *retroactively* over stored raw events
    — no collection-time change needed."""
    g = parse_group(text)
    GROUPS[g.name] = g
    return g


def formula_for(metric: str) -> Optional[str]:
    """The formula behind a group metric name, or None.

    ``metric`` may be qualified (``MEM.hbm_bw_util``) to pin a group, or
    bare (``hbm_bw_util``) to search every registered group — the hook
    that lets a query spec (``repro.core.query``) or an analysis rule name
    any group metric and have it derived at query time from stored raw
    events."""
    if "." in metric:
        gname, _, mname = metric.partition(".")
        g = GROUPS.get(gname)
        if g is not None:
            for name, formula in g.metrics:
                if name == mname:
                    return formula
        return None
    # snapshot before iterating: register_group may insert concurrently
    # (the httpd is a threading server), and a size change mid-iteration
    # would raise RuntimeError out of a perfectly valid query
    for g in list(GROUPS.values()):
        for name, formula in g.metrics:
            if name == metric:
                return formula
    return None


def derive_all(raw_events: dict, skipped: Optional[list] = None) -> dict:
    """Run every group whose event set is (partially) satisfied."""
    out = {}
    for g in list(GROUPS.values()):     # snapshot vs concurrent register
        out.update(g.derive(raw_events, skipped=skipped))
    return out
