"""Derived-metric query engine — planned, cached, pushdown-federated
performance-group queries (paper §V, grown query-side).

The paper's core abstraction is the LIKWID *performance group*: raw HPM
events plus formulas for derived metrics.  The seed stack derived metrics
exactly once, at collection time (``HostAgent.collect_step``), so nothing
could be derived retroactively, across measurements, or over rollup
tiers.  This module moves derivation to *query time* — the capability
MPCDF's job-specific monitoring and PerSyst both put at the center of
their analysis stacks:

* a declarative :class:`QuerySpec` (measurement, tag filters, time range,
  window, group-by tag, derived-metric expressions, top-k/order-by) that
  serializes to JSON — the same spec runs locally, against a sharded
  database, or pushed down to remote LMS instances;
* a planner (:func:`make_plan`) that compiles every formula once
  (``perf_groups.compile_formula`` — module-level parse cache) and picks
  the cheapest data tier: rollup windows when the query window nests into
  a tier (``RollupConfig.tier_for``), raw columns otherwise.  Rollup
  plans keep answering after raw-point retention;
* vectorized evaluation: per input field the engine gathers *mergeable*
  ``WindowAgg`` partials, aligns them into window columns per group, and
  applies each compiled expression across all windows in one pass
  (``CompiledFormula.eval_columns``) — including cross-measurement joins
  written as ``measurement.field`` (e.g. a roofline fraction mixing
  ``hpm`` and ``system`` inputs);
* an LRU result cache keyed by ``(plan fingerprint, per-measurement
  ingest watermark)`` (:meth:`Database.data_version`): repeated dashboard
  renders are O(1) dict hits until new points actually arrive;
* shard/federation transparency: collection happens through the partials
  protocol from PR 2, so a ``ShardedDatabase`` executes the sub-plan per
  shard and merges ``WindowAgg`` state, and backends exposing
  ``query_partials`` (``HttpQueryClient`` via ``POST /query/v2``,
  ``FederatedQuery`` fanning out) receive the *whole spec* in one round
  trip and plan against their own tier/retention state — the pushdown
  path that replaces pulling raw series over the wire.

Range semantics (windowed specs): ``t_min``/``t_max`` bound the result at
*window* granularity — a window is included iff its epoch-aligned start
lies in ``[t_min - t_min % w, t_max - t_max % w]``.  The raw fallback
expands its point-level scan to the same whole windows, so the rollup and
raw tiers answer identically whenever both hold the data (the planner
property tests pin this).
"""

from __future__ import annotations

import hashlib
import json
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Iterable, Optional

from jax.profiler import TraceAnnotation

from repro.core.perf_groups import (HW_CONSTANTS, CompiledFormula,
                                    compile_formula, formula_for)
from repro.core.rollup import ROLLUP_AGGS, known_agg, quantile_of
from repro.core.shard import (decode_partials, encode_partials,
                              merge_scalar_partials, merge_windowed_partials)
from repro.core.tsdb import Series, _agg

__all__ = [
    "QueryEngine", "QueryPlan", "QueryResult", "QuerySpec",
    "collect_backend_partials", "decode_plan_partials",
    "derived_rollup_series", "derived_select_series",
    "encode_plan_partials", "evaluate_plan", "make_plan",
]


# --------------------------------------------------------------------------
# The declarative spec
# --------------------------------------------------------------------------


def _normalize_metrics(metrics) -> tuple:
    """Canonical ``((name, expr_or_None), ...)``.

    Accepted entries:

    * ``"field"`` — passthrough of a stored field;
    * ``"name=expr"`` — derived metric with an explicit formula;
    * ``"@metric"`` / ``"@GROUP.metric"`` — derived metric resolved from
      the registered performance groups (``perf_groups.formula_for``), so
      a spec can name ``@hbm_bw_util`` and have the MEM group's formula
      applied at query time over stored raw events;
    * ``(name, expr)`` / ``(name, None)`` pairs (the canonical form).
    """
    if isinstance(metrics, str):
        metrics = (metrics,)
    out = []
    for m in metrics:
        if isinstance(m, str):
            if m.startswith("@"):
                ref = m[1:]
                expr = formula_for(ref)
                if expr is None:
                    raise ValueError(f"no performance group defines "
                                     f"metric {ref!r}")
                name = ref.rpartition(".")[2]
                out.append((name, expr))
            elif "=" in m:
                name, _, expr = m.partition("=")
                out.append((name.strip(), expr.strip()))
            else:
                out.append((m, None))
        else:
            name, expr = m
            out.append((str(name), None if expr is None else str(expr)))
    if not out:
        raise ValueError("QuerySpec needs at least one metric")
    seen = set()
    for name, _ in out:
        if name in seen:
            raise ValueError(f"duplicate metric name {name!r}")
        seen.add(name)
    return tuple(out)


@dataclass(frozen=True)
class QuerySpec:
    """One declarative query, compiled once into a :class:`QueryPlan`.

    ``agg`` reduces each input field's windows to a value before formulas
    apply (per-window means by default — the same inputs the offline
    perf-group derivation saw per step).  ``order_by``/``order_agg``/
    ``limit`` rank groups by a result metric reduced over its windows and
    keep the top-k (server-side: applied after the federated merge).
    """

    measurement: str
    metrics: tuple
    tags: tuple = ()
    t_min: Optional[int] = None
    t_max: Optional[int] = None
    window_ns: Optional[int] = None
    group_by: Optional[str] = None
    agg: str = "mean"
    order_by: Optional[str] = None
    order_agg: str = "mean"
    limit: Optional[int] = None
    descending: bool = True

    def __post_init__(self):
        if not self.measurement:
            raise ValueError("QuerySpec needs a measurement")
        object.__setattr__(self, "metrics", _normalize_metrics(self.metrics))
        tags = self.tags
        if isinstance(tags, dict):
            tags = tags.items()
        object.__setattr__(self, "tags", tuple(
            sorted((str(k), str(v)) for k, v in tags)))
        for agg in (self.agg, self.order_agg):
            if not known_agg(agg):
                raise ValueError(f"unknown agg {agg!r} "
                                 f"(expected one of {ROLLUP_AGGS} "
                                 f"or a pNN quantile)")
        if self.window_ns is not None:
            object.__setattr__(self, "window_ns", int(self.window_ns))
            if self.window_ns <= 0:
                raise ValueError("window_ns must be positive")
        if self.limit is not None:
            object.__setattr__(self, "limit", int(self.limit))
            if self.limit <= 0:
                raise ValueError("limit must be positive")
        names = {name for name, _ in self.metrics}
        if self.order_by is not None and self.order_by not in names:
            raise ValueError(f"order_by {self.order_by!r} is not one of "
                             f"the spec's metrics {sorted(names)}")

    # -- wire form -----------------------------------------------------------

    def to_dict(self) -> dict:
        return {"measurement": self.measurement,
                "metrics": [list(m) for m in self.metrics],
                "tags": dict(self.tags),
                "t_min": self.t_min, "t_max": self.t_max,
                "window_ns": self.window_ns, "group_by": self.group_by,
                "agg": self.agg, "order_by": self.order_by,
                "order_agg": self.order_agg, "limit": self.limit,
                "descending": self.descending}

    @classmethod
    def from_dict(cls, d: dict) -> "QuerySpec":
        return cls(measurement=d["measurement"], metrics=d["metrics"],
                   tags=d.get("tags") or (), t_min=d.get("t_min"),
                   t_max=d.get("t_max"), window_ns=d.get("window_ns"),
                   group_by=d.get("group_by"), agg=d.get("agg", "mean"),
                   order_by=d.get("order_by"),
                   order_agg=d.get("order_agg", "mean"),
                   limit=d.get("limit"),
                   descending=d.get("descending", True))

    def fingerprint(self) -> str:
        """Stable content hash — the plan/result cache key half that
        identifies *what* is asked (the ingest watermark is the other
        half, identifying *over which data*)."""
        fp = getattr(self, "_fp", None)
        if fp is None:
            blob = json.dumps(self.to_dict(), sort_keys=True,
                              separators=(",", ":"))
            fp = hashlib.sha1(blob.encode()).hexdigest()
            object.__setattr__(self, "_fp", fp)
        return fp


# --------------------------------------------------------------------------
# Planning: compile formulas, resolve inputs, pick the data tier
# --------------------------------------------------------------------------


class QueryPlan:
    """A compiled spec: outputs (compiled formulas / passthroughs), the
    unique ``(measurement, field)`` inputs they need, and the tier
    decision.  Built once per (spec fingerprint, backend tier config)."""

    __slots__ = ("spec", "outputs", "inputs", "use_rollups", "tier_ns",
                 "measurements", "fingerprint")

    def __init__(self, spec: QuerySpec,
                 outputs: tuple, inputs: tuple,
                 use_rollups: bool, tier_ns: Optional[int]):
        self.spec = spec
        self.outputs = outputs      # ((name, CompiledFormula|None, refs),)
        self.inputs = inputs        # ((measurement, field), ...)
        self.use_rollups = use_rollups
        self.tier_ns = tier_ns
        self.measurements = tuple(sorted({m for m, _ in inputs}
                                         or {spec.measurement}))
        self.fingerprint = spec.fingerprint()


def _resolve_ident(ident: str, default_measurement: str):
    """Formula identifier -> input key.  ``m.f`` joins another
    measurement; bare names read the spec's measurement; hardware
    constants are compile-time constants, not inputs."""
    if "." in ident:
        m, _, f = ident.partition(".")
        return (m, f)
    if ident in HW_CONSTANTS:
        return None
    return (default_measurement, ident)


def _split_quantile_ident(ident: str):
    """``"p95(hpm.flops)"`` -> ``("hpm.flops", "p95")`` — the synthetic
    identifiers ``perf_groups`` emits for quantile calls; None for plain
    identifiers."""
    if not ident.endswith(")"):
        return None
    fn, _, rest = ident.partition("(")
    if quantile_of(fn) is None:
        return None
    return rest[:-1], fn


def make_plan(spec: QuerySpec, rollup_config=None) -> QueryPlan:
    """Compile a spec against a backend's tier layout.

    Tier selection: a windowed query is served from the rollup tiers iff
    the window nests into some tier (coarsest such tier; exact by the
    rollup design notes) — that plan survives raw retention.  A window
    that aligns with no tier falls back to a raw rescan.  Scalar specs
    (``window_ns=None``) always scan raw, like ``Database.aggregate``.

    Raw plans span the hot columns *and* the compressed cold tier
    (``repro.core.coldstore``) when one is attached: sealed fragments
    are merged under the hot columns inside ``Database.select``, so the
    collection path below is tier-transparent by construction and a raw
    plan answers byte-identically whether its range is resident, sealed,
    or straddles the seal point.  :func:`plan_tiers` reports which tiers
    a plan's range actually touches (``QueryResult.meta["tiers"]``).
    """
    outputs = []
    inputs: list = []

    def add_input(key):
        if key not in inputs:
            inputs.append(key)

    for name, expr in spec.metrics:
        if expr is None:
            key = (spec.measurement, name)
            add_input(key)
            outputs.append((name, None, ((name, key, None),)))
            continue
        cf = compile_formula(expr)
        refs = []
        for ident in cf.names:
            qs = _split_quantile_ident(ident)
            if qs is None:
                key = _resolve_ident(ident, spec.measurement)
                agg_override = None
            else:
                inner, agg_override = qs
                key = _resolve_ident(inner, spec.measurement)
                if key is None:
                    raise ValueError(
                        f"cannot take {agg_override} of constant {inner!r}")
            if key is None:
                continue
            add_input(key)
            # 3-tuple refs: a per-ref agg override (quantile calls like
            # p95(flops)) reduces the same merged partials with its own
            # agg — the partials wire form stays agg-agnostic
            refs.append((ident, key, agg_override))
        outputs.append((name, cf, tuple(refs)))
    use_rollups = False
    tier_ns = None
    if spec.window_ns is not None and rollup_config is not None:
        tier_ns = rollup_config.tier_for(spec.window_ns)
        use_rollups = tier_ns is not None
    return QueryPlan(spec, tuple(outputs), tuple(inputs), use_rollups,
                     tier_ns)


# --------------------------------------------------------------------------
# Collection: mergeable per-input partials from any backend
# --------------------------------------------------------------------------


def _raw_bounds(spec: QuerySpec):
    """Expand point-level bounds to whole windows so the raw fallback
    covers exactly the windows the rollup path would (see module notes);
    scalar specs keep point-granularity bounds."""
    w = spec.window_ns
    if w is None:
        return spec.t_min, spec.t_max
    t_min = spec.t_min - spec.t_min % w if spec.t_min is not None else None
    t_max = (spec.t_max - spec.t_max % w) + w - 1 \
        if spec.t_max is not None else None
    return t_min, t_max


def plan_tiers(plan: QueryPlan, backend) -> list:
    """Which storage tiers this plan's collection reads — planner
    metadata only (the read path itself is tier-transparent).  A
    rollup-served plan reads the rollup tier alone; a raw plan reads the
    hot columns plus, when the backend has sealed chunks overlapping the
    plan's whole-window raw bounds, the cold tier."""
    if plan.use_rollups:
        return ["rollup"]
    tiers = ["hot"]
    fn = getattr(backend, "cold_time_range", None)
    if fn is None:
        return tiers
    t_min, t_max = _raw_bounds(plan.spec)
    for m in plan.measurements:
        try:
            rng = fn(m)
        except (TypeError, ValueError):
            rng = None
        if rng is not None and \
                (t_min is None or rng[1] >= t_min) and \
                (t_max is None or rng[0] <= t_max):
            tiers.append("cold")
            break
    return tiers


def collect_backend_partials(backend, spec: QuerySpec) -> dict:
    """Execute the spec's *collection* half against one Database-shaped
    backend: ``{(measurement, field): partials}`` where partials are the
    mergeable ``aggregate_partials`` maps (``{group: {w0: WindowAgg}}``
    windowed, ``{group: WindowAgg}`` scalar).

    Plans against the backend's own ``rollup_config``: a backend whose
    raw points are gone answers from its surviving rollup tiers, a
    rollup-disabled backend from raw — per-backend tier choice is exactly
    why federation pushes the *spec* down, not a finished plan.
    """
    plan = make_plan(spec, getattr(backend, "rollup_config", None))
    tags = dict(spec.tags) or None
    out = {}
    if plan.use_rollups:
        t_min, t_max, use = spec.t_min, spec.t_max, True
    else:
        (t_min, t_max), use = _raw_bounds(spec), False
    for meas, fieldname in plan.inputs:
        out[(meas, fieldname)] = backend.aggregate_partials(
            meas, fieldname, tags=tags, t_min=t_min, t_max=t_max,
            group_by_tag=spec.group_by, window_ns=spec.window_ns,
            use_rollups=use if spec.window_ns is not None else "auto")
    return out


def merge_plan_partials(parts: Iterable[dict], windowed: bool) -> dict:
    """Merge per-backend ``{input: partials}`` maps input-by-input with
    the PR 2 ``WindowAgg`` merge semantics — the gather half of the
    federated/sharded execution."""
    parts = [p for p in parts if p]
    keys: list = []
    for p in parts:
        for k in p:
            if k not in keys:
                keys.append(k)
    merge = merge_windowed_partials if windowed else merge_scalar_partials
    return {k: merge([p[k] for p in parts if k in p]) for k in keys}


# -- wire form (httpd POST /query/v2, mode=partials) -------------------------


def encode_plan_partials(collected: dict, windowed: bool) -> list:
    """JSON-safe, deterministically ordered per-input partials."""
    return [{"m": m, "field": f,
             "partials": encode_partials(collected[(m, f)], windowed)}
            for m, f in sorted(collected)]


def decode_plan_partials(items: list, windowed: bool) -> dict:
    return {(d["m"], d["field"]): decode_partials(d["partials"], windowed)
            for d in items}


# --------------------------------------------------------------------------
# Evaluation: aligned window columns -> derived metric columns
# --------------------------------------------------------------------------


@dataclass
class QueryResult:
    """Finalized result.  ``groups`` is ordered (ranked when the spec
    orders, else by group key), windowed entries are
    ``{metric: {"times": [...], "values": [...]}}``, scalar entries
    ``{metric: value}``.  ``to_json`` is canonical — equal results are
    byte-identical across local, sharded and HTTP-federated execution.
    ``meta`` (tier choice, cache hit) is diagnostics, not payload."""

    fingerprint: str
    window_ns: Optional[int]
    groups: dict
    meta: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"fingerprint": self.fingerprint,
                "window_ns": self.window_ns, "groups": self.groups}

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), separators=(",", ":"))

    @classmethod
    def from_dict(cls, d: dict, meta: Optional[dict] = None) -> "QueryResult":
        return cls(d["fingerprint"], d.get("window_ns"), d["groups"],
                   meta or {})

    def column(self, metric: str, group: str = ""):
        """``(times, values)`` of one metric in one group (the dashboard
        sparkline shape); empty lists when absent."""
        g = self.groups.get(group)
        if not g or metric not in g:
            return [], []
        if self.window_ns is None:
            return [], [g[metric]]
        m = g[metric]
        return m["times"], m["values"]


def evaluate_plan(plan: QueryPlan, collected: dict) -> QueryResult:
    """Merged per-input partials -> finalized result: reduce each window
    with the spec's input agg, align columns, run every compiled formula
    across all windows, then rank/limit groups."""
    spec = plan.spec
    windowed = spec.window_ns is not None
    group_names: list = []
    for key in plan.inputs:
        for g in collected.get(key, ()):
            if g not in group_names:
                group_names.append(g)
    group_names.sort()
    groups: dict = {}
    for g in group_names:
        if windowed:
            entry = _evaluate_windowed_group(plan, collected, g)
        else:
            entry = _evaluate_scalar_group(plan, collected, g)
        if entry:
            groups[g] = entry
    groups = _rank_groups(spec, groups, windowed)
    return QueryResult(plan.fingerprint, spec.window_ns, groups,
                       meta={"tier_ns": plan.tier_ns,
                             "use_rollups": plan.use_rollups,
                             "inputs": [list(k) for k in plan.inputs]})


def _evaluate_windowed_group(plan: QueryPlan, collected: dict,
                             g: str) -> dict:
    spec = plan.spec
    # reduce each (input, agg) pair's WindowAggs once per group; shared
    # across outputs.  Windows whose aggregate cannot answer (None: empty
    # merge, quantile without a sketch / tainted) are skipped like gaps.
    vals_by_input: dict = {}

    def reduced(key, agg):
        ck = (key, agg)
        if ck not in vals_by_input:
            wins = collected.get(key, {}).get(g)
            m = None
            if wins:
                m = {}
                for w0, wa in wins.items():
                    v = wa.value(agg)
                    if v is not None:
                        m[w0] = v
                m = m or None
            vals_by_input[ck] = m
        return vals_by_input[ck]

    entry = {}
    for name, cf, refs in plan.outputs:
        if cf is None:
            vals = reduced(refs[0][1], spec.agg)
            if not vals:
                continue
            starts = sorted(vals)
            entry[name] = {"times": starts,
                           "values": [vals[w] for w in starts]}
            continue
        starts: list = []
        seen = set()
        for _, key, agg_override in refs:
            for w0 in reduced(key, agg_override or spec.agg) or ():
                if w0 not in seen:
                    seen.add(w0)
                    starts.append(w0)
        if not starts:
            continue
        starts.sort()
        cols = {}
        for ident, key, agg_override in refs:
            vals = reduced(key, agg_override or spec.agg)
            if vals is not None:
                cols[ident] = [vals.get(w0) for w0 in starts]
        derived = cf.eval_columns(cols, len(starts))
        times = [w0 for w0, v in zip(starts, derived) if v is not None]
        if times:
            entry[name] = {"times": times,
                           "values": [v for v in derived if v is not None]}
    return entry


def _evaluate_scalar_group(plan: QueryPlan, collected: dict, g: str) -> dict:
    spec = plan.spec
    vals_by_input: dict = {}

    def reduced(key, agg):
        ck = (key, agg)
        if ck not in vals_by_input:
            wa = collected.get(key, {}).get(g)
            v = None
            if wa is not None and wa.count:
                v = wa.value(agg)
            vals_by_input[ck] = v
        return vals_by_input[ck]

    entry = {}
    for name, cf, refs in plan.outputs:
        if cf is None:
            v = reduced(refs[0][1], spec.agg)
            if v is not None:
                entry[name] = v
            continue
        env = {}
        for ident, key, agg_override in refs:
            v = reduced(key, agg_override or spec.agg)
            if v is not None:
                env[ident] = v
        try:
            v = cf.eval(env)
        except (KeyError, ZeroDivisionError, OverflowError):
            continue
        if not isinstance(v, complex):      # same skip rule as eval_columns
            entry[name] = v
    return entry


def _rank_groups(spec: QuerySpec, groups: dict, windowed: bool) -> dict:
    if spec.order_by is None:
        ordered = sorted(groups)
        if spec.limit is not None:
            ordered = ordered[:spec.limit]
        return {g: groups[g] for g in ordered}
    ranked = []
    for g, entry in groups.items():
        m = entry.get(spec.order_by)
        if m is None:
            continue                    # unrankable groups drop out
        # _agg: the one aggregate dispatcher (shared with Database)
        rank = _agg(m["values"], spec.order_agg) if windowed else m
        ranked.append((rank, g))
    ranked.sort(key=lambda rg: ((-rg[0] if spec.descending else rg[0]),
                                rg[1]))
    if spec.limit is not None:
        ranked = ranked[:spec.limit]
    return {g: groups[g] for _, g in ranked}


# --------------------------------------------------------------------------
# The engine: plan cache + watermark-keyed LRU result cache
# --------------------------------------------------------------------------


class _LRUCache:
    def __init__(self, maxsize: int):
        self.maxsize = int(maxsize)
        self._d: OrderedDict = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            v = self._d.get(key)
            if v is not None:
                self._d.move_to_end(key)
            return v

    def put(self, key, value):
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)

    def __len__(self):
        with self._lock:
            return len(self._d)


class QueryEngine:
    """Plan, execute and cache :class:`QuerySpec` queries over one
    Database-shaped backend (plain/sharded database, ``FederatedQuery``
    view or ``HttpQueryClient`` remote).

    Execution prefers a backend-side ``query_partials(spec)`` (whole-spec
    pushdown: a sharded database fans the sub-plan per shard, a remote
    client ships one ``POST /query/v2``); otherwise it collects per-input
    partials locally.  Results are cached in an LRU keyed by
    ``(plan fingerprint, per-measurement ingest watermark)`` — a repeat
    query is a dict hit until one of the touched measurements actually
    ingested (or retired) data.  Backends without ``data_version`` are
    simply never cached.
    """

    def __init__(self, backend, *, cache_size: int = 128):
        self.backend = backend
        # plans are keyed by the full spec fingerprint, which includes
        # t_min/t_max — a dashboard issuing t_max=now per render mints a
        # new fingerprint every time, so this must be bounded like the
        # result cache or a long-lived server engine leaks plans
        self._plans = _LRUCache(max(2 * cache_size, 256))
        self._cache = _LRUCache(cache_size)
        self.stats = {"queries": 0, "cache_hits": 0, "cache_misses": 0,
                      "plans_compiled": 0}

    def plan(self, spec: QuerySpec) -> QueryPlan:
        fp = spec.fingerprint()
        plan = self._plans.get(fp)
        if plan is None:
            plan = make_plan(
                spec, getattr(self.backend, "rollup_config", None))
            self._plans.put(fp, plan)
            self.stats["plans_compiled"] += 1
        return plan

    def _watermark(self, plan: QueryPlan):
        ver = getattr(self.backend, "data_version", None)
        if ver is None:
            return None
        try:
            return tuple(ver(m) for m in plan.measurements)
        except (AttributeError, ValueError):
            # a backend that cannot report a watermark — a local view
            # lacking data_version (AttributeError) or a remote whose
            # /meta doesn't serve one (ValueError): never cache, always
            # recompute; the query itself must still run
            return None

    def query(self, spec: QuerySpec) -> QueryResult:
        with TraceAnnotation("lms.query.exec") as sp:
            plan = self.plan(spec)
            self.stats["queries"] += 1
            wm = self._watermark(plan)
            if wm is not None:
                hit = self._cache.get((plan.fingerprint, wm))
                if hit is not None:
                    self.stats["cache_hits"] += 1
                    sp.set_metadata(cache="hit")
                    return hit
            self.stats["cache_misses"] += 1
            sp.set_metadata(cache="miss")
            collected = self.collect(spec)
            res = evaluate_plan(plan, collected)
            # advisory: which storage tiers the collection actually spanned
            # (never part of to_json(), so parity comparisons are
            # unaffected)
            res.meta["tiers"] = plan_tiers(plan, self.backend)
            if wm is not None:
                res.meta["watermark"] = list(wm)
                self._cache.put((plan.fingerprint, wm), res)
            return res

    def collect(self, spec: QuerySpec) -> dict:
        """Merged per-input partials for a spec (the mergeable half —
        what ``/query/v2`` mode=partials serves)."""
        qp = getattr(self.backend, "query_partials", None)
        if qp is not None:
            return qp(spec)
        return collect_backend_partials(self.backend, spec)

    def cache_info(self) -> dict:
        return {**self.stats, "cached_results": len(self._cache),
                "cached_plans": len(self._plans)}


# --------------------------------------------------------------------------
# Per-series query-time derivation (the analysis engine's rule input)
# --------------------------------------------------------------------------


def _expr_inputs(expr: str) -> list:
    """``[(ident, field, agg_override)]`` for every data input of a
    per-series rule expression — ``agg_override`` is the quantile name
    for ``pNN(field)`` calls, else None (use the caller's agg)."""
    cf = compile_formula(expr)
    inputs = []
    for ident in cf.names:
        qs = _split_quantile_ident(ident)
        fieldname, agg_override = (ident, None) if qs is None else qs
        if "." in fieldname:
            raise ValueError(
                f"per-series derivation cannot join measurements "
                f"({ident!r}); use a QuerySpec with group-by instead")
        if qs is not None or fieldname not in HW_CONSTANTS:
            inputs.append((ident, fieldname, agg_override))
    return inputs


def derived_rollup_series(db, measurement: str, name: str, expr: str, *,
                          tags: Optional[dict] = None,
                          t_min: Optional[int] = None,
                          t_max: Optional[int] = None,
                          window_ns: Optional[int] = None,
                          agg: str = "mean") -> list:
    """Evaluate ``expr`` per raw series over its rollup windows: one
    :class:`Series` per stored series with the *derived* metric as its
    single field — the shape ``AnalysisEngine`` consumes, so threshold
    rules may reference metrics that were never emitted at collection
    time (``ThresholdRule.expr``).  Windows missing an input (or hitting
    a domain error) are skipped, like any gap.  Quantile calls
    (``p95(field)``) reduce that field's rollup windows with their own
    agg — served from the window sketches when the field is opted into
    ``RollupConfig(sketch_fields=...)``, absent otherwise."""
    cf = compile_formula(expr)
    inputs = _expr_inputs(expr)
    per_series: dict = {}       # tags_key -> (tags, {ident: {w0: val}})
    for ident, fieldname, agg_override in inputs:
        for s in db.rollup_series(measurement, fieldname,
                                  agg=agg_override or agg,
                                  tags=tags, window_ns=window_ns,
                                  t_min=t_min, t_max=t_max):
            key = tuple(sorted(s.tags.items()))
            entry = per_series.get(key)
            if entry is None:
                entry = per_series[key] = (s.tags, {})
            entry[1][ident] = dict(zip(s.times,
                                       s.values.get(fieldname, ())))
    out = []
    for key in sorted(per_series):
        stags, by_ident = per_series[key]
        starts = sorted({w0 for vals in by_ident.values() for w0 in vals})
        if not starts:
            continue
        cols = {i: [vals.get(w0) for w0 in starts]
                for i, vals in by_ident.items()}
        derived = cf.eval_columns(cols, len(starts))
        times = [w0 for w0, v in zip(starts, derived) if v is not None]
        if times:
            out.append(Series(measurement, dict(stags), times,
                              {name: [v for v in derived
                                      if v is not None]}))
    return out


def _numeric_col(col: list) -> list:
    return [v if isinstance(v, (int, float)) and not isinstance(v, bool)
            else None for v in col]


def derived_select_series(db, measurement: str, name: str, expr: str, *,
                          tags: Optional[dict] = None,
                          t_min: Optional[int] = None,
                          t_max: Optional[int] = None) -> list:
    """Raw-point twin of :func:`derived_rollup_series` (rollup-disabled
    databases): evaluates the compiled expression per point over each
    series' aligned columns.

    Inputs are fetched one field per ``select`` — the remote client's
    wire form (``HttpQueryClient.select``) is single-field, and this
    function must stay federation-transparent like every other rule
    input path.  Columns of one series normally share one timestamp
    list (one store) and align by index; if they ever differ (ingest
    raced between per-field fetches on a remote), alignment falls back
    to the timestamp union.

    A quantile call (``p95(field)``) degenerates to per-point identity
    here: the quantile of a single raw point is that point.  Rules that
    need real windowed quantiles belong on the rollup path
    (:func:`derived_rollup_series`)."""
    cf = compile_formula(expr)
    inputs = _expr_inputs(expr)
    fields = sorted({f for _, f, _ in inputs})
    if not fields:          # constants-only formula: any series' clock
        return [Series(measurement, dict(s.tags), list(s.times),
                       {name: cf.eval_columns({}, len(s.times))})
                for s in db.select(measurement, None, tags, t_min, t_max)
                if s.times]
    per_series: dict = {}   # tags_key -> (tags, {field: (times, col)})
    for f in fields:
        for s in db.select(measurement, [f], tags, t_min, t_max):
            key = tuple(sorted(s.tags.items()))
            entry = per_series.get(key)
            if entry is None:
                entry = per_series[key] = (s.tags, {})
            entry[1][f] = (s.times, _numeric_col(s.values.get(f, [])))
    out = []
    for key in sorted(per_series):
        stags, by_field = per_series[key]
        time_lists = [t for t, _ in by_field.values()]
        if all(t == time_lists[0] for t in time_lists[1:]):
            times0 = time_lists[0]
            by_f = {f: col for f, (_, col) in by_field.items()}
        else:               # rare cross-fetch skew: align on the union
            times0 = sorted({t for ts, _ in by_field.values() for t in ts})
            by_f = {f: [m.get(t) for t in times0]
                    for f, (ts, col) in by_field.items()
                    for m in (dict(zip(ts, col)),)}
        cols = {ident: by_f[f] for ident, f, _ in inputs if f in by_f}
        derived = cf.eval_columns(cols, len(times0))
        times = [t for t, v in zip(times0, derived) if v is not None]
        if times:
            out.append(Series(measurement, dict(stags), times,
                              {name: [v for v in derived
                                      if v is not None]}))
    return out
