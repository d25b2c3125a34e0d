"""Data-analysis methodology (paper §V) — the continuous analysis engine.

Two analysis layers, as the paper structures them:

1. **Pathological-job detection** — simple rules over resource-utilization
   metrics using *thresholds and timeouts* (paper Fig. 4: FP rate and memory
   bandwidth below thresholds for more than 10 minutes => "break in
   computation").  Implemented as :class:`ThresholdRule` with a full alert
   *lifecycle*: a violation stretch opens at its first violating sample,
   extends while the condition holds, fires once it outlasts the rule's
   timeout, and **resolves** at its last violating sample once the metric
   has stayed clear for the rule's hysteresis window
   (``clear_duration_s`` — a flapping metric does not re-fire every
   window).  Three evaluators share one state machine (:class:`_Stretch`),
   so they agree exactly on the same data:

   * :func:`evaluate_rule` — offline, over one (time, value) series;
   * :class:`StreamAnalyzer` — point-driven, fed raw points (router
     subscriber or direct calls), thread-safe, out-of-order-guarded;
   * :class:`AnalysisEngine` — the *continuous* subsystem: it evaluates
     the streaming **rollup windows** the TSDB already maintains
     (O(#windows) per tick on a background thread — zero work on the
     ingest hot path) and writes alert transitions and per-job reports
     back into the TSDB as the ``analysis`` measurement, so sharding,
     federation and WAL durability apply transparently and alert state
     survives a restart (:meth:`AnalysisEngine.recover`).

2. **Performance-pattern decision tree** — marking applications with
   significant optimization potential (Treibig/Hager performance patterns,
   refined into a decision tree in the FEPA project).  Implemented as a
   data-driven tree over derived metrics; on the TPU the discriminating
   metrics are the three roofline terms, so the tree classifies jobs as
   compute-, memory- or collective-bound (+ load imbalance / ingest-stall
   branches) and attaches a remedy.  Missing inputs are never silently
   defaulted: pathology tests (``>`` nodes) treat a missing signal as "no
   evidence" and record it in the decision path; goodness tests (``<``
   nodes) cannot certify either branch without data and classify as
   ``insufficient-data``.

(The per-region roofline is not computed here: the query engine prices it
from marker work counters, ``repro.core.marker.roofline_spec``.)

The ``analysis`` measurement schema (what :func:`load_alerts` /
:func:`load_job_report` read back, also over HTTP or federated views):

* alerts — tags ``{kind: "alert", rule, hostname, severity[, jobid]}``;
  one point per lifecycle event, fields ``state`` ("firing"/"resolved"),
  ``start_ns``, ``last_ns`` (last violating sample/window), ``evidence``,
  and on resolution ``end_ns`` + ``duration_s``.  Episodes of the same
  series are keyed by their ``start_ns``.
* job reports — tags ``{kind: "job_report", jobid}``; fields ``report``
  (the full JSON document), ``pattern``, ``status``, ``alerts_total``.
"""

from __future__ import annotations

import bisect
import json
import math
import threading
import time
import warnings
from dataclasses import dataclass
from typing import Callable, Iterable, Optional

from repro.core.fingerprint import (fingerprint_outliers, fingerprint_point,
                                    job_fingerprint, load_fingerprints)
from repro.core.line_protocol import Point, now_ns
from repro.core.tsdb import _tags_key

ANALYSIS_MEASUREMENT = "analysis"
INSUFFICIENT_DATA = "insufficient-data"

# ==========================================================================
# 1. Threshold + timeout rules, alert lifecycle
# ==========================================================================

_OPS = {
    "<": lambda a, b: a < b,
    ">": lambda a, b: a > b,
    "<=": lambda a, b: a <= b,
    ">=": lambda a, b: a >= b,
}


@dataclass(frozen=True)
class ThresholdRule:
    """``metric op threshold`` sustained for ``min_duration_s`` => finding.

    ``clear_duration_s`` is the resolution hysteresis: a firing alert
    resolves only after the metric has stayed non-violating for this long
    past the last violation (0 = resolve at the first clear sample, the
    exact offline-scan semantics).

    ``expr`` makes ``metric`` a *query-time derived* metric: a
    performance-group formula (``repro.core.perf_groups``) over the
    measurement's stored fields, evaluated per rollup window (or per raw
    point on rollup-disabled databases) by ``repro.core.query`` — so a
    rule can threshold a metric that was never emitted at collection
    time (e.g. ``hbm_bw_util`` over stored raw byte counters).
    """

    name: str
    measurement: str
    metric: str
    op: str
    threshold: float
    min_duration_s: float
    severity: str = "warning"          # warning | critical
    description: str = ""
    clear_duration_s: float = 0.0
    expr: Optional[str] = None

    def check(self, value: float) -> bool:
        if value is None or (isinstance(value, float) and math.isnan(value)):
            return self.op in ("<", "<=")   # NaN counts as "below threshold"
        return _OPS[self.op](value, self.threshold)


@dataclass
class Finding:
    rule: str
    severity: str
    host: str
    start_ns: int
    end_ns: int
    evidence: str

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


@dataclass
class Alert:
    """One alert episode with its lifecycle state.

    ``last_ns`` tracks the most recent violating sample (window); while
    firing it keeps extending, and on resolution ``end_ns`` freezes at the
    *last violating* sample — the recovery sample is never counted into
    the violation's duration.
    """

    rule: str
    severity: str
    host: str
    jobid: str
    start_ns: int
    last_ns: int
    end_ns: Optional[int] = None
    state: str = "firing"              # firing | resolved
    evidence: str = ""

    @property
    def active(self) -> bool:
        return self.state == "firing"

    @property
    def duration_s(self) -> float:
        end = self.end_ns if self.end_ns is not None else self.last_ns
        return (end - self.start_ns) / 1e9

    def to_dict(self) -> dict:
        return {"rule": self.rule, "severity": self.severity,
                "host": self.host, "jobid": self.jobid, "state": self.state,
                "start_ns": self.start_ns, "last_ns": self.last_ns,
                "end_ns": self.end_ns, "duration_s": self.duration_s,
                "evidence": self.evidence}

    @classmethod
    def from_dict(cls, d: dict) -> "Alert":
        return cls(d["rule"], d["severity"], d["host"], d.get("jobid", ""),
                   d["start_ns"], d["last_ns"], d.get("end_ns"),
                   d.get("state", "firing"), d.get("evidence", ""))


class _Stretch:
    """Violation-stretch state machine shared by every evaluator.

    Semantics (identical offline, point-streamed and window-streamed):
    a stretch opens at the first violating sample, ``last_violation_ns``
    tracks the latest violation, and a non-violating sample closes the
    stretch at ``last_violation_ns`` once it is ``clear_duration_s`` past
    it.  A closed stretch *qualifies* (is a finding / fired alert) iff the
    violations alone span ``min_duration_s`` — so a data gap before the
    recovery sample can never inflate the reported duration past
    ``min_duration_s`` (the seed evaluator closed at the recovery sample's
    timestamp and did exactly that).
    """

    __slots__ = ("start_ns", "last_violation_ns")

    def __init__(self):
        self.start_ns = None
        self.last_violation_ns = None

    def qualified(self, rule: ThresholdRule) -> bool:
        return self.start_ns is not None and \
            (self.last_violation_ns - self.start_ns) / 1e9 >= \
            rule.min_duration_s

    def advance(self, rule: ThresholdRule, ts: int, value):
        """Feed one sample; returns ``(qualified, closed)`` where
        ``qualified`` says the (still open) stretch now outlasts the rule
        timeout and ``closed`` is ``(start, end, qualified)`` when this
        sample closed a stretch."""
        closed = None
        if rule.check(value):
            if self.start_ns is None:
                self.start_ns = ts
            self.last_violation_ns = ts
        elif self.start_ns is not None and \
                (ts - self.last_violation_ns) / 1e9 >= rule.clear_duration_s:
            closed = (self.start_ns, self.last_violation_ns,
                      self.qualified(rule))
            self.start_ns = self.last_violation_ns = None
        return self.qualified(rule), closed

    def close(self, rule: ThresholdRule):
        """Forced close (end of series / job end): ``(start, end,
        qualified)`` or None when no stretch is open."""
        if self.start_ns is None:
            return None
        span = (self.start_ns, self.last_violation_ns, self.qualified(rule))
        self.start_ns = self.last_violation_ns = None
        return span


# Default rule set: the paper's elementary resource-utilization checks,
# translated to TPU-job metrics (DESIGN.md §2).  Thresholds are config knobs.
def default_rules(*, mfu_floor: float = 0.02, mem_floor_gbs: float = 1.0,
                  idle_timeout_s: float = 60.0,
                  straggler_skew: float = 0.15,
                  roofline_floor: float = 0.05) -> list:
    # query-time derived rule over the marker measurement: regions without
    # flops/bytes counters produce no derived windows at all (the query
    # layer skips them), so the rule can only fire on instrumented regions
    from repro.core.marker import low_roofline_rule
    clear = idle_timeout_s / 4          # hysteresis: see ThresholdRule
    return [
        low_roofline_rule(roofline_floor, min_duration_s=idle_timeout_s,
                          clear_duration_s=clear),
        ThresholdRule("compute_break", "hpm", "mfu", "<", mfu_floor,
                      idle_timeout_s, "critical",
                      "FP rate below threshold for too long -> break in "
                      "computation (paper Fig. 4)", clear),
        ThresholdRule("membw_break", "hpm", "mem_gb_per_s", "<",
                      mem_floor_gbs, idle_timeout_s, "warning",
                      "memory bandwidth below threshold -> idle/stalled",
                      clear),
        ThresholdRule("data_stall", "hpm", "data_stall_frac", ">", 0.3,
                      idle_timeout_s, "warning",
                      "input pipeline starves the accelerator", clear),
        ThresholdRule("step_time_straggler", "hpm", "straggler_skew", ">",
                      straggler_skew, idle_timeout_s / 2, "warning",
                      "per-host step time skew -> straggler", clear),
    ]


def evaluate_rule(rule: ThresholdRule, times: list, values: list,
                  host: str = "") -> list:
    """Offline evaluation over one series -> list of Finding.

    A finding opens when the condition first holds and closes at the
    *last violating* sample; only stretches whose violations span the
    rule's timeout are reported (Fig. 4).  Out-of-order samples (possible
    in hand-built series; DB series are sorted) are dropped, matching the
    streaming evaluators' monotonic guard.
    """
    findings = []
    stretch = _Stretch()
    last_t = None
    for t, v in zip(times, values):
        if last_t is not None and t < last_t:
            continue
        last_t = t
        _, closed = stretch.advance(rule, t, v)
        if closed is not None and closed[2]:
            findings.append(Finding(rule.name, rule.severity, host,
                                    closed[0], closed[1], rule.description))
    tail = stretch.close(rule)
    if tail is not None and tail[2]:
        findings.append(Finding(rule.name, rule.severity, host, tail[0],
                                tail[1], rule.description))
    return findings


def evaluate_rules_on_db(db: "Database", rules: list, *, jobid: Optional[str] = None,
                         group_by_tag: str = "hostname",
                         use_rollups: object = "auto") -> list:
    """Run every rule over every matching host series in a Database.

    ``db`` is duck-typed: a plain ``Database``, a sharded one
    (``repro.core.shard.ShardedDatabase``) or a ``FederatedQuery`` view
    all work — ``rollup_series``/``select`` federate by concatenation
    (each host series lives on exactly one shard), so pathological-job
    findings are shard-transparent.

    With ``use_rollups`` (the default), rule evaluation reads the finest
    rollup tier — per-window means with window starts as timestamps —
    instead of rescanning raw points, so the cost is O(#windows) and the
    rules keep working after retention dropped the raw data.  Threshold +
    timeout semantics are preserved: a sustained excursion spans the same
    windows it spans points (tier windows are far shorter than any rule
    timeout).  ``use_rollups=False`` forces the raw scan; ``True`` forces
    the rollup path and raises on a rollup-disabled database rather than
    silently evaluating nothing.

    This is the *batch* evaluator; the continuous subsystem
    (:class:`AnalysisEngine`) produces byte-identical findings
    incrementally and persists them — readers should prefer
    :func:`load_alerts` over re-running this scan.
    """
    rollups_available = getattr(db, "rollup_config", None) is not None
    if use_rollups is True and not rollups_available:
        raise ValueError(f"database {getattr(db, 'name', '?')!r} has "
                         "rollups disabled; cannot force use_rollups=True")
    findings = []
    for rule in rules:
        tags = {"jobid": jobid} if jobid else None
        series_list = None
        if rule.expr:
            # query-time derived metric (repro.core.query): per-series
            # windows (or raw points) of a formula over stored fields
            from repro.core.query import (derived_rollup_series,
                                          derived_select_series)
            if use_rollups is not False and rollups_available:
                series_list = derived_rollup_series(
                    db, rule.measurement, rule.metric, rule.expr,
                    tags=tags)
            if not series_list and use_rollups is not True:
                series_list = derived_select_series(
                    db, rule.measurement, rule.metric, rule.expr,
                    tags=tags)
        else:
            if use_rollups is not False and rollups_available:
                series_list = db.rollup_series(rule.measurement,
                                               rule.metric,
                                               agg="mean", tags=tags)
            if not series_list and use_rollups is not True:
                series_list = db.select(rule.measurement, [rule.metric],
                                        tags)
        for series in series_list or []:
            vals = series.values.get(rule.metric)
            if not vals:
                continue
            host = series.tags.get(group_by_tag, "")
            findings.extend(evaluate_rule(rule, series.times, vals, host))
    return findings


class _KeyState:
    """Per-(rule, series) streaming state: monotonic clock + stretch +
    the currently firing alert (None between episodes)."""

    __slots__ = ("last_ns", "stretch", "alert", "last_persist_ns", "cursor")

    def __init__(self):
        self.last_ns = None
        self.stretch = _Stretch()
        self.alert: Optional[Alert] = None
        self.last_persist_ns = 0
        self.cursor = 0                 # AnalysisEngine: next window to eat


def _lifecycle_close(st: _KeyState, rule: ThresholdRule, host: str,
                     jobid: str, span: tuple, findings: list,
                     fired: list) -> Optional[Alert]:
    """Close a stretch (clear-sample past hysteresis, or forced at job /
    stream end): resolve the firing alert at the stretch's last violation;
    a qualified stretch that never fired live (e.g. forced close right as
    it crossed the timeout) fires and resolves in one go.  Returns the
    resolved alert, if any.  Shared by StreamAnalyzer and AnalysisEngine
    so the lifecycle cannot drift between them."""
    start, end, qualified = span
    a = st.alert
    if a is None and qualified:
        a = Alert(rule.name, rule.severity, host, jobid, start, end,
                  evidence=rule.description)
        findings.append(a)
        fired.append(a)
    st.alert = None
    if a is None:
        return None
    a.last_ns = end
    a.end_ns = end
    a.state = "resolved"
    return a


def _lifecycle_advance(st: _KeyState, rule: ThresholdRule, host: str,
                       jobid: str, ts: int, value, findings: list,
                       fired: list):
    """One sample through the shared alert lifecycle.  Returns
    ``(event, alert)`` with event in {None, "fired", "extended",
    "resolved"} — what persistence layers key their write-back on."""
    if isinstance(value, str):
        return None, None               # events are not gauges
    qualified, closed = st.stretch.advance(rule, ts, value)
    resolved = None
    if closed is not None:
        resolved = _lifecycle_close(st, rule, host, jobid, closed,
                                    findings, fired)
    if qualified:
        if st.alert is None:
            st.alert = Alert(rule.name, rule.severity, host, jobid,
                             st.stretch.start_ns,
                             st.stretch.last_violation_ns,
                             evidence=rule.description)
            findings.append(st.alert)
            fired.append(st.alert)
            return "fired", st.alert
        st.alert.last_ns = st.stretch.last_violation_ns
        return "extended", st.alert
    if resolved is not None:
        return "resolved", resolved
    return None, None


class StreamAnalyzer:
    """Online point-driven rule evaluation (router subscriber, the paper's
    ZeroMQ analogue): keeps per-(rule, host) stretch state and fires
    ``on_finding`` the moment a threshold+timeout trips — the paper's
    "detect badly behaving jobs directly for instant user feedback".

    Thread-safe (router subscribers run on concurrent ingest threads);
    per-key out-of-order samples are dropped by a monotonic guard instead
    of silently resetting or rewinding rule state.  ``findings`` holds
    every fired :class:`Alert` (active and resolved).  Wire
    :meth:`on_job_end` to a ``JobRegistry`` end hook so per-host state is
    pruned (and tail stretches closed) when a job's hosts stop reporting.
    """

    def __init__(self, rules: Optional[list] = None,
                 on_finding: Optional[Callable] = None):
        self.rules = rules if rules is not None else default_rules()
        self.on_finding = on_finding
        self.findings: list = []
        self._rules_by_meas: dict = {}
        for r in self.rules:
            self._rules_by_meas.setdefault(r.measurement, []).append(r)
        self._keys: dict = {}            # (rule_name, host) -> _KeyState
        self._lock = threading.RLock()

    def __call__(self, kind: str, payload):
        if kind == "points":
            self.observe_batch(payload)
        elif kind == "job_end":
            self.on_job_end(payload)

    def observe(self, p: Point):
        self.observe_batch((p,))

    def observe_batch(self, points: Iterable[Point]):
        if isinstance(points, Point):
            points = (points,)
        fired: list = []
        with self._lock:
            for p in points:
                rules = self._rules_by_meas.get(p.measurement)
                if not rules:
                    continue
                ts = p.timestamp if p.timestamp is not None else now_ns()
                host = p.tags.get("hostname", "")
                jobid = p.tags.get("jobid", "")
                for rule in rules:
                    if rule.metric in p.fields:
                        self._observe_one(rule, host, jobid, ts,
                                          p.fields[rule.metric], fired)
        self._notify(fired)

    def _observe_one(self, rule: ThresholdRule, host: str, jobid: str,
                     ts: int, value, fired: list):
        key = (rule.name, host)
        st = self._keys.get(key)
        if st is None:
            st = self._keys[key] = _KeyState()
        elif st.last_ns is not None and ts < st.last_ns:
            return          # stale out-of-order sample: state must hold
        st.last_ns = ts
        _lifecycle_advance(st, rule, host, jobid, ts, value,
                           self.findings, fired)

    def on_job_end(self, job):
        """JobRegistry end hook: close tail stretches for the job's hosts
        and prune their per-(rule, host) state (no unbounded growth when
        hosts stop reporting)."""
        hosts = set(getattr(job, "hosts", ()) or ())
        jobid = getattr(job, "job_id", "") or ""
        fired: list = []
        with self._lock:
            for key in [k for k in self._keys if k[1] in hosts]:
                st = self._keys.pop(key)
                rule = self._rule(key[0])
                if rule is None:
                    continue
                span = st.stretch.close(rule)
                if span is not None:
                    _lifecycle_close(st, rule, key[1], jobid, span,
                                     self.findings, fired)
        self._notify(fired)

    def _rule(self, name: str) -> Optional[ThresholdRule]:
        for r in self.rules:
            if r.name == name:
                return r
        return None

    def _notify(self, fired: list):
        if self.on_finding:
            for a in fired:
                try:
                    self.on_finding(a)
                except Exception:    # a broken callback must not stall us
                    pass


# --------------------------------------------------------------------------
# Persisted alert / report read-back (shared by engine, httpd, dashboards)
# --------------------------------------------------------------------------


def load_alerts(db: "Database", *, jobid: Optional[str] = None,
                host: Optional[str] = None, rule: Optional[str] = None,
                state: str = "all") -> list:
    """Reconstruct :class:`Alert` episodes from the persisted ``analysis``
    measurement.

    ``db`` is any Database-shaped view (plain, sharded,
    ``FederatedQuery``, ``HttpQueryClient``) — only ``select`` is used, so
    alerts federate by concatenation exactly like any other series.
    ``state`` filters to ``active`` / ``resolved`` / ``all``.
    """
    tags = {"kind": "alert"}
    if jobid:
        tags["jobid"] = jobid
    if host:
        tags["hostname"] = host
    if rule:
        tags["rule"] = rule
    alerts: list = []
    for s in db.select(ANALYSIS_MEASUREMENT, None, tags):
        n = len(s.times)
        col = {f: s.values.get(f) or [None] * n
               for f in ("state", "start_ns", "last_ns", "end_ns",
                         "evidence")}
        episodes: dict = {}
        for i in range(n):              # points are time-sorted per series
            start = col["start_ns"][i]
            if start is None:
                continue
            a = episodes.get(start)
            if a is None:
                a = episodes[start] = Alert(
                    s.tags.get("rule", ""),
                    s.tags.get("severity", "warning"),
                    s.tags.get("hostname", ""), s.tags.get("jobid", ""),
                    int(start), int(start))
            last = col["last_ns"][i]
            if last is not None and int(last) >= a.last_ns:
                a.last_ns = int(last)
            if col["evidence"][i]:
                a.evidence = col["evidence"][i]
            if col["state"][i] == "resolved":
                end = col["end_ns"][i]
                a.end_ns = int(end) if end is not None else a.last_ns
                a.state = "resolved"
        alerts.extend(episodes.values())
    if state == "active":
        alerts = [a for a in alerts if a.active]
    elif state == "resolved":
        alerts = [a for a in alerts if not a.active]
    elif state != "all":
        raise ValueError(f"unknown alert state filter {state!r} "
                         "(expected active|resolved|all)")
    alerts.sort(key=lambda a: (a.start_ns, a.rule, a.host))
    return alerts


def load_job_report(db: "Database", jobid: str) -> Optional[dict]:
    """Latest persisted footprint report for one job (see
    :meth:`AnalysisEngine.job_report`), or None."""
    best, best_t = None, None
    for s in db.select(ANALYSIS_MEASUREMENT, ["report"],
                       {"kind": "job_report", "jobid": jobid}):
        for t, r in zip(s.times, s.values.get("report", ())):
            if r is not None and (best_t is None or t >= best_t):
                best, best_t = r, t
    return json.loads(best) if best else None


def _job_ended(db: "Database", jobid: str) -> bool:
    for s in db.select("job_event", ["event"], {"jobid": jobid}):
        if "end" in (s.values.get("event") or ()):
            return True
    return False


class AnalysisEngine:
    """The continuous analysis subsystem (MPCDF / PerSyst shape): rule
    evaluation runs against the TSDB's streaming **rollup windows**, and
    every result is written back into the TSDB.

    Why windows, not raw points: per-point evaluation on the ingest path
    costs more than ingest itself (it would halve throughput), while the
    rollup tiers already hold exactly the per-window means the offline
    rollup path (:func:`evaluate_rules_on_db`) evaluates — so a
    cursor-driven sweep over *new* windows is O(#windows), runs on a
    background thread, and produces byte-identical findings to the offline
    scan.  The newest window of each series is held back until a newer one
    exists (or a ``final`` tick): its mean may still change.  Late data
    behind a consumed cursor is absorbed by the rollups but not
    re-evaluated (standard watermark semantics).

    Wiring (``MonitoringStack`` does all of this):

    * subscribe to the router — a batch publish just marks the engine
      dirty (O(1)); a rate-limited worker thread ticks;
    * ``JobRegistry.on_end`` -> :meth:`on_job_end`: final-ticks the job's
      series, resolves its open alerts, writes its footprint report and
      prunes all per-series state;
    * :meth:`recover` on restart: reinstates persisted firing alerts
      (same episode continues — no duplicate re-fire) and resolves alerts
      whose job ended while the engine was down.

    Databases without rollups are still handled: the tick falls back to a
    cursor-bounded raw ``select`` (point-granularity semantics).
    """

    def __init__(self, rules: Optional[list] = None,
                 on_finding: Optional[Callable] = None,
                 backend=None, db_name: str = "global", *,
                 report_measurements: tuple = ("hpm", "system"),
                 extend_persist_interval_s: float = 60.0,
                 tick_interval_s: float = 0.25,
                 auto_tick: bool = True,
                 max_resolved_alerts: int = 10_000,
                 fingerprints: bool = True,
                 fingerprint_sigma: float = 3.0,
                 fingerprint_min_runs: int = 3):
        self.rules = rules if rules is not None else default_rules()
        self.on_finding = on_finding
        self.backend = backend
        self.db_name = db_name
        self.report_measurements = tuple(report_measurements)
        self.alerts: list = []           # fired alerts, active + resolved
        self.findings = self.alerts      # StreamAnalyzer-compatible alias
        self._rule_by_name = {r.name: r for r in self.rules}
        self._series: dict = {}          # (rule, series_key) -> _KeyState
        self._lowwater: dict = {}        # rule -> min cursor (tick t_min)
        self._tick_count = 0
        self._running: set = set()       # jobids with a live allocation
        self._ended: set = set()         # jobids whose analysis is closed
        self._recovered: dict = {}       # (rule, host, jobid) -> Alert
        self._extend_ns = int(extend_persist_interval_s * 1e9)
        self._lock = threading.RLock()
        self.stats = {"ticks": 0, "windows_evaluated": 0,
                      "alerts_fired": 0, "alerts_resolved": 0,
                      "reports_written": 0, "alerts_recovered": 0,
                      "fingerprints_written": 0, "fingerprint_outliers": 0}
        self.fingerprints = bool(fingerprints)
        self.fingerprint_sigma = float(fingerprint_sigma)
        self.fingerprint_min_runs = int(fingerprint_min_runs)
        self._max_resolved = int(max_resolved_alerts)
        # background ticker: publishes mark dirty, the worker coalesces
        self._auto_tick = bool(auto_tick)
        self._tick_interval_s = float(tick_interval_s)
        self._cv = threading.Condition(threading.Lock())
        self._dirty = False
        self._stop = False
        self._thread: Optional[threading.Thread] = None

    # -- router subscription (O(1) on the ingest path) -----------------------

    def __call__(self, kind: str, payload):
        if kind == "points":
            if self._auto_tick:
                self._signal()
        elif kind == "job_start":
            jid = getattr(payload, "job_id", "") or ""
            if jid:
                with self._lock:
                    self._running.add(jid)
                    self._ended.discard(jid)   # requeued/restarted job id
        elif kind == "job_end":
            self.on_job_end(payload)

    def _signal(self):
        with self._cv:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._worker, daemon=True, name="lms-analysis")
                self._thread.start()
            self._dirty = True
            self._cv.notify()

    def _worker(self):
        while True:
            with self._cv:
                while not self._dirty and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
                self._dirty = False
            try:
                self.tick()
            except Exception as e:          # noqa: BLE001
                warnings.warn(f"analysis tick failed: {e!r}")
            # rate limit: coalesce bursts of publishes into one tick
            time.sleep(self._tick_interval_s)

    def close(self):
        with self._cv:
            self._stop = True
            self._cv.notify_all()
            thread = self._thread
        # bounded join outside the condition (the worker needs _cv to
        # observe _stop); the sleep-based rate limiter caps the wait
        if thread is not None and thread is not threading.current_thread():
            thread.join(timeout=2.0 + self._tick_interval_s)
            if thread.is_alive():
                with self._lock:
                    self.stats["tick_join_timeouts"] = \
                        self.stats.get("tick_join_timeouts", 0) + 1

    # -- the continuous evaluation sweep -------------------------------------

    def _db(self) -> "Optional[Database]":
        # Database-shaped: plain or sharded depending on the backend.
        # The annotation is load-bearing for repro.analyzer lock-order
        # resolution — ticks call into the database under self._lock.
        if self.backend is None:
            return None
        return self.backend.db(self.db_name)

    def flush(self, final: bool = False) -> "AnalysisEngine":
        """Synchronous tick — call before reading live state in tests or
        request handlers (``final`` also consumes held-back newest
        windows).  Always a full sweep: the read-your-writes promise must
        not depend on where the background ticker's counter happens to
        sit (a series backfilled entirely below the cursor low-water —
        e.g. a new job at older timestamps than a finished one — would
        otherwise stay invisible for up to FULL_SWEEP_EVERY ticks)."""
        self.tick(final=final, full=True)
        return self

    # incremental ticks bound their readout by the per-rule cursor
    # low-water; every FULL_SWEEP_EVERY-th tick (and every final or
    # explicitly full tick) is an unbounded full sweep, which is what
    # discovers a series backfilled entirely below the low-water —
    # worst-case staleness for such a series is FULL_SWEEP_EVERY
    # *background* ticks, and flush()/job-end evaluation is always exact.
    # (A stalled series pins the low-water, degrading incremental ticks
    # toward full-sweep cost until its job ends — the underlying
    # per-series window scan is O(stored windows) either way; the
    # low-water only trims result materialization.)
    FULL_SWEEP_EVERY = 8

    def tick(self, final: bool = False, full: Optional[bool] = None) -> int:
        """Advance every rule over the windows (or raw points) that became
        visible since the last tick; returns samples evaluated."""
        db = self._db()
        if db is None:
            return 0
        out: list = []
        fired: list = []
        with self._lock:
            if full is None:
                full = self._tick_count % self.FULL_SWEEP_EVERY == 0
            full = full or final
            self._tick_count += 1
            n = self._tick_locked(db, None, final, fired, out, full=full)
            self.stats["ticks"] += 1
            self.stats["windows_evaluated"] += n
        self._emit(out, fired)
        return n

    def _tick_locked(self, db: "Database", only_tags: Optional[dict],
                     final: bool,
                     fired: list, out: list, full: bool = True) -> int:
        rollups = getattr(db, "rollup_config", None) is not None
        evaluated = 0
        global_sweep = only_tags is None
        for rule in self.rules:
            t_min = None if (full or not global_sweep) \
                else self._lowwater.get(rule.name)
            series_list = self._rule_series(db, rule, only_tags, t_min,
                                            rollups)
            for s in series_list:
                vals = s.values.get(rule.metric)
                if not vals:
                    continue
                jobid = s.tags.get("jobid", "")
                if jobid and not self._job_live(db, jobid):
                    continue             # job over: its report is final
                skey = (rule.name, _tags_key(s.tags))
                st = self._series.get(skey)
                if st is None:
                    st = self._series[skey] = _KeyState()
                    self._adopt_recovered(rule, s.tags, st)
                    if t_min is not None and st.cursor < t_min:
                        full = self._rule_series(db, rule, s.tags, None,
                                                 rollups)
                        s = next((f for f in full
                                  if _tags_key(f.tags) == skey[1]), s)
                        vals = s.values.get(rule.metric) or vals
                host = s.tags.get("hostname", "")
                # hold the newest window back unless final: its aggregate
                # may still change (raw points are immutable -> no holdback)
                limit = len(s.times) if (final or not rollups) \
                    else len(s.times) - 1
                i = bisect.bisect_left(s.times, st.cursor)
                while i < limit:
                    ts = s.times[i]
                    self._advance(rule, st, host, jobid, ts, vals[i],
                                  fired, out)
                    st.cursor = ts + 1
                    evaluated += 1
                    i += 1
            if global_sweep:
                cursors = [st.cursor for (rn, _), st in self._series.items()
                           if rn == rule.name]
                if cursors:
                    self._lowwater[rule.name] = min(cursors)
        return evaluated

    @staticmethod
    def _rule_series(db: "Database", rule: ThresholdRule,
                     tags: Optional[dict],
                     t_min: Optional[int], rollups: bool) -> list:
        if rule.expr:
            # derived rule input (repro.core.query): the metric is a
            # formula over the measurement's stored fields, evaluated per
            # rollup window — it need never have been emitted
            from repro.core.query import (derived_rollup_series,
                                          derived_select_series)
            if rollups:
                return derived_rollup_series(db, rule.measurement,
                                             rule.metric, rule.expr,
                                             tags=tags, t_min=t_min)
            return derived_select_series(db, rule.measurement, rule.metric,
                                         rule.expr, tags=tags, t_min=t_min)
        if rollups:
            return db.rollup_series(rule.measurement, rule.metric,
                                    agg="mean", tags=tags, t_min=t_min)
        return db.select(rule.measurement, [rule.metric], tags, t_min)

    def _job_live(self, db: "Database", jobid: str) -> bool:
        """False once a job's analysis is closed (its end hook ran, or it
        was found ended in the DB — e.g. before a restart)."""
        if jobid in self._ended:
            return False
        if jobid in self._running:
            return True
        if _job_ended(db, jobid):
            self._ended.add(jobid)
            return False
        self._running.add(jobid)
        return True

    def _adopt_recovered(self, rule: ThresholdRule, tags: dict,
                         st: _KeyState):
        """First sighting of a series after :meth:`recover`: continue the
        persisted episode instead of re-firing a duplicate."""
        rec = self._recovered.pop(
            (rule.name, tags.get("hostname", ""), tags.get("jobid", "")),
            None)
        if rec is None:
            return
        st.cursor = rec.last_ns + 1
        if rec.active:
            st.alert = rec
            st.stretch.start_ns = rec.start_ns
            st.stretch.last_violation_ns = rec.last_ns
            st.last_persist_ns = rec.last_ns

    def _advance(self, rule: ThresholdRule, st: _KeyState, host: str,
                 jobid: str, ts: int, value, fired: list, out: list):
        n_fired = len(fired)
        event, a = _lifecycle_advance(st, rule, host, jobid, ts, value,
                                      self.alerts, fired)
        self.stats["alerts_fired"] += len(fired) - n_fired
        if event == "fired":
            st.last_persist_ns = ts
            out.append(self._alert_point(a, "firing", ts))
        elif event == "extended":
            if ts - st.last_persist_ns >= self._extend_ns:
                st.last_persist_ns = ts
                out.append(self._alert_point(a, "firing", ts))
        elif event == "resolved":
            self.stats["alerts_resolved"] += 1
            out.append(self._alert_point(a, "resolved", ts))
            self._trim_alerts()

    def _resolve(self, rule: ThresholdRule, st: _KeyState, host: str,
                 jobid: str, span: tuple, ts: int, fired: list, out: list):
        """Forced close (job end / recovery of a dead job)."""
        n_fired = len(fired)
        a = _lifecycle_close(st, rule, host, jobid, span, self.alerts,
                             fired)
        self.stats["alerts_fired"] += len(fired) - n_fired
        if a is not None:
            self.stats["alerts_resolved"] += 1
            out.append(self._alert_point(a, "resolved", ts))
            self._trim_alerts()

    def _trim_alerts(self):
        if len(self.alerts) <= self._max_resolved:
            return
        keep = [a for a in self.alerts if a.active]
        resolved = [a for a in self.alerts if not a.active]
        drop = len(self.alerts) - self._max_resolved
        self.alerts[:] = resolved[drop:] + keep

    def _alert_point(self, a: Alert, state: str, ts: int) -> Point:
        tags = {"kind": "alert", "rule": a.rule, "hostname": a.host,
                "severity": a.severity}
        if a.jobid:
            tags["jobid"] = a.jobid
        fields = {"state": state, "start_ns": a.start_ns,
                  "last_ns": a.last_ns, "evidence": a.evidence}
        if state == "resolved":
            fields["end_ns"] = a.end_ns
            fields["duration_s"] = a.duration_s
        return Point(ANALYSIS_MEASUREMENT, tags, fields, ts)

    def _emit(self, out: list, fired: list):
        if out and self.backend is not None:
            self.backend.write(out, self.db_name)
        if fired and self.on_finding:
            for a in fired:
                try:
                    self.on_finding(a)
                except Exception:   # a broken callback must not stall us
                    pass

    # -- job lifecycle --------------------------------------------------------

    def on_job_end(self, job):
        """JobRegistry end hook: final-tick the job's series, resolve its
        open alerts (end = last violating window), write its footprint
        report, and prune every per-series state it owned.  Idempotent —
        the router also republishes job_end to subscribers."""
        jobid = getattr(job, "job_id", job) or ""
        with self._lock:
            if not jobid or jobid in self._ended:
                return
            end_ns = getattr(job, "end_ns", None) or now_ns()
            hosts = set(getattr(job, "hosts", ()) or ())
            db = self._db()
            out: list = []
            fired: list = []
            if db is not None:
                # force-live for the final sweep (the end event may already
                # be in the DB when this arrives via the router's publish)
                self._running.add(jobid)
                self._tick_locked(db, {"jobid": jobid}, True, fired, out)
            self._ended.add(jobid)
            self._running.discard(jobid)
            for skey in list(self._series):
                rule_name, tags_key = skey
                tags = dict(tags_key)
                owned = tags.get("jobid") == jobid or (
                    not tags.get("jobid") and tags.get("hostname") in hosts)
                if not owned:
                    continue
                st = self._series.pop(skey)
                rule = self._rule_by_name.get(rule_name)
                if rule is None:
                    continue
                span = st.stretch.close(rule)
                if span is not None:
                    self._resolve(rule, st, tags.get("hostname", ""),
                                  tags.get("jobid", "") or jobid, span,
                                  end_ns, fired, out)
            if db is not None:
                report = self._build_report(db, jobid, running=False)
                if report is not None:
                    out.append(Point(
                        ANALYSIS_MEASUREMENT,
                        {"kind": "job_report", "jobid": jobid},
                        {"report": json.dumps(report),
                         "pattern": report["pattern"],
                         "status": report["status"],
                         "alerts_total": float(len(report["alerts"]))},
                        end_ns))
                    self.stats["reports_written"] += 1
                self._fingerprint_job(db, job, jobid, end_ns, out, fired)
        self._emit(out, fired)

    def _fingerprint_job(self, db, job, jobid: str, end_ns: int,
                         out: list, fired: list):
        """Fingerprint the finished job and apply the fleet rule: compare
        its p95 quantile vector against its own past runs (same family —
        jobname tag, else user) and flag >sigma deviations through the
        normal alert surface.  History is read before this job's point is
        emitted, so the new run never pollutes its own baseline.  Called
        under self._lock; failures are counted, never allowed to block job
        teardown."""
        if not self.fingerprints:
            return
        try:
            fp = job_fingerprint(db, jobid, self.report_measurements)
            if not fp:
                return
            tags = getattr(job, "tags", None) or {}
            family = tags.get("jobname") or getattr(job, "user", "") or ""
            history = [e["fingerprint"] for e in load_fingerprints(db)
                       if e["family"] == family and e["jobid"] != jobid]
            out.append(fingerprint_point(jobid, family, fp, end_ns))
            self.stats["fingerprints_written"] += 1
            outliers = fingerprint_outliers(
                fp, history, sigma=self.fingerprint_sigma,
                min_runs=self.fingerprint_min_runs)
            if not outliers:
                return
            ev = "; ".join(
                f"{o['metric']} {o['quantile']}={o['value']:.6g} vs "
                f"fleet mean {o['mean']:.6g} "
                f"(z={o['z']:.1f}, {o['runs']} past runs)"
                for o in outliers[:3])
            a = Alert(rule="fingerprint_outlier", severity="warning",
                      host="", jobid=jobid, start_ns=end_ns,
                      last_ns=end_ns, end_ns=end_ns, state="resolved",
                      evidence=ev)
            self.alerts.append(a)
            self._trim_alerts()
            self.stats["alerts_fired"] += 1
            self.stats["alerts_resolved"] += 1
            self.stats["fingerprint_outliers"] += 1
            out.append(self._alert_point(a, "resolved", end_ns))
            fired.append(a)
        except Exception:   # noqa: BLE001 - teardown must complete
            self.stats["fingerprint_errors"] = \
                self.stats.get("fingerprint_errors", 0) + 1

    # -- job footprint reports ------------------------------------------------

    def job_report(self, jobid: str) -> Optional[dict]:
        """Footprint summary + pattern classification for one job: live
        (recomputed from the rollup windows) while the job runs, the
        persisted report afterwards."""
        db = self._db()
        if db is None:
            return None
        with self._lock:
            if jobid in self._ended:
                return load_job_report(db, jobid)
            return self._build_report(db, jobid, running=True)

    def _build_report(self, db, jobid: str, *, running: bool) \
            -> Optional[dict]:
        """Time-weighted per-metric stats (means averaged over the uniform
        rollup windows, i.e. time-weighted at window granularity) plus the
        pattern-tree classification — the paper's "statistical foundation
        about application specific system usage" per job."""
        tags = {"jobid": jobid}
        metrics: dict = {}
        hosts: set = set()
        span = [None, None]
        rollups = getattr(db, "rollup_config", None) is not None
        for meas in self.report_measurements:
            for fieldname in db.field_keys(meas):
                if fieldname in metrics:
                    continue             # first measurement wins the name
                if rollups:
                    series_list = db.rollup_series(meas, fieldname,
                                                   tags=tags)
                else:
                    series_list = db.select(meas, [fieldname], tags)
                count = 0
                vmin = vmax = None
                wmean_sum = 0.0
                for s in series_list:
                    vals = s.values.get(fieldname) or ()
                    numeric = [v for v in vals
                               if isinstance(v, (int, float)) and
                               not isinstance(v, bool) and v == v]
                    if not numeric:
                        continue
                    hosts.add(s.tags.get("hostname", ""))
                    if s.times:
                        if span[0] is None or s.times[0] < span[0]:
                            span[0] = s.times[0]
                        if span[1] is None or s.times[-1] > span[1]:
                            span[1] = s.times[-1]
                    count += len(numeric)
                    wmean_sum += sum(numeric)
                    lo, hi = min(numeric), max(numeric)
                    vmin = lo if vmin is None else min(vmin, lo)
                    vmax = hi if vmax is None else max(vmax, hi)
                if count:
                    metrics[fieldname] = {
                        "mean": wmean_sum / count, "min": vmin,
                        "max": vmax, "samples": count}
        if not metrics:
            return None
        m = {k: v["mean"] for k, v in metrics.items()}
        # roofline term fractions from the utilization gauges, when present
        cu, mu, iu = (m.get("hw_flops_util"), m.get("hbm_bw_util"),
                      m.get("ici_bw_util"))
        if cu is not None and mu is not None and iu is not None and \
                (cu + mu + iu) > 0:
            tot = cu + mu + iu
            m.setdefault("compute_frac", cu / tot)
            m.setdefault("memory_frac", mu / tot)
            m.setdefault("collective_frac", iu / tot)
        cls = classify_job(m)
        alerts = [a.to_dict() for a in self.alerts if a.jobid == jobid]
        return {"jobid": jobid, "running": running,
                "hosts": sorted(hosts),
                "window_ns": span,
                "metrics": dict(sorted(metrics.items())),
                "pattern": cls["pattern"], "remedy": cls["remedy"],
                "missing": cls["missing"], "path": cls["path"],
                "alerts": alerts,
                "status": "unhealthy" if any(
                    a["severity"] == "critical" for a in alerts) else "ok"}

    # -- restart recovery (the WAL brought the analysis series back) ---------

    def recover(self) -> dict:
        """Reinstate persisted alert state after a restart: active alerts
        continue as the same episode (adopted when their series next
        ticks); alerts whose job ended while the engine was down are
        resolved; resolved history seeds per-series cursors so old
        stretches are not re-fired as duplicates."""
        db = self._db()
        if db is None:
            return {"alerts_recovered": 0, "alerts_closed": 0}
        out: list = []
        recovered = closed = 0
        dead_jobs: set = set()
        with self._lock:
            for a in load_alerts(db):
                key = (a.rule, a.host, a.jobid)
                job_dead = a.jobid and not self._job_live(db, a.jobid)
                if a.active and job_dead:
                    # its job ended while the engine was down
                    a.end_ns = a.last_ns
                    a.state = "resolved"
                    out.append(self._alert_point(a, "resolved", a.last_ns))
                    closed += 1
                    dead_jobs.add(a.jobid)
                elif a.active:
                    recovered += 1
                # the full history (resolved episodes included) comes back
                # so a post-restart job report still lists every episode
                self.alerts.append(a)
                # cursor floor per key (latest episode wins): an already-
                # reported stretch is never re-evaluated -> no duplicate
                # re-fire after restart
                cur = self._recovered.get(key)
                if cur is None or a.last_ns >= cur.last_ns:
                    self._recovered[key] = a
            # jobs that ended while the engine was down never got their
            # footprint report written — write it now (alerting jobs only;
            # quiet jobs that ended while down stay report-less)
            for jid in sorted(dead_jobs):
                if load_job_report(db, jid) is None:
                    report = self._build_report(db, jid, running=False)
                    if report is not None:
                        out.append(Point(
                            ANALYSIS_MEASUREMENT,
                            {"kind": "job_report", "jobid": jid},
                            {"report": json.dumps(report),
                             "pattern": report["pattern"],
                             "status": report["status"],
                             "alerts_total":
                                 float(len(report["alerts"]))},
                            report["window_ns"][1] or now_ns()))
                        self.stats["reports_written"] += 1
            self.stats["alerts_recovered"] += recovered
        self._emit(out, [])
        return {"alerts_recovered": recovered, "alerts_closed": closed}

    # -- read API -------------------------------------------------------------

    def active_alerts(self, jobid: Optional[str] = None) -> list:
        with self._lock:
            return [a for a in self.alerts if a.active and
                    (jobid is None or a.jobid == jobid)]

    def resolved_alerts(self, jobid: Optional[str] = None) -> list:
        with self._lock:
            return [a for a in self.alerts if not a.active and
                    (jobid is None or a.jobid == jobid)]

    def engine_stats(self) -> dict:
        with self._lock:
            return {**self.stats, "series_tracked": len(self._series),
                    "alerts_active": sum(a.active for a in self.alerts),
                    "jobs_running": len(self._running),
                    "jobs_closed": len(self._ended)}


# ==========================================================================
# 2. Performance-pattern decision tree
# ==========================================================================


@dataclass
class PatternNode:
    """Internal node: test ``metric op threshold``; leaf: pattern+remedy.

    Missing inputs are never silently treated as 0.0 (the seed behavior,
    which routed jobs down arbitrary branches): a pathology test (``>`` /
    ``>=``) with no data means "no evidence of that pathology" — the
    false branch is taken and the gap recorded in the decision path and
    the ``missing`` list; a goodness test (``<`` / ``<=``) cannot certify
    either branch without data and classifies as ``insufficient-data``.
    """

    pattern: Optional[str] = None
    remedy: Optional[str] = None
    metric: Optional[str] = None
    op: Optional[str] = None
    threshold: Optional[float] = None
    if_true: Optional["PatternNode"] = None
    if_false: Optional["PatternNode"] = None

    def classify(self, metrics: dict, path: Optional[list] = None,
                 missing: Optional[list] = None):
        path = path if path is not None else []
        missing = missing if missing is not None else []
        if self.pattern is not None:
            return self.pattern, self.remedy, path, missing
        v = metrics.get(self.metric)
        if v is None or (isinstance(v, float) and math.isnan(v)):
            missing.append(self.metric)
            if self.op in ("<", "<="):
                path.append(f"{self.metric}=missing -> insufficient-data")
                return (INSUFFICIENT_DATA,
                        "metrics missing for classification: "
                        + ", ".join(missing), path, missing)
            path.append(f"{self.metric}=missing -> False (no evidence)")
            return self.if_false.classify(metrics, path, missing)
        taken = _OPS[self.op](v, self.threshold)
        path.append(f"{self.metric}={v:.3g} {self.op} {self.threshold}"
                    f" -> {taken}")
        nxt = self.if_true if taken else self.if_false
        return nxt.classify(metrics, path, missing)


def leaf(pattern, remedy):
    return PatternNode(pattern=pattern, remedy=remedy)


def node(metric, op, threshold, if_true, if_false):
    return PatternNode(metric=metric, op=op, threshold=threshold,
                       if_true=if_true, if_false=if_false)


# TPU adaptation of the FEPA decision tree: discriminate on the roofline
# term fractions + goodput metrics.  Inputs (all in [0, ~1]):
#   compute_frac / memory_frac / collective_frac : term_i / sum(terms)
#   mfu            : model FLOPs utilization
#   useful_flop_ratio : model_flops / hlo_flops
#   data_stall_frac, straggler_skew
DEFAULT_TREE = node(
    "data_stall_frac", ">", 0.3,
    leaf("ingest-bound",
         "input pipeline too slow: add prefetch/workers, shard files"),
    node("straggler_skew", ">", 0.15,
         leaf("load-imbalance",
              "straggler host: checkpoint-restart without it (elastic), "
              "check MoE expert balance"),
         node("collective_frac", ">", 0.4,
              leaf("collective-bound",
                   "overlap collectives with compute, rethink sharding axes, "
                   "gradient compression, larger per-device batch"),
              node("memory_frac", ">", 0.5,
                   node("useful_flop_ratio", "<", 0.6,
                        leaf("recompute-heavy memory-bound",
                             "relax remat policy; fuse attention (flash) to "
                             "cut activation traffic"),
                        leaf("memory-bound",
                             "increase arithmetic intensity: fuse ops, "
                             "quantize weights/cache, batch decode requests")),
                   node("mfu", "<", 0.25,
                        leaf("latency/overhead-bound",
                             "kernel launch / small-batch overheads: grow "
                             "per-device batch, unroll scan, check host "
                             "callbacks"),
                        leaf("compute-bound",
                             "good: push block shapes / MXU alignment; "
                             "consider int8/fp8 matmuls"))))))


def classify_job(metrics: dict, tree: PatternNode = DEFAULT_TREE) -> dict:
    pattern, remedy, path, missing = tree.classify(dict(metrics))
    return {"pattern": pattern, "remedy": remedy, "path": path,
            "missing": missing}
