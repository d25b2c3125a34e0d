"""Marker-region instrumentation + query-side rooflines (ROADMAP item 3).

The LIKWID marker API (``pylikwid.markerstartregion`` /
``markerstopregion``, SNIPPETS.md snippet 1) is how application phases
get attributed HPM data in the paper's stack.  This module is its LMS
analogue for the repo's own jax/pallas workloads:

* :class:`MarkerSession` — per-process region accounting with
  **thread-local region stacks**, so nested regions get exact
  inclusive/exclusive wall time and concurrent threads never corrupt
  each other's nesting.  Per region it accumulates call count,
  inclusive/exclusive seconds and user-supplied work counters (flops,
  bytes, tokens, ...).
* Emission: accumulated *deltas since the last flush* leave through any
  ``UserMetric``-shaped emitter as the ``marker`` measurement — tags
  ``{region}`` plus the emitter's defaults (hostname; the router adds
  jobid/username while a job is live), fields ``{time_s, excl_time_s,
  calls, <counters>...}``.  Delta emission makes ``QuerySpec(agg="sum")``
  over rollup windows yield exact per-window totals, which is what the
  ROOFLINE rate formulas need.
* Query side: the ``ROOFLINE`` performance group
  (``repro.core.perf_groups``) derives ``intensity`` (flops/byte),
  ``achieved_gflops`` and ``roofline_frac`` = achieved / min(peak_flops,
  peak_bw * intensity) from stored marker fields — evaluated by the
  existing query engine over rollup tiers, so per-region roofline
  placement federates, caches and survives raw-point retention like any
  derived metric.  :func:`roofline_spec` is the one canonical
  ``QuerySpec`` the dashboard panel, the analysis rule, ``/query/v2``
  callers and the tests all share.

Calibration-point convention: measured machine peaks (e.g. from
``benchmarks/roofline.py`` microbenchmarks) are stored as ordinary
``marker`` points under the reserved region :data:`CALIB_REGION` with
fields ``peak_flops`` / ``peak_bw``.  :func:`roofline_peaks` reads the
latest one back; :func:`register_roofline_group` re-registers ROOFLINE
with the peaks baked in as numeric literals.  Because a ``QuerySpec``
resolves ``@metric`` references to formula *text* at construction, a
calibrated spec ships its peaks inside the spec — remote federation
stays byte-identical with zero remote calibration state.
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Callable, Optional

from jax.profiler import TraceAnnotation, annotate_function

from repro.core.line_protocol import now_ns
from repro.core.perf_groups import (formula_for, register_group,
                                    roofline_group_text)
from repro.core.query import QuerySpec

__all__ = [
    "CALIB_REGION", "MARKER_MEASUREMENT", "MarkerSession", "calibrate",
    "low_roofline_rule", "register_roofline_group", "roofline_group_text",
    "roofline_peaks", "roofline_spec",
]

MARKER_MEASUREMENT = "marker"
# reserved region name carrying machine-peak calibration points; never a
# real code region (leading underscore keeps it sorted apart and obvious)
CALIB_REGION = "_calib"


class _Frame:
    """One open region on one thread's stack, with its ``marker.<name>``
    span (open from start to stop)."""

    __slots__ = ("name", "t0", "child_s", "counters", "span")

    def __init__(self, name: str, t0: float):
        self.name = name
        self.t0 = t0
        self.child_s = 0.0          # inclusive seconds of finished children
        self.counters = None
        self.span = TraceAnnotation(f"marker.{name}")
        self.span.__enter__()


class Region:
    """Context manager handle; ``seconds`` holds the inclusive wall time
    after exit.  Exception-safe: the region stops (and is accounted) even
    when the body raises — LIKWID's stop-on-error discipline without the
    boilerplate."""

    __slots__ = ("_session", "name", "counters", "seconds", "_frame")

    def __init__(self, session: "MarkerSession", name: str,
                 counters: Optional[dict]):
        self._session = session
        self.name = name
        self.counters = dict(counters) if counters else None
        self.seconds = None
        self._frame = None

    def add(self, **counters):
        """Add work counters from inside the region body."""
        if self.counters is None:
            self.counters = {}
        for k, v in counters.items():
            self.counters[k] = self.counters.get(k, 0.0) + float(v)
        return self

    def __enter__(self):
        self._frame = self._session.start_region(self.name)
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = self._session._stop_frame(self._frame, self.counters)
        self._frame = None
        return False


class MarkerSession:
    """pylikwid-style marker session over an LMS emitter.

    ``emitter`` is anything with ``.metric(name, fields, tags=, ts=)``
    (a :class:`~repro.core.usermetric.UserMetric`); ``None`` accumulates
    only — :meth:`flush` still returns the drained per-region deltas, so
    a session is usable standalone (tests, overhead benchmarks).

    ``clock`` is injectable for deterministic tests.  All public methods
    are thread-safe; region *stacks* are thread-local by design (nesting
    is a per-thread property), the accumulator table is shared under a
    lock (totals merge across threads).
    """

    def __init__(self, emitter=None, *, emit_interval_s: float = 5.0,
                 measurement: str = MARKER_MEASUREMENT,
                 clock: Callable[[], float] = time.monotonic):
        self._emitter = emitter
        self.emit_interval_s = float(emit_interval_s)
        self.measurement = measurement
        self._clock = clock
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._pending: dict = {}        # region -> delta acc since flush
        self._totals: dict = {}         # region -> lifetime acc
        self._last_emit = clock()
        self._closed = False

    # -- region stack (thread-local) ----------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def start_region(self, name: str) -> _Frame:
        """Open a region on the calling thread; returns its frame token."""
        fr = _Frame(str(name), self._clock())
        self._stack().append(fr)
        return fr

    def stop_region(self, name: Optional[str] = None,
                    counters: Optional[dict] = None) -> float:
        """Close the innermost open region; returns inclusive seconds.

        ``name`` (when given) must match the innermost region —
        mismatched stop order is a caller bug and raises rather than
        silently misattributing time.  Prefer :meth:`region`, which is
        exception-safe by construction.
        """
        st = self._stack()
        if not st:
            raise ValueError(f"stop_region({name!r}): no region open "
                             "on this thread")
        if name is not None and st[-1].name != name:
            raise ValueError(f"stop_region({name!r}): innermost open "
                             f"region is {st[-1].name!r}")
        return self._stop_frame(st[-1], counters)

    def _stop_frame(self, frame: _Frame, counters: Optional[dict]) -> float:
        """Close ``frame`` (and any regions leaked open inside it)."""
        st = self._stack()
        if frame not in st:
            raise ValueError(f"region {frame.name!r} is not open "
                             "on this thread")
        now = self._clock()
        # close leaked children first so their time still attributes
        # correctly (a child started but never stopped must not swallow
        # the parent's exclusive time)
        while st[-1] is not frame:
            self._pop(st, now, None)
        incl = self._pop(st, now, counters)
        self._maybe_emit(now)
        return incl

    def _pop(self, st: list, now: float, counters: Optional[dict]) -> float:
        fr = st.pop()
        fr.span.__exit__(None, None, None)
        incl = max(now - fr.t0, 0.0)
        excl = max(incl - fr.child_s, 0.0)
        if st:
            st[-1].child_s += incl
        merged = fr.counters
        if counters:
            merged = dict(merged) if merged else {}
            for k, v in counters.items():
                merged[k] = merged.get(k, 0.0) + float(v)
        self._accumulate(fr.name, 1, incl, excl, merged)
        return incl

    def region(self, name: str, counters: Optional[dict] = None) -> Region:
        """``with session.region("fwd", counters={"flops": f}):`` —
        counters are credited once per call on exit (static per-call
        costs: pass them up front; measured ones: ``r.add(...)``)."""
        return Region(self, name, counters)

    def record(self, name: str, seconds: float,
               counters: Optional[dict] = None, calls: int = 1):
        """Account an externally-timed region (a wait measured by someone
        else, e.g. ``DataLoader.wait_time_s``) without entering the
        stack: inclusive == exclusive == ``seconds``."""
        s = float(seconds)
        self._accumulate(str(name), calls, s, s,
                         dict(counters) if counters else None)
        self._maybe_emit(self._clock())

    # -- accumulators ---------------------------------------------------------

    @staticmethod
    def _merge(acc: dict, calls: int, incl: float, excl: float,
               counters: Optional[dict]):
        acc["calls"] = acc.get("calls", 0.0) + float(calls)
        acc["time_s"] = acc.get("time_s", 0.0) + incl
        acc["excl_time_s"] = acc.get("excl_time_s", 0.0) + excl
        if counters:
            for k, v in counters.items():
                acc[k] = acc.get(k, 0.0) + float(v)

    def _accumulate(self, name: str, calls: int, incl: float, excl: float,
                    counters: Optional[dict]):
        with self._lock:
            self._merge(self._pending.setdefault(name, {}), calls, incl,
                        excl, counters)
            self._merge(self._totals.setdefault(name, {}), calls, incl,
                        excl, counters)

    def _maybe_emit(self, now: float):
        if self._emitter is None:
            return
        with self._lock:
            due = now - self._last_emit >= self.emit_interval_s
        if due:
            self.flush()

    def snapshot(self) -> dict:
        """Lifetime per-region totals (never reset by flush)."""
        with self._lock:
            return {name: dict(acc) for name, acc in self._totals.items()}

    def open_regions(self) -> list:
        """Names of regions open on the *calling* thread, outermost first."""
        return [fr.name for fr in self._stack()]

    # -- emission -------------------------------------------------------------

    @partial(annotate_function, name="lms.marker.flush")
    def flush(self, ts: Optional[int] = None) -> dict:
        """Drain pending deltas; emit one ``marker`` point per region (all
        points of one flush share one timestamp, so cross-region queries
        align).  Returns ``{region: fields}`` of what was emitted."""
        with self._lock:
            pending, self._pending = self._pending, {}
            self._last_emit = self._clock()
        if not pending:
            return {}
        t = ts if ts is not None else now_ns()
        out = {}
        for name in sorted(pending):
            fields = {k: float(v) for k, v in pending[name].items()}
            out[name] = fields
            if self._emitter is not None:
                self._emitter.metric(self.measurement, fields,
                                     tags={"region": name}, ts=t)
        if out and self._emitter is not None:
            # push through the emitter's buffer now (UserMetric's internal
            # flush, NOT its public one — that would re-drain this session
            # recursively); failures re-buffer there and never raise into
            # the instrumented code path
            push = getattr(self._emitter, "_flush", None)
            if push is not None:
                push(raise_errors=False)
        return out

    def close(self) -> dict:
        """Final flush (the emitter is NOT closed — it is shared)."""
        self._closed = True
        return self.flush()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# --------------------------------------------------------------------------
# ROOFLINE query side
# --------------------------------------------------------------------------

def register_roofline_group(peak_flops: Optional[float] = None,
                            peak_bw: Optional[float] = None):
    """(Re-)register ROOFLINE, optionally with calibrated peaks baked in.
    Specs built *afterwards* resolve ``@ROOFLINE.*`` to the new text."""
    return register_group(roofline_group_text(peak_flops, peak_bw))


def calibrate(emitter, peak_flops: float, peak_bw: float, *,
              register: bool = True, ts: Optional[int] = None):
    """Persist measured machine peaks as a ``marker`` calibration point
    (region :data:`CALIB_REGION`) and, by default, re-register ROOFLINE
    so new specs use them."""
    emitter.metric(MARKER_MEASUREMENT,
                   {"peak_flops": float(peak_flops),
                    "peak_bw": float(peak_bw)},
                   tags={"region": CALIB_REGION},
                   ts=ts if ts is not None else now_ns())
    flush = getattr(emitter, "flush", None)
    if flush is not None:
        flush()                 # a calibration point must land now
    if register:
        register_roofline_group(peak_flops, peak_bw)


def roofline_peaks(db) -> Optional[tuple]:
    """Latest stored calibration point -> ``(peak_flops, peak_bw)`` or
    ``None``.  ``db`` is any Database-shaped view (plain, sharded,
    federated, HTTP client)."""
    best = None
    for s in db.select(MARKER_MEASUREMENT, ["peak_flops", "peak_bw"],
                       {"region": CALIB_REGION}):
        pf = s.values.get("peak_flops", [])
        bw = s.values.get("peak_bw", [])
        for i, t in enumerate(s.times):
            if i < len(pf) and i < len(bw) and \
                    (best is None or t > best[0]):
                best = (t, float(pf[i]), float(bw[i]))
    return None if best is None else (best[1], best[2])


def roofline_spec(jobid: Optional[str] = None, *,
                  window_ns: int = 10 * 10**9,
                  t_min: Optional[int] = None, t_max: Optional[int] = None,
                  region: Optional[str] = None,
                  limit: Optional[int] = None) -> QuerySpec:
    """THE canonical per-region roofline query — one spec shared by the
    dashboard panel, the ``/query/v2`` acceptance path and the tests.

    ``agg="sum"`` turns the delta-emitted marker fields into exact
    per-window totals, so every ROOFLINE rate formula sees true window
    rates; ``group_by="region"`` yields one group per code region.
    The ``@ROOFLINE.*`` references resolve to formula text *here*, at
    construction — a calibrated group registered before this call is
    carried inside the spec to shards and remote instances.
    """
    tags = {}
    if jobid:
        tags["jobid"] = jobid
    if region:
        tags["region"] = region
    return QuerySpec(measurement=MARKER_MEASUREMENT,
                     metrics=("time_s", "calls", "@ROOFLINE.intensity",
                              "@ROOFLINE.achieved_gflops",
                              "@ROOFLINE.roofline_frac"),
                     tags=tags, t_min=t_min, t_max=t_max,
                     window_ns=window_ns, group_by="region", agg="sum",
                     limit=limit)


def low_roofline_rule(frac: float = 0.05, *, min_duration_s: float = 60.0,
                      clear_duration_s: float = 15.0,
                      severity: str = "warning"):
    """``ThresholdRule`` flagging regions that sustain below ``frac`` of
    their attainable roofline.  Query-time derived (``expr``): marker
    points never carry ``roofline_frac``; the engine evaluates the
    ROOFLINE formula per rollup window.  Regions without flops/bytes
    counters produce no derived windows and can never fire."""
    from repro.core.analysis import ThresholdRule
    return ThresholdRule(
        "low_roofline", MARKER_MEASUREMENT, "roofline_frac", "<",
        float(frac), min_duration_s, severity,
        "region sustains a low fraction of its attainable roofline "
        "(compute- or bandwidth-starved phase)", clear_duration_s,
        expr=formula_for("ROOFLINE.roofline_frac"))
