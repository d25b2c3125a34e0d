"""Metrics router — the central LMS component (paper §III.B).

Responsibilities (all from the paper):

* mimic the InfluxDB write interface plus an endpoint for job start/end
  signals (the HTTP face lives in ``repro.core.httpd``; this class is the
  in-process engine both faces share);
* keep a *tag store* keyed by the mandatory ``hostname`` tag and enrich every
  incoming metric with the owning job's tags;
* forward enriched points to the database back-end, duplicating them into
  per-user databases when configured;
* store job signals as events so the dashboards can render annotations;
* publish metrics + meta information to attached subscribers — the ZeroMQ
  fan-out of the paper becomes an in-process subscriber registry with the
  same semantics (stream analyzers, aggregators).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Iterable, Optional, Union

from jax.profiler import TraceAnnotation, annotate_function

from repro.core.jobs import JobRegistry
from repro.core.line_protocol import (Point, decode_batch_errors,
                                      encode_point, now_ns)
from repro.core.tsdb import Database, TSDBServer, _tags_key


@dataclass
class RouterStats:
    """Monotonic ingest counters.

    Mutated only through :meth:`add` (plain ``+=`` on a shared dataclass
    is a read-modify-write race under concurrent batched writers); read
    via :meth:`snapshot` — both take the internal lock, so a snapshot is
    a consistent cut (e.g. ``points_in == points_out + dropped_no_host``
    holds between batches).
    """

    points_in: int = 0
    points_out: int = 0
    signals: int = 0
    parse_errors: int = 0
    dropped_no_host: int = 0

    def __post_init__(self):
        self._lock = threading.Lock()

    def add(self, **deltas: int):
        with self._lock:
            for k, v in deltas.items():
                setattr(self, k, getattr(self, k) + v)

    def snapshot(self) -> dict:
        with self._lock:
            return {"points_in": self.points_in,
                    "points_out": self.points_out,
                    "signals": self.signals,
                    "parse_errors": self.parse_errors,
                    "dropped_no_host": self.dropped_no_host}


def _safe_db_name(raw: str) -> str:
    """Remote-supplied usernames/jobids become database names, and a
    persisted database name becomes a directory — a '/' (or a bare
    '.'/'..') in one would make the durable store reject every write to
    that scope forever.  Map the hostile characters instead of failing
    per-write."""
    name = raw.replace("/", "_").replace("\\", "_")
    return name if name not in ("", ".", "..") else name.replace(".", "_")


class MetricsRouter:
    """Tag-enriching, duplicating, publishing metrics router."""

    HOST_TAG = "hostname"

    def __init__(self, backend: TSDBServer, *, global_db: str = "global",
                 per_user_db: bool = False, per_job_db: bool = False,
                 require_host_tag: bool = True):
        self.backend = backend
        self.jobs = JobRegistry()
        self.global_db = global_db
        self.per_user_db = per_user_db
        self.per_job_db = per_job_db
        self.require_host_tag = require_host_tag
        self.stats = RouterStats()
        # the continuous analysis engine serving this router's data, when
        # one is attached (MonitoringStack wires it); the HTTP face uses it
        # for live job reports and engine stats
        self.analysis = None
        # the binary ingest plane serving this router, when one is
        # attached (repro.core.ingest.IngestServer wires itself here);
        # the HTTP face reads its shed/queue counters (/meta?what=ingest)
        self.ingest = None
        self._subs: list = []
        self._lock = threading.RLock()

    # -- pub-sub (ZeroMQ analogue) -------------------------------------------

    def subscribe(self, fn: Callable) -> Callable:
        """fn(kind, payload): kind in {"points", "job_start", "job_end"}."""
        with self._lock:
            self._subs.append(fn)
        return fn

    def unsubscribe(self, fn: Callable):
        with self._lock:
            if fn in self._subs:
                self._subs.remove(fn)

    @partial(annotate_function, name="lms.router.publish")
    def _publish(self, kind: str, payload):
        with self._lock:
            subs = list(self._subs)
        for fn in subs:
            try:
                fn(kind, payload)
            except Exception:       # a broken analyzer must not stall ingest
                pass

    # -- job signals -----------------------------------------------------------

    def job_start(self, job_id: str, user: str, hosts: list,
                  tags: Optional[dict] = None, ts: Optional[int] = None):
        job = self.jobs.start(job_id, user, hosts, tags, ts)
        self.stats.add(signals=1)
        # signals are stored as events -> dashboard annotations (paper §III.B)
        self.backend.write([Point(
            "job_event", {"jobid": job_id, "username": user},
            {"event": "start", "hosts": ",".join(hosts)},
            job.start_ns)], self.global_db)
        self._publish("job_start", job)
        return job

    def job_end(self, job_id: str, ts: Optional[int] = None):
        job = self.jobs.end(job_id, ts)
        self.stats.add(signals=1)
        if job is not None:
            self.backend.write([Point(
                "job_event", {"jobid": job_id, "username": job.user},
                {"event": "end"}, job.end_ns)], self.global_db)
            self._publish("job_end", job)
        return job

    # -- ingest ------------------------------------------------------------------

    def write_lines(self, data: str) -> dict:
        """HTTP body (line protocol, possibly batched) -> route.

        Partial-write semantics: every line that parses is written; every
        malformed line becomes a per-line error record instead of
        aborting its siblings.  Returns ``{"written": n, "errors":
        [{"line": lineno, "error": msg}, ...]}`` — the ``/write``
        response body.
        """
        points, errors = decode_batch_errors(data)
        if errors:
            self.stats.add(parse_errors=len(errors))
        if points:
            self.write(points)
        return {"written": len(points), "errors": errors}

    def write(self, points: Union[Point, Iterable[Point]]):
        if isinstance(points, Point):
            points = [points]
        elif not isinstance(points, (list, tuple)):
            points = list(points)
        with TraceAnnotation("lms.router.write", points=len(points)):
            self._write(points)

    def _write(self, points: list):
        # batch fast path: the tag-store lookup (a lock per call) is done
        # once per distinct host in the batch, not once per point
        host_tags: dict = {}
        enriched = []
        dropped = 0
        for p in points:
            host = p.tags.get(self.HOST_TAG)
            if host is None and self.require_host_tag:
                dropped += 1
                continue
            if p.timestamp is None:
                p = Point(p.measurement, p.tags, p.fields, now_ns())
            if host is None:
                job_tags = {}
            else:
                job_tags = host_tags.get(host)
                if job_tags is None:
                    job_tags = host_tags[host] = self.jobs.tags_for_host(host)
            enriched.append(p.with_tags(job_tags))
        self.stats.add(points_in=len(points), dropped_no_host=dropped,
                       points_out=len(enriched))
        if not enriched:
            return
        # the backend groups the batch per series — and, for a sharded
        # database, per shard — so this call contends only on the shards
        # the batch's hosts actually map to
        self.backend.write(enriched, self.global_db)
        # duplication into user/job scoped databases (paper §III.B)
        if self.per_user_db or self.per_job_db:
            by_db: dict = {}
            for p in enriched:
                if self.per_user_db and "username" in p.tags:
                    by_db.setdefault(
                        "user_" + _safe_db_name(p.tags["username"]),
                        []).append(p)
                if self.per_job_db and "jobid" in p.tags:
                    by_db.setdefault(
                        "job_" + _safe_db_name(p.tags["jobid"]),
                        []).append(p)
            for db, pts in by_db.items():
                self.backend.write(pts, db)
        self._publish("points", enriched)

    # -- columnar ingest (the binary plane, repro.core.ingest) ----------------

    def write_entries(self, entries: Iterable) -> int:
        """Columnar twin of :meth:`write`: route ``[(measurement, tags,
        times, {field: column}), ...]`` series entries (the binary wire
        form, == the WAL record form) without ever materializing
        per-point objects.

        Enrichment (job-tag merge, host-tag requirement) happens once per
        *series*, not per point; the enriched columns go to the backend
        through ``write_columns`` — and, on a persisted backend, into the
        WAL re-encoded with the same codec the wire used.  Returns the
        number of points routed.
        """
        host_tags: dict = {}
        by_cols: dict = {}
        tags_of: dict = {}
        n_in = n_out = dropped = 0
        for m, tags, times, cols in entries:
            n = len(times)
            if not n:
                continue
            n_in += n
            host = tags.get(self.HOST_TAG)
            if host is None and self.require_host_tag:
                dropped += n
                continue
            if host is None:
                job_tags = {}
            else:
                job_tags = host_tags.get(host)
                if job_tags is None:
                    job_tags = host_tags[host] = self.jobs.tags_for_host(host)
            if job_tags:
                tags = dict(tags)
                tags.update(job_tags)
            if any(times[i] > times[i + 1] for i in range(n - 1)):
                # defensive: write_columns requires ascending times per
                # series; a misbehaving client pays a sort, not corruption
                times, cols = Database.transpose_items(
                    [(t, {k: c[i] for k, c in cols.items()
                          if c[i] is not None})
                     for i, t in enumerate(times)])
            key = (m, _tags_key(tags))
            if key in by_cols:      # same series split across entries
                old_t, old_c = by_cols[key]
                by_cols[key] = Database.transpose_items(
                    [(t, {k: c[i] for k, c in old_c.items()
                          if c[i] is not None})
                     for i, t in enumerate(old_t)] +
                    [(t, {k: c[i] for k, c in cols.items()
                          if c[i] is not None})
                     for i, t in enumerate(times)])
            else:
                by_cols[key] = (times, cols)
                tags_of[key] = tags
            n_out += n
        self.stats.add(points_in=n_in, dropped_no_host=dropped,
                       points_out=n_out)
        if not by_cols:
            return 0
        self.backend.write_columns(by_cols, tags_of, self.global_db)
        if self.per_user_db or self.per_job_db:
            # duplication is per *series* here: a series' enriched tags
            # decide its scoped databases once, columns are shared
            by_db: dict = {}
            for key, tc in by_cols.items():
                tags = tags_of[key]
                scopes = []
                if self.per_user_db and "username" in tags:
                    scopes.append("user_" + _safe_db_name(tags["username"]))
                if self.per_job_db and "jobid" in tags:
                    scopes.append("job_" + _safe_db_name(tags["jobid"]))
                for scope in scopes:
                    cols_map, tmap = by_db.setdefault(scope, ({}, {}))
                    cols_map[key] = tc
                    tmap[key] = tags
            for db, (cols_map, tmap) in by_db.items():
                self.backend.write_columns(cols_map, tmap, db)
        self._publish("points", _LazyPoints(by_cols, tags_of))
        return n_out


class _LazyPoints:
    """Deferred Point materialization for the columnar publish path.

    Subscribers that only mark state dirty (``AnalysisEngine``) never
    iterate the payload, so the binary hot path pays nothing; a
    subscriber that really consumes points (``StreamAnalyzer``)
    materializes them on first iteration and the rows are cached for the
    next subscriber.
    """

    __slots__ = ("_by_cols", "_tags_of", "_pts")

    def __init__(self, by_cols: dict, tags_of: dict):
        self._by_cols = by_cols
        self._tags_of = tags_of
        self._pts = None

    def _materialize(self) -> list:
        if self._pts is None:
            pts = []
            for (m, key), (times, cols) in self._by_cols.items():
                tags = self._tags_of[key]
                for i, t in enumerate(times):
                    pts.append(Point(
                        m, tags,
                        {k: c[i] for k, c in cols.items()
                         if c[i] is not None}, t))
            self._pts = pts
        return self._pts

    def __iter__(self):
        return iter(self._materialize())

    def __len__(self):
        return sum(len(times) for times, _ in self._by_cols.values())
