"""HTTP face of the LMS (paper §III: "the communication protocol inside the
whole system (HTTP) is commonly available on all machines").

Server: mimics the InfluxDB 1.x write API plus the router's job-signal
endpoint, so any existing collector that can POST line protocol (Diamond,
curl cronjobs, Ganglia pull-proxies in the paper) integrates unchanged:

    POST /write?db=global           body: line protocol (batched);
                                    partial-write semantics — every line
                                    that parses is written, the response
                                    is ``{"written": n, "errors":
                                    [{"line", "error"}, ...]}`` (400 only
                                    when nothing parsed); bodies past the
                                    configurable cap (8 MiB) answer 413
    POST /job/start                 body: JSON {jobid, user, hosts, tags}
    POST /job/end                   body: JSON {jobid}
    POST /query/v2[?db=]            body: JSON {"spec": QuerySpec.to_dict(),
                                    "mode": "result"|"partials"} — the
                                    derived-metric query engine
                                    (``repro.core.query``).  mode=result
                                    executes the whole spec server-side
                                    (planned against this instance's
                                    tiers, served from the watermark-
                                    keyed cache) and returns the
                                    finalized groups; mode=partials
                                    returns the *mergeable* per-input
                                    WindowAgg partials — the federated
                                    pushdown wire format
                                    (``HttpQueryClient.query_partials``)
    GET  /ping
    GET  /query?db=&m=&field=&agg=  simple JSON query (dashboards/tests);
                                    &window_ns= adds windowed aggregation
                                    served from the rollup tiers;
                                    &t_min=/&t_max= bound the range;
                                    &rollups=auto|force|raw picks the path;
                                    &partials=1 returns *mergeable* partial
                                    aggregates (WindowAgg state) — the
                                    scatter half of cross-instance
                                    federation (``repro.core.shard``);
                                    &partials=rollup forces the rollup-tier
                                    windowed form (window_ns defaults to
                                    the finest tier, survives retention)
    GET  /meta?what=measurements    introspection (also what=fields&m=,
                                    what=tags&m=&tag=, what=persistence:
                                    WAL/snapshot stats of the durability
                                    layer, what=analysis: continuous-
                                    engine counters, and what=ingest:
                                    binary ingest plane shed/queue
                                    counters) for remote clients
    GET  /alerts?[db=][&jobid=][&rule=][&state=active|resolved|all]
                                    alert episodes reconstructed from the
                                    persisted ``analysis`` measurement
                                    (``repro.core.analysis``) — reads the
                                    DB, not engine memory, so it answers
                                    for recovered state and federates
                                    like any other series query
    GET  /jobs/<id>/report          per-job footprint report: live from
                                    the attached engine while the job
                                    runs, the persisted report afterwards
    GET  /dbs                       list databases
    POST /admin/snapshot[?db=]      snapshot + compact the WAL of one or
                                    all persisted databases
                                    (``repro.core.wal``)

The server is a ``ThreadingHTTPServer``: each request runs on its own
thread, so with a sharded backend (``TSDBServer(shards=N)``) concurrent
``/write`` POSTs from different hosts really do take different shard
locks, and ``/query`` scatter-gathers across the shards.

Clients: :class:`HttpSink` POSTs batched lines — the transport used by the
out-of-process ``usermetric_cli`` and by forward agents.
:class:`HttpQueryClient` is the read side: a Database-shaped query surface
over a remote LMS instance, usable directly or as a
``repro.core.shard.FederatedQuery`` backend (multi-router federation).
"""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.parse
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from jax.profiler import TraceAnnotation

from repro.core.analysis import Alert, load_alerts, load_job_report
from repro.core.line_protocol import Point, encode_batch
from repro.core.router import MetricsRouter
from repro.core.rollup import ROLLUP_AGGS, SCALAR_AGGS, quantile_of
from repro.core.shard import (decode_partials, encode_partials,
                              finalize_scalar, finalize_windowed)
from repro.core.tsdb import Series

_ROLLUPS_PARAM = {"auto": "auto", "force": True, "raw": False}
_UNSET = object()           # HttpQueryClient's not-yet-fetched sentinel


DEFAULT_MAX_BODY_BYTES = 8 * 1024 * 1024


class _PayloadTooLarge(Exception):
    """Request body exceeds the handler's cap (-> 413)."""


class LMSRequestHandler(BaseHTTPRequestHandler):
    router: MetricsRouter = None      # set by make_server
    max_body_bytes: int = DEFAULT_MAX_BODY_BYTES

    def log_message(self, fmt, *args):   # quiet
        pass

    def _send(self, code: int, payload: Optional[dict] = None):
        self.send_response(code)
        if code == 204:
            # RFC 9110 §6.4.1: a 204 response MUST NOT carry a body —
            # a body here desynchronizes keep-alive clients
            self.end_headers()
            return
        body = json.dumps(payload or {}).encode()
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _body(self) -> bytes:
        n = int(self.headers.get("Content-Length", 0))
        if n > self.max_body_bytes:
            # refuse before reading: an unbounded (or hostile)
            # Content-Length must not buffer gigabytes per request
            raise _PayloadTooLarge(
                f"request body of {n} bytes exceeds limit "
                f"{self.max_body_bytes}")
        return self.rfile.read(n) if n else b""

    def _known_db(self, name: str) -> bool:
        """True for databases that already exist (or the router's global
        scope, which may simply not have ingested yet)."""
        return name == self.router.global_db or \
            name in self.router.backend.databases()

    def do_GET(self):
        try:
            self._do_get()
        except Exception as e:                      # noqa: BLE001
            # bad query params (window_ns=abc, unknown agg) must produce a
            # 400, not a dropped connection
            self._send(400, {"error": str(e)})

    def _do_get(self):
        url = urllib.parse.urlparse(self.path)
        # keep_blank_values: a tag filter on an empty tag value (tag_k=)
        # must filter, not silently vanish
        q = dict(urllib.parse.parse_qsl(url.query, keep_blank_values=True))
        if url.path == "/ping":
            self._send(204)
        elif url.path == "/dbs":
            self._send(200, {"databases": self.router.backend.databases()})
        elif url.path == "/query":
            dbname = q.get("db", "global")
            if not self._known_db(dbname):
                # resolve-before-check would *register* the typo'd name
                # server-side (remote-fillable memory); see /query/v2
                self._send(404, {"error": f"unknown database {dbname!r}"})
                return
            db = self.router.backend.db(dbname)
            meas = q.get("m", "")
            fieldname = q.get("field", "value")
            tags = {k[4:]: v for k, v in q.items() if k.startswith("tag_")}
            t_min = int(q["t_min"]) if "t_min" in q else None
            t_max = int(q["t_max"]) if "t_max" in q else None
            window = int(q["window_ns"]) if "window_ns" in q else None
            rollups = q.get("rollups", "auto")
            if rollups not in _ROLLUPS_PARAM:
                raise ValueError(f"unknown rollups={rollups!r} "
                                 "(expected auto|force|raw)")
            use_rollups = _ROLLUPS_PARAM[rollups]
            if q.get("partials") == "rollup":
                # always windowed: window_ns=None means the finest tier,
                # exactly like the local rollup_window_partials default
                parts = db.rollup_window_partials(
                    meas, fieldname, tags=tags, t_min=t_min, t_max=t_max,
                    group_by_tag=q.get("group_by"), window_ns=window)
                self._send(200, {"windowed": True,
                                 "partials": encode_partials(parts, True)})
            elif q.get("partials") in ("1", "true"):
                parts = db.aggregate_partials(
                    meas, fieldname, tags=tags, t_min=t_min, t_max=t_max,
                    group_by_tag=q.get("group_by"), window_ns=window,
                    use_rollups=use_rollups)
                self._send(200, {
                    "windowed": window is not None,
                    "partials": encode_partials(parts, window is not None)})
            elif q.get("rollup_series") in ("1", "true"):
                series = db.rollup_series(meas, fieldname,
                                          agg=q.get("agg", "mean"),
                                          tags=tags, window_ns=window,
                                          t_min=t_min, t_max=t_max)
                self._send(200, {"series": [
                    {"tags": s.tags, "times": s.times,
                     "values": s.values.get(fieldname, [])}
                    for s in series]})
            elif "agg" in q or window is not None:
                out = db.aggregate(meas, fieldname, agg=q.get("agg", "mean"),
                                   tags=tags, t_min=t_min, t_max=t_max,
                                   group_by_tag=q.get("group_by"),
                                   window_ns=window,
                                   use_rollups=use_rollups)
                self._send(200, {"result": out})
            elif "field" in q:
                series = db.select(meas, [fieldname], tags, t_min, t_max)
                self._send(200, {"series": [
                    {"tags": s.tags, "times": s.times,
                     "values": s.values.get(fieldname, [])}
                    for s in series]})
            else:
                # no field param: all fields per series (events etc.)
                series = db.select(meas, None, tags, t_min, t_max)
                self._send(200, {"series": [
                    {"tags": s.tags, "times": s.times, "fields": s.values}
                    for s in series]})
        elif url.path == "/meta":
            what = q.get("what", "measurements")
            if what in ("query_cache", "data_version"):
                # checked BEFORE backend.db() resolves (and registers)
                # the name: these metas are hit programmatically per
                # cache check, and an unknown database must 404, not
                # mint a database (+ engine) per caller-supplied name
                name = q.get("db", "global")
                if not self._known_db(name):
                    self._send(404, {"error": f"unknown database "
                                              f"{name!r}"})
                elif what == "query_cache":
                    self._send(200, {"query_cache": self.router.backend
                                     .query_engine(name).cache_info()})
                else:
                    # the query-cache ingest watermark (repro.core.query):
                    # lets a *local* engine cache results over this remote
                    self._send(200, {"version": self.router.backend
                                     .db(name).data_version(
                                         q.get("m") or None)})
                return
            name = q.get("db", "global")
            if not self._known_db(name):
                self._send(404, {"error": f"unknown database {name!r}"})
                return
            db = self.router.backend.db(name)
            if what == "measurements":
                self._send(200, {"values": db.measurements()})
            elif what == "fields":
                self._send(200, {"values": db.field_keys(q.get("m", ""))})
            elif what == "tags":
                self._send(200, {"values": db.tag_values(q.get("m", ""),
                                                         q.get("tag", ""))})
            elif what == "rollup_config":
                cfg = getattr(db, "rollup_config", None)
                self._send(200, {"rollup_config": None if cfg is None else {
                    "tiers_ns": list(cfg.tiers_ns),
                    "max_age_ns": cfg.max_age_ns,
                    "sketch_fields": cfg.sketch_field_map(),
                    "sketch_rel_acc": cfg.sketch_rel_acc,
                    "sketch_max_bins": cfg.sketch_max_bins}})
            elif what == "rollups":
                # the aggregate family this instance serves: scalar aggs,
                # tier layout, and per-measurement quantile-sketch opt-in
                # (gamma/bin cap) — what HttpQueryClient validates a
                # requested agg against before paying a round trip
                cfg = getattr(db, "rollup_config", None)
                self._send(200, {"rollups": {
                    "aggs": list(ROLLUP_AGGS),
                    "quantiles": "pNN",
                    "tiers_ns": list(cfg.tiers_ns) if cfg else [],
                    "sketch": None if cfg is None else {
                        "fields": cfg.sketch_field_map(),
                        "rel_acc": cfg.sketch_rel_acc,
                        "gamma": cfg.sketch_gamma,
                        "max_bins": cfg.sketch_max_bins}}})
            elif what == "point_count":
                self._send(200, {"count": db.point_count()})
            elif what == "stored_points":
                self._send(200, {"count": db.stored_points()})
            elif what == "rollup_window_count":
                tier = int(q["tier_ns"]) if "tier_ns" in q else None
                tags = {k[4:]: v for k, v in q.items()
                        if k.startswith("tag_")}
                self._send(200, {"count": db.rollup_window_count(
                    q.get("m", ""), q.get("field", "value"), tags=tags,
                    tier_ns=tier)})
            elif what == "persistence":
                self._send(200,
                           {"persistence":
                            self.router.backend.persistence_stats()})
            elif what == "analysis":
                engine = self.router.analysis
                self._send(200, {"analysis": engine.engine_stats()
                                 if engine is not None else None})
            elif what == "ingest":
                # binary ingest plane shed/queue counters
                # (repro.core.ingest); null when no plane is attached
                ingest = self.router.ingest
                self._send(200, {"ingest": ingest.stats()
                                 if ingest is not None else None})
            elif what == "cold":
                # compressed cold tier (repro.core.coldstore): chunk /
                # compression / corruption counters plus the sealed time
                # span; null when no cold tier is configured
                view = getattr(db, "cold_view", None)
                view = view() if view is not None else None
                if view is None and getattr(db, "shards", None):
                    for sdb in db.shards:
                        view = sdb.cold_view()
                        if view is not None:
                            break
                rng = db.cold_time_range(q.get("m") or None) \
                    if hasattr(db, "cold_time_range") else None
                self._send(200, {"cold": None if view is None else dict(
                    view.stats(), time_range=list(rng) if rng else None)})
            elif what == "roofline":
                # the ROOFLINE perf group as this instance resolves it
                # (formula text a QuerySpec would embed), plus the latest
                # calibration point, if any ("_calib" marker convention)
                from repro.core.marker import roofline_peaks
                from repro.core.perf_groups import GROUPS
                grp = GROUPS["ROOFLINE"]
                peaks = roofline_peaks(db)
                self._send(200, {"roofline": {
                    "metrics": dict(sorted(grp.metrics)),
                    "calibrated": None if peaks is None else
                    {"peak_flops": peaks[0], "peak_bw": peaks[1]}}})
            else:
                self._send(400, {"error": f"unknown meta {what!r}"})
        elif url.path == "/alerts":
            dbname = q.get("db", "global")
            if not self._known_db(dbname):
                self._send(404, {"error": f"unknown database {dbname!r}"})
                return
            engine = self.router.analysis
            if engine is not None:
                engine.flush()      # read-your-writes for fresh ingest
            alerts = load_alerts(
                self.router.backend.db(dbname),
                jobid=q.get("jobid"), host=q.get("host"),
                rule=q.get("rule"), state=q.get("state", "all"))
            self._send(200, {"alerts": [a.to_dict() for a in alerts]})
        elif url.path.startswith("/jobs/") and url.path.endswith("/report"):
            jid = urllib.parse.unquote(url.path[len("/jobs/"):
                                                -len("/report")])
            engine = self.router.analysis
            if engine is not None:
                report = engine.flush().job_report(jid)
            else:
                dbname = q.get("db", "global")
                if not self._known_db(dbname):
                    self._send(404, {"error": f"unknown database "
                                              f"{dbname!r}"})
                    return
                report = load_job_report(
                    self.router.backend.db(dbname), jid)
            if report is None:
                self._send(404, {"error": f"no report for job {jid!r}"})
            else:
                self._send(200, {"report": report})
        else:
            self._send(404, {"error": "not found"})

    def do_POST(self):
        url = urllib.parse.urlparse(self.path)
        with TraceAnnotation("lms.http.post", path=url.path):
            self._do_post(url)

    def _do_post(self, url):
        try:
            body = self._body()
        except _PayloadTooLarge as e:
            # the oversized body was never read off the socket, so this
            # connection cannot be reused for a next request
            self.close_connection = True
            self._send(413, {"error": str(e),
                             "max_body_bytes": self.max_body_bytes})
            return
        try:
            if url.path == "/write":
                res = self.router.write_lines(body.decode())
                # partial-write semantics: 200 reports per-line errors
                # alongside the written count; only a batch where
                # *nothing* parsed is a 400
                code = 400 if res["errors"] and not res["written"] else 200
                self._send(code, res)
            elif url.path == "/job/start":
                d = json.loads(body)
                self.router.job_start(d["jobid"], d.get("user", "unknown"),
                                      d.get("hosts", []), d.get("tags"))
                self._send(200, {"ok": True})
            elif url.path == "/job/end":
                d = json.loads(body)
                self.router.job_end(d["jobid"])
                self._send(200, {"ok": True})
            elif url.path == "/query/v2":
                from repro.core.query import (QuerySpec,
                                              encode_plan_partials)
                q = dict(urllib.parse.parse_qsl(url.query,
                                                keep_blank_values=True))
                d = json.loads(body)
                spec = QuerySpec.from_dict(d["spec"])
                name = q.get("db", d.get("db", "global"))
                if not self._known_db(name):
                    # like /admin/snapshot: a caller-supplied name must
                    # not register a fresh database + engine per request
                    # (a remote-fillable leak)
                    self._send(404, {"error": f"unknown database "
                                              f"{name!r}"})
                    return
                engine = self.router.backend.query_engine(name)
                if d.get("mode") == "partials":
                    # the pushdown half: this instance plans against its
                    # own tiers/retention and ships mergeable partials
                    windowed = spec.window_ns is not None
                    collected = engine.collect(spec)
                    self._send(200, {
                        "windowed": windowed,
                        "inputs": encode_plan_partials(collected,
                                                       windowed)})
                else:
                    res = engine.query(spec)
                    self._send(200, {"result": res.to_dict(),
                                     "meta": res.meta})
            elif url.path == "/admin/snapshot":
                # operator trigger: snapshot + compact one database (the
                # ?db= param) or every persisted database
                q = dict(urllib.parse.parse_qsl(url.query,
                                                keep_blank_values=True))
                backend = self.router.backend
                name = q.get("db")
                if not backend.persistence_stats().get("enabled"):
                    self._send(409, {"error": "persistence not enabled "
                                              "(no persist_dir)"})
                elif name is not None and \
                        name not in backend.databases():
                    # a typo'd name must not silently register a fresh
                    # empty database (and its on-disk WAL directories)
                    self._send(404, {"error": f"unknown database "
                                              f"{name!r}"})
                else:
                    self._send(200, {"snapshots": backend.snapshot(name)})
            else:
                self._send(404, {"error": "not found"})
        except Exception as e:                      # noqa: BLE001
            self._send(400, {"error": str(e)})


class _LMSThreadingHTTPServer(ThreadingHTTPServer):
    # stdlib default backlog is 5: a burst of connects from a few dozen
    # concurrent agents overflows the accept queue and the kernel resets
    # the excess.  Match the binary ingest plane's listen(128).
    request_queue_size = 128


def make_server(router: MetricsRouter, host: str = "127.0.0.1",
                port: int = 0,
                max_body_bytes: int = DEFAULT_MAX_BODY_BYTES
                ) -> ThreadingHTTPServer:
    """Create (but do not start) the HTTP endpoint; port=0 picks a free one."""
    handler = type("BoundHandler", (LMSRequestHandler,),
                   {"router": router,
                    "max_body_bytes": int(max_body_bytes)})
    return _LMSThreadingHTTPServer((host, port), handler)


class LMSHttpServer:
    """Server lifecycle helper (background thread)."""

    def __init__(self, router: MetricsRouter, host: str = "127.0.0.1",
                 port: int = 0,
                 max_body_bytes: int = DEFAULT_MAX_BODY_BYTES):
        self.httpd = make_server(router, host, port, max_body_bytes)
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)

    @property
    def url(self) -> str:
        h, p = self.httpd.server_address[:2]
        return f"http://{h}:{p}"

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        # bounded: serve_forever returns promptly after shutdown(), but
        # a wedged handler must not hang teardown forever
        self._thread.join(timeout=2.0)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False


class HttpSink:
    """Batched line-protocol POST client (forward agent / CLI transport)."""

    def __init__(self, url: str, db: str = "global", timeout_s: float = 5.0):
        self.url = url.rstrip("/")
        self.db = db
        self.timeout_s = timeout_s

    def write(self, points):
        if isinstance(points, Point):
            points = [points]
        data = encode_batch(points).encode()
        req = urllib.request.Request(
            f"{self.url}/write?db={self.db}", data=data, method="POST",
            headers={"Content-Type": "text/plain"})
        with urllib.request.urlopen(req, timeout=self.timeout_s) as r:
            return r.status

    def job_start(self, jobid: str, user: str, hosts: list,
                  tags: Optional[dict] = None):
        self._post_json("/job/start", {"jobid": jobid, "user": user,
                                       "hosts": hosts, "tags": tags or {}})

    def job_end(self, jobid: str):
        self._post_json("/job/end", {"jobid": jobid})

    def _post_json(self, path: str, payload: dict):
        req = urllib.request.Request(
            self.url + path, data=json.dumps(payload).encode(),
            method="POST", headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=self.timeout_s) as r:
            return r.status


class HttpQueryClient:
    """Database-shaped query surface over a remote LMS ``/query`` endpoint.

    Exposes the partials protocol (``aggregate_partials`` /
    ``rollup_window_partials``) plus ``select``/``aggregate``/meta lookups,
    so an instance can stand in for a local ``Database`` inside a
    ``repro.core.shard.FederatedQuery`` — scatter-gather across multiple
    LMS router instances, merged with exact WindowAgg semantics.

    ``select`` fetches one field per request (the ``/query`` series form is
    single-field); pass ``fields=[name]``.
    """

    # FederatedQuery fans remote backends out concurrently (a federated
    # query costs ~the slowest instance, not the sum of round-trips)
    is_remote = True

    def __init__(self, url: str, db: str = "global", timeout_s: float = 5.0):
        self.url = url.rstrip("/")
        self.db = db
        self.timeout_s = timeout_s
        self._rollup_config = _UNSET
        self._rollups_meta = _UNSET

    @property
    def rollup_config(self):
        """The remote database's rollup layout (fetched once, cached) —
        lets rollup-aware readers (dashboards, rule evaluation) treat a
        remote instance exactly like a local database.  Sketch keys are
        read with ``.get`` so older servers (plain tiers/max-age form)
        still reconstruct."""
        if self._rollup_config is _UNSET:
            d = self._get("/meta", {"db": self.db,
                                    "what": "rollup_config"})["rollup_config"]
            from repro.core.rollup import RollupConfig
            self._rollup_config = None if d is None else RollupConfig(
                tiers_ns=tuple(d["tiers_ns"]), max_age_ns=d["max_age_ns"],
                sketch_fields=d.get("sketch_fields") or (),
                sketch_rel_acc=d.get("sketch_rel_acc", 0.01),
                sketch_max_bins=d.get("sketch_max_bins", 2048))
        return self._rollup_config

    def rollups_meta(self):
        """``/meta?what=rollups`` — the aggregate family the remote
        serves — fetched once and cached; None against an older server
        that predates the endpoint (validation is then skipped)."""
        if self._rollups_meta is _UNSET:
            try:
                self._rollups_meta = self._get(
                    "/meta", {"db": self.db, "what": "rollups"})["rollups"]
            except ValueError:
                self._rollups_meta = None
        return self._rollups_meta

    def _check_agg(self, agg: str, measurement: str, field: str):
        """Fail fast on an agg the remote cannot serve — a clear local
        ValueError instead of a remote 500/empty answer.  Scalar aggs are
        checked against the served list; quantiles additionally require
        the (measurement, field) to be sketch-enabled remotely."""
        meta = self.rollups_meta()
        if meta is None:            # pre-family server: no validation
            return
        if quantile_of(agg) is None:
            if agg not in meta.get("aggs", SCALAR_AGGS):
                raise ValueError(
                    f"agg {agg!r} is not served by {self.url} "
                    f"(served: {meta.get('aggs')})")
            return
        sketch = meta.get("sketch")
        fields = (sketch or {}).get("fields", {}).get(measurement)
        if fields != "*" and (not fields or field not in fields):
            raise ValueError(
                f"agg {agg!r} needs a quantile sketch on "
                f"{measurement}.{field} at {self.url}; the remote "
                f"sketches {((sketch or {}).get('fields')) or 'nothing'} "
                f"— opt in via RollupConfig(sketch_fields=...)")

    def _get(self, path: str, params: dict) -> dict:
        qs = urllib.parse.urlencode(
            {k: v for k, v in params.items() if v is not None})
        try:
            with urllib.request.urlopen(f"{self.url}{path}?{qs}",
                                        timeout=self.timeout_s) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            # surface the server's error (e.g. an unservable forced-rollup
            # window) as the same ValueError the local path raises
            try:
                msg = json.loads(e.read()).get("error", str(e))
            except Exception:               # noqa: BLE001
                msg = str(e)
            raise ValueError(f"remote query failed: {msg}") from None

    def _post(self, path: str, payload: dict) -> dict:
        req = urllib.request.Request(
            f"{self.url}{path}", data=json.dumps(payload).encode(),
            method="POST", headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=self.timeout_s) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                msg = json.loads(e.read()).get("error", str(e))
            except Exception:               # noqa: BLE001
                msg = str(e)
            raise ValueError(f"remote query failed: {msg}") from None

    # -- derived-metric query engine (repro.core.query) -----------------------

    def query_partials(self, spec) -> dict:
        """Whole-spec pushdown: one ``POST /query/v2`` carrying the spec;
        the remote plans against its own tiers/retention and returns
        *mergeable* per-input ``WindowAgg`` partials — no raw series
        cross the wire.  This is what a ``FederatedQuery`` /
        ``QueryEngine`` calls when this client is a backend."""
        from repro.core.query import decode_plan_partials
        resp = self._post("/query/v2", {"db": self.db, "mode": "partials",
                                        "spec": spec.to_dict()})
        return decode_plan_partials(resp["inputs"], resp["windowed"])

    def query(self, spec):
        """Execute a full spec remotely (``mode=result``): planned,
        cached and finalized server-side — repeated dashboard-shape
        queries hit the remote's watermark-keyed cache."""
        from repro.core.query import QueryResult
        resp = self._post("/query/v2", {"db": self.db, "mode": "result",
                                        "spec": spec.to_dict()})
        return QueryResult.from_dict(resp["result"], resp.get("meta"))

    def data_version(self, measurement=None) -> int:
        """The remote ingest watermark — lets a local engine cache
        results over this remote (one cheap ``/meta`` round trip per
        cache check instead of re-running the query)."""
        return self._get("/meta", {"db": self.db, "what": "data_version",
                                   "m": measurement})["version"]

    def _query_params(self, measurement, field, tags, t_min, t_max,
                      group_by_tag, window_ns, use_rollups="auto") -> dict:
        params = {"db": self.db, "m": measurement, "field": field,
                  "t_min": t_min, "t_max": t_max, "group_by": group_by_tag,
                  "window_ns": window_ns}
        if use_rollups != "auto":
            params["rollups"] = "force" if use_rollups is True else "raw"
        for k, v in (tags or {}).items():
            params[f"tag_{k}"] = v
        return params

    def aggregate_partials(self, measurement: str, field: str, *,
                           tags: Optional[dict] = None,
                           t_min: Optional[int] = None,
                           t_max: Optional[int] = None,
                           group_by_tag: Optional[str] = None,
                           window_ns: Optional[int] = None,
                           use_rollups: object = "auto") -> dict:
        params = self._query_params(measurement, field, tags, t_min, t_max,
                                    group_by_tag, window_ns, use_rollups)
        params["partials"] = "1"
        resp = self._get("/query", params)
        return decode_partials(resp["partials"], resp["windowed"])

    def rollup_window_partials(self, measurement: str, field: str, *,
                               tags: Optional[dict] = None,
                               t_min: Optional[int] = None,
                               t_max: Optional[int] = None,
                               group_by_tag: Optional[str] = None,
                               window_ns: Optional[int] = None) -> dict:
        params = self._query_params(measurement, field, tags, t_min, t_max,
                                    group_by_tag, window_ns)
        params["partials"] = "rollup"
        resp = self._get("/query", params)
        return decode_partials(resp["partials"], resp["windowed"])

    def aggregate(self, measurement: str, field: str, *, agg: str = "mean",
                  tags: Optional[dict] = None, t_min: Optional[int] = None,
                  t_max: Optional[int] = None,
                  group_by_tag: Optional[str] = None,
                  window_ns: Optional[int] = None,
                  use_rollups: object = "auto"):
        self._check_agg(agg, measurement, field)
        merged = self.aggregate_partials(
            measurement, field, tags=tags, t_min=t_min, t_max=t_max,
            group_by_tag=group_by_tag, window_ns=window_ns,
            use_rollups=use_rollups)
        if window_ns is None:
            return finalize_scalar(merged, agg)
        return finalize_windowed(merged, agg)

    def select(self, measurement: str, fields: Optional[list] = None,
               tags: Optional[dict] = None, t_min: Optional[int] = None,
               t_max: Optional[int] = None) -> list:
        if fields is not None and len(fields) != 1:
            raise ValueError("HttpQueryClient.select takes one field per "
                             f"request (or None for all), got {fields!r}")
        fieldname = fields[0] if fields else None
        params = self._query_params(measurement, fieldname, tags, t_min,
                                    t_max, None, None)
        resp = self._get("/query", params)
        if fieldname is None:       # all-fields form (events etc.)
            return [Series(measurement, s["tags"], s["times"], s["fields"])
                    for s in resp["series"]]
        return [Series(measurement, s["tags"], s["times"],
                       {fieldname: s["values"]})
                for s in resp["series"]]

    def rollup_aggregate(self, measurement: str, field: str, *,
                         agg: str = "mean", tags: Optional[dict] = None,
                         t_min: Optional[int] = None,
                         t_max: Optional[int] = None,
                         group_by_tag: Optional[str] = None,
                         window_ns: Optional[int] = None):
        self._check_agg(agg, measurement, field)
        return finalize_windowed(self.rollup_window_partials(
            measurement, field, tags=tags, t_min=t_min, t_max=t_max,
            group_by_tag=group_by_tag, window_ns=window_ns), agg)

    def rollup_series(self, measurement: str, field: str, *,
                      agg: str = "mean", tags: Optional[dict] = None,
                      window_ns: Optional[int] = None,
                      t_min: Optional[int] = None,
                      t_max: Optional[int] = None) -> list:
        self._check_agg(agg, measurement, field)
        params = self._query_params(measurement, field, tags, t_min, t_max,
                                    None, window_ns)
        params["rollup_series"] = "1"
        params["agg"] = agg
        resp = self._get("/query", params)
        return [Series(measurement, s["tags"], s["times"],
                       {field: s["values"]})
                for s in resp["series"]]

    # -- analysis surface (repro.core.analysis) ------------------------------

    def alerts(self, *, jobid: Optional[str] = None,
               rule: Optional[str] = None, host: Optional[str] = None,
               state: str = "all") -> list:
        """Alert episodes from the remote instance's persisted ``analysis``
        measurement, as :class:`repro.core.analysis.Alert` objects —
        concatenable across instances exactly like ``load_alerts`` over a
        federated view."""
        params = {"db": self.db, "jobid": jobid, "rule": rule,
                  "host": host, "state": state}
        return [Alert.from_dict(d)
                for d in self._get("/alerts", params)["alerts"]]

    def job_report(self, jobid: str) -> Optional[dict]:
        """The remote instance's footprint report for one job, or None
        when it has none (404)."""
        try:
            return self._get(
                f"/jobs/{urllib.parse.quote(jobid, safe='')}/report",
                {"db": self.db})["report"]
        except ValueError:
            return None

    def rollup_window_count(self, measurement: str, field: str, *,
                            tags: Optional[dict] = None,
                            tier_ns: Optional[int] = None) -> int:
        params = {"db": self.db, "what": "rollup_window_count",
                  "m": measurement, "field": field, "tier_ns": tier_ns}
        for k, v in (tags or {}).items():
            params[f"tag_{k}"] = v
        return self._get("/meta", params)["count"]

    def point_count(self) -> int:
        return self._get("/meta", {"db": self.db,
                                   "what": "point_count"})["count"]

    def stored_points(self) -> int:
        return self._get("/meta", {"db": self.db,
                                   "what": "stored_points"})["count"]

    def measurements(self) -> list:
        return self._get("/meta", {"db": self.db,
                                   "what": "measurements"})["values"]

    def field_keys(self, measurement: str) -> list:
        return self._get("/meta", {"db": self.db, "what": "fields",
                                   "m": measurement})["values"]

    def tag_values(self, measurement: str, tag: str) -> list:
        return self._get("/meta", {"db": self.db, "what": "tags",
                                   "m": measurement, "tag": tag})["values"]
