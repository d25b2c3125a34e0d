"""LIKWID Monitoring Stack (LMS), TPU-native — the paper's contribution.

``MonitoringStack`` wires the components of paper Fig. 1 together for the
common case (in-process stack inside a training/serving job); every
component also works standalone, which is the paper's headline design goal
("components can be used as a complete stack, standalone or in parts").
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Optional

from repro.core.analysis import (
    ANALYSIS_MEASUREMENT, Alert, AnalysisEngine, DEFAULT_TREE, Finding,
    StreamAnalyzer, ThresholdRule,
    classify_job, default_rules, evaluate_rules_on_db, load_alerts,
    load_job_report)
from repro.core.dashboard import DashboardAgent
from repro.core.host_agent import HostAgent
from repro.core.httpd import HttpSink, LMSHttpServer
from repro.core.jobs import JobInfo, JobRegistry
from repro.core.line_protocol import (Point, decode_batch, decode_line,
                                      encode_batch, encode_point, now_ns)
from repro.core.marker import (CALIB_REGION, MARKER_MEASUREMENT,
                               MarkerSession, calibrate, low_roofline_rule,
                               register_roofline_group, roofline_group_text,
                               roofline_peaks, roofline_spec)
from repro.core.perf_groups import (GROUPS, HBM_BW, ICI_BW, PEAK_FLOPS,
                                    CompiledFormula, PerfGroup,
                                    compile_formula, derive_all,
                                    formula_for, parse_group,
                                    register_group)
from repro.core.query import (QueryEngine, QueryResult, QuerySpec,
                              derived_rollup_series, make_plan)
from repro.core.fingerprint import (FINGERPRINT_KIND, fingerprint_outliers,
                                    fingerprint_point, job_fingerprint,
                                    load_fingerprints)
from repro.core.rollup import (DEFAULT_TIERS_NS, QUANTILE_AGGS, ROLLUP_AGGS,
                               QuantileSketch, RollupConfig, SeriesRollups,
                               SketchAgg, WindowAgg, known_agg, quantile_of)
from repro.core.coldstore import ColdStore, ColdView
from repro.core.httpd import HttpQueryClient
from repro.core.ingest import BinarySink, IngestServer
from repro.core.router import MetricsRouter
from repro.core.shard import FederatedQuery, ShardedDatabase, shard_index
from repro.core.tsdb import Database, TSDBServer
from repro.core.usermetric import UserMetric
from repro.core.wal import DurableStore, SegmentedWal, import_legacy_jsonl

__all__ = [
    "ANALYSIS_MEASUREMENT", "Alert", "AnalysisEngine", "BinarySink",
    "CALIB_REGION", "MARKER_MEASUREMENT", "MarkerSession",
    "ColdStore", "ColdView", "CompiledFormula",
    "DEFAULT_TIERS_NS", "DEFAULT_TREE", "Database", "DashboardAgent",
    "DurableStore", "FederatedQuery", "Finding", "GROUPS", "HBM_BW",
    "HostAgent", "IngestServer", "SegmentedWal", "import_legacy_jsonl",
    "HttpQueryClient", "HttpSink", "ICI_BW", "JobInfo", "JobRegistry",
    "LMSHttpServer", "MetricsRouter", "MonitoringStack", "PEAK_FLOPS",
    "PerfGroup", "Point", "QueryEngine", "QueryResult", "QuerySpec",
    "FINGERPRINT_KIND", "QUANTILE_AGGS", "QuantileSketch",
    "ROLLUP_AGGS", "RollupConfig",
    "SeriesRollups", "SketchAgg",
    "ShardedDatabase", "StreamAnalyzer", "TSDBServer", "ThresholdRule",
    "UserMetric", "WindowAgg", "calibrate", "classify_job",
    "compile_formula",
    "decode_batch", "decode_line", "default_rules", "derive_all",
    "derived_rollup_series", "encode_batch", "encode_point",
    "evaluate_rules_on_db", "fingerprint_outliers", "fingerprint_point",
    "formula_for", "job_fingerprint", "known_agg", "load_alerts",
    "load_fingerprints", "load_job_report", "low_roofline_rule",
    "make_plan", "now_ns",
    "parse_group", "quantile_of", "register_group",
    "register_roofline_group", "roofline_group_text", "roofline_peaks",
    "roofline_spec", "shard_index",
]


class MonitoringStack:
    """The full Fig. 1 stack, in-process: TSDB + router + agents + analysis.

    Usage::

        stack = MonitoringStack.inprocess(out_dir="runs/lms")
        with stack.job("train-1", user="alice", hosts=hosts,
                       tags={"arch": "lms-demo"}) as job:
            um = stack.usermetric(host=hosts[0])
            agent = stack.host_agent(hosts[0])
            ... per step: agent.collect_step(...), um.metric(...)
        stack.dashboards.write_dashboard(job)
    """

    def __init__(self, *, per_job_db: bool = True, per_user_db: bool = False,
                 rules: Optional[list] = None, out_dir: str = "lms_out",
                 persist_dir: Optional[str] = None, fsync: str = "batch",
                 recover: bool = True,
                 serve_http: bool = False, serve_ingest: bool = False,
                 shards: int = 1, cold_tier: bool = False,
                 rollup_config: Optional[RollupConfig] = RollupConfig()):
        # cold_tier=True (requires persist_dir): retention seals expired
        # raw history into compressed immutable chunks instead of
        # dropping it — months of raw data at a fraction of the bytes,
        # still answering every query (repro.core.coldstore)
        # rollup_config: e.g. RollupConfig(sketch_fields={"hpm": "*"})
        # opts fields into quantile sketches so p50/p95/p99 are served
        # from the rollup tiers; the default carries no sketches
        self.backend = TSDBServer(persist_dir=persist_dir, shards=shards,
                                  fsync=fsync, cold=cold_tier,
                                  rollup_config=rollup_config)
        # crash-safe durability: a restarted stack keeps serving the job
        # histories it had already collected (repro.core.wal)
        self.recovery_stats = self.backend.load_persisted() \
            if (persist_dir and recover) else {}
        self.router = MetricsRouter(self.backend, per_job_db=per_job_db,
                                    per_user_db=per_user_db)
        self._finding_cbs = []
        # continuous analysis engine (repro.core.analysis): evaluates the
        # rollup windows on a background thread (O(1) on the ingest path),
        # persists alert lifecycle + job reports into the TSDB, and closes
        # a job's state through the registry end hook
        self.analysis = AnalysisEngine(
            rules if rules is not None else default_rules(),
            on_finding=self._on_finding, backend=self.backend,
            db_name=self.router.global_db)
        self.analyzer = self.analysis       # pre-engine name, kept working
        self.router.subscribe(self.analysis)
        self.router.analysis = self.analysis
        self.router.jobs.on_end(self.analysis.on_job_end)
        # restart: recovered analysis series bring the alert state back —
        # open episodes continue instead of re-firing
        self.analysis_recovery = self.analysis.recover() \
            if (persist_dir and recover) else {}
        self.dashboards = DashboardAgent(self.backend, out_dir=out_dir)
        self.http: Optional[LMSHttpServer] = None
        if serve_http:
            self.http = LMSHttpServer(self.router).start()
        # binary ingest plane (repro.core.ingest), served alongside the
        # HTTP endpoint: persistent sockets, backpressure, shed frames
        self.ingest: Optional[IngestServer] = None
        if serve_ingest:
            self.ingest = IngestServer(self.router).start()

    @classmethod
    def inprocess(cls, **kw) -> "MonitoringStack":
        return cls(**kw)

    # -- findings fan-out ------------------------------------------------------

    def on_finding(self, cb):
        self._finding_cbs.append(cb)
        return cb

    def _on_finding(self, f: Finding):
        for cb in self._finding_cbs:
            try:
                cb(f)
            except Exception:
                pass

    # -- components --------------------------------------------------------------

    def usermetric(self, host: Optional[str] = None, **tags) -> UserMetric:
        return UserMetric(self.router, hostname=host,
                          default_tags=tags or None)

    def host_agent(self, hostname: str, **consts) -> HostAgent:
        return HostAgent(self.router, hostname, consts or None)

    def marker_session(self, host: Optional[str] = None,
                       **tags) -> MarkerSession:
        """A :class:`MarkerSession` (repro.core.marker) emitting through a
        fresh UserMetric into this stack — region points arrive as the
        ``marker`` measurement and get the live job's tags from the
        router like any other metric."""
        return self.usermetric(host=host, **tags).markers

    # -- job lifecycle --------------------------------------------------------------

    def job(self, job_id: Optional[str] = None, *, user: str = "user",
            hosts: Optional[list] = None, tags: Optional[dict] = None):
        stack = self
        job_id = job_id or uuid.uuid4().hex[:8]
        hosts = hosts or ["host0"]

        class _JobCtx:
            def __enter__(self):
                self.info = stack.router.job_start(job_id, user, hosts, tags)
                return self.info

            def __exit__(self, exc_type, exc, tb):
                stack.router.job_end(job_id)
                return False
        return _JobCtx()

    def findings(self) -> list:
        """Every fired alert (active + resolved), after a synchronous
        evaluation sweep — read-your-writes for callers that just
        ingested."""
        self.analysis.flush()
        return list(self.analysis.findings)

    def binary_sink(self, db: str = "global", **kw) -> "BinarySink":
        """A client for this stack's binary ingest plane (requires
        ``serve_ingest=True``); pass ``fallback=HttpSink(...)`` to add
        the HTTP line-path fallback."""
        if self.ingest is None:
            raise RuntimeError("stack was built without serve_ingest=True")
        return BinarySink(self.ingest.host, self.ingest.port, db=db, **kw)

    def close(self):
        self.analysis.close()
        if self.http:
            self.http.stop()
        if self.ingest:
            self.ingest.stop()
        self.backend.close()
