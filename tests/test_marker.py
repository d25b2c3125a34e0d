"""Marker-region instrumentation + per-region rooflines
(``repro.core.marker``, ROADMAP item 3).

Contracts under test:

* **Region accounting** — nested regions get exact inclusive/exclusive
  wall time (fake clock), mismatched stops raise, leaked children are
  force-closed into their own accumulators, the context manager stops on
  exception, region stacks are thread-local while totals merge.
* **Emission** — deltas since last flush, one shared timestamp per flush,
  ``UserMetric.region`` reroutes through the session (exact reentrant
  call counts) while still emitting the legacy ``<name>_time_s`` field.
* **Roofline query side** — :func:`roofline_spec` answers byte-identically
  local, sharded and HTTP-federated, keeps answering from rollups after
  raw retention, and calibration points bake measured peaks into specs
  built afterwards.
* **Analysis/dashboard wiring** — the ``low_roofline`` derived rule fires
  only on counter-instrumented regions; the dashboard grows a Roofline
  row whose panel embeds the same spec.
* Satellite regression: ``compiled_step_constants`` threads real
  collective operand/wire bytes from the HLO walk into the HPM step
  constants (the seed hardcoded ``collective_bytes=0.0``).
"""

import threading
import time

import pytest

from repro.core import MonitoringStack
from repro.core.analysis import default_rules, evaluate_rules_on_db
from repro.core.httpd import HttpQueryClient, LMSHttpServer
from repro.core.line_protocol import Point
from repro.core.marker import (CALIB_REGION, MARKER_MEASUREMENT,
                               MarkerSession, calibrate, low_roofline_rule,
                               register_roofline_group, roofline_peaks,
                               roofline_spec)
from repro.core.perf_groups import roofline_group_text
from repro.core.query import QueryEngine, QuerySpec
from repro.core.router import MetricsRouter
from repro.core.shard import FederatedQuery, ShardedDatabase
from repro.core.tsdb import Database, TSDBServer
from repro.core.usermetric import UserMetric

S = 1_000_000_000


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, s):
        self.t += s


class CapturingEmitter:
    """UserMetric-shaped: records every metric() call."""

    def __init__(self):
        self.points = []

    def metric(self, name, fields, tags=None, ts=None):
        self.points.append((name, dict(fields), dict(tags or {}), ts))


# --------------------------------------------------------------------------
# region accounting
# --------------------------------------------------------------------------


def test_nested_inclusive_exclusive_time():
    clk = FakeClock()
    ms = MarkerSession(clock=clk)
    ms.start_region("outer")
    clk.tick(1.0)
    with ms.region("inner", counters={"flops": 5.0}):
        clk.tick(2.0)
    clk.tick(0.5)
    ms.stop_region("outer")
    snap = ms.snapshot()
    assert snap["outer"]["time_s"] == pytest.approx(3.5)
    assert snap["outer"]["excl_time_s"] == pytest.approx(1.5)
    assert snap["inner"]["time_s"] == pytest.approx(2.0)
    assert snap["inner"]["excl_time_s"] == pytest.approx(2.0)
    assert snap["inner"]["flops"] == 5.0
    assert snap["outer"]["calls"] == snap["inner"]["calls"] == 1.0


def test_mismatched_or_empty_stop_raises():
    ms = MarkerSession()
    with pytest.raises(ValueError):
        ms.stop_region("nope")
    ms.start_region("a")
    ms.start_region("b")
    with pytest.raises(ValueError):
        ms.stop_region("a")         # innermost is "b"
    assert ms.open_regions() == ["a", "b"]


def test_leaked_children_force_closed():
    clk = FakeClock()
    ms = MarkerSession(clock=clk)
    with ms.region("outer"):
        ms.start_region("leaked")   # never stopped by the caller
        clk.tick(1.0)
    snap = ms.snapshot()
    assert snap["leaked"]["time_s"] == pytest.approx(1.0)
    assert snap["outer"]["excl_time_s"] == pytest.approx(0.0)
    assert ms.open_regions() == []


def test_region_stops_on_exception():
    clk = FakeClock()
    ms = MarkerSession(clock=clk)
    with pytest.raises(RuntimeError):
        with ms.region("body"):
            clk.tick(1.0)
            raise RuntimeError("boom")
    assert ms.open_regions() == []
    assert ms.snapshot()["body"]["time_s"] == pytest.approx(1.0)


def test_region_add_counters():
    ms = MarkerSession()
    with ms.region("r", counters={"bytes": 1.0}) as r:
        r.add(bytes=2.0, tokens=3.0)
    acc = ms.snapshot()["r"]
    assert acc["bytes"] == 3.0 and acc["tokens"] == 3.0


def test_record_external_timing():
    ms = MarkerSession()
    ms.record("wait", 0.25, counters={"bytes": 4.0})
    ms.record("wait", 0.75)
    acc = ms.snapshot()["wait"]
    assert acc["calls"] == 2.0
    assert acc["time_s"] == pytest.approx(1.0)
    assert acc["excl_time_s"] == pytest.approx(1.0)
    assert acc["bytes"] == 4.0


def test_thread_local_stacks_shared_totals():
    ms = MarkerSession()
    barrier = threading.Barrier(2)
    errs = []

    def worker(name):
        try:
            with ms.region("shared"):
                with ms.region(f"only_{name}"):
                    barrier.wait(timeout=5)
                    # both threads inside: my stack sees MY nesting only
                    assert ms.open_regions() == ["shared", f"only_{name}"]
                    barrier.wait(timeout=5)
        except Exception as e:          # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(n,)) for n in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    snap = ms.snapshot()
    assert snap["shared"]["calls"] == 2.0       # totals merged
    assert snap["only_a"]["calls"] == snap["only_b"]["calls"] == 1.0


# --------------------------------------------------------------------------
# emission
# --------------------------------------------------------------------------


def test_flush_emits_deltas_with_shared_ts():
    em = CapturingEmitter()
    clk = FakeClock()
    ms = MarkerSession(em, clock=clk, emit_interval_s=1e9)
    with ms.region("a"):
        clk.tick(1.0)
    out = ms.flush(ts=7)
    assert set(out) == {"a"}
    with ms.region("a"):
        clk.tick(2.0)
    out2 = ms.flush(ts=9)
    # second flush carries only the delta since the first
    assert out2["a"]["time_s"] == pytest.approx(2.0)
    assert out2["a"]["calls"] == 1.0
    assert ms.flush() == {}                     # drained
    assert [p[3] for p in em.points] == [7, 9]
    assert all(p[0] == MARKER_MEASUREMENT for p in em.points)
    assert em.points[0][2] == {"region": "a"}
    # lifetime totals are not reset by flush
    assert ms.snapshot()["a"]["time_s"] == pytest.approx(3.0)


def test_periodic_emission_on_interval():
    em = CapturingEmitter()
    clk = FakeClock()
    ms = MarkerSession(em, clock=clk, emit_interval_s=5.0)
    with ms.region("r"):
        clk.tick(1.0)
    assert em.points == []                      # interval not reached
    clk.tick(10.0)
    with ms.region("r"):
        clk.tick(1.0)
    assert len(em.points) == 1                  # auto-flushed on stop


def test_usermetric_region_reentrant_and_legacy():
    pts = []

    class Sink:
        def write(self, batch):
            pts.extend(batch)

    um = UserMetric(Sink(), hostname="h0", batch_size=10_000)

    def phase():
        with um.region("phase"):
            time.sleep(0.001)

    def outer():
        with um.region("phase"):        # reentrant: phase inside phase
            phase()

    outer()
    phase()
    um.flush()
    marker = [p for p in pts if p.measurement == MARKER_MEASUREMENT]
    legacy = [p for p in pts if p.measurement == "phase_time_s"]
    # the old implementation emitted only per-call durations; the marker
    # path counts the 3 calls exactly (2 reentrant + 1 plain)
    assert sum(p.fields["calls"] for p in marker) == 3.0
    assert len(legacy) == 3                     # backward-compat field
    total = sum(p.fields["time_s"] for p in marker)
    assert total >= sum(p.fields["value"] for p in legacy) - 1e-9


# --------------------------------------------------------------------------
# roofline query side: parity + retention + calibration
# --------------------------------------------------------------------------


def _marker_points(n=90, regions=("fwd", "opt"), hosts=2):
    """Deterministic marker deltas (binary fractions) across regions/hosts;
    region ``opt`` carries no flops/bytes counters."""
    pts = []
    for i in range(n):
        for h in range(hosts):
            tags_base = {"hostname": f"h{h}", "jobid": "j0"}
            pts.append(Point(MARKER_MEASUREMENT,
                             {**tags_base, "region": "fwd"},
                             {"time_s": 0.25 + 0.125 * (i % 2),
                              "calls": 2.0,
                              "flops": float((h + 1) * 2 ** 30),
                              "bytes": float((h + 1) * 2 ** 20)},
                             i * S))
            pts.append(Point(MARKER_MEASUREMENT,
                             {**tags_base, "region": "opt"},
                             {"time_s": 0.0625, "calls": 2.0}, i * S))
    return pts


def _write(db, pts, batch=64):
    for i in range(0, len(pts), batch):
        db.write(pts[i:i + batch])


def test_roofline_spec_local_sharded_federated_identical():
    pts = _marker_points()
    spec = roofline_spec("j0")
    single = Database("one")
    _write(single, pts)
    a = QueryEngine(single).query(spec)
    # per-region groups with derived roofline columns; the counter-less
    # region yields no derived windows but keeps its time/calls columns
    assert set(a.groups) == {"fwd", "opt"}
    assert a.groups["fwd"]["roofline_frac"]["values"]
    assert "roofline_frac" not in a.groups["opt"]
    assert a.groups["opt"]["time_s"]["values"]
    for shards in (2, 4, 7):
        sharded = ShardedDatabase("many", shards=shards)
        _write(sharded, pts)
        b = QueryEngine(sharded).query(spec)
        assert a.to_json() == b.to_json(), shards
    routers = [MetricsRouter(TSDBServer(shards=2)) for _ in range(2)]
    for p in pts:       # each host's series lives on exactly one instance
        routers[int(p.tags["hostname"][1:]) % 2].backend.write([p])
    with LMSHttpServer(routers[0]) as sa, LMSHttpServer(routers[1]) as sb:
        fed = FederatedQuery([HttpQueryClient(sa.url),
                              HttpQueryClient(sb.url)])
        c = QueryEngine(fed).query(spec)
        assert a.to_json() == c.to_json()


def test_roofline_survives_raw_retention():
    pts = _marker_points()
    db = Database("ret")
    _write(db, pts)
    spec = roofline_spec("j0")          # 10s window nests into 10s tier
    before = QueryEngine(db).query(spec)
    dropped = db.enforce_retention(max_points_per_series=1)
    assert dropped["raw_points_dropped"] > 0
    after = QueryEngine(db).query(spec)
    assert before.to_json() == after.to_json()


def test_calibration_points_and_group_registration():
    try:
        db = Database("cal")
        assert roofline_peaks(db) is None
        um = UserMetric(db, hostname="h0", batch_size=10_000)
        calibrate(um, peak_flops=1e12, peak_bw=1e11, ts=5 * S)
        calibrate(um, peak_flops=2e12, peak_bw=2e11, ts=9 * S)
        assert roofline_peaks(db) == (2e12, 2e11)   # latest point wins
        # specs built after calibration embed the peaks as literals — the
        # formula text (not remote state) carries them to any federation
        frac = dict(roofline_spec().metrics)["roofline_frac"]
        assert "2000000000000.0" in frac and "200000000000.0" in frac
        # uncalibrated text references the HW constants instead
        assert "PEAK_FLOPS" in roofline_group_text()
    finally:
        register_roofline_group()       # restore defaults for other tests
    assert "PEAK_FLOPS" in dict(roofline_spec().metrics)["roofline_frac"]


def test_low_roofline_rule_only_fires_on_instrumented_regions():
    db = Database("rule")
    pts = []
    for i in range(100):
        base = {"hostname": "h0", "jobid": "j0"}
        # instrumented region sustained at ~1e-5 of attainable
        pts.append(Point(MARKER_MEASUREMENT, {**base, "region": "slow"},
                         {"time_s": 1.0, "calls": 1.0, "flops": 1e9,
                          "bytes": 1e9}, i * S))
        # un-instrumented region: no counters -> no derived windows ->
        # the "<" rule must never treat it as violating
        pts.append(Point(MARKER_MEASUREMENT, {**base, "region": "plain"},
                         {"time_s": 1.0, "calls": 1.0}, i * S))
    _write(db, pts)
    rule = low_roofline_rule(0.05, min_duration_s=30.0)
    findings = evaluate_rules_on_db(db, [rule], group_by_tag="region")
    assert findings, "sustained low-roofline region must fire"
    assert {f.host for f in findings} == {"slow"}
    assert all(f.rule == "low_roofline" for f in findings)
    # wired into the default rule set
    assert any(r.name == "low_roofline" and r.expr
               for r in default_rules())


# --------------------------------------------------------------------------
# stack wiring: dashboard row + /meta endpoint + end-to-end emission
# --------------------------------------------------------------------------


def test_stack_markers_dashboard_and_meta(tmp_path):
    st = MonitoringStack.inprocess(out_dir=str(tmp_path))
    try:
        with st.job("mj", user="u", hosts=["h0"]) as job:
            mk = st.marker_session(host="h0")
            with mk.region("phase:a", counters={"flops": 2.0 ** 40,
                                                "bytes": 2.0 ** 30}):
                time.sleep(0.002)
            mk.flush()
        db = st.backend.db("global")
        # router enriched the points with the live job's tags
        series = db.select(MARKER_MEASUREMENT, None, {"region": "phase:a"})
        assert series and series[0].tags["jobid"] == "mj"
        # dashboard: Roofline row embeds the canonical /query/v2 spec,
        # marker is excluded from the generic app rows
        dash = st.dashboards.build_dashboard(job)
        rows = {r["title"]: r for r in dash["dashboard"]["rows"]}
        assert "Roofline" in rows and "app:marker" not in rows
        tgt = rows["Roofline"]["panels"][0]["targets"][0]
        assert tgt["query_v2"] == roofline_spec("mj").to_dict()
        html = st.dashboards.render_html(job, dash)
        assert "phase:a" in html and "roofline frac" in html
        # the panel's spec IS executable via the engine (what /query/v2
        # would run) and groups by region
        res = st.backend.query_engine("global").query(
            QuerySpec.from_dict(tgt["query_v2"]))
        assert "phase:a" in res.groups
        assert res.groups["phase:a"]["roofline_frac"]["values"]
    finally:
        st.close()


def test_meta_roofline_endpoint(tmp_path):
    st = MonitoringStack.inprocess(out_dir=str(tmp_path), serve_http=True)
    try:
        import json
        import urllib.request
        meta = json.loads(urllib.request.urlopen(
            f"{st.http.url}/meta?what=roofline").read())["roofline"]
        assert "roofline_frac" in meta["metrics"]
        assert meta["calibrated"] is None
        calibrate(st.usermetric(host="h0"), 1e12, 1e11, register=False)
        meta = json.loads(urllib.request.urlopen(
            f"{st.http.url}/meta?what=roofline").read())["roofline"]
        assert meta["calibrated"] == {"peak_flops": 1e12, "peak_bw": 1e11}
    finally:
        st.close()


def test_kernel_wrappers_instrumented_eager_only():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    ms = MarkerSession()
    prev = ops.set_kernel_markers(ms)
    try:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((1, 128, 2, 16)), jnp.float32)
        ops.flash_attention_bshd(q, q, q, interpret=True)
        x = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)
        ops.fused_rmsnorm(x, jnp.ones((32,), jnp.float32), interpret=True)
        snap = ms.snapshot()
        assert snap["kernel:flash_attention"]["flops"] > 0
        assert snap["kernel:rmsnorm"]["bytes"] > 0
        # under jit the wrapper body runs at trace time on tracers:
        # instrumentation must skip (timing a trace is noise)
        before = ms.snapshot()["kernel:flash_attention"]["calls"]
        jit_fa = jax.jit(lambda a: ops.flash_attention_bshd(
            a, a, a, interpret=True))
        jit_fa(q)
        assert ms.snapshot()["kernel:flash_attention"]["calls"] == before
    finally:
        ops.set_kernel_markers(prev)


# --------------------------------------------------------------------------
# satellite regression: collective bytes reach the HPM step constants
# --------------------------------------------------------------------------

_SHARDED_HLO = """HloModule m, num_partitions=4

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p: f32[1024,256]) -> f32[1024,256] {
  %p = f32[1024,256]{1,0} parameter(0)
  ROOT %ar = f32[1024,256]{1,0} all-reduce(%p), replica_groups={},
    to_apply=%sum
}
"""


class _StubCompiled:
    """Compiled-artifact shape: as_text."""

    def as_text(self):
        return _SHARDED_HLO


def test_compiled_step_constants_threads_collective_bytes():
    from repro.train.loop import compiled_step_constants
    consts = compiled_step_constants(_StubCompiled(), model_flops=2e9,
                                     tokens_per_step=4096.0)
    # every count is the HLO walk's: an all-reduce does no flops and
    # moves its operand in and its result out
    assert consts["hlo_flops"] == 0.0
    assert consts["hlo_bytes"] == pytest.approx(2 * 1024 * 256 * 4)
    # the seed hardcoded collective_bytes=0.0; the HLO walk sees the
    # all-reduce (1024*256 f32 operand = 1 MiB per device)
    assert consts["collective_bytes"] == pytest.approx(1024 * 256 * 4)
    assert consts["wire_bytes"] > 0
    assert consts["model_flops"] == 2e9
    assert consts["tokens_per_step"] == 4096.0


def test_compiled_step_constants_no_collectives():
    from repro.train.loop import compiled_step_constants

    class _Plain(_StubCompiled):
        def as_text(self):
            return """HloModule m

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{1,0} parameter(0)
  ROOT %t = f32[8]{1,0} tanh(%p)
}
"""
    consts = compiled_step_constants(_Plain(), model_flops=1.0,
                                     tokens_per_step=1.0)
    assert consts["collective_bytes"] == 0.0
    assert consts["wire_bytes"] == 0.0


@pytest.mark.parametrize("d", [64, 128])
def test_compiled_step_constants_count_every_scanned_layer(d):
    """XLA's cost_analysis counts a scanned layer body once; the constants
    come from the trip-count-aware walk, so adding scanned layers adds
    the same per-layer part to ``hlo_flops`` and to ``hlo_bytes``."""
    import jax
    import jax.numpy as jnp
    from repro.train.loop import compiled_step_constants

    def consts(layers):
        def f(x, ws):
            def layer(h, w):
                return jnp.tanh(h @ w), None
            return jax.lax.scan(layer, x, ws)[0].sum()
        compiled = jax.jit(f).lower(
            jax.ShapeDtypeStruct((8, d), jnp.float32),
            jax.ShapeDtypeStruct((layers, d, d), jnp.float32)).compile()
        return compiled_step_constants(compiled, model_flops=1.0,
                                       tokens_per_step=1.0)

    c = {n: consts(n) for n in (1, 2, 3, 4)}
    flops = {n: c[n]["hlo_flops"] for n in c}
    per_layer = flops[3] - flops[2]
    # a layer is its 2*8*d*d matmul flops and a few elementwise ones
    assert 2 * 8 * d * d <= per_layer <= 1.05 * 2 * 8 * d * d
    # the part outside the layers is the same at every depth (a 1-trip
    # loop is unrolled, which saves its counter's two flops)
    assert flops[2] - flops[1] == pytest.approx(per_layer, rel=1e-3)

    # bytes: each trip slices one (d, d) layer out of the stack, so the
    # per-layer part is the same at every depth (from 2 layers, where the
    # loop appears) and 2 -> 4 layers adds twice what 2 -> 3 does; charging
    # each slice the whole (L, d, d) stack would grow it with L
    hbm = {n: c[n]["hlo_bytes"] for n in c}
    per_layer = hbm[3] - hbm[2]
    assert hbm[4] - hbm[2] == pytest.approx(2 * per_layer, rel=1e-6)
    # at least the layer's weights once, at most a few times over
    assert 4 * d * d <= per_layer <= 4 * 4 * d * d


def test_compiled_step_constants_walk_failure_propagates():
    """A failed HLO walk fails the job instead of zeroing the constants
    (zeroed collective_bytes reads as a job without collectives)."""
    from repro.train.loop import compiled_step_constants

    class _Broken(_StubCompiled):
        def as_text(self):
            raise RuntimeError("no HLO text")

    with pytest.raises(RuntimeError, match="no HLO text"):
        compiled_step_constants(_Broken(), model_flops=1.0,
                                tokens_per_step=1.0)


@pytest.mark.parametrize("platform,kind,ok", [
    ("tpu", "TPU v5 lite", True),
    ("tpu", "TPU v4", False),
    ("cpu", "cpu", True),
])
def test_check_device_peaks(platform, kind, ok):
    """The peaks are the v5e's; a job on another TPU kind is refused."""
    from types import SimpleNamespace
    from repro.core.perf_groups import check_device_peaks
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    if ok:
        check_device_peaks(dev)
    else:
        with pytest.raises(ValueError, match="TPU v4"):
            check_device_peaks(dev)


def test_serving_engine_request_phase_regions(tmp_path):
    np = pytest.importorskip("numpy")
    from repro.configs import get_config
    from repro.models.transformer import init_model_params
    from repro.serve.engine import ServingEngine

    cfg = get_config("lms-demo", smoke=True)
    params = init_model_params(cfg, seed=0)
    st = MonitoringStack.inprocess(out_dir=str(tmp_path))
    try:
        with st.job("sv1", user="u", hosts=["h0"]):
            um = st.usermetric(host="h0")
            eng = ServingEngine(cfg, params, max_batch=4, max_len=64,
                                usermetric=um, jit=False)
            for i in range(3):
                eng.submit(np.arange(1, 5 + i), max_new_tokens=4)
            done = eng.run_until_empty()
            um.flush()
        assert len(done) == 3
        snap = eng.markers.snapshot()
        # one prefill+decode per batch, one request record per request
        assert snap["serve:prefill"]["calls"] == 1.0
        assert snap["serve:decode"]["calls"] == 1.0
        assert snap["serve:request"]["calls"] == 3.0
        assert snap["serve:request"]["tokens"] == sum(
            len(r.output) for r in done)
        assert snap["serve:decode"]["tokens"] > 0
        db = st.backend.db("global")
        regions = set(db.tag_values(MARKER_MEASUREMENT, "region"))
        assert {"serve:prefill", "serve:decode",
                "serve:request"} <= regions
    finally:
        st.close()
