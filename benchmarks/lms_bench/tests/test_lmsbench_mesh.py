"""A training cell on four chips, from its files alone: a whole tiny run
on four host devices over the launcher's mesh, the reference split over
them against the same reference on one device, and a one-device job that
gets no mesh; the trace reductions on two steps recorded on a 2x2 v5e
mesh.

Four devices need a process of their own (the test process keeps seeing
one), so those tests run a subprocess with
``--xla_force_host_platform_device_count=4``."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import pytest

from lmsbench_tiny import cell

from benchmarks.lms_bench import bench, progtrace, trace_reduce

TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parents[2]
MESH = {"data": 2, "model": 2}     # make_mesh_for(4), as the launcher's
# two whole steps of a traced granite-3-8b-l2 job (dash8's mix without
# viewers, B=4 S=2048) on a 2x2 mesh of TPU v5e chips: every device
# plane's ops [name, start, end, scope], the program's spans and the
# harness's host spans, times in ns from the first step's start
MESH_EXCERPT = TESTS / "data" / "progtrace_excerpt_v5e_mesh2x2.json"
ONE_CHIP_EXCERPT = TESTS / "data" / "progtrace_excerpt_v5e_dash8.json"


def _run4(code: str) -> dict:
    """``code`` on four host devices; the JSON of its last output line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT), str(ROOT / "src"), str(TESTS)]
        + [p for p in [env.get("PYTHONPATH")] if p])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_tiny_mesh_cell_runs_whole_and_is_correct():
    """dash8's mix without viewers, batch 4, on four chips: ``train()``
    gets the launcher's 2x2 mesh, constraints and input shardings, the
    job's state is split over the four devices, and the run reads
    correct."""
    got = _run4(f"""
        import json
        import jax
        import repro.train.loop as loop
        from lmsbench_tiny import cell, run

        seen = {{}}
        real = loop.train

        def spy(*args, **kw):
            seen["mesh"] = dict(kw["mesh"].shape)
            seen["pc"] = kw["pc"] is not None
            seen["state_devices"] = sorted({{
                len(s.device_set)
                for s in jax.tree.leaves(kw["in_shardings"][:2])}})
            return real(*args, **kw)
        loop.train = spy

        c = cell("granite-3-8b.train.dash8")
        c.entry = dict(c.entry, chips=4)
        c.traffic.pop("readers")
        c.traffic.update(batch=4, serve_http=False)
        out = run(c)
        print(json.dumps({{"seen": seen, "correct": out.correct,
                          "checks": {{k.name: k.value for k in out.checks}},
                          "chips": out.ctx["chips"],
                          "steps": out.ctx["steps"]}}))
    """)
    assert got["seen"] == {"mesh": MESH, "pc": True, "state_devices": [4]}
    assert got["correct"], got["checks"]
    assert {"loss3_gap", "grad_norm_gap", "change_norm_gap", "change_diff",
            "points_lost"} <= set(got["checks"])
    assert got["chips"] == 4 and got["steps"] > 0


def test_reference_over_four_devices_agrees_with_one():
    """Losses, first-gradient and change norms within 1e-5 relative of the
    one-device reference, for the whole batch (rows split) and for half
    of it (rows replicated); every leaf of the state is split, none whole
    on one device."""
    got = _run4("""
        import json
        import jax
        from lmsbench_tiny import TRAIN
        from benchmarks.lms_bench.reference import dense_train as dt

        conf, devs = dict(TRAIN), jax.devices()
        tcfg = dict(conf["train"])
        out = {}
        for name, rows in [("whole", None), ("half", slice(0, 2))]:
            one = dt.run(conf, tcfg, 7, 4, 32, 1000, rows=rows)
            four = dt.run(conf, tcfg, 7, 4, 32, 1000, rows=rows,
                          devices=devs)
            leaves = jax.tree.leaves(four["params"])
            out[name] = {
                "losses": [four["losses"], one["losses"]],
                "grad_norms": [four["grad_norms"], one["grad_norms"]],
                "change_norms": [
                    dt.change_norms(conf, 7, four["params"], devs),
                    dt.change_norms(conf, 7, one["params"])],
                "split": all(len(x.sharding.device_set) == 4
                             and x.addressable_shards[0].data.size
                             < x.size for x in leaves)}
        print(json.dumps(out))
    """)
    for name, r in got.items():
        assert r.pop("split"), name
        for key, (four, one) in r.items():
            assert len(four) == len(one) > 0
            for a, b in zip(four, one):
                assert abs(a - b) <= 1e-5 * abs(b), (name, key, a, b)


def test_a_one_device_job_gets_no_mesh(monkeypatch):
    """On one device ``train()`` is called as the launcher calls it for a
    one-device job: no ``mesh``, ``pc`` or ``in_shardings``."""
    import jax
    import repro.train.loop as loop
    from benchmarks.lms_bench.generators import train as train_drv

    seen = {}

    def spy(*args, **kw):
        seen.update(kw)
    monkeypatch.setattr(loop, "train", spy)
    train_drv.warmup(cell("granite-3-8b.train.unmonitored"), 4,
                     devices=jax.devices()[:1])
    assert seen["job_id"] == "lms-bench-granite-3-8b.train.unmonitored"
    assert not {"mesh", "pc", "in_shardings"} & set(seen)


def _collective_frac(monkeypatch, ev):
    monkeypatch.setattr(progtrace, "for_ctx", lambda ctx: ev)
    return bench.metric_reader("step_collective_frac")({"trace": {}})


@pytest.mark.parametrize("name,collective", [
    ("all-gather.3", True), ("all-gather-start.3", True),
    ("all-gather-done.3", True), ("all-reduce.12", True),
    ("all-reduce-start", True), ("reduce-scatter.1", True),
    ("all-to-all.2", True), ("collective-permute-done.7", True),
    ("async-collective-start.7", True), ("async-collective-done", True),
    ("fusion.12", False), ("all-reduce-fusion.1", False),
    ("while.4", False)])
def test_collective_ops_are_named_by_their_hlo_op(monkeypatch, name,
                                                  collective):
    """A collective's own time, innermost under a ``while``, is the
    share; any other op gives 0; a trace with no op gives nothing."""
    ops = [["while.1", 0.0, 1000.0, None], [name, 100.0, 300.0, None],
           ["fusion.9", 300.0, 400.0, "mlp"]]
    ev = {"window": [0.0, 1000.0], "ops": ops, "spans": []}
    assert _collective_frac(monkeypatch, ev) == (0.2 if collective else 0.0)
    assert _collective_frac(monkeypatch, dict(ev, ops=[])) is None
    assert _collective_frac(monkeypatch, None) is None


def _mesh_excerpt():
    return json.loads(MESH_EXCERPT.read_text())


def _first_plane_events(ex):
    return {"window": ex["window"], "spans": ex["spans"],
            "ops": ex["devices"]["/device:TPU:0"]}


def test_trace_reduce_averages_busy_time_over_the_four_planes():
    ex = _mesh_excerpt()
    assert sorted(ex["devices"]) == [f"/device:TPU:{i}" for i in range(4)]
    ev = {"devices": {p: [op[:3] for op in ops]
                      for p, ops in ex["devices"].items()},
          "host": ex["host"], "lines": {}}
    r = trace_reduce.reduce(ev)
    lo, hi = ex["window"]
    per_plane = [sum(e - s for s, e in progtrace._union(
        [(max(s, lo), min(e, hi)) for _, s, e, _ in ops
         if e > lo and s < hi])) * 1e-9 for ops in ex["devices"].values()]
    assert r["busy_s"] == pytest.approx(sum(per_plane) / 4, rel=1e-12)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0.9 < r["busy_share"] <= 1.0
    # an op's time in the breakdown is its mean over the planes
    name, secs = r["breakdown"]["device_ops"][0]
    mean = sum(min(e, hi) - max(s, lo) for ops in ex["devices"].values()
               for n, s, e, _ in ops if n == name) / 4 * 1e-9
    assert secs == pytest.approx(mean, rel=1e-9)


def test_progtrace_takes_the_first_plane_and_scopes_ops_and_phases(
        monkeypatch):
    """The first ``/device:`` plane by name with ops (the Megascale plane
    of a four-chip trace has none); its ops carry the step's scopes, the
    loop's phases tile each step, and every program reader finds what it
    reads, the collectives' share among them."""
    ex = _mesh_excerpt()
    planes = [SimpleNamespace(name="/device:CUSTOM:Megascale Trace",
                              lines=[])]
    for p, ops in sorted(ex["devices"].items(), reverse=True):
        planes.append(SimpleNamespace(name=p, lines=[
            SimpleNamespace(name="Steps", events=["step"]),
            SimpleNamespace(name="XLA Ops", events=ops)]))
    assert progtrace._first_device_ops(planes) == \
        ex["devices"]["/device:TPU:0"]

    ev = _first_plane_events(ex)
    progtrace.check(ev)
    steps = progtrace.spans_in_window(ev, "train")
    assert len(steps) == 2
    for _, s, e, thread, meta in steps:
        assert "step_num" in meta
        phases = progtrace.innermost(
            [(ps, pe, n) for n, ps, pe, t, _ in ev["spans"]
             if n.startswith(progtrace.PHASE) and t == thread], s, e)
        assert sum(pe - ps for ps, pe, _ in phases) >= 0.95 * (e - s)
    assert progtrace.idle_by_phase(ev)[1] == 2
    assert set(progtrace.busy_by_scope(ev)) == set(progtrace.SCOPES) | {None}

    monkeypatch.setattr(progtrace, "for_ctx", lambda ctx: ev)
    names = [p.stem for p in (bench.HERE / "metrics").glob("*.py")
             if "progtrace" in p.read_text()]
    got = {n: bench.metric_reader(n)({"trace": {}}) for n in names}
    # the mix has no viewers, so the query readers find no query
    assert {n for n, v in got.items() if v is None} == {
        "query_server_ms_p95", "query_cache_hit_frac"}
    assert 0.05 < got["step_collective_frac"] < 0.3


def test_collective_share_by_hand_on_the_recorded_trace(monkeypatch):
    """The reader's share is the collectives' innermost busy time over all
    busy time on the first plane; a one-chip trace has none."""
    ev = _first_plane_events(_mesh_excerpt())
    lo, hi = ev["window"]
    ops = ev["ops"]
    pieces = progtrace.innermost(
        [(s, e, i) for i, (_, s, e, _) in enumerate(ops)], lo, hi)
    named = [(e - s, ops[i][0]) for s, e, i in pieces]
    kinds = {n.split(".")[0] for d, n in named
             if n.split(".")[0].startswith(("all-", "collective-",
                                            "async-collective-"))}
    assert kinds == {"all-gather", "all-reduce", "all-to-all",
                     "collective-permute-start", "collective-permute-done",
                     "async-collective-start", "async-collective-done"}
    want = sum(d for d, n in named if n.split(".")[0] in kinds) / \
        sum(d for d, _ in named)
    assert _collective_frac(monkeypatch, ev) == pytest.approx(want,
                                                              rel=1e-12)
    one_chip = json.loads(ONE_CHIP_EXCERPT.read_text())
    assert _collective_frac(monkeypatch, one_chip) == 0.0
