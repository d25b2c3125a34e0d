"""The trace reduction, on a small trace recorded on a TPU v5e (50 ms of a
granite training window) and on one recorded here on the CPU."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT)]

from benchmarks.lms_bench import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _recorded():
    return json.loads((DATA / "trace_excerpt_v5e_train.json").read_text())


def test_busy_time_is_the_union_of_op_intervals_in_the_window():
    ev = _recorded()
    r = trace_reduce.reduce(ev)
    win = [h for h in ev["host"] if h[0] == trace_reduce.WINDOW][0]
    lo, hi = win[1], win[2]
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # by hand: sweep the clipped intervals in start order
    ivs = sorted((max(s, lo), min(e, hi))
                 for evs in ev["devices"].values() for _, s, e in evs
                 if min(e, hi) > max(s, lo))
    busy, end = 0.0, lo
    for s, e in ivs:
        if e > end:
            busy += e - max(s, end)
            end = e
    assert r["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0.0 < r["busy_share"] <= 1.0
    # the training step keeps the device busy most of the time
    assert r["busy_share"] > 0.9


def test_breakdown_lists_ops_and_gaps():
    r = trace_reduce.reduce(_recorded())
    ops = r["breakdown"]["device_ops"]
    gaps = r["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= trace_reduce.TOP
    assert all(" = " not in name for name, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert len(gaps) <= trace_reduce.TOP
    assert all(s * 1e9 >= trace_reduce.MIN_GAP_NS for _, s in gaps)
    total_idle = r["window_s"] - r["busy_s"]
    assert sum(s for _, s in gaps) <= total_idle + 1e-12


def test_gaps_are_named_by_the_host_span_that_covers_them():
    ev = {"devices": {"/device:TPU:0": [["a", 0.0, 100.0],
                                        ["b", 5000.0, 6000.0],
                                        ["c", 9000.0, 10000.0]]},
          "host": [[trace_reduce.WINDOW, 0.0, 10000.0, "main"],
                   ["ingest:router.write", 200.0, 4000.0, "main"]],
          "lines": {}}
    r = trace_reduce.reduce(ev)
    assert r["busy_s"] == pytest.approx(2100e-9)
    assert r["breakdown"]["idle_gaps"] == [
        ["ingest:router.write", pytest.approx(4900e-9)],
        ["host:other", pytest.approx(3000e-9)]]


def test_op_names_are_cut_from_the_hlo_text():
    assert trace_reduce.op_name(
        "%fusion.12 = bf16[4096]{0} fusion(%p), kind=kLoop") == "fusion.12"


def test_load_events_reads_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        with jax.profiler.TraceAnnotation("query:engine.query"):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    ev = trace_reduce.load_events(trace_reduce.find_xplane(str(tmp_path)))
    names = [h[0] for h in ev["host"]]
    assert trace_reduce.WINDOW in names and "query:engine.query" in names
    # the CPU backend writes no device plane: nothing to reduce
    with pytest.raises(ValueError):
        trace_reduce.reduce(ev)
