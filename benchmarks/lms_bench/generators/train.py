"""Training traffic: the job as the launcher runs it, and what reads it.

``repro.train.loop.train`` runs exactly as ``launch/train.py`` calls it,
with the harness's own ``MonitoringStack``.  Its first ``warmup_steps``
steps are set-up: they compile the step, and the step callback records
what the correctness check needs (the losses, the first gradient as the
optimizer holds it after step 1, the parameters after the last warm-up
step).  The window opens when the last warm-up step returns and closes at
the first step that ends ``--seconds`` later: the callback then raises
``WindowClosed``, which the harness catches.  With ``readers`` in the
traffic mix, dashboard viewers query the stack's ``/query/v2`` for the
whole window.

A cell on several chips runs the job over them as ``launch/train.py``
runs a multi-device job: the mesh of ``launch/mesh.make_mesh_for`` over
the chip count (4 chips: data 2 x model 2), the ``train`` sharding rules,
their partition constraints and the train bundle's input shardings.  The
reference is then split over the same devices.  On one chip the job gets
none of these, as the launcher's does.

The loop does not hand its state to the callback, so the callback reads
``params`` and ``opt_state`` from the loop's frame.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import statistics
import sys
import threading
import time

from benchmarks.lms_bench import bench, trace_reduce
from benchmarks.lms_bench.generators import dashboards
from benchmarks.lms_bench.reference import dense_train

TOTAL_STEPS = 10 ** 9           # the loop runs until the window closes
JOB_HOST = "host0"
# a closed dashboard window is compared only once it ended this long
# before its query was sent (writes are synchronous, far faster than this)
ANSWER_MARGIN_NS = 10 ** 9
# leaves whose reference gradient is below this share of the median
# leaf's move by round-off alone and are not compared
QUIET_LEAF = 1e-3


class WindowClosed(Exception):
    """Raised from the step callback to end the loop at the window's end."""


PROGRAM_KEYS = {            # configuration key -> ModelConfig attribute
    "hidden_size": "d_model", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "param_dtype": "param_dtype",
    "compute_dtype": "dtype", "num_hidden_layers": "num_layers",
}


def model_config(conf: dict):
    """The program's ModelConfig for ``conf``; refuses one that departs
    from what the configuration file states."""
    from repro.configs import get_config
    cfg = dataclasses.replace(
        get_config(conf["arch"], smoke=conf.get("smoke", False)),
        num_layers=conf["num_hidden_layers"])
    bad = {k: (conf[k], getattr(cfg, a)) for k, a in PROGRAM_KEYS.items()
           if k in conf and conf[k] != getattr(cfg, a)}
    if bad:
        raise ValueError(f"the program's {conf['arch']} departs from "
                         f"{conf['name']}: {bad}")
    conf.setdefault("vocab_pad_to", cfg.vocab_pad_to)
    return cfg


def train_config(conf: dict, traffic: dict, seed: int):
    from repro.configs import TrainConfig
    t = conf["train"]
    return TrainConfig(
        learning_rate=t["learning_rate"], weight_decay=t["weight_decay"],
        beta1=t["beta1"], beta2=t["beta2"], eps=t["eps"],
        grad_clip_norm=t["grad_clip_norm"], warmup_steps=t["warmup_steps"],
        remat_policy=t["remat_policy"], total_steps=TOTAL_STEPS, seed=seed,
        monitor=traffic["monitor"],
        monitor_interval=traffic["monitor_interval"])


def _leaf_norm_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree.leaves(tree)]
    return norms


def mesh_args(cfg, tcfg, shape, devices) -> dict:
    """``train()``'s ``mesh``, ``pc`` and ``in_shardings`` for a job on
    several devices, built as ``launch/train.py`` builds them; none for
    one device."""
    if devices is None or len(devices) < 2:
        return {}
    from repro.launch.mesh import make_mesh_for
    from repro.launch.steps import build_train_bundle, make_pc
    from repro.parallel.sharding import rules_for
    mesh = make_mesh_for(len(devices))
    rules = rules_for("train")
    return {"mesh": mesh, "pc": make_pc(rules, mesh),
            "in_shardings": build_train_bundle(cfg, shape, tcfg, mesh,
                                               rules).in_shardings}


def _loop_locals():
    """The train loop's frame, two calls up from the step callback."""
    frame = sys._getframe(2)
    if frame.f_code.co_name != "train":
        raise RuntimeError(f"step callback not called from train() but "
                           f"{frame.f_code.co_name}")
    return frame.f_locals


def warmup(cell: bench.Cell, seed: int, on_window=None, stack=None,
           seconds: float = 0.0, trace_dir=None, devices=None):
    """Set up and run the job over ``devices`` (the default device when
    ``None``).  Returns the capture of the warm-up steps and, when
    ``on_window`` is given, of the window's step stamps."""
    import jax
    from repro.configs import ShapeConfig
    from repro.train.loop import train

    conf, traffic = cell.config, cell.traffic
    s31 = bench.seed31(seed)
    cfg = model_config(conf)
    tcfg = train_config(conf, traffic, s31)
    shape = ShapeConfig("bench", seq_len=traffic["seq_len"],
                        global_batch=traffic["batch"], kind="train")
    warm = traffic["warmup_steps"]
    leaf_norms = _leaf_norm_fn()
    cap = {"losses": [], "grad_norms": None, "params": None,
           "capture_s": 0.0, "stamps": [], "t_start": None, "t_end": None,
           "trace": None}

    def callback(step, metrics):
        t = time.monotonic()
        if step <= warm:
            cap["losses"].append(float(metrics["loss"]))
            if step == 1:
                m = _loop_locals()["opt_state"]["m"]
                cap["grad_norms"] = [float(x) / (1 - tcfg.beta1)
                                     for x in leaf_norms(m)]
            if step == warm:
                cap["params"] = jax.device_get(
                    jax.tree.leaves(_loop_locals()["params"]))
                cap["capture_s"] += time.monotonic() - t
                if on_window is None:
                    raise WindowClosed
                cap["t_start"] = time.monotonic()
                on_window(cap["t_start"])
            elif step == 1:
                cap["capture_s"] += time.monotonic() - t
            return
        cap["stamps"].append(t)
        if trace_dir is not None and cap["trace"] is None and \
                t >= cap["t_start"] + seconds - traffic["trace_s"]:
            cap["trace"] = _start_trace(trace_dir)
        if t - cap["t_start"] >= seconds:
            cap["t_end"] = t
            if cap["trace"] is not None:
                cap["trace"].__exit__(None, None, None)
            raise WindowClosed

    try:
        train(cfg, tcfg, shape, stack=stack, hosts=[JOB_HOST],
              step_callback=callback, user="bench",
              job_id=f"lms-bench-{cell.name}", markers=traffic["markers"],
              **mesh_args(cfg, tcfg, shape, devices))
    except WindowClosed:
        pass
    return cap


def _start_trace(trace_dir):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
    ann.__enter__()
    return ann


# --------------------------------------------------------------------------
# the comparison with the reference
# --------------------------------------------------------------------------


def worst_leaf_gap(prog, ref, counted, zero_ref=False) -> float:
    """Largest |program - reference| over the counted leaves, each against
    the larger of its reference value and the median reference leaf
    (``zero_ref``: ``prog`` already holds the per-leaf differences)."""
    med = statistics.median(ref[i] for i in counted)
    return max((prog[i] if zero_ref else abs(prog[i] - ref[i]))
               / max(ref[i], med) for i in counted)


def readings(prog, ref, diff=None) -> dict:
    """The compared numbers of one seed, program (or control) against the
    reference: each step's loss, the first gradient and the parameters'
    change, the last two by the worst leaf; with ``diff`` (per-leaf norms
    of the parameters' difference from the reference's after the last
    step), the worst leaf's difference against the reference's change."""
    med = statistics.median(ref["grad_norms"])
    counted = [i for i, g in enumerate(ref["grad_norms"])
               if g >= QUIET_LEAF * med]
    out = {f"loss{i + 1}_gap": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]))}
    out["grad_norm_gap"] = worst_leaf_gap(prog["grad_norms"],
                                          ref["grad_norms"], counted)
    out["change_norm_gap"] = worst_leaf_gap(prog["change_norms"],
                                            ref["change_norms"], counted)
    if diff is not None:
        out["change_diff"] = worst_leaf_gap(diff, ref["change_norms"],
                                            counted, zero_ref=True)
    out["grad_worst_leaf"] = max(
        counted, key=lambda i: abs(prog["grad_norms"][i]
                                   - ref["grad_norms"][i]))
    out["leaves_counted"] = len(counted)
    return out


def reference(conf, traffic, seed31, devices, others=(), log=None,
              keep_params=False, **kw) -> dict:
    """The reference's first steps, split over the cell's ``devices``;
    ``others`` are parameter leaves (in the reference's order) of runs to
    hold against its parameters."""
    import jax
    tdict = dict(conf["train"])
    ref = dense_train.run(conf, tdict, seed31, traffic["batch"],
                          traffic["seq_len"], TOTAL_STEPS,
                          steps=traffic["warmup_steps"], log=log,
                          devices=devices, **kw)
    params = ref.pop("params")
    if keep_params:
        ref["params"] = jax.device_get(jax.tree.leaves(params))
    ref["diff_norms"] = [dense_train.diff_norms(params, o, devices)
                         for o in others]
    ref["change_norms"] = dense_train.change_norms(conf, seed31, params,
                                                   devices)
    if log:
        log("reference change norms done")
    return ref


def program_readout(conf, seed31, cap, devices) -> dict:
    return {"losses": cap["losses"], "grad_norms": cap["grad_norms"],
            "params": cap["params"],
            "change_norms": dense_train.change_norms(conf, seed31,
                                                     cap["params"], devices)}


# --------------------------------------------------------------------------
# a run
# --------------------------------------------------------------------------


def run(cell: bench.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, devices) -> bench.Outcome:
    import jax
    from repro.core import MonitoringStack
    from benchmarks.lms_bench.hostspans import Spans

    traffic = cell.traffic
    out = bench.OUT_DIR / cell.name
    trace_dir = out / "trace"
    if trace_dir.exists():
        import shutil
        shutil.rmtree(trace_dir)
    stack = MonitoringStack.inprocess(out_dir=str(out / "lms"),
                                      serve_http=traffic["serve_http"])
    points: list = []
    router = stack.router

    def capture_write(pts):
        pts = [pts] if hasattr(pts, "measurement") else list(pts)
        points.extend(pts)
        # looked up per call, so that a span installed on the class counts
        return type(router).write(router, pts)
    router.write = capture_write

    spans = Spans().install() if trace else None
    rcfg = traffic.get("readers")
    readers = None
    job_id = f"lms-bench-{cell.name}"

    def on_window(t0):
        nonlocal readers
        if rcfg:
            board = dashboards.build_board(
                points, panel_window_s=rcfg["panel_window_s"],
                roofline_window_s=rcfg["roofline_window_s"])
            readers = dashboards.Readers(stack.http.url, job_id, board,
                                         rcfg["count"],
                                         rcfg["board_refresh_s"], seed)
            readers.start(t0)
        if spans is not None:
            spans.active = True

    train_thread = threading.get_ident()
    try:
        cap = warmup(cell, seed, on_window=on_window, stack=stack,
                     seconds=seconds, trace_dir=trace_dir if trace else None,
                     devices=devices)
        t_end = cap["t_end"]
        if spans is not None:
            spans.active = False
        stuck = readers.stop(t_end, rcfg["answer_grace_s"]) if readers \
            else []
        if cap["trace"] is not None:
            jax.profiler.stop_trace()
        peak = bench.memory_peak(devices)
        checks, attempted, failed, e2e, ctx = [], 0, 0, {}, {}

        t_start, stamps = cap["t_start"], cap["stamps"]
        steps = len(stamps)
        window_s = t_end - t_start
        step_s = [b - a for a, b in zip([t_start] + stamps, stamps)]
        tokens = traffic["batch"] * traffic["seq_len"]
        e2e["train_tokens_per_s"] = steps * tokens / window_s
        e2e["train_step_p95_ms"] = bench.percentile(step_s, 95) * 1e3
        e2e["setup_s"] = t_start - t_process - cap["capture_s"]
        attempted += steps
        ctx.update(steps=steps, window_s=window_s, chips=len(devices),
                   config=cell.config, batch=traffic["batch"],
                   seq_len=traffic["seq_len"],
                   device_kind=devices[0].device_kind)

        if readers is not None:
            due = [a for a in readers.answers if t_start <= a.due < t_end]
            ok = [a for a in due if a.result is not None]
            attempted += len(due) + len(stuck)
            failed += len(due) - len(ok) + len(stuck)
            ctx["query_ms"] = [a.latency_s * 1e3 for a in ok]
            gaps = [dashboards.answer_gap(points, readers.board[a.panel], a,
                                          ANSWER_MARGIN_NS) for a in ok]
            # no answer to compare cannot pass
            checks.append(("dashboard_gap", max(gaps, default=math.inf)))
            ctx["queries"] = len(due)
        if traffic["monitor"]:
            checks.append(("points_lost", float(_points_lost(stack,
                                                             points))))
        if spans is not None:
            ctx["spans"] = spans
            ctx["train_thread"] = train_thread
    finally:
        if spans is not None:
            spans.uninstall()
        stack.close()

    # the program's state is gone with the loop's frame; the reference
    # runs on what is left of the chip
    bench.log(f"window closed, stack checked at "
              f"{time.monotonic() - t_process:.1f} s")
    s31 = bench.seed31(seed)
    prog = program_readout(cell.config, s31, cap, devices)
    cap = None
    gc.collect()
    bench.log(f"program read out at {time.monotonic() - t_process:.1f} s")
    ref = reference(cell.config, traffic, s31, devices,
                    others=[prog["params"]], log=bench.log)
    r = readings(prog, ref, diff=ref["diff_norms"][0])
    bench.log(f"reference compared at {time.monotonic() - t_process:.1f} s")
    checks = [bench.Check(n, float(v), cell.limits[n]) for n, v in
              [(k, r[k]) for k in r if k in cell.limits] + checks]
    outcome = bench.Outcome(e2e, attempted, failed, checks, peak, ctx)
    if trace:
        outcome.trace = trace_reduce.reduce_dir(
            str(trace_dir), excerpt_path=out / "trace_excerpt.json")
        ctx["trace"] = outcome.trace
    return outcome


def _points_lost(stack, points) -> int:
    """Points the job wrote that the stack does not give back: the size of
    the difference of the two multisets of (measurement, host, time,
    field, value)."""
    from collections import Counter
    wrote = Counter()
    for p in points:
        for f, v in p.fields.items():
            if dashboards._numeric(v):
                wrote[(p.measurement, p.tags.get("hostname"), p.timestamp,
                       f, float(v))] += 1
    db = stack.backend.db("global")
    stored = Counter()
    for meas in {k[0] for k in wrote}:
        fields = sorted({k[3] for k in wrote if k[0] == meas})
        for s in db.select(meas, fields):
            for f in fields:
                for t, v in zip(s.times, s.values.get(f, [])):
                    if dashboards._numeric(v):
                        stored[(meas, s.tags.get("hostname"), t, f,
                                float(v))] += 1
    return sum(((wrote - stored) + (stored - wrote)).values())


def calibrate_seed(cell: bench.Cell, seed: int, stack,
                   control: bool = True, devices=None) -> dict:
    """The program's warm-up steps as a run drives them, then the
    reference, the float8 control and the reference with half of the
    batch left out (a planted fault), each compared with the reference by
    the cell's own numbers; the job and the references run over
    ``devices`` (the default device when ``None``).  A state left
    unchanged reads 1 on ``change_norm_gap`` by construction and needs no
    run."""
    import jax.numpy as jnp
    s31 = bench.seed31(seed)
    cap = warmup(cell, seed, stack=stack, devices=devices)
    prog = program_readout(cell.config, s31, cap, devices)
    cap = None
    gc.collect()
    traffic = cell.traffic
    log = bench.log
    ctl = half = None
    if control:
        ctl = reference(cell.config, traffic, s31, devices, log=log,
                        mm_dtype=jnp.float8_e4m3fn, keep_params=True)
        half = reference(cell.config, traffic, s31, devices, log=log,
                         rows=slice(0, traffic["batch"] // 2),
                         keep_params=True)
    others = [prog["params"]] + ([ctl.pop("params"), half.pop("params")]
                                 if control else [])
    ref = reference(cell.config, traffic, s31, devices, others=others,
                    log=log)
    d = ref["diff_norms"]
    out = {"seed": seed,
           "program": readings(prog, ref, diff=d[0]),
           "program_losses": prog["losses"],
           "reference_losses": ref["losses"]}
    if control:
        out["control_fp8"] = readings(ctl, ref, diff=d[1])
        out["fault_half_batch"] = readings(half, ref, diff=d[2])
    return out
