"""Monitored training loop — where the paper's stack becomes load-bearing.

The loop is a *job* in the LMS sense (DESIGN.md §4):

* job start/end signals bracket the run (router tag store tags every metric);
* one :class:`HostAgent` per host emits the XLA-derived HPM metrics each
  step (FLOPs/bytes/collective counters come from a walk of the compiled
  step's HLO, set once after compile);
* ``libusermetric`` carries application-level series (loss, grad norm,
  tokens/s — the paper's Fig. 3 analogue) and events (checkpoint saved,
  restart, failure injected);
* the stream analyzer watches for pathological behaviour (NaN loss, idle,
  straggler skew) and the loop *reacts*: NaN -> halt + checkpoint skip,
  straggler finding -> recorded for the elastic-restart decision.

Fault tolerance: auto-resume from the latest checkpoint, atomic keep-k
saves, deterministic data replay (step-keyed source), optional failure
injection to exercise the restart path end-to-end.
"""

from __future__ import annotations

import math
import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.ckpt import CheckpointManager
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core import MonitoringStack
from repro.core.perf_groups import check_device_peaks
from repro.data import DataLoader, SyntheticTokenSource, make_batch_fn
from repro.models.transformer import init_model_params
from repro.train.optim import get_optimizer
from repro.train.step import make_train_step


class InjectedFailure(RuntimeError):
    """Raised by the failure-injection hook (restart-path testing)."""


@dataclass
class TrainResult:
    steps_run: int
    final_step: int
    last_loss: float
    findings: list
    resumed_from: Optional[int]


def compiled_step_constants(compiled, *, model_flops: float,
                            tokens_per_step: float) -> dict:
    """HPM step constants from one compiled step artifact.

    Every count comes from the trip-count-aware HLO walk (``analyze_hlo``)
    over ``compiled.as_text()``, per device: XLA's own ``cost_analysis()``
    counts a scanned layer body once and reports nothing for collectives.
    ``hlo_bytes`` is the walk's in-place model (``bytes_fused``): a slice
    of a layer-stacked buffer moves the slice, not the whole stack.  A
    failed walk raises: zeroed constants would read as a job that does no
    work and has no collectives.
    """
    from repro.launch.hlo_analysis import analyze_hlo
    per_dev = analyze_hlo(compiled.as_text())["per_device"]
    return {
        "hlo_flops": float(per_dev["flops"]),
        "hlo_bytes": float(per_dev["bytes_fused"]),
        "collective_bytes": float(per_dev["collective_operand_bytes"]),
        "wire_bytes": float(per_dev["collective_wire_bytes"]),
        "model_flops": model_flops,
        "tokens_per_step": tokens_per_step,
    }


def train(model_cfg: ModelConfig, train_cfg: TrainConfig,
          shape: ShapeConfig, *, stack: Optional[MonitoringStack] = None,
          hosts: Optional[list] = None, jit: bool = True,
          pc=None, mesh=None, in_shardings=None,
          fail_at_step: Optional[int] = None,
          step_callback: Optional[Callable] = None,
          user: str = "user", job_id: Optional[str] = None,
          markers: bool = True) -> TrainResult:
    """Run (or resume) a monitored training job on the current devices.

    ``in_shardings`` (the train bundle's, ``launch/steps.py``) places the
    params and optimizer state on the mesh before the first step."""
    check_device_peaks(jax.devices()[0])
    stack = stack or MonitoringStack.inprocess(out_dir="lms_out")
    hosts = hosts or [f"host{i}" for i in range(jax.process_count())]
    host = hosts[jax.process_index() % len(hosts)]
    job_id = job_id or f"{model_cfg.name}-{int(time.time())}"

    # ---- data (deterministic, resumable) ---------------------------------
    source = SyntheticTokenSource(model_cfg.vocab_size, seed=train_cfg.seed)
    batch_fn = make_batch_fn(source, model_cfg, shape,
                             extras_fn=_extras_fn(model_cfg, shape))

    # ---- params / resume ---------------------------------------------------
    opt = get_optimizer(train_cfg)
    ckpt = CheckpointManager(train_cfg.ckpt_dir, keep=train_cfg.ckpt_keep) \
        if train_cfg.ckpt_dir else None
    resumed_from = None
    start_step = 0
    params = init_model_params(model_cfg, seed=train_cfg.seed)
    opt_state = opt.init(params)
    if ckpt and ckpt.latest_step() is not None:
        start_step, trees = ckpt.restore(
            {"params": params, "opt_state": opt_state})
        params, opt_state = trees["params"], trees["opt_state"]
        resumed_from = start_step
    if in_shardings is not None:
        params, opt_state = jax.device_put((params, opt_state),
                                           tuple(in_shardings[:2]))

    loader = DataLoader(batch_fn, global_batch=shape.global_batch,
                        start_step=start_step)

    # ---- step fn -------------------------------------------------------------
    train_step, _ = make_train_step(model_cfg, train_cfg, pc=pc, mesh=mesh)
    if jit:
        train_step = jax.jit(train_step, donate_argnums=(0, 1),
                             in_shardings=in_shardings)

    # ---- LMS wiring -------------------------------------------------------------
    tokens_per_step = shape.global_batch * shape.seq_len
    model_flops = 6 * model_cfg.active_param_count() * tokens_per_step
    agent = stack.host_agent(host)
    um = stack.usermetric(host=host)
    # marker regions (repro.core.marker): per-phase attribution of the
    # loop itself — data_wait / train_step / checkpoint — emitted as the
    # ``marker`` measurement for the per-region roofline query
    mk = um.markers if (markers and train_cfg.monitor) else None
    step_counters: dict = {}
    halted = {"reason": None}

    @stack.on_finding
    def _react(finding):
        if finding.rule == "nan_loss":
            halted["reason"] = "nan_loss"
        # monitoring is load-bearing: a sustained straggler finding asks the
        # launcher for an elastic restart without the slow host (checkpoints
        # are mesh-independent, so the restarted job reshapes freely)
        if finding.rule == "step_time_straggler" and \
                getattr(train_cfg, "halt_on_straggler", False):
            halted["reason"] = f"straggler:{finding.host}"

    last_loss = float("nan")
    steps_run = 0
    step = start_step
    try:
        with stack.job(job_id, user=user, hosts=hosts,
                       tags={"arch": model_cfg.name, "shape": shape.name}):
            um.event("run_state", f"starting {model_cfg.name} at step "
                     f"{start_step}")
            compiled = not jit
            while step < train_cfg.total_steps:
                # one step event per iteration; inside it the train.loop.*
                # phases tile the iteration on this thread, so the trace
                # can say what the host did while the device sat idle
                with jax.profiler.StepTraceAnnotation("train",
                                                      step_num=step):
                    with TraceAnnotation("train.loop.data"):
                        step_idx, np_batch = next(loader)
                        data_wait = loader.wait_time_s
                    with TraceAnnotation("train.loop.h2d"):
                        batch = jax.device_put(
                            np_batch, None if in_shardings is None
                            else in_shardings[2])
                    if not compiled:
                        with TraceAnnotation("train.loop.compile"):
                            # one-time (pre-execution, params still alive
                            # despite donation): compile the step once and
                            # run that artifact, whose HPM constants go to
                            # the agent — including the real per-device
                            # collective operand / wire bytes from the HLO
                            # walk
                            train_step = train_step.lower(
                                params, opt_state, batch, step_idx).compile()
                            consts = compiled_step_constants(
                                train_step, model_flops=model_flops,
                                tokens_per_step=tokens_per_step)
                            agent.set_step_constants(**consts)
                            # static per-call work counters seeding the
                            # train_step marker region's roofline operands
                            step_counters = {
                                k: v for k, v in
                                (("flops", consts["hlo_flops"]),
                                 ("bytes", consts["hlo_bytes"]))
                                if v > 0.0}
                            compiled = True

                    # marker bookkeeping: the region's start and stop (and
                    # the flush its stop may make) are monitoring, the step
                    # inside it is its own phases
                    with TraceAnnotation("train.loop.monitor"):
                        if mk:
                            mk.record("data_wait", data_wait)
                        t0 = time.monotonic()
                        with (mk.region("train_step",
                                        counters=step_counters or None)
                              if mk else nullcontext()):
                            # fwd + bwd + optimizer update are one fused
                            # jitted step (donated buffers) — not separable
                            # into sub-regions without splitting the
                            # compiled artifact
                            with TraceAnnotation("train.loop.dispatch"):
                                params, opt_state, metrics = train_step(
                                    params, opt_state, batch, step_idx)
                            with TraceAnnotation("train.loop.sync"):
                                loss = float(metrics["loss"])
                        step_time = time.monotonic() - t0

                    with TraceAnnotation("train.loop.monitor"):
                        # LMS per-step emission
                        if train_cfg.monitor and \
                                step_idx % train_cfg.monitor_interval == 0:
                            agent.collect_step(
                                step=step_idx, step_time_s=step_time,
                                extra_events={"data_wait_s": data_wait})
                            um.metric("train",
                                      {"loss": loss,
                                       "grad_norm": float(
                                           metrics["grad_norm"]),
                                       "lr": float(metrics["lr"])})
                        if math.isnan(loss):
                            um.event("run_state",
                                     f"NaN loss at step {step_idx}")
                            halted["reason"] = "nan_loss"

                        last_loss = loss
                        steps_run += 1
                        step = step_idx + 1

                    if step_callback:
                        with TraceAnnotation("train.loop.callback"):
                            step_callback(step, metrics)
                    if ckpt and step % train_cfg.ckpt_interval == 0 and \
                            not math.isnan(loss):
                        with TraceAnnotation("train.loop.ckpt"):
                            with (mk.region("checkpoint") if mk
                                  else nullcontext()):
                                ckpt.save(step, {"params": params,
                                                 "opt_state": opt_state},
                                          {"arch": model_cfg.name,
                                           "step": step})
                            um.event("run_state", f"checkpoint at {step}")
                    if fail_at_step is not None and step >= fail_at_step:
                        um.event("run_state", f"injected failure at {step}")
                        raise InjectedFailure(f"injected at step {step}")
                    if halted["reason"]:
                        um.event("run_state", f"halt: {halted['reason']}")
                        break
            um.event("run_state", "finished")
            # flush inside the job bracket so marker points are enriched
            # with the live job's tags (jobid/username) by the router
            um.flush()
    finally:
        um.flush()
        loader.close()
        if ckpt:
            ckpt.wait()

    return TrainResult(steps_run, step, last_loss, stack.findings(),
                       resumed_from)


def _extras_fn(cfg: ModelConfig, shape: ShapeConfig):
    if cfg.family == "vlm":
        def fn(step, rows):
            p = min(cfg.vlm_num_patches, max(shape.seq_len - 2, 1))
            return {
                "patches": np.zeros((rows, p, cfg.d_model), np.float32),
                "mrope_pos": np.broadcast_to(
                    np.arange(shape.seq_len, dtype=np.int32)[None, :, None],
                    (rows, shape.seq_len, 3)).copy()}
        return fn
    if cfg.family == "encdec":
        def fn(step, rows):
            return {"src_frames": np.zeros(
                (rows, cfg.encdec_source_len, cfg.d_model), np.float32)}
        return fn
    return None
