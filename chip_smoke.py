#!/usr/bin/env python3
"""Chip smoke: a monitored granite-3-8b job, end to end, on a TPU.

    python chip_smoke.py             # one chip: train, serve, kernels
    python chip_smoke.py --chips 4   # four chips: the sharded train step
                                     # against the same step on one device

One process, no children.  Every phase checks its results and raises on a
mismatch, so any failure exits non-zero with a traceback.  Only when every
phase passed does the last line of stdout carry
``{"ok": true, "device": {"platform", "kind", "count"}}``.  A run that finds
no TPU fails: it never falls back to the CPU.

The model is granite-3-8b at its published widths with the depth cut to
2 layers (random weights from ``--seed``).  The monitoring stack runs in
process and writes its dashboards under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

ARCH = "granite-3-8b"
LAYERS = 2                  # of 40; dense, so one layer is a whole period
TRAIN_SHAPE = dict(seq_len=2048, global_batch=2)
TRAIN_STEPS = 5
MESH_STEPS = 3
SERVE_REQUESTS = 8
SERVE_PROMPT = (512, 1024)  # prompt lengths, inclusive
SERVE_NEW_TOKENS = 32
SERVE_MAX_BATCH = 8
SERVE_MAX_LEN = 2048

# |first loss - ln(vocab)|: a uniform prediction scores ln(vocab) on any
# labels; random-init logits (std ~0.9 at these widths) add ~sigma^2/2 =
# 0.43 nats (11.23 vs 10.80 on the CPU at the same widths, seq 256).  A
# forward pass that is broken (NaN, exploding or collapsed logits) lands
# far outside 1.0.
FIRST_LOSS_TOL = 1.0
# mesh vs one device, first-step loss: the same bf16 math summed in another
# partition order; over 4094 tokens the mean moves by far less than this
MESH_LOSS_TOL = 2e-2
# the engine's greedy token vs the cacheless forward: its reference logit
# lies within this of the reference max (bf16 prefill/decode vs train path)
SERVE_LOGIT_TOL = 0.1
# kernels vs kernels/ref.py in f32 at "highest" matmul precision; the
# flash kernel's MXU passes may round operands to bf16 (2^-9 relative)
FLASH_TOL = 2e-2
RMSNORM_TOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts XLA backend compiles (and persistent-cache hits) through
    ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring
        self.seconds: list = []
        self.names: list = []
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds.append(secs)
            self.names.append(kw.get("fun_name", ""))

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def count(self) -> int:
        return len(self.seconds)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {d.platform!r} devices")
    if len(devs) < chips:
        raise RuntimeError(f"--chips {chips} needs {chips} devices, "
                           f"found {len(devs)}")
    return d


def model_config():
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(ARCH), num_layers=LAYERS)
    log(f"config: {ARCH} at published widths (d_model {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads of {cfg.head_dim}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); depth cut 40 -> "
        f"{cfg.num_layers} layers; {cfg.param_count() / 1e6:.1f}M params")
    return cfg


def _job_hpm(stack, job_id: str, field: str) -> list:
    db = stack.backend.db("global")
    vals = []
    for s in db.select("hpm", [field], {"jobid": job_id}):
        vals.extend(v for v in s.values.get(field, []) if v is not None)
    return vals


def _job_regions(stack, job_id: str) -> set:
    db = stack.backend.db("global")
    return {s.tags.get("region")
            for s in db.select("marker", ["calls"], {"jobid": job_id})}


def _monitored_train(stack, cfg, tcfg, shape, job_id, counter, **kw):
    """``repro.train.loop.train`` with per-step timing taken in the step
    callback, each step ended by ``block_until_ready``."""
    import jax
    from repro.train.loop import train

    losses, stamps = [], []
    marks = {}

    def cb(step, metrics):
        jax.block_until_ready(metrics)
        stamps.append(time.perf_counter())
        losses.append(float(metrics["loss"]))
        if len(stamps) == 1:
            marks["compiles_at_first_step"] = counter.count

    t0 = time.perf_counter()
    res = train(cfg, tcfg, shape, stack=stack, job_id=job_id,
                step_callback=cb, **kw)
    steps = [b - a for a, b in zip(stamps, stamps[1:])]
    return {"result": res, "losses": losses,
            "first_step_s": stamps[0] - t0, "step_s": steps,
            "compiles_after_warmup":
                counter.count - marks["compiles_at_first_step"]}


def phase_train(stack, cfg, counter, seed: int):
    import jax
    from repro.configs import ShapeConfig, TrainConfig
    from repro.core.marker import roofline_spec

    shape = ShapeConfig("smoke", kind="train", **TRAIN_SHAPE)
    tcfg = TrainConfig(total_steps=TRAIN_STEPS, seed=seed)
    job = "smoke-train"
    n0 = counter.count
    r = _monitored_train(stack, cfg, tcfg, shape, job, counter)
    losses = r["losses"]
    log(f"train: B={shape.global_batch} S={shape.seq_len} "
        f"steps={len(losses)} losses={losses}")
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        raise AssertionError(f"losses not all finite: {losses}")
    ln_v = math.log(cfg.vocab_size)
    if abs(losses[0] - ln_v) > FIRST_LOSS_TOL:
        raise AssertionError(f"first loss {losses[0]} vs ln(V)={ln_v:.4f} "
                             f"beyond {FIRST_LOSS_TOL}")
    step_compiles = sum("train_step" in n for n in counter.names[n0:])
    stats = jax.devices()[0].memory_stats() or {}
    log(f"train: compile_s={sum(counter.seconds[n0:]):.2f} "
        f"(train_step compiles={step_compiles}, "
        f"cache_hits={counter.cache_hits}) "
        f"first_step_s={r['first_step_s']:.2f} "
        f"median_step_s={statistics.median(r['step_s']):.4f} "
        f"step_s={[round(s, 4) for s in r['step_s']]} "
        f"compiles_after_warmup={r['compiles_after_warmup']} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    if r["compiles_after_warmup"] != 0:
        raise AssertionError("the train loop recompiled after warm-up")

    gflops = _job_hpm(stack, job, "gflops_per_s")
    if len(gflops) != TRAIN_STEPS or min(gflops) <= 0:
        raise AssertionError(f"hpm points (hlo_flops > 0) missing: {gflops}")
    if "train_step" not in _job_regions(stack, job):
        raise AssertionError("train_step marker region not in the stack")
    res = stack.backend.query_engine("global").query(roofline_spec(job))
    if "train_step" not in res.groups:
        raise AssertionError(f"roofline query groups: {list(res.groups)}")
    frac = res.groups["train_step"]["roofline_frac"]["values"]
    log(f"stack: hpm points={len(gflops)} gflops_per_s={gflops} "
        f"roofline groups={sorted(res.groups)} "
        f"train_step roofline_frac={frac}")


def phase_serve(stack, cfg, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.transformer import forward, init_model_params
    from repro.serve.engine import ServingEngine

    params = init_model_params(cfg, seed=seed)
    rng = np.random.default_rng(seed)
    job = "smoke-serve"
    with stack.job(job, user="server", hosts=["host0"]):
        um = stack.usermetric(host="host0")
        eng = ServingEngine(cfg, params, max_batch=SERVE_MAX_BATCH,
                            max_len=SERVE_MAX_LEN, usermetric=um)
        for _ in range(SERVE_REQUESTS):
            plen = int(rng.integers(SERVE_PROMPT[0], SERVE_PROMPT[1] + 1))
            eng.submit(rng.integers(1, cfg.vocab_size, plen),
                       max_new_tokens=SERVE_NEW_TOKENS)
        done = eng.run_until_empty()
        um.flush()
    if len(done) != SERVE_REQUESTS or any(
            len(r.output) != SERVE_NEW_TOKENS for r in done):
        raise AssertionError("not every request finished")
    if not all(0 <= t < cfg.vocab_size for r in done for t in r.output):
        raise AssertionError("token id out of the vocabulary")
    ttft = [r.first_token_at - r.submitted_at for r in done]
    lat = [r.finished_at - r.submitted_at for r in done]
    log(f"serve: {len(done)} requests, prompts "
        f"{sorted(len(r.prompt) for r in done)}, "
        f"{SERVE_NEW_TOKENS} new tokens each; ttft_p50_s="
        f"{float(np.percentile(ttft, 50)):.4f} latency_p50_s="
        f"{float(np.percentile(lat, 50)):.4f}")

    # the longest prompt has no padding: its first two greedy tokens (one
    # from prefill, one from a cached decode step) must be argmaxes of the
    # cacheless forward pass
    r = max(done, key=lambda q: len(q.prompt))
    last_logits = jax.jit(
        lambda p, t: forward(p, cfg, tokens=t)[0][0, -1, :cfg.vocab_size])
    for i in range(2):
        seq = np.concatenate([r.prompt, np.asarray(r.output[:i], np.int32)])
        ref = np.asarray(last_logits(params, jnp.asarray(seq[None])),
                         np.float32)
        gap = float(ref.max() - ref[r.output[i]])
        log(f"serve: token {i} = {r.output[i]}, reference argmax "
            f"{int(ref.argmax())}, logit gap {gap:.5f}")
        if gap > SERVE_LOGIT_TOL:
            raise AssertionError(f"token {i} is not a reference argmax")

    regions = _job_regions(stack, job)
    if not {"serve:prefill", "serve:decode"} <= regions:
        raise AssertionError(f"serve regions missing: {regions}")
    log(f"stack: serve regions={sorted(regions)}")


def phase_kernels(stack, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import flash_attention as fa, ops, ref
    from repro.kernels import rmsnorm as rms
    from repro.launch.hlo_analysis import analyze_hlo

    key = jax.random.key(seed)
    kq, kk, kv, kx, ks = jax.random.split(key, 5)
    b, s, h, kvh, d = 1, 2048, 32, 8, 128          # granite attention
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, kvh, d), jnp.float32)
    x = jax.random.normal(kx, (4096, 4096), jnp.float32)   # (B*S, d_model)
    scale = 1.0 + 0.1 * jax.random.normal(ks, (4096,), jnp.float32)

    job = "smoke-kernels"
    with stack.job(job, user="kernels", hosts=["host0"]):
        session = stack.marker_session(host="host0")
        prev = ops.set_kernel_markers(session)
        try:
            o = ops.flash_attention_bshd(q, k, v, causal=True,
                                         interpret=False)
            y = ops.fused_rmsnorm(x, scale, interpret=False)
        finally:
            ops.set_kernel_markers(prev)
        session.flush()
    with jax.default_matmul_precision("highest"):
        o_ref = ref.attention_ref(q.transpose(0, 2, 1, 3),
                                  k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3)).transpose(
                                      0, 2, 1, 3)
        y_ref = ref.rmsnorm_ref(x, scale)
    err_fa = float(jnp.max(jnp.abs(o - o_ref)))
    err_rms = float(jnp.max(jnp.abs(y - y_ref) / (1.0 + jnp.abs(y_ref))))
    log(f"kernels: flash_attention max_abs_err={err_fa:.3e} "
        f"(tol {FLASH_TOL}); rmsnorm max_rel_err={err_rms:.3e} "
        f"(tol {RMSNORM_TOL})")
    if not (np.isfinite(err_fa) and err_fa <= FLASH_TOL):
        raise AssertionError("flash_attention disagrees with attention_ref")
    if not (np.isfinite(err_rms) and err_rms <= RMSNORM_TOL):
        raise AssertionError("rmsnorm disagrees with rmsnorm_ref")

    # the regions' counters are the HLO walk over each compiled kernel
    qt, kt = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
    walks = {
        "kernel:flash_attention": fa.flash_attention.lower(qt, kt, kt),
        "kernel:rmsnorm": rms.rmsnorm.lower(x, scale),
    }
    snap = session.snapshot()
    for region, lowered in walks.items():
        text = lowered.compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{region} did not compile to Mosaic")
        per = analyze_hlo(text)["per_device"]
        got = {c: snap[region][c] for c in ("flops", "bytes")}
        log(f"kernels: {region} counters={got} hlo_walk="
            f"{{'flops': {per['flops']}, 'bytes': {per['bytes']}}}")
        if got != {"flops": per["flops"], "bytes": per["bytes"]} or \
                per["flops"] <= 0:
            raise AssertionError(f"{region} costs are not the HLO walk's")
    regions = _job_regions(stack, job)
    if not set(walks) <= regions:
        raise AssertionError(f"kernel regions missing: {regions}")


def phase_mesh(stack, cfg, counter, seed: int, chips: int):
    from repro.configs import ShapeConfig, TrainConfig
    from repro.launch.mesh import make_mesh_for
    from repro.launch.steps import build_train_bundle, make_pc
    from repro.parallel.sharding import rules_for

    shape = ShapeConfig("smoke", kind="train", **TRAIN_SHAPE)
    tcfg = TrainConfig(total_steps=MESH_STEPS, seed=seed)
    one = _monitored_train(stack, cfg,
                           dataclasses.replace(tcfg, total_steps=1), shape,
                           "smoke-1dev", counter)
    mesh = make_mesh_for(chips)
    rules = rules_for("train")
    bundle = build_train_bundle(cfg, shape, tcfg, mesh, rules)
    job = "smoke-mesh"
    r = _monitored_train(stack, cfg, tcfg, shape, job, counter,
                         pc=make_pc(rules, mesh), mesh=mesh,
                         in_shardings=bundle.in_shardings)
    diff = abs(r["losses"][0] - one["losses"][0])
    coll = _job_hpm(stack, job, "ici_gb_per_s")
    log(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
        f"losses={r['losses']} one_device_first_loss={one['losses'][0]} "
        f"diff={diff:.3e} (tol {MESH_LOSS_TOL}) "
        f"median_step_s={statistics.median(r['step_s']):.4f} "
        f"compiles_after_warmup={r['compiles_after_warmup']} "
        f"ici_gb_per_s={coll}")
    if not all(map(math.isfinite, r["losses"])) or diff > MESH_LOSS_TOL:
        raise AssertionError("mesh loss disagrees with one device")
    if len(coll) != MESH_STEPS or min(coll) <= 0:
        raise AssertionError("hpm collective_bytes is not > 0 on the mesh")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    dev = phase_device(args.chips)
    log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()

    from repro.core import MonitoringStack
    cfg = model_config()
    stack = MonitoringStack.inprocess(out_dir=str(OUT_DIR / "lms"))
    try:
        if args.chips == 4:
            phase_mesh(stack, cfg, counter, args.seed, args.chips)
        else:
            phase_train(stack, cfg, counter, args.seed)
            phase_serve(stack, cfg, args.seed)
            phase_kernels(stack, args.seed)
    finally:
        stack.close()

    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
