"""Serving traffic: a closed loop of clients against the static-batch engine.

``clients`` clients each send their next request when the last one is
answered; the engine (``ServingEngine.submit`` / ``run_batch``) serves up
to ``max_batch`` at a time, so every batch is full.  The requests' sizes
come from the traffic file's batch deck, taken in its order: every seed
serves the same sizes, and the seed draws the prompts' tokens and which
prompt of a batch gets which answer length.  Warm-up serves one short
batch per padded prompt length of the deck, which compiles every prefill
and the decode program the window uses.

The window opens after warm-up and closes when the first batch ending
``--seconds`` later is answered.  Every call of the engine's decode step in
the window is stamped on the host clock: the gaps between a batch's
consecutive calls are the inter-token latency its clients see.

Then the check.  Whole batches of the window, drawn from the seed (the one
holding the longest request first) until they hold ``sample_requests``
requests, are run again through the engine's own jitted prefill and
decode, laid out as ``run_batch`` lays them out, with the served tokens
forced as the decode inputs (``replay``): the same compiled programs at
the same shapes, so ``replay_tokens_mismatch`` counts the served tokens
that the replay's argmax does not give again (an exact count).  The
replay's logits at the served positions, over the real vocabulary, are
compared with the plain reference's (``reference/moe_serve.py``, float32
at ``highest`` precision) over the prompt as the engine laid it out
(left-padded with id 0 to the batch's length) and the served tokens:
``served_logit_rel_err`` is the mean over those positions of
``|l_prog - l_ref| / |l_ref|`` (L2 norms).

The weights are made here, on the device, in one jitted call from the
seed, in the program's layout and in the type they are served in.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import random
import time
from typing import Optional

import numpy as np

from benchmarks.lms_bench import bench, servetrace, trace_reduce
from benchmarks.lms_bench.generators.train import _start_trace
from benchmarks.lms_bench.hostspans import Spans
from benchmarks.lms_bench.reference import moe_serve

JOB_HOST = "host0"

PROGRAM_KEYS = {            # configuration key -> ModelConfig attribute
    "hidden_size": "d_model", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "compute_dtype": "dtype", "num_hidden_layers": "num_layers",
    "sliding_window": "sliding_window",
}
MOE_KEYS = {"num_local_experts": "num_experts",
            "num_experts_per_tok": "top_k",
            "intermediate_size": "d_ff_expert",
            "moe_capacity_factor": "capacity_factor"}


def model_config(conf: dict):
    from repro.configs import get_config
    base = get_config(conf["arch"], smoke=conf.get("smoke", False))
    cfg = dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"],
        moe=dataclasses.replace(base.moe,
                                capacity_factor=conf["moe_capacity_factor"]))
    bad = {k: (conf[k], getattr(cfg, a)) for k, a in PROGRAM_KEYS.items()
           if k in conf and conf[k] != getattr(cfg, a)}
    bad.update({k: (conf[k], getattr(cfg.moe, a))
                for k, a in MOE_KEYS.items()
                if k in conf and conf[k] != getattr(cfg.moe, a)})
    if bad:
        raise ValueError(f"the program's {conf['arch']} departs from "
                         f"{conf['name']}: {bad}")
    return cfg


def make_weights(cfg, conf: dict, seed: int):
    """Every leaf of the program's parameter tree, normal with standard
    deviation 1/sqrt(fan-in) (the spec's own scale where it states one),
    norms at one, all in ``weight_dtype``, in one jitted call.  Large
    leaves are made one slice of their leading axes at a time."""
    import jax
    import jax.numpy as jnp
    from repro.models.params import ParamSpec
    from repro.models.transformer import model_specs

    dtype = jnp.dtype(conf["weight_dtype"])
    specs = model_specs(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))

    def std(path, s):
        if s.scale is not None:
            return s.scale
        name = str(path[-1])
        fan_in = conf["intermediate_size"] if "w_down" in name \
            else conf["hidden_size"]
        return 1.0 / math.sqrt(fan_in)

    def leaf(seed_arr, i, path, s):
        if s.init == "ones":
            return jnp.ones(s.shape, dtype)
        if s.init == "zeros":
            return jnp.zeros(s.shape, dtype)
        key = jax.random.fold_in(jax.random.key(seed_arr), i)
        sd = std(path, s)
        lead = s.shape[:-2] if len(s.shape) > 2 else ()
        n = math.prod(lead)
        if n <= 1:
            return (jax.random.normal(key, s.shape, jnp.float32) * sd
                    ).astype(dtype)
        keys = jax.random.split(key, n)
        out = jax.lax.map(lambda k: (jax.random.normal(
            k, s.shape[-2:], jnp.float32) * sd).astype(dtype), keys)
        return out.reshape(s.shape)

    def make(seed_arr):
        return jax.tree.unflatten(
            treedef, [leaf(seed_arr, i, p, s) for i, (p, s) in enumerate(flat)])
    # the seed is an argument, so one compiled program serves every seed
    return jax.jit(make)(jnp.uint32(seed))


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Batch:
    plen: int
    start: float
    end: float
    requests: list              # finished repro Requests


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    batches: list               # [Batch]
    ann: object                 # the traced window's annotation, or None
    trace_from: Optional[int]   # index of the first traced batch


def batch_requests(traffic: dict, k: int, rng, vocab: int) -> list:
    """(prompt ids, new tokens) of the deck's k-th batch; ``rng`` is a
    numpy Generator."""
    deck = traffic["batches"][k % len(traffic["batches"])]
    new = rng.permutation(deck["new"])
    return [(rng.integers(1, vocab, plen, dtype=np.int32), int(n))
            for plen, n in zip(deck["prompts"], new)]


def _serve(engine, reqs) -> list:
    for ids, n in reqs:
        engine.submit(ids, max_new_tokens=n)
    return engine.run_batch()


def _warmup(engine, traffic, rng, vocab):
    """One short batch per padded prompt length the deck uses."""
    seen = set()
    for k in range(len(traffic["batches"])):
        plen = max(traffic["batches"][k]["prompts"])
        if plen in seen:
            continue
        seen.add(plen)
        reqs = [(ids, traffic["warmup_new_tokens"]) for ids, _ in
                batch_requests(traffic, k, rng, vocab)]
        _serve(engine, reqs)


class DecodeClock:
    """Host-clock stamps of the decode step's calls, one list per batch,
    taken while ``active``."""

    def __init__(self):
        self.active = False
        self.stamps = []

    def wrap(self, fn):
        def clocked(*args, **kwargs):
            if self.active:
                self.stamps[-1].append(time.monotonic())
            return fn(*args, **kwargs)
        return clocked

    def new_batch(self):
        self.stamps.append([])

    def gaps(self) -> list:
        return [b - a for st in self.stamps for a, b in zip(st, st[1:])]


def serve_window(engine, traffic, seed, seconds, trace_dir=None,
                 n_batches=None, clock=None) -> Window:
    """Warm-up, then batches until one ends ``seconds`` after the window
    opened (or ``n_batches`` of them)."""
    vocab = engine.cfg.vocab_size
    rng = np.random.default_rng(bench.seed31(seed))
    _warmup(engine, traffic, np.random.default_rng(bench.seed31(seed) + 1),
            vocab)
    if clock is not None:
        clock.active = True
    t0 = time.monotonic()
    batches, ann, trace_from, k = [], None, None, 0
    while True:
        reqs = batch_requests(traffic, k, rng, vocab)
        start = time.monotonic()
        if trace_dir is not None and ann is None and \
                start >= t0 + seconds - traffic["trace_s"]:
            ann, trace_from = _start_trace(trace_dir), k
        if clock is not None:
            clock.new_batch()
        done = _serve(engine, reqs)
        end = time.monotonic()
        plen = max(len(r.prompt) for r in done)
        batches.append(Batch(plen, start, end, done))
        k += 1
        if (n_batches is None and end - t0 >= seconds) or k == n_batches:
            if ann is not None:
                ann.__exit__(None, None, None)
            if clock is not None:
                clock.active = False
            return Window(t0, end, batches, ann, trace_from)


# --------------------------------------------------------------------------
# the comparison with the reference
# --------------------------------------------------------------------------


def sample_batches(batches, n: int, seed: int) -> list:
    """Indices of whole batches holding at least ``n`` requests: a batch
    holding a longest request (prompt and answer; the seed breaks ties),
    then others drawn from the seed."""
    order = list(range(len(batches)))
    random.Random(bench.seed31(seed) + 2).shuffle(order)
    longest = max(order, key=lambda k: max(
        (batches[k].plen + len(r.output), len(r.output))
        for r in batches[k].requests))
    picked, rows = [], 0
    for k in [longest] + [k for k in order if k != longest]:
        if rows >= n:
            break
        picked.append(k)
        rows += len(batches[k].requests)
    return picked


def replay(engine, batch: Batch):
    """The batch run again through the engine's own jitted prefill and
    decode, laid out as ``run_batch`` lays it out (prompts left-padded to
    the batch's length, a fresh ``init_cache`` of ``max_len``, the same
    positions), each row's served tokens forced as its decode inputs (a
    row past its answer gets the step's own argmax, as in ``run_batch``).
    Returns (served tokens that the replay's argmax does not give,
    [each row's logits at its served positions, real vocabulary, float32])."""
    import jax.numpy as jnp
    import repro.serve.engine as engine_mod
    reqs, plen = batch.requests, batch.plen
    vocab = engine.cfg.vocab_size
    toks = np.zeros((len(reqs), plen), np.int32)
    for i, r in enumerate(reqs):
        toks[i, plen - len(r.prompt):] = r.prompt
    cache = engine_mod.init_cache(engine.cfg, len(reqs), engine.max_len)
    logits, cache = engine.prefill(engine.params, jnp.asarray(toks), cache)
    steps = max(r.max_new_tokens for r in reqs)
    rows, mismatch = [[] for _ in reqs], 0
    for s in range(steps):
        nxt = np.array(jnp.argmax(logits, axis=-1))
        host = np.asarray(logits)[:, :vocab].astype(np.float32)
        for i, r in enumerate(reqs):
            if s < len(r.output):
                rows[i].append(host[i])
                mismatch += int(nxt[i] != r.output[s])
                nxt[i] = r.output[s]
        if s + 1 < steps:
            logits, cache = engine.decode(engine.params, cache,
                                          jnp.asarray(nxt)[:, None],
                                          jnp.int32(plen + s))
    return mismatch, [np.stack(x) for x in rows]


def replay_sample(engine, batches, n: int, seed: int):
    """``replay`` of the sampled batches: (mismatches, [(request, batch
    plen, program logits)])."""
    mismatch, picked = 0, []
    for k in sample_batches(batches, n, seed):
        m, rows = replay(engine, batches[k])
        mismatch += m
        picked += [(r, batches[k].plen, lg)
                   for r, lg in zip(batches[k].requests, rows)]
    return mismatch, picked


def laid_out(req, plen: int, max_len: int):
    """The sequence as the engine ran it (left-padded prompt, then the
    served tokens but the last), padded at the end to ``max_len``, and
    the positions whose logits chose each served token."""
    pad = plen - len(req.prompt)
    seq = np.zeros(max_len, np.int32)
    seq[pad:plen] = req.prompt
    seq[plen:plen + len(req.output) - 1] = req.output[:-1]
    positions = np.arange(plen - 1, plen - 1 + len(req.output))
    return seq, positions


# A routing decision whose margin in the reference (second expert's
# probability less the third's) is under this can go either way in a bf16
# program: its router sees activations about 1 % off the float32 ones,
# which moves a probability of ~0.2 by ~2e-3.
ROUTE_EPS = 3e-3


def readings(params, conf, picked, max_len, control=None) -> dict:
    """The program's logits (``picked``: [(request, batch plen, logits at
    its served positions)]) against the reference's at the same positions:
    ``served_logit_rel_err``, the mean relative L2 error over the served
    positions whose routing is decided (``ROUTE_EPS``): a position whose
    own margin in the reference is under it is left out, and so is a row
    whose left padding's is, since one pad token's choice of experts is
    repeated at every pad position that all of the row's tokens attend.
    Logged, not compared: the same mean over every position
    (``served_logit_rel_err_all``) and ``served_logit_gap``, the widest gap
    by which a served token's reference logit lies below the reference's
    best.  With ``control`` (a narrow float type), the reference in that
    precision read the same way: ``control_logit_rel_err``."""
    import jax.numpy as jnp
    key = moe_serve.cfg_key(conf)
    rel, every, gap, ctl, rows_out = [], [], [], [], 0
    for req, plen, prog in picked:
        seq, pos = laid_out(req, plen, max_len)
        seq, at = jnp.asarray(seq), jnp.asarray(pos)
        ref, margin = moe_serve.logits(params, seq, key)
        margin = np.asarray(margin)
        pad = plen - len(req.prompt)
        keep = margin[pos] >= ROUTE_EPS
        if pad and margin[:pad].min() < ROUTE_EPS:
            keep[:], rows_out = False, rows_out + 1
        rows = np.asarray(ref[at])
        err = moe_serve.rel_errors(prog, rows)
        every.append(err)
        rel.append(err[keep])
        gap.append(moe_serve.token_gaps(ref, pos,
                                        np.asarray(req.output, np.int32)))
        if control is not None:
            low = moe_serve.logits(params, seq, key, control)[0]
            ctl.append(moe_serve.rel_errors(np.asarray(low[at]), rows)[keep])
    rel = np.concatenate(rel)
    bench.log(f"check: {len(rel)} of {sum(len(e) for e in every)} served "
              f"positions compared; {rows_out} rows left out for their "
              "padding's routing")
    out = {"served_logit_rel_err": float(rel.mean()),
           "served_logit_rel_err_all": float(np.concatenate(every).mean()),
           "served_logit_gap": float(np.concatenate(gap).max())}
    if ctl:
        out["control_logit_rel_err"] = float(np.concatenate(ctl).mean())
    return out


def _bad_requests(batches, vocab) -> int:
    return sum(1 for b in batches for r in b.requests
               if len(r.output) != r.max_new_tokens
               or not all(0 <= t < vocab for t in r.output))


# --------------------------------------------------------------------------
# a run
# --------------------------------------------------------------------------


def _engine(cell, params, stack):
    from repro.serve.engine import ServingEngine
    um = stack.usermetric(host=JOB_HOST)
    engine = ServingEngine(model_config(cell.config), params,
                           max_batch=cell.traffic["max_batch"],
                           max_len=cell.traffic["max_len"], usermetric=um)
    return engine, um


def _weights(cell, seed):
    return make_weights(model_config(cell.config), cell.config,
                        bench.seed31(seed))


def run(cell: bench.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, devices) -> bench.Outcome:
    import jax
    from repro.core import MonitoringStack

    traffic = cell.traffic
    out = bench.OUT_DIR / cell.name
    trace_dir = out / "trace"
    if trace_dir.exists():
        import shutil
        shutil.rmtree(trace_dir)
    stack = MonitoringStack.inprocess(out_dir=str(out / "lms"))
    spans = Spans() if trace else None
    clock = DecodeClock()
    try:
        with stack.job(f"lms-bench-{cell.name}", user="bench",
                       hosts=[JOB_HOST]):
            engine, um = _engine(cell, _weights(cell, seed), stack)
            engine.decode = clock.wrap(engine.decode)
            if spans is not None:
                # name the device's idle gaps by the engine's host calls
                import repro.serve.engine as engine_mod
                spans.install_attr(engine, "prefill", "serve:prefill")
                spans.install_attr(engine, "decode", "serve:decode")
                spans.install_attr(engine_mod, "init_cache",
                                   "serve:init_cache")
            w = serve_window(engine, traffic, seed, seconds,
                             trace_dir if trace else None, clock=clock)
            um.flush()
        if w.ann is not None:
            jax.profiler.stop_trace()
        peak = bench.memory_peak(devices)
    finally:
        if spans is not None:
            spans.uninstall()
        stack.close()
    batches = w.batches
    # each batch: its time, then to its first decode call (init_cache,
    # prefill, the first token's sync) and its longest gap between calls
    bench.log("batches " + " ".join(
        f"{b.plen}:{b.end - b.start:.3f}s({(st or [b.end])[0] - b.start:.3f}"
        f"/{max((y - x for x, y in zip(st, st[1:])), default=0.0):.4f})"
        for b, st in zip(batches, clock.stamps)))

    reqs = [r for b in batches for r in b.requests]
    delivered = sum(len(r.output) for r in reqs)
    window_s = w.t_end - w.t0
    e2e = {"serve_tokens_per_s": delivered / window_s,
           "setup_s": w.t0 - t_process}
    bad = _bad_requests(batches, cell.config["vocab_size"])
    t = time.monotonic()
    mismatch, picked = replay_sample(engine, batches,
                                     traffic["sample_requests"], seed)
    t_replay = time.monotonic() - t
    params = engine.params
    engine = None
    gc.collect()
    g = readings(params, cell.config, picked, traffic["max_len"])
    bench.log(f"check: {len(picked)} requests, "
              f"{sum(len(lg) for _, _, lg in picked)} served positions; "
              f"replay {t_replay:.1f} s, reference "
              f"{time.monotonic() - t - t_replay:.1f} s")
    g["replay_tokens_mismatch"] = float(mismatch)
    g["requests_wrong"] = float(bad)
    bench.log(f"not compared: mean over every position "
              f"{g['served_logit_rel_err_all']!r}, widest served gap "
              f"{g['served_logit_gap']!r}")
    checks = [bench.Check(n, g[n], cell.limits[n]) for n in cell.limits]
    ctx = {"serve_batches": batches, "window_s": window_s,
           "config": cell.config, "chips": len(devices),
           "device_kind": devices[0].device_kind,
           "max_batch": traffic["max_batch"],
           "decode_gaps_s": clock.gaps(), "trace_from": w.trace_from}
    outcome = bench.Outcome(e2e, len(reqs), bad, checks, peak, ctx)
    if trace:
        outcome.trace = trace_reduce.reduce_dir(
            str(trace_dir), excerpt_path=out / "trace_excerpt.json")
        ctx["trace"] = outcome.trace
        ctx["serve_trace"] = servetrace.load(
            trace_reduce.find_xplane(str(trace_dir)))
    return outcome


# --------------------------------------------------------------------------
# calibration: the program, the control and planted faults
# --------------------------------------------------------------------------


def _decode_pos_off(engine):
    """The decode step given ``pos - 1``: each token's key and value
    overwrite the one before, and its rotary position is one off."""
    decode = engine.decode
    engine.decode = lambda params, cache, tokens, pos: decode(
        params, cache, tokens, pos - 1)


def _expert_dropped(engine):
    """Expert 0 of every layer gives nothing (its down projection zeroed
    in the program's weights only)."""
    p = engine.params
    moe = dict(p["moe_layers"]["moe"])
    moe["w_down"] = moe["w_down"].at[:, 0].set(0)
    engine.params = dict(p, moe_layers=dict(p["moe_layers"], moe=moe))


FAULTS = {"decode_pos_off": _decode_pos_off,
          "expert_dropped": _expert_dropped}


def check_pass(cell: bench.Cell, params, seed: int, stack, plant=None,
               control=None) -> dict:
    """One pass of the batch deck at the cell's load (with ``plant``
    applied to the engine), then the check's readings; with ``control``,
    the control's as well."""
    traffic = cell.traffic
    with stack.job(f"lms-bench-cal-{seed}", user="bench", hosts=[JOB_HOST]):
        engine, _ = _engine(cell, params, stack)
        if plant is not None:
            plant(engine)
        batches = serve_window(engine, traffic, seed, 0.0,
                               n_batches=len(traffic["batches"])).batches
    mismatch, picked = replay_sample(engine, batches,
                                     traffic["sample_requests"], seed)
    engine = None
    gc.collect()
    g = readings(params, cell.config, picked, traffic["max_len"], control)
    g["replay_tokens_mismatch"] = mismatch
    g["requests_wrong"] = _bad_requests(batches, cell.config["vocab_size"])
    return g


def calibrate_seed(cell: bench.Cell, seed: int, stack,
                   control: bool = True, devices=None) -> dict:
    """The program's readings on one seed; with ``control``, the float8
    control's and each planted fault's too.  Serving runs on the default
    device whatever ``devices`` holds."""
    import jax.numpy as jnp
    params = _weights(cell, seed)
    prog = check_pass(cell, params, seed, stack,
                      control=jnp.float8_e4m3fn if control else None)
    out = {"seed": seed, "program": prog}
    if control:
        out["control_fp8"] = {
            "served_logit_rel_err": prog.pop("control_logit_rel_err")}
        for name, plant in FAULTS.items():
            out[name] = check_pass(cell, params, seed, stack, plant=plant)
    return out
