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
``--seconds`` later is answered.  Then a sample of the window's requests,
drawn from the seed and holding the longest, is run through the plain
reference (``reference/moe_serve.py``) over the prompt as the engine laid
it out (left-padded with id 0 to the batch's length) and the served
tokens: ``served_logit_gap`` is the widest gap by which a served token's
reference logit lies below the reference's best at its position.

The weights are made here, on the device, in one jitted call from the
seed, in the program's layout and in the type they are served in.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import random
import time

import numpy as np

from benchmarks.lms_bench import bench, trace_reduce
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


def serve_window(engine, traffic, seed, seconds, trace_dir=None,
                 n_batches=None):
    """Warm-up, then batches until one ends ``seconds`` after the window
    opened (or ``n_batches`` of them).  Returns (t0, t_end, batches,
    trace annotation)."""
    vocab = engine.cfg.vocab_size
    rng = np.random.default_rng(bench.seed31(seed))
    _warmup(engine, traffic, np.random.default_rng(bench.seed31(seed) + 1),
            vocab)
    t0 = time.monotonic()
    batches, ann, k = [], None, 0
    while True:
        reqs = batch_requests(traffic, k, rng, vocab)
        start = time.monotonic()
        if trace_dir is not None and ann is None and \
                start >= t0 + seconds - traffic["trace_s"]:
            ann = _start_trace(trace_dir)
        done = _serve(engine, reqs)
        end = time.monotonic()
        plen = max(len(r.prompt) for r in done)
        batches.append(Batch(plen, start, end, done))
        k += 1
        if (n_batches is None and end - t0 >= seconds) or k == n_batches:
            if ann is not None:
                ann.__exit__(None, None, None)
            return t0, end, batches, ann


# --------------------------------------------------------------------------
# the comparison with the reference
# --------------------------------------------------------------------------


def sample(batches, n: int, seed: int) -> list:
    """(request, batch plen) pairs: the longest request and ``n - 1``
    others drawn from the seed."""
    allr = [(r, b.plen) for b in batches for r in b.requests]
    longest = max(allr, key=lambda rp: (rp[1] + len(rp[0].output),
                                        len(rp[0].output)))
    rest = [rp for rp in allr if rp[0] is not longest[0]]
    rng = random.Random(bench.seed31(seed) + 2)
    return [longest] + rng.sample(rest, min(n - 1, len(rest)))


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


def gaps(params, conf, picked, max_len, control=None) -> dict:
    """The reference's gap of every served token (widest and mean over the
    sample); with ``control``, the same for the token the control puts
    first at each of those positions."""
    import jax.numpy as jnp
    key = moe_serve.cfg_key(conf)
    served, ctl = [], []
    for req, plen in picked:
        seq, pos = laid_out(req, plen, max_len)
        ref = moe_serve.logits(params, jnp.asarray(seq), key)
        served.append(moe_serve.token_gaps(
            ref, pos, np.asarray(req.output, np.int32)))
        if control is not None:
            low = moe_serve.logits(params, jnp.asarray(seq), key, control)
            first = np.asarray(jnp.argmax(low[jnp.asarray(pos)], axis=-1))
            ctl.append(moe_serve.token_gaps(ref, pos, first))
    out = {}
    for name, g in (("served", served), ("control", ctl)):
        if g:
            g = np.concatenate(g)
            out[f"{name}_logit_gap"] = float(g.max())
            out[f"{name}_logit_gap_mean"] = float(g.mean())
    return out


def _bad_requests(batches, vocab) -> int:
    return sum(1 for b in batches for r in b.requests
               if len(r.output) != r.max_new_tokens
               or not all(0 <= t < vocab for t in r.output))


# --------------------------------------------------------------------------
# a run
# --------------------------------------------------------------------------


def _engine(cell, seed, stack):
    from repro.serve.engine import ServingEngine
    cfg = model_config(cell.config)
    params = make_weights(cfg, cell.config, bench.seed31(seed))
    um = stack.usermetric(host=JOB_HOST)
    engine = ServingEngine(cfg, params, max_batch=cell.traffic["max_batch"],
                           max_len=cell.traffic["max_len"], usermetric=um)
    return engine, um


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
    try:
        with stack.job(f"lms-bench-{cell.name}", user="bench",
                       hosts=[JOB_HOST]):
            engine, um = _engine(cell, seed, stack)
            if spans is not None:
                # name the device's idle gaps by the engine's host calls
                import repro.serve.engine as engine_mod
                spans.install_attr(engine, "prefill", "serve:prefill")
                spans.install_attr(engine, "decode", "serve:decode")
                spans.install_attr(engine_mod, "init_cache",
                                   "serve:init_cache")
            t0, t_end, batches, ann = serve_window(
                engine, traffic, seed, seconds,
                trace_dir if trace else None)
            um.flush()
        if ann is not None:
            jax.profiler.stop_trace()
        mem = devices[0].memory_stats() or {}
        peak = int(mem.get("peak_bytes_in_use", 0))
    finally:
        if spans is not None:
            spans.uninstall()
        stack.close()
    bench.log("batches " + " ".join(
        f"{b.plen}:{b.end - b.start:.3f}s" for b in batches))

    vocab = cell.config["vocab_size"]
    reqs = [r for b in batches for r in b.requests]
    delivered = sum(len(r.output) for r in reqs)
    window_s = t_end - t0
    e2e = {"serve_tokens_per_s": delivered / window_s,
           "setup_s": t0 - t_process}
    bad = _bad_requests(batches, vocab)
    picked = sample(batches, traffic["sample_requests"], seed)
    params = engine.params
    engine = None
    gc.collect()
    g = gaps(params, cell.config, picked, traffic["max_len"])
    g["requests_wrong"] = float(bad)
    bench.log(f"widest served gap {g['served_logit_gap']!r} (not compared)")
    checks = [bench.Check(n, g[n], cell.limits[n])
              for n in cell.limits]
    ctx = {"serve_batches": batches, "window_s": window_s,
           "config": cell.config, "chips": len(devices),
           "device_kind": devices[0].device_kind,
           "max_batch": traffic["max_batch"]}
    outcome = bench.Outcome(e2e, len(reqs), bad, checks, peak, ctx)
    if trace:
        outcome.trace = trace_reduce.reduce_dir(
            str(trace_dir), excerpt_path=out / "trace_excerpt.json")
        ctx["trace"] = outcome.trace
    return outcome


def calibrate_seed(cell: bench.Cell, seed: int, stack,
                   control: bool = True) -> dict:
    """The program's and the control's readings on one seed: one pass of
    the batch deck at the cell's load, then the sample's comparison."""
    import jax.numpy as jnp
    traffic = cell.traffic
    with stack.job(f"lms-bench-cal-{seed}", user="bench", hosts=[JOB_HOST]):
        engine, um = _engine(cell, seed, stack)
        _, _, batches, _ = serve_window(engine, traffic, seed, 0.0,
                                        n_batches=len(traffic["batches"]))
    params = engine.params
    engine = None
    picked = sample(batches, traffic["sample_requests"], seed)
    g = gaps(params, cell.config, picked, traffic["max_len"],
             control=jnp.float8_e4m3fn if control else None)
    out = {"seed": seed,
           "program": {k: g[k] for k in ("served_logit_gap",
                                         "served_logit_gap_mean")},
           "requests_wrong": _bad_requests(batches,
                                           cell.config["vocab_size"])}
    if control:
        out["control_fp8"] = {
            "served_logit_gap": g["control_logit_gap"],
            "served_logit_gap_mean": g["control_logit_gap_mean"]}
    return out
