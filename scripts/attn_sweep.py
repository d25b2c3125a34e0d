#!/usr/bin/env python3
"""Time causal GQA attention, forward plus backward, on the chip: the flash
kernels at several block sizes against the masked XLA path the train step
ran before, at a train cell's shape (granite-3-8b: B=2, S=2048, 32 query
heads over 8 KV heads of 128, bf16).  Checks each against the float32
reference's output and gradients first.

    python scripts/attn_sweep.py [--blocks 512x512,256x512,...] [--iters 20]

Prints one line per variant and, last, a JSON object of the medians (ms
per forward + backward call, and per forward call alone).  Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def _times_ms(fn, args, iters, reps):
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) / iters * 1e3)
    return times


def _median_ms(fn, args, iters, reps):
    return statistics.median(_times_ms(fn, args, iters, reps))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--blocks", default="512x512,256x512,512x256,256x256,"
                                        "128x128")
    ap.add_argument("--shape", default="2,32,8,2048,128",
                    help="B,H,KV,S,D")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.models.attention import full_attention

    if jax.default_backend() != "tpu":
        print("attn_sweep: no TPU", file=sys.stderr)
        return 2
    b, h, kvh, s, d = map(int, args.shape.split(","))
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.bfloat16)     # BSHD,
    k = jax.random.normal(keys[1], (b, s, kvh, d), jnp.bfloat16)   # as the
    v = jax.random.normal(keys[2], (b, s, kvh, d), jnp.bfloat16)   # model
    do = jax.random.normal(keys[3], (b, s, h, d), jnp.bfloat16)

    def vjp_of(f):
        @jax.jit
        def run(q, k, v, do):
            o, pull = jax.vjp(f, q, k, v)
            return (o,) + pull(do)
        return run

    def reference(q, k, v):
        t = (0, 2, 1, 3)
        return ref.attention_ref(q.transpose(t), k.transpose(t),
                                 v.transpose(t)).transpose(t)

    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    with jax.default_matmul_precision("highest"):
        want = vjp_of(reference)(*f32)

    variants = {"masked": lambda q, k, v: full_attention(q, k, v,
                                                         causal=True)}
    for blk in args.blocks.split(","):
        bq, bk = map(int, blk.split("x"))
        variants[f"flash_{bq}x{bk}"] = (
            lambda q, k, v, bq=bq, bk=bk: ops.flash_attention_bshd(
                q, k, v, bq=bq, bk=bk))

    out = {}
    for name, f in variants.items():
        run = vjp_of(f)
        got = run(q, k, v, do)
        errs = [float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                      / jnp.max(jnp.abs(w))) for g, w in zip(got, want)]
        times = _times_ms(run, (q, k, v, do), args.iters, args.reps)
        ms = statistics.median(times)
        fwd_ms = _median_ms(jax.jit(f), (q, k, v), args.iters, args.reps)
        out[name] = {"ms": ms, "fwd_ms": fwd_ms, "rel_err_o_dq_dk_dv": errs}
        print(f"{name}: {ms:.3f} ms (reps {[round(x, 3) for x in times]}), "
              f"forward alone {fwd_ms:.3f} ms; rel err o/dq/dk/dv "
              f"{[f'{e:.2e}' for e in errs]}", flush=True)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "shape": args.shape, "results": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
