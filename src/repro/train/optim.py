"""Optimizers (pure pytree functions, no external deps).

* **AdamW** — default for <100B-parameter configs.
* **Adafactor** — factored second moment + bf16 momentum; the production
  choice for the assigned giants (nemotron-4-340b, deepseek-v2-236b), where
  AdamW's 8 bytes/param of moments would not fit v5e HBM at 256 chips
  (DESIGN.md §6).  Factored states follow Shazeer & Stern 2018.

Optimizer states are pytrees of the same structure as the params, so the
logical-axis sharding rules apply to them unchanged (moments inherit the
param's ParamSpec axes — see ``opt_state_specs``).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.models.params import ParamSpec, spec


@dataclass(frozen=True)
class Optimizer:
    """init(params)->state; update(grads, state, params, lr)->(new_p, new_s)."""

    init: Callable
    update: Callable
    name: str = ""


# --------------------------------------------------------------------------
# Utilities
# --------------------------------------------------------------------------


@jax.named_scope("optimizer")
def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


@jax.named_scope("optimizer")
def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale
                                   ).astype(x.dtype), tree), norm


def lr_schedule(cfg: TrainConfig):
    """Linear warmup -> cosine decay to 10% of peak."""
    @jax.named_scope("optimizer")
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = cfg.learning_rate * step / max(cfg.warmup_steps, 1)
        prog = jnp.clip((step - cfg.warmup_steps)
                        / max(cfg.total_steps - cfg.warmup_steps, 1), 0., 1.)
        cos = cfg.learning_rate * (0.1 + 0.45 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < cfg.warmup_steps, warm, cos)
    return lr


# --------------------------------------------------------------------------
# AdamW
# --------------------------------------------------------------------------


def adamw(cfg: TrainConfig) -> Optimizer:
    b1, b2, eps, wd = cfg.beta1, cfg.beta2, cfg.eps, cfg.weight_decay

    def init(params):
        zeros = lambda p: jnp.zeros_like(p, jnp.float32)  # noqa: E731
        return {"m": jax.tree.map(zeros, params),
                "v": jax.tree.map(zeros, params),
                "count": jnp.zeros((), jnp.int32)}

    @jax.named_scope("optimizer")
    def update(grads, state, params, lr):
        count = state["count"] + 1
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            step = (m / c1) / (jnp.sqrt(v / c2) + eps)
            step = step + wd * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v

        flat = jax.tree.map(upd, grads, state["m"], state["v"], params)
        new_p = jax.tree.map(lambda t: t[0], flat,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda t: t[1], flat,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda t: t[2], flat,
                             is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"m": new_m, "v": new_v, "count": count}

    return Optimizer(init, update, "adamw")


# --------------------------------------------------------------------------
# Adafactor
# --------------------------------------------------------------------------


def _factored(shape) -> bool:
    return len(shape) >= 2 and shape[-1] > 1 and shape[-2] > 1


def adafactor(cfg: TrainConfig, momentum_dtype=jnp.bfloat16) -> Optimizer:
    eps2 = 1e-30
    clip_thresh = 1.0
    wd = cfg.weight_decay
    b1 = cfg.beta1                     # bf16 momentum (0 disables)

    def init(params):
        def one(p):
            if _factored(p.shape):
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                        jnp.float32),
                        "m": jnp.zeros_like(p, momentum_dtype)
                        if b1 else jnp.zeros((), jnp.float32)}
            return {"v": jnp.zeros_like(p, jnp.float32),
                    "m": jnp.zeros_like(p, momentum_dtype)
                    if b1 else jnp.zeros((), jnp.float32)}
        return {"s": jax.tree.map(one, params),
                "count": jnp.zeros((), jnp.int32)}

    @jax.named_scope("optimizer")
    def update(grads, state, params, lr):
        count = state["count"] + 1
        beta2 = 1.0 - count.astype(jnp.float32) ** -0.8   # schedule

        def one(g, s, p):
            g = g.astype(jnp.float32)
            g2 = jnp.square(g) + eps2
            if "vr" in s:
                vr = beta2 * s["vr"] + (1 - beta2) * jnp.mean(g2, axis=-1)
                vc = beta2 * s["vc"] + (1 - beta2) * jnp.mean(g2, axis=-2)
                denom = jnp.maximum(jnp.mean(vr, axis=-1, keepdims=True),
                                    eps2)
                vhat = vr[..., None] * vc[..., None, :] / denom[..., None]
                upd = g * jax.lax.rsqrt(vhat + eps2)
                new_s = {"vr": vr, "vc": vc}
            else:
                v = beta2 * s["v"] + (1 - beta2) * g2
                upd = g * jax.lax.rsqrt(v + eps2)
                new_s = {"v": v}
            # update clipping by RMS (Shazeer & Stern eq. 6)
            rms = jnp.sqrt(jnp.mean(jnp.square(upd)) + eps2)
            upd = upd / jnp.maximum(1.0, rms / clip_thresh)
            if b1:
                m = b1 * s["m"].astype(jnp.float32) + (1 - b1) * upd
                upd = m
                new_s["m"] = m.astype(momentum_dtype)
            else:
                new_s["m"] = s["m"]
            upd = upd + wd * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), new_s

        pairs = jax.tree.map(one, grads, state["s"], params,
                             is_leaf=lambda x: isinstance(x, dict)
                             and ("v" in x or "vr" in x))
        new_p = jax.tree.map(lambda t: t[0], pairs,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_s = jax.tree.map(lambda t: t[1], pairs,
                             is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"s": new_s, "count": count}

    return Optimizer(init, update, "adafactor")


def get_optimizer(cfg: TrainConfig) -> Optimizer:
    if cfg.optimizer == "adamw":
        return adamw(cfg)
    if cfg.optimizer == "adafactor":
        return adafactor(cfg)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")


# --------------------------------------------------------------------------
# Spec-level optimizer state (for AOT lowering + sharding derivation)
# --------------------------------------------------------------------------


def opt_state_specs(param_specs, cfg: TrainConfig):
    """ParamSpec tree for the optimizer state (moments inherit param axes)."""
    count = spec((), (), jnp.int32, init="zeros")
    if cfg.optimizer == "adamw":
        def mom(s: ParamSpec) -> ParamSpec:
            return spec(s.shape, s.axes, jnp.float32, init="zeros")
        return {"m": jax.tree.map(mom, param_specs,
                                  is_leaf=lambda x: isinstance(x, ParamSpec)),
                "v": jax.tree.map(mom, param_specs,
                                  is_leaf=lambda x: isinstance(x, ParamSpec)),
                "count": count}

    def one(s: ParamSpec):
        if _factored(s.shape):
            return {"vr": spec(s.shape[:-1], s.axes[:-1], jnp.float32,
                               init="zeros"),
                    "vc": spec(s.shape[:-2] + s.shape[-1:],
                               s.axes[:-2] + s.axes[-1:], jnp.float32,
                               init="zeros"),
                    "m": spec(s.shape, s.axes, jnp.bfloat16, init="zeros")
                    if cfg.beta1 else spec((), (), jnp.float32,
                                           init="zeros")}
        return {"v": spec(s.shape, s.axes, jnp.float32, init="zeros"),
                "m": spec(s.shape, s.axes, jnp.bfloat16, init="zeros")
                if cfg.beta1 else spec((), (), jnp.float32, init="zeros")}
    return {"s": jax.tree.map(one, param_specs,
                              is_leaf=lambda x: isinstance(x, ParamSpec)),
            "count": count}
