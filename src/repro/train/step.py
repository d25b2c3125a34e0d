"""Distributed train step factory.

Builds the jit-able ``train_step(params, opt_state, batch, step)`` for a
(model config x train config x mesh).  Features:

* microbatched gradient accumulation (``num_microbatches``) via lax.scan,
  fp32 accumulators;
* global-norm clipping;
* remat policy + attention implementation knobs (the §Perf levers);
* hierarchical gradient sync: per-pod gradients under a manual-``pod``
  shard_map with int8 compression on the slow cross-pod links, while
  GSPMD keeps managing FSDP/TP inside the pod (``grad_compression`` knob);
* optimizer update (AdamW / Adafactor) fused into the step;
* rich step metrics for the LMS host agent (loss, grad norm, MoE stats).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.configs.base import ModelConfig, TrainConfig
from repro.models.transformer import loss_fn
from repro.train.compression import compressed_pmean
from repro.train.optim import (clip_by_global_norm, get_optimizer,
                               global_norm, lr_schedule)


def _grads_and_metrics(params, batch, model_cfg: ModelConfig,
                       train_cfg: TrainConfig, pc):
    """Microbatched value_and_grad; returns (grads fp32, metrics)."""
    nm = train_cfg.num_microbatches
    vg = jax.value_and_grad(
        partial(loss_fn, cfg=model_cfg, pc=pc,
                attn_impl=getattr(train_cfg, "attn_impl", "auto"),
                remat=train_cfg.remat_policy,
                scan_unroll=getattr(train_cfg, "scan_unroll", 1)),
        has_aux=True)

    sync_dt = jnp.dtype(getattr(train_cfg, "grad_sync_dtype", "float32"))

    def _sync_cast(grads):
        """Cast pre-reduction gradients so the DP all-reduce runs at the
        configured precision (bf16 halves the dominant collective volume;
        the optimizer math stays fp32)."""
        if sync_dt == jnp.float32:
            return jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return jax.tree.map(
            lambda g: g.astype(sync_dt).astype(jnp.float32), grads)

    if nm <= 1:
        (loss, metrics), grads = vg(params, batch=batch)
        return _sync_cast(grads), metrics

    # Interleaved microbatch split: (B, ...) -> (nm, B/nm, ...) where
    # microbatch m takes rows {m, m+nm, m+2nm, ...}.  Each DP shard's
    # contiguous row-block then contributes one row to EVERY microbatch, so
    # the per-microbatch slice keeps the full (pod, data) batch sharding —
    # a contiguous split would leave microbatches spanning a fraction of
    # the DP axis and GSPMD silently replicates the rest (verified in the
    # dry-run: 10x per-device FLOPs on the 2x16x16 mesh).
    def split(x):
        return x.reshape((x.shape[0] // nm, nm) + x.shape[1:]).swapaxes(0, 1)
    mbatch = jax.tree.map(split, batch)

    def body(carry, mb):
        acc, metrics_acc = carry
        (loss, metrics), grads = vg(params, batch=mb)
        acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / nm,
                           acc, grads)
        metrics_acc = jax.tree.map(lambda a, m: a + m / nm, metrics_acc,
                                   metrics)
        return (acc, metrics_acc), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    zmetrics = {"loss": jnp.float32(0), "moe_aux_loss": jnp.float32(0),
                "moe_dropped_frac": jnp.float32(0),
                "moe_max_load": jnp.float32(0)}
    (grads, metrics), _ = jax.lax.scan(body, (zeros, zmetrics), mbatch)
    return _sync_cast(grads), metrics


def make_train_step(model_cfg: ModelConfig, train_cfg: TrainConfig, *,
                    pc=None, mesh: Optional[Mesh] = None):
    """Returns train_step(params, opt_state, batch, step) -> (p, o, metrics).

    ``batch`` is the global batch dict; under pjit its leaves arrive sharded
    per the input shardings chosen by the launcher.
    """
    opt = get_optimizer(train_cfg)
    lr_fn = lr_schedule(train_cfg)
    compress = train_cfg.grad_compression
    use_pod_sync = (compress not in ("", "none") and mesh is not None
                    and "pod" in mesh.axis_names
                    and mesh.devices.shape[mesh.axis_names.index("pod")] > 1)

    def compute_grads(params, batch):
        if not use_pod_sync:
            return _grads_and_metrics(params, batch, model_cfg, train_cfg,
                                      pc)

        # manual pod axis: per-pod grads -> compressed cross-pod mean.
        # GSPMD (auto axes) keeps handling data/model sharding inside.
        def per_pod(params, batch):
            grads, metrics = _grads_and_metrics(params, batch, model_cfg,
                                                train_cfg, pc)
            grads = compressed_pmean(grads, "pod", compress)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"),
                                   metrics)
            return grads, metrics

        pspec = jax.tree.map(lambda _: P(), params)
        bspec = jax.tree.map(lambda _: P("pod"), batch)
        return shard_map(
            per_pod, mesh=mesh,
            in_specs=(pspec, bspec),
            out_specs=(pspec, jax.tree.map(lambda _: P(), {"loss": 0,
                       "moe_aux_loss": 0, "moe_dropped_frac": 0,
                       "moe_max_load": 0})),
            check_vma=False, axis_names={"pod"})(params, batch)

    def train_step(params, opt_state, batch, step):
        grads, metrics = compute_grads(params, batch)
        if train_cfg.grad_clip_norm > 0:
            grads, gnorm = clip_by_global_norm(grads,
                                               train_cfg.grad_clip_norm)
        else:
            gnorm = global_norm(grads)
        lr = lr_fn(step)
        new_params, new_opt = opt.update(grads, opt_state, params, lr)
        metrics = dict(metrics)
        metrics.update({"grad_norm": gnorm, "lr": lr,
                        "param_norm": global_norm(new_params)})
        return new_params, new_opt, metrics

    return train_step, opt


def make_eval_step(model_cfg: ModelConfig, train_cfg: TrainConfig, *,
                   pc=None):
    def eval_step(params, batch):
        _, metrics = loss_fn(params, model_cfg, batch, pc=pc)
        return metrics
    return eval_step
