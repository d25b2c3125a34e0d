"""Step builders + abstract input specs for AOT lowering and the drivers.

Everything here is ShapeDtypeStruct-level: no allocation.  Input specs carry
*logical axes* (same ParamSpec mechanism as model weights), so one rule table
derives every input sharding.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.models.params import ParamSpec, abstract_params, spec
from repro.models.transformer import (cache_specs, forward, model_specs)
from repro.parallel.sharding import (PartitionConstraints, ShardingRules,
                                     logical_to_pspec, rules_for,
                                     shardings_for_specs)
from repro.train.optim import opt_state_specs
from repro.train.step import make_train_step


# --------------------------------------------------------------------------
# Param / cache spec variants
# --------------------------------------------------------------------------


def serve_param_specs(cfg: ModelConfig):
    """Serving weights in bf16 (fp32 master copies are a training concern)."""
    def f(s: ParamSpec) -> ParamSpec:
        if jnp.dtype(s.dtype).kind == "f":
            return ParamSpec(s.shape, s.axes, jnp.bfloat16, s.init, s.scale,
                             s.value)
        return s
    return jax.tree.map(f, model_specs(cfg),
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# --------------------------------------------------------------------------
# Input specs (ParamSpec trees with logical axes)
# --------------------------------------------------------------------------


def _extras_specs(cfg: ModelConfig, shape: ShapeConfig, *, decode: bool):
    out = {}
    if cfg.family == "vlm":
        if not decode:
            p = min(cfg.vlm_num_patches, max(shape.seq_len - 2, 1))
            out["patches"] = spec((shape.global_batch, p, cfg.d_model),
                                  ("batch", None, None), jnp.bfloat16)
        out["mrope_pos"] = spec(
            (shape.global_batch, 1 if decode else shape.seq_len, 3),
            ("batch", None, None), jnp.int32)
    if cfg.family == "encdec" and not decode:
        out["src_frames"] = spec(
            (shape.global_batch, cfg.encdec_source_len, cfg.d_model),
            ("batch", None, None), jnp.bfloat16)
    return out


def train_input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    return {"tokens": spec((b, s), ("batch", "seq"), jnp.int32),
            "labels": spec((b, s), ("batch", "seq"), jnp.int32),
            **_extras_specs(cfg, shape, decode=False)}


def prefill_input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    return {"tokens": spec((b, s), ("batch", "seq"), jnp.int32),
            **_extras_specs(cfg, shape, decode=False)}


def decode_input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b = shape.global_batch
    return {"tokens": spec((b, 1), ("batch", None), jnp.int32),
            **_extras_specs(cfg, shape, decode=True)}


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """The assignment's entry point: stand-ins for every model input of the
    (arch x shape) cell, keyed by step-function argument."""
    if shape.kind == "train":
        return train_input_specs(cfg, shape)
    if shape.kind == "prefill":
        return prefill_input_specs(cfg, shape)
    return decode_input_specs(cfg, shape)


# --------------------------------------------------------------------------
# Assembled lowering bundles
# --------------------------------------------------------------------------


@dataclasses.dataclass
class StepBundle:
    """Everything needed to ``jax.jit(fn, in_shardings=...).lower(*abstract)``."""

    fn: "object"
    abstract_args: tuple
    in_shardings: tuple
    donate_argnums: tuple = ()
    name: str = ""


def _sds(spec_tree):
    return abstract_params(spec_tree)


def _shard(spec_tree, rules, mesh):
    return shardings_for_specs(spec_tree, rules, mesh)


def make_pc(rules: ShardingRules, mesh: Optional[Mesh],
            enable: bool = True) -> PartitionConstraints:
    return PartitionConstraints(rules, mesh, enable)


def _moe_localized(cfg: ModelConfig, mesh: Mesh) -> ModelConfig:
    """Locality-aware MoE dispatch: one dispatch group per DP shard (keeps
    the routing sort/scatter shard-local).  When the expert count
    divides the TP axis on a single-pod mesh, upgrade to the shard_map
    ragged all-to-all dispatch (strictly less wire than GSPMD's masked-AR
    scatter; apply_moe re-checks shape divisibility and falls back)."""
    if cfg.moe is None:
        return cfg
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes.get("pod", 1) * sizes.get("data", 1)
    tp = sizes.get("model", 1)
    impl = "a2a" if ("pod" not in sizes
                     and cfg.moe.num_experts % tp == 0) else "grouped"
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch_groups=dp,
                                     impl=impl))


def build_train_bundle(cfg: ModelConfig, shape: ShapeConfig,
                       train_cfg: TrainConfig, mesh: Mesh,
                       rules: Optional[ShardingRules] = None) -> StepBundle:
    rules = rules or rules_for("train")
    cfg = _moe_localized(cfg, mesh)
    pc = make_pc(rules, mesh)
    pspecs = model_specs(cfg)
    ospecs = opt_state_specs(pspecs, train_cfg)
    ispecs = train_input_specs(cfg, shape)
    step_fn, _ = make_train_step(cfg, train_cfg, pc=pc, mesh=mesh)
    scalar = NamedSharding(mesh, P())
    return StepBundle(
        fn=step_fn,
        abstract_args=(_sds(pspecs), _sds(ospecs), _sds(ispecs),
                       jax.ShapeDtypeStruct((), jnp.int32)),
        in_shardings=(_shard(pspecs, rules, mesh),
                      _shard(ospecs, rules, mesh),
                      _shard(ispecs, rules, mesh), scalar),
        donate_argnums=(0, 1),
        name=f"train:{cfg.name}:{shape.name}")


def build_prefill_bundle(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                         rules: Optional[ShardingRules] = None) -> StepBundle:
    rules = rules or rules_for("serve")
    cfg = _moe_localized(cfg, mesh)
    pc = make_pc(rules, mesh)
    pspecs = serve_param_specs(cfg)
    cspecs = cache_specs(cfg, shape.global_batch, shape.seq_len)
    ispecs = prefill_input_specs(cfg, shape)

    def prefill(params, tokens, cache, extras):
        logits, cache, _ = forward(params, cfg, tokens=tokens,
                                   mode="prefill", cache=cache, pc=pc,
                                   extras=extras)
        return logits[:, -1], cache

    return StepBundle(
        fn=prefill,
        abstract_args=(_sds(pspecs), _sds(ispecs)["tokens"], _sds(cspecs),
                       {k: v for k, v in _sds(ispecs).items()
                        if k != "tokens"}),
        in_shardings=(_shard(pspecs, rules, mesh),
                      _shard(ispecs, rules, mesh)["tokens"],
                      _shard(cspecs, rules, mesh),
                      {k: v for k, v in _shard(ispecs, rules, mesh).items()
                       if k != "tokens"}),
        donate_argnums=(2,),
        name=f"prefill:{cfg.name}:{shape.name}")


def build_decode_bundle(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                        rules: Optional[ShardingRules] = None) -> StepBundle:
    rules = rules or rules_for("serve")
    cfg = _moe_localized(cfg, mesh)
    pc = make_pc(rules, mesh)
    pspecs = serve_param_specs(cfg)
    # decode against a full cache of seq_len (+1 slot for the new token)
    cspecs = cache_specs(cfg, shape.global_batch, shape.seq_len)
    ispecs = decode_input_specs(cfg, shape)
    scalar = NamedSharding(mesh, P())

    # cache-write policy: when kv_heads takes the TP axis the cache
    # sequence dim is unsharded -> in-place DUS (cheapest); when the seq dim
    # carries the TP axis instead (kv_heads not divisible), a dynamic-index
    # DUS would force collectives, so use the elementwise one-hot write.
    tp = mesh.devices.shape[list(mesh.axis_names).index("model")] \
        if "model" in mesh.axis_names else 1
    kv_sharded = (cfg.attention_type != "mla"
                  and cfg.num_kv_heads % tp == 0 and cfg.num_kv_heads >= tp)
    cache_update = "dus" if kv_sharded or tp == 1 else "onehot"

    def decode(params, cache, tokens, pos, extras):
        logits, cache, _ = forward(params, cfg, tokens=tokens, mode="decode",
                                   cache=cache, pos=pos, pc=pc,
                                   extras=extras, cache_update=cache_update)
        return logits[:, -1], cache

    return StepBundle(
        fn=decode,
        abstract_args=(_sds(pspecs), _sds(cspecs), _sds(ispecs)["tokens"],
                       jax.ShapeDtypeStruct((), jnp.int32),
                       {k: v for k, v in _sds(ispecs).items()
                        if k != "tokens"}),
        in_shardings=(_shard(pspecs, rules, mesh),
                      _shard(cspecs, rules, mesh),
                      _shard(ispecs, rules, mesh)["tokens"], scalar,
                      {k: v for k, v in _shard(ispecs, rules, mesh).items()
                       if k != "tokens"}),
        donate_argnums=(1,),
        name=f"decode:{cfg.name}:{shape.name}")


def build_bundle(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                 train_cfg: Optional[TrainConfig] = None,
                 rules: Optional[ShardingRules] = None) -> StepBundle:
    if shape.kind == "train":
        return build_train_bundle(cfg, shape, train_cfg or TrainConfig(),
                                  mesh, rules)
    if shape.kind == "prefill":
        return build_prefill_bundle(cfg, shape, mesh, rules)
    return build_decode_bundle(cfg, shape, mesh, rules)


def lower_bundle(bundle: StepBundle, mesh: Mesh):
    """jit(...).lower(*abstract) under the mesh context."""
    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     donate_argnums=bundle.donate_argnums)
    with mesh:
        return jitted.lower(*bundle.abstract_args)
