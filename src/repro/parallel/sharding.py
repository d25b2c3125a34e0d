"""Logical-axis sharding engine.

Models name their dimensions with *logical axes* (``"embed"``, ``"heads"``,
``"mlp"``, ...).  A :class:`ShardingRules` table maps each logical axis to one
or more mesh axes.  At bind time every rule is checked for divisibility
against the actual dimension size and the actual mesh; rules that do not
divide are **dropped to replication** (never an error).  This single fallback
keeps every (arch x shape x mesh) combination compiling without per-arch hand
tuning:

* phi3-medium kv_heads=10, granite/yi/mixtral/nemotron kv<=8 < model=16
  -> kv_heads replicated over the TP axis (weights stay FSDP-sharded);
* yi-34b 56 heads % 16 != 0 -> head dim replicated, embed stays sharded;
* mixtral 8 experts % 16 != 0 -> expert buffers fall back, expert hidden dim
  takes the TP axis instead (the rule lists ``("experts", "mlp")``).

Two rule tables exist: TRAIN (FSDP weights over ``data``; TP over ``model``)
and SERVE (pure TP weights, batch over ``data``; weights *also* FSDP-sharded
over ``data`` for >digit-billion models via the same table — serving uses the
same rules, the fallback logic handles small dims).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import ParamSpec, param_axes


def _mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _flatten_mesh_axes(entry) -> tuple:
    """A rule entry is None, a mesh-axis name, or a tuple of names."""
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


@dataclass(frozen=True)
class ShardingRules:
    """Map logical axis name -> mesh axis name(s) (or None = replicate)."""

    rules: dict = field(default_factory=dict)

    def mesh_axes_for(self, logical: Optional[str]):
        if logical is None:
            return None
        return self.rules.get(logical)

    def with_overrides(self, **overrides) -> "ShardingRules":
        d = dict(self.rules)
        d.update(overrides)
        return ShardingRules(d)


# Default production rule tables.  ``batch`` spans the pure-DP axes ("pod" is
# present only on the multi-pod mesh; missing axes are dropped at bind time).
TRAIN_RULES = ShardingRules({
    "batch": ("pod", "data"),
    "seq": None,
    "embed": "data",              # FSDP / ZeRO-3 weight sharding
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": ("model",),
    "layers": None,
    "norm": None,
    "q_lora": None,
    "kv_lora": None,
    "cache_seq": None,
    "state": None,
    "inner": "model",             # mamba d_inner / rwkv projections
    "ssm_heads": "model",
    "frames": None,
})

# Serving: same table; batch carries DP, weights stay FSDP+TP sharded (for
# >100B models TP alone does not fit v5e HBM).  Decode KV caches shard batch
# over data and kv_heads over model, falling back to cache_seq -> model when
# kv_heads does not divide (see cache rule fallback in ``logical_to_pspec``).
SERVE_RULES = TRAIN_RULES.with_overrides(cache_seq="model")


def rules_for(kind: str) -> ShardingRules:
    return TRAIN_RULES if kind == "train" else SERVE_RULES


# Axes with higher numbers bind *after* the rest: "cache_seq"/"seq" only get
# a mesh axis when no higher-priority dim (kv_heads, heads, ...) claimed it.
_AXIS_PRIORITY = {"cache_seq": 1, "seq": 1}


def logical_to_pspec(axes: tuple, shape: tuple, rules: ShardingRules,
                     mesh: Mesh) -> P:
    """Bind logical axes to a PartitionSpec with divisibility fallback.

    Every mesh axis is used at most once per spec (GSPMD requirement); a
    logical axis whose dim does not divide the product of its mesh axes is
    replicated instead.  Binding order follows ``_AXIS_PRIORITY`` so e.g. a
    KV cache spec ("batch", "cache_seq", "kv_heads", None) shards kv_heads
    over the TP axis when divisible and falls back to sharding the sequence
    dim otherwise.
    """
    sizes = _mesh_axis_sizes(mesh)
    used = set()
    out: list = [None] * len(axes)
    order = sorted(range(len(axes)),
                   key=lambda i: _AXIS_PRIORITY.get(axes[i] or "", 0))
    for i in order:
        dim, logical = shape[i], axes[i]
        entry = rules.mesh_axes_for(logical)
        names = [a for a in _flatten_mesh_axes(entry)
                 if a in sizes and a not in used]
        prod = int(np.prod([sizes[a] for a in names])) if names else 1
        if names and dim % prod == 0 and dim >= prod:
            used.update(names)
            out[i] = tuple(names) if len(names) > 1 else names[0]
    # trim trailing Nones (canonical form)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shardings_for_specs(specs, rules: ShardingRules, mesh: Mesh):
    """NamedSharding tree matching a ParamSpec tree."""
    def f(s: ParamSpec):
        return NamedSharding(mesh, logical_to_pspec(s.axes, s.shape, rules,
                                                    mesh))
    return jax.tree.map(f, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


# --------------------------------------------------------------------------
# Activation partition constraints
# --------------------------------------------------------------------------


class PartitionConstraints:
    """Activation ``with_sharding_constraint`` helper handed to models.

    Models call ``pc.act(x, "batch", "seq", "embed")`` at block boundaries;
    outside a mesh context (CPU smoke tests) every method is the identity, so
    models stay mesh-agnostic.
    """

    def __init__(self, rules: ShardingRules, mesh: Optional[Mesh] = None,
                 enable: bool = True):
        self.rules = rules
        self.mesh = mesh
        self.enable = enable and mesh is not None

    def _constraint(self, x, logical_axes: tuple):
        if not self.enable:
            return x
        pspec = logical_to_pspec(logical_axes, x.shape, self.rules, self.mesh)
        return self._apply(x, pspec)

    def _apply(self, x, pspec):
        # inside a partial-manual shard_map (the per-pod gradient region)
        # GSPMD propagates the auto axes itself: a constraint naming two
        # auto axes there aborts XLA's SPMD partitioner (JAX 0.9)
        if jax.sharding.get_abstract_mesh().manual_axes:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, pspec))

    def act(self, x, *logical_axes):
        """Constrain an activation; pass one logical name (or None) per dim."""
        if len(logical_axes) != x.ndim:
            raise ValueError(f"{len(logical_axes)} axes for rank-{x.ndim}")
        return self._constraint(x, tuple(logical_axes))

    # -- common patterns ----------------------------------------------------

    def tokens(self, x):                       # (B, S, d)
        return self.act(x, "batch", "seq", "embed")

    def heads(self, x):                        # (B, S, H, D)
        return self.act(x, "batch", "seq", "heads", None)

    def kv(self, x):                           # (B, S, KV, D)
        return self.act(x, "batch", "seq", "kv_heads", None)

    def kv_cache(self, x):                     # (B, S_cache, KV, D)
        """Decode KV cache: batch x DP, kv_heads x TP; if kv_heads does not
        divide the TP axis the *sequence* dim takes it instead (keeps the
        cache within HBM for GQA models with few KV heads)."""
        if not self.enable:
            return x
        sizes = _mesh_axis_sizes(self.mesh)
        tp = sizes.get("model", 1)
        kv_heads = x.shape[2]
        if kv_heads % tp == 0 and kv_heads >= tp:
            axes = ("batch", None, "kv_heads", None)
        else:
            axes = ("batch", "cache_seq", None, None)
        rules = self.rules.with_overrides(cache_seq="model")
        pspec = logical_to_pspec(axes, x.shape, rules, self.mesh)
        return self._apply(x, pspec)

    def expert_buffer(self, x):                # (E, C, d)
        return self.act(x, "experts", None, None)

    def grouped_expert_buffer(self, x):        # (G, E, C, d)
        """Locality-aware MoE dispatch buffers: groups ride the DP axes,
        experts the EP/TP axis."""
        return self.act(x, "batch", "experts", None, None)

    def logits(self, x):                       # (B, S, vocab)
        return self.act(x, "batch", "seq", "vocab")


class NullConstraints(PartitionConstraints):
    """Identity constraints for CPU smoke paths."""

    def __init__(self):
        super().__init__(TRAIN_RULES, mesh=None, enable=False)
