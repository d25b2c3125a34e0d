"""Parameter specification system.

Models declare their parameters as pytrees of :class:`ParamSpec` — shape,
dtype, *logical axis names* and an initializer.  From one spec tree we derive:

* concrete initialized parameters (``init_params``),
* abstract ``ShapeDtypeStruct`` stand-ins for AOT lowering (``abstract_params``),
* ``NamedSharding`` trees via the logical-axis rules in ``repro.parallel``.

This keeps shapes, shardings and initialization in a single source of truth,
which is what makes the 40-cell dry-run tractable without per-arch hand
tuning.
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class ParamSpec:
    """Specification of one parameter tensor."""

    shape: tuple
    axes: tuple                     # logical axis name (or None) per dim
    dtype: Any = jnp.float32
    init: str = "normal"            # normal | zeros | ones | constant
    scale: Optional[float] = None   # stddev override for "normal"
    value: float = 0.0              # for "constant"

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(
                f"shape {self.shape} and axes {self.axes} rank mismatch")

    @property
    def size(self) -> int:
        return int(math.prod(self.shape))


def spec(shape, axes, dtype=jnp.float32, init="normal", scale=None,
         value=0.0) -> ParamSpec:
    return ParamSpec(tuple(shape), tuple(axes), dtype, init, scale, value)


def stack_specs(tree, n: int, axis_name: str = "layers"):
    """Prepend a stacked-layer dimension to every spec in a tree."""
    def f(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.dtype,
                         s.init, s.scale, s.value)
    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def _fan_in(shape) -> int:
    if len(shape) == 0:
        return 1
    if len(shape) == 1:
        return shape[0]
    # all dims but the last are treated as fan-in (matches our (in, out...)
    # weight layout convention)
    return int(math.prod(shape[:-1]))


def _init_leaf(s: ParamSpec, key) -> jax.Array:
    if s.init == "zeros":
        return jnp.zeros(s.shape, s.dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, s.dtype)
    if s.init == "constant":
        return jnp.full(s.shape, s.value, s.dtype)
    std = s.scale if s.scale is not None else 1.0 / math.sqrt(max(_fan_in(s.shape), 1))
    return (jax.random.normal(key, s.shape, jnp.float32) * std).astype(s.dtype)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(specs, seed: int = 0):
    """Initialize concrete parameters; per-leaf keys folded from tree paths."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    paths = jax.tree_util.tree_flatten_with_path(specs, is_leaf=_is_spec)[0]
    base = jax.random.key(seed)
    out = []
    for (path, s) in paths:
        pstr = "/".join(str(p) for p in path)
        # crc32, not hash(): str hashing is salted per process, and the
        # same seed must give the same weights in every run
        key = jax.random.fold_in(base, zlib.crc32(pstr.encode()) % (2 ** 31))
        out.append(_init_leaf(s, key))
    return jax.tree.unflatten(treedef, out)


def abstract_params(specs):
    """ShapeDtypeStruct tree for AOT lowering (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs,
        is_leaf=_is_spec)


def param_axes(specs):
    """Tree of logical-axis tuples (same structure as the params)."""
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=_is_spec)


def param_count(specs) -> int:
    return sum(s.size for s in jax.tree.leaves(specs, is_leaf=_is_spec))


def param_bytes(specs) -> int:
    return sum(s.size * jnp.dtype(s.dtype).itemsize
               for s in jax.tree.leaves(specs, is_leaf=_is_spec))
