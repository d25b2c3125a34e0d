"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For the deepest assigned configs (nemotron 96L) a pure FSDP+TP mesh leaves
the per-layer weight all-gathers on the critical path; a ``pipe`` axis
splits layers into stages so weights stay resident and only activations
move (one (mb, seq, d) tensor per tick over neighbor ICI links).

Mapping: the stage loop runs inside ``jax.shard_map`` over the ``pipe``
mesh axis.  Stage s holds the stacked params slice s (in_spec P("pipe")),
microbatches tick through ``num_microbatches + stages - 1`` steps, and the
inter-stage handoff is ``jax.lax.ppermute`` (lowered to collective-permute —
neighbor-only traffic, visible in the dry-run HLO).  The bubble fraction is
the usual (S-1)/(M+S-1); pick M >= 4*S in production.

This module is the distribution substrate's PP building block: it is
exercised standalone (tests/test_pipeline.py lowers and runs it on an
8-device host mesh) and composes with the data/model axes of the
production mesh (the stage_fn body remains free to use them).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh: Mesh,
                   axis: str = "pipe", num_microbatches: int = 4):
    """Run ``x`` through ``stages`` sequential stages, pipelined.

    stage_fn(params_slice, x_mb) -> y_mb        (one stage's compute)
    stage_params: pytree with a leading stage dimension (= pipe axis size)
    x: (B, ...) global batch; B must divide num_microbatches.
    Returns y: (B, ...) after all stages.
    """
    stages = mesh.devices.shape[mesh.axis_names.index(axis)]
    b = x.shape[0]
    assert b % num_microbatches == 0, (b, num_microbatches)
    m = num_microbatches
    mb = b // m

    def run(params_local, x_local):
        # params_local: (1, ...) slice; x_local: full batch (replicated on
        # the pipe axis — activations are small relative to weights)
        params_local = jax.tree.map(lambda t: t[0], params_local)
        idx = jax.lax.axis_index(axis)
        xs = x_local.reshape((m, mb) + x_local.shape[1:])

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (if still in range)
            inject = jnp.clip(t, 0, m - 1)
            state = jnp.where(idx == 0, xs[inject], state)
            y = stage_fn(params_local, state)
            # collect at the last stage: tick t finishes microbatch t-S+1
            out_slot = jnp.clip(t - stages + 1, 0, m - 1)
            valid = jnp.logical_and(idx == stages - 1, t >= stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(valid, y.astype(outputs.dtype),
                          jax.lax.dynamic_index_in_dim(outputs, out_slot,
                                                       keepdims=False)),
                out_slot, axis=0)
            # hand off to the next stage (ring; stage S-1 -> 0 is ignored)
            state = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % stages) for i in range(stages)])
            return (state, outputs), None

        state0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        (_, outputs), _ = jax.lax.scan(tick, (state0, out0),
                                       jnp.arange(m + stages - 1))
        # replicate the last stage's outputs across the pipe axis (masked
        # psum — ppermute cannot express a one-to-all broadcast)
        outputs = jax.lax.psum(
            jnp.where(idx == stages - 1, outputs, 0.0), axis)
        return outputs.reshape((b,) + x_local.shape[1:])

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return shard_map(
        run, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False, axis_names={axis})(stage_params, x)


def bubble_fraction(stages: int, num_microbatches: int) -> float:
    """Pipeline bubble overhead (the napkin-math term used in §Perf)."""
    return (stages - 1) / (num_microbatches + stages - 1)
