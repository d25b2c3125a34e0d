"""Mixture-of-Experts FFN with sort-based capacity dispatch.

GShard's dispatch einsum materializes a (tokens, experts, capacity) one-hot —
for DeepSeek-V2 (160 experts) that is O(10^10) elements.  We instead use the
sort-based dispatch (MegaBlocks-style, adapted to fixed capacity so shapes
stay static for XLA):

  1. top-k routing -> (token, expert, gate) triples,
  2. stable sort by expert, rank-within-expert via cumulative counts,
  3. triples whose rank exceeds capacity are dropped (scattered to a dummy
     row), the rest are scattered into an (E, C, d) buffer,
  4. batched expert FFN over (E, C, d) — an einsum the MXU loves,
  5. weighted scatter-add back to token order.

**Locality:** a single global dispatch makes the argsort/scatter a
cross-mesh data-dependent shuffle, which dominates DeepSeek-V2's collective
term on a large mesh.  With ``dispatch_groups = DP``
the token axis is split into shard-aligned groups and every sort/scatter is
batched over a sharded group dim (purely local under GSPMD); only the
expert-parallel buffer exchange crosses the mesh.  Capacity is per-group, so
the buffers are (G, E, C/G, d) — same total memory.

Expert parallelism: when ``E % tp == 0`` the (.., E, C, d) buffer is sharded
over the model axis (EP; GSPMD inserts the all-to-all), otherwise the expert
FFN hidden dim takes the TP axis and experts stay FSDP-sharded weights.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.configs.base import ModelConfig
from repro.models.layers import apply_mlp, mlp_specs
from repro.models.params import spec


def moe_specs(cfg: ModelConfig):
    m = cfg.moe
    d = cfg.d_model
    out = {
        "router": spec((d, m.num_experts), ("embed", "experts"),
                       scale=0.02),
        "w_gate": spec((m.num_experts, d, m.d_ff_expert),
                       ("experts", "embed", "mlp")),
        "w_up": spec((m.num_experts, d, m.d_ff_expert),
                     ("experts", "embed", "mlp")),
        "w_down": spec((m.num_experts, m.d_ff_expert, d),
                       ("experts", "mlp", "embed")),
    }
    if m.num_shared_experts:
        shared_cfg = dataclasses.replace(cfg, mlp_type="swiglu")
        out["shared"] = mlp_specs(shared_cfg, d_ff=m.d_ff_shared)
    return out


def capacity(cfg: ModelConfig, num_tokens: int) -> int:
    """Per-dispatch-group expert capacity, lane-aligned."""
    m = cfg.moe
    c = int(math.ceil(m.top_k * num_tokens * m.capacity_factor
                      / m.num_experts))
    return max(8, ((c + 7) // 8) * 8)


def route_topk(router_logits: jax.Array, top_k: int):
    """Softmax-then-top-k routing with renormalized gates.

    router_logits: (T, E) fp32 -> (gates (T,k), experts (T,k), probs (T,E))
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, experts, probs


def _dispatch_group(xt, logits, cfg: ModelConfig, cap: int):
    """One group's sort-based dispatch.  xt: (T, d); logits: (T, E).

    Returns (xe (E, C, d), combine state, stats) — pure function, vmapped
    over the (sharded) group dimension by apply_moe.
    """
    m = cfg.moe
    dt = xt.dtype
    t, d = xt.shape
    e = m.num_experts
    gates, experts, probs = route_topk(logits, m.top_k)

    flat_e = experts.reshape(-1)                         # (T*k,)
    flat_g = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), m.top_k)
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    tok_sorted = flat_tok[order]
    g_sorted = flat_g[order]
    counts = jnp.bincount(flat_e, length=e)              # (E,)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * m.top_k) - starts[e_sorted]
    keep = rank < cap
    buf_idx = jnp.where(keep, e_sorted * cap + rank, e * cap)

    xbuf = jnp.zeros((e * cap + 1, d), dt).at[buf_idx].set(
        xt[tok_sorted] * keep[:, None].astype(dt))
    xe = xbuf[: e * cap].reshape(e, cap, d)

    frac_tokens = counts.astype(jnp.float32) / jnp.maximum(t * m.top_k, 1)
    mean_probs = jnp.mean(probs, axis=0)
    stats = {
        "aux_loss": e * jnp.sum(frac_tokens * mean_probs),
        "dropped": jnp.sum(1.0 - keep.astype(jnp.float32))
        / jnp.maximum(t * m.top_k, 1),
        "max_load": jnp.max(frac_tokens) * e,
    }
    return xe, (buf_idx, tok_sorted, g_sorted), stats


def _combine_group(ye, state, t: int):
    """Scatter one group's expert outputs back to token order."""
    buf_idx, tok_sorted, g_sorted = state
    e, cap, d = ye.shape
    dt = ye.dtype
    ybuf = jnp.concatenate([ye.reshape(e * cap, d),
                            jnp.zeros((1, d), dt)], axis=0)
    y_sorted = ybuf[buf_idx] * g_sorted[:, None].astype(dt)
    return jnp.zeros((t, d), dt).at[tok_sorted].add(y_sorted)


def apply_moe(p, x, cfg: ModelConfig, pc=None):
    """x: (B, S, d) -> (y, aux).  aux carries load-balance statistics."""
    m = cfg.moe
    if getattr(m, "impl", "grouped") == "a2a" and pc is not None and \
            getattr(pc, "mesh", None) is not None:
        sizes = dict(zip(pc.mesh.axis_names, pc.mesh.devices.shape))
        tp = sizes.get("model", 1)
        dp = sizes.get("data", 1)
        tloc = (x.shape[0] // max(dp, 1)) * x.shape[1]
        if "pod" not in sizes and m.num_experts % tp == 0 and \
                x.shape[0] % dp == 0 and tloc % tp == 0:
            return apply_moe_a2a(p, x, cfg, pc.mesh)
    dt = x.dtype
    b, s, d = x.shape
    t = b * s

    g = max(getattr(m, "dispatch_groups", 1), 1)
    if t % g != 0 or (t // g) * m.top_k < 8:
        g = 1
    tg = t // g
    cap = capacity(cfg, tg)
    xt = x.reshape(g, tg, d)

    logits = jnp.einsum(
        "gtd,de->gte", xt,
        p["router"].astype(jnp.float32 if m.router_dtype == "float32"
                           else dt))

    xe, state, stats = jax.vmap(
        lambda xg, lg: _dispatch_group(xg, lg, cfg, cap))(xt, logits)
    # xe: (G, E, C, d); group dim is batch-sharded, experts go to the EP axis
    if pc is not None:
        xe = pc.grouped_expert_buffer(xe)

    # ---- batched expert FFN (swiglu) -----------------------------------
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"].astype(dt))
                    ) * jnp.einsum("gecd,edf->gecf", xe,
                                   p["w_up"].astype(dt))
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dt))
    if pc is not None:
        ye = pc.grouped_expert_buffer(ye)

    # ---- combine --------------------------------------------------------
    yt = jax.vmap(lambda yg, st: _combine_group(yg, st, tg))(ye, state)
    y = yt.reshape(b, s, d)

    if m.num_shared_experts:
        shared_cfg = dataclasses.replace(cfg, mlp_type="swiglu")
        y = y + apply_mlp(p["shared"], x, shared_cfg)

    aux = {"moe_aux_loss": jnp.mean(stats["aux_loss"]),
           "moe_dropped_frac": jnp.mean(stats["dropped"]),
           "moe_max_load": jnp.max(stats["max_load"])}
    return y, aux


# ==========================================================================
# Expert-parallel ragged dispatch (opt-in, for DeepSeek-V2)
# ==========================================================================


def apply_moe_a2a(p, x, cfg: ModelConfig, mesh):
    """shard_map MoE dispatch: explicit all-to-all over the EP ("model")
    axis instead of GSPMD's masked-all-reduce scatter fallback.

    Tokens are batch-sharded over "data" and replicated over "model"; each
    model shard therefore dispatches only its 1/tp *slice* of the local
    tokens (so every token crosses the wire once), buckets them by the
    model shard that owns their expert (capacity ``cap_send`` per
    destination), exchanges with ``jax.lax.all_to_all``, runs the local
    experts (weights FSDP-gathered over "data"), exchanges back, combines,
    and all-gathers the per-slice outputs over "model".  Wire volume
    ~= tokens x top_k x d / tp per device per direction — ~4x below the
    fp32+u32 all-reduce pair GSPMD emits for the grouped scatter.

    Preconditions (checked): single-pod mesh ("data","model"),
    num_experts % tp == 0, local tokens % tp == 0.  Shared experts and the
    router aux stats run outside the manual region.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    dt = x.dtype
    b, s, d = x.shape
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get("model", 1)
    dp = sizes.get("data", 1)
    e = m.num_experts
    assert "pod" not in sizes, "a2a dispatch: single-pod meshes only"
    assert e % tp == 0 and b % dp == 0
    e_local = e // tp
    t_loc = (b // dp) * s
    assert t_loc % tp == 0, (t_loc, tp)
    t_my = t_loc // tp                                   # this shard's slice

    def _cap(n):
        return max(8, ((n + 7) // 8) * 8)

    cap_send = _cap(math.ceil(m.top_k * t_my * m.capacity_factor / tp))
    cap_loc = capacity(cfg, t_loc)                       # per local expert

    def local_fn(router_w, w_gate, w_up, w_down, x_loc):
        midx = jax.lax.axis_index("model")
        xt_all = x_loc.reshape(t_loc, d)
        xt = jax.lax.dynamic_slice_in_dim(xt_all, midx * t_my, t_my, 0)

        rw = jax.lax.all_gather(router_w, "data", axis=0, tiled=True)
        rw = jax.lax.all_gather(rw, "model", axis=1, tiled=True)  # (d, E)
        logits = xt.astype(jnp.float32) @ rw.astype(jnp.float32)
        gates, experts, _ = route_topk(logits, m.top_k)

        # ---- bucket my tokens by destination shard -----------------------
        flat_e = experts.reshape(-1)                     # (t_my*k,)
        dst = flat_e // e_local
        flat_tok = jnp.repeat(jnp.arange(t_my), m.top_k)
        order = jnp.argsort(dst, stable=True)
        dst_s, tok_s, exp_s = dst[order], flat_tok[order], flat_e[order]
        gate_s = gates.reshape(-1)[order]
        counts = jnp.bincount(dst, length=tp)
        rank = jnp.arange(t_my * m.top_k) - \
            (jnp.cumsum(counts) - counts)[dst_s]
        keep = rank < cap_send
        slot = jnp.where(keep, dst_s * cap_send + rank, tp * cap_send)

        send_x = jnp.zeros((tp * cap_send + 1, d), dt).at[slot].set(
            xt[tok_s] * keep[:, None].astype(dt))[:-1]
        send_le = jnp.full((tp * cap_send + 1,), e_local, jnp.int32) \
            .at[slot].set(jnp.where(keep, exp_s % e_local, e_local))[:-1]
        recv_x = jax.lax.all_to_all(
            send_x.reshape(tp, cap_send, d), "model", 0, 0)
        recv_le = jax.lax.all_to_all(
            send_le.reshape(tp, cap_send), "model", 0, 0)

        # ---- local expert compute ----------------------------------------
        rx = recv_x.reshape(tp * cap_send, d)
        rle = recv_le.reshape(tp * cap_send)             # e_local = padding
        order2 = jnp.argsort(rle, stable=True)
        rle_s = rle[order2]
        c2 = jnp.bincount(rle, length=e_local + 1)[:e_local]
        rank2 = jnp.arange(tp * cap_send) - \
            (jnp.cumsum(c2) - c2)[jnp.minimum(rle_s, e_local - 1)]
        keep2 = jnp.logical_and(rle_s < e_local, rank2 < cap_loc)
        slot2 = jnp.where(keep2, rle_s * cap_loc + rank2,
                          e_local * cap_loc)
        xe = jnp.zeros((e_local * cap_loc + 1, d), dt).at[slot2].set(
            rx[order2] * keep2[:, None].astype(dt))[:-1] \
            .reshape(e_local, cap_loc, d)

        wg = jax.lax.all_gather(w_gate, "data", axis=1, tiled=True)
        wu = jax.lax.all_gather(w_up, "data", axis=1, tiled=True)
        wd = jax.lax.all_gather(w_down, "data", axis=2, tiled=True)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt))) \
            * jnp.einsum("ecd,edf->ecf", xe, wu.astype(dt))
        ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(dt))

        # ---- return path --------------------------------------------------
        ybuf = jnp.concatenate([ye.reshape(e_local * cap_loc, d),
                                jnp.zeros((1, d), dt)])
        y_recv = jnp.zeros((tp * cap_send, d), dt).at[order2].set(
            ybuf[slot2])
        back = jax.lax.all_to_all(
            y_recv.reshape(tp, cap_send, d), "model", 0, 0)
        ybuf2 = jnp.concatenate([back.reshape(tp * cap_send, d),
                                 jnp.zeros((1, d), dt)])
        y_sorted = ybuf2[jnp.minimum(slot, tp * cap_send)] * \
            (gate_s * keep.astype(jnp.float32))[:, None].astype(dt)
        y_my = jnp.zeros((t_my, d), dt).at[tok_s].add(y_sorted)
        # slices -> full local tokens, replicated over "model"
        y_full = jax.lax.all_gather(y_my, "model", axis=0, tiled=True)
        return y_full.reshape(b // dp, s, d)

    y = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P("data", "model"),                 # router (d, E)
                  P("model", "data", None),           # w_gate (E, d, f)
                  P("model", "data", None),           # w_up
                  P("model", None, "data"),           # w_down (E, f, d)
                  P("data", None, None)),             # x
        out_specs=P("data", None, None),
        check_vma=False)(p["router"], p["w_gate"], p["w_up"], p["w_down"],
                         x)

    if m.num_shared_experts:
        shared_cfg = dataclasses.replace(cfg, mlp_type="swiglu")
        y = y + apply_mlp(p["shared"], x, shared_cfg)
    # aux stats from a cheap global routing pass (outside the manual region)
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(dt))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    mean_probs = jnp.mean(probs.reshape(-1, e), axis=0)
    aux = {"moe_aux_loss": e * jnp.sum(mean_probs * mean_probs),
           "moe_dropped_frac": jnp.float32(0.0),
           "moe_max_load": jnp.max(mean_probs) * e}
    return y, aux
