"""Plain reference of the dense decoder's training step.

Written from the configuration alone and imports nothing of the program:
the same equations (RMSNorm, rotary GQA attention, SwiGLU MLP, tied LM
head over the padded vocabulary with the padded logits masked out of the
loss, global-norm clipping, AdamW with linear warm-up and cosine decay)
in plain ``jax.numpy``, float32 at ``highest`` matmul precision, with no
kernels and no fused step.  Attention and the loss are computed in blocks
of queries and of tokens, and every layer is rematerialised, so that the
whole step fits beside the optimizer state.

``devices`` (the default device when none are given) places the arrays
and nothing else.  The parameters, both AdamW moments and the gradients
are split over a 1-D mesh of those devices, each leaf along its largest
axis that the device count divides (replicated where none does), and the
rows of the batch where the count divides them; on several devices no
leaf is whole on one of them, and on one device nothing is split.  The
arithmetic is not partitioned by hand: XLA partitions the same program,
so only the order of the reductions across devices may differ.

The weights and the data are remade from the seed by the same recipes the
program states (normal weights with a per-leaf key folded from the leaf's
path, Zipf tokens keyed by (seed, step)), so no array the program made is
read.  ``mm_dtype`` puts the step in a lower precision (per-tensor scaled
float8 operands for every matmul), the control that must fail the limits;
``rows`` keeps only some rows of the batch, a planted fault.
"""

from __future__ import annotations

import math
import zlib
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

NEG = -1e9


def padded_vocab(cfg: dict) -> int:
    pad = cfg.get("vocab_pad_to", 2048)
    return ((cfg["vocab_size"] + pad - 1) // pad) * pad


# --------------------------------------------------------------------------
# weights and data from the seed
# --------------------------------------------------------------------------


def leaf_shapes(cfg: dict) -> dict:
    """Nested dict of (shape, init, std) in the program's state layout."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ff, vp = cfg["intermediate_size"], padded_vocab(cfg)

    def normal(shape, std=None):
        fan_in = math.prod(shape[:-1]) if len(shape) > 1 else shape[0]
        return (tuple(shape), "normal",
                std if std is not None else 1.0 / math.sqrt(max(fan_in, 1)))

    def ones(shape):
        return (tuple(shape), "ones", None)

    return {
        "embed": {"embedding": normal((vp, d), 0.02)},
        "final_norm": {"scale": ones((d,))},
        "dense_layers": {
            "ln1": {"scale": ones((L, d))},
            "attn": {"wq": normal((L, d, h, hd)),
                     "wk": normal((L, d, kv, hd)),
                     "wv": normal((L, d, kv, hd)),
                     "wo": normal((L, h, hd, d))},
            "ln2": {"scale": ones((L, d))},
            "mlp": {"w_gate": normal((L, d, ff)),
                    "w_up": normal((L, d, ff)),
                    "w_down": normal((L, ff, d))},
        },
    }


def _is_desc(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def leaf_paths(cfg: dict) -> list:
    """[(path string, (shape, init, std))] in flattening order."""
    flat = jax.tree_util.tree_flatten_with_path(leaf_shapes(cfg),
                                                is_leaf=_is_desc)[0]
    return [("/".join(str(p) for p in path), desc) for path, desc in flat]


# --------------------------------------------------------------------------
# placement over the devices
# --------------------------------------------------------------------------


def _devices(devices) -> tuple:
    """The devices to place the arrays on: those given, or the default."""
    return tuple(devices) if devices else (jax.devices()[0],)


def _mesh(devices: tuple) -> Mesh:
    return Mesh(np.array(devices), ("d",), axis_types=(AxisType.Auto,))


def leaf_sharding(shape, devices: tuple) -> NamedSharding:
    """Split along the largest axis that the device count divides (the
    first of equal ones), or replicated where none does."""
    n = len(devices)
    axes = [i for i, size in enumerate(shape) if size % n == 0]
    spec = [None] * len(shape)
    if axes:
        spec[max(axes, key=lambda i: (shape[i], -i))] = "d"
    return NamedSharding(_mesh(devices), P(*spec))


def row_sharding(rows: int, devices: tuple) -> NamedSharding:
    """Tokens and labels: split by row where the device count divides the
    rows, replicated where it does not."""
    split = rows % len(devices) == 0
    return NamedSharding(_mesh(devices), P("d" if split else None))


def place(leaves, devices) -> list:
    """Leaves (host or device arrays) onto the devices, each split by
    ``leaf_sharding``."""
    devs = _devices(devices)
    return jax.device_put(list(leaves), [leaf_sharding(np.shape(x), devs)
                                         for x in leaves])


def init_leaf(seed: int, path: str, desc) -> jax.Array:
    shape, kind, std = desc
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(jax.random.key(seed),
                             zlib.crc32(path.encode()) % (2 ** 31))
    return jax.random.normal(key, shape, jnp.float32) * std


def init_state(cfg: dict, seed: int, devices: tuple) -> tuple:
    """(params, m, v) made split over ``devices`` in one jitted call."""
    return _init(_cfg_key(cfg), devices)(seed, _cfg_key(cfg))


@lru_cache(maxsize=None)
def _init(cfg_key: tuple, devices: tuple):
    def init(seed, cfg_key):
        cfg = dict(cfg_key)
        params = jax.tree.unflatten(
            jax.tree.structure(leaf_shapes(cfg), is_leaf=_is_desc),
            [init_leaf(seed, p, d) for p, d in leaf_paths(cfg)])
        return (params, jax.tree.map(jnp.zeros_like, params),
                jax.tree.map(jnp.zeros_like, params))
    sh = shardings(dict(cfg_key), devices)
    return jax.jit(init, static_argnums=(1,), out_shardings=(sh, sh, sh))


def shardings(cfg: dict, devices: tuple) -> dict:
    """``leaf_sharding`` of every parameter, in the parameters' tree."""
    return jax.tree.map(lambda d: leaf_sharding(d[0], devices),
                        leaf_shapes(cfg), is_leaf=_is_desc)


def batch(cfg: dict, seed: int, step: int, rows: int, seq_len: int,
          zipf_a: float = 1.2) -> tuple:
    """(tokens, labels) of one step: Zipf ids clipped to the vocabulary,
    with document boundaries (id 0) sprinkled at rate 1/512."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    toks = rng.zipf(zipf_a, size=(rows, seq_len + 1))
    toks = np.minimum(toks, cfg["vocab_size"] - 1).astype(np.int32)
    doc = rng.random((rows, seq_len + 1)) < (1.0 / 512)
    toks = np.where(doc, 0, toks)
    return toks[:, :-1], toks[:, 1:].copy()


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------


def _scaled_round_trip(x, dtype):
    """Per-tensor scaled round trip through a narrow float type."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fp8(x, dtype):
    return _scaled_round_trip(x, dtype)


def _fp8_fwd(x, dtype):
    return _scaled_round_trip(x, dtype), None


def _fp8_bwd(dtype, _, g):
    # gradients go through the wider-range float8 type, as fp8 training
    # does (e4m3 forward, e5m2 backward), each tensor scaled to its range
    return (_scaled_round_trip(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _quant(x, dtype):
    return x if dtype is None else _fp8(x, dtype)


def _mm(eq, a, b, mm_dtype):
    return jnp.einsum(eq, _quant(a, mm_dtype), _quant(b, mm_dtype),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs      # (S, half)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _attention(q, k, v, mm_dtype, q_block: int):
    """Causal GQA attention, one block of queries at a time.
    q: (B, S, H, D); k, v: (B, S, KV, D)."""
    b, s, h, dd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qb = min(q_block, s)
    nb = s // qb
    qs = q.reshape(b, nb, qb, kvh, g, dd)
    kpos = jnp.arange(s)

    def one(i):
        qi = qs[:, i]                                       # (B,qb,KV,G,D)
        sc = _mm("bqkgd,bskd->bkgqs", qi, k, mm_dtype) / math.sqrt(dd)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, NEG)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("bkgqs,bskd->bqkgd", p, v, mm_dtype)     # (B,qb,KV,G,D)

    out = jax.lax.map(jax.checkpoint(one), jnp.arange(nb))  # (nb,B,qb,..)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, dd)


def _layer(x, lp, cfg, mm_dtype, q_block):
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(x.shape[1])
    h = _rms(x, lp["ln1"]["scale"], eps)
    a = lp["attn"]
    q = _rope(_mm("bsd,dhk->bshk", h, a["wq"], mm_dtype), pos,
              cfg["rope_theta"])
    k = _rope(_mm("bsd,dhk->bshk", h, a["wk"], mm_dtype), pos,
              cfg["rope_theta"])
    v = _mm("bsd,dhk->bshk", h, a["wv"], mm_dtype)
    o = _attention(q, k, v, mm_dtype, q_block)
    x = x + _mm("bshk,hkd->bsd", o, a["wo"], mm_dtype)
    h = _rms(x, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    u = jax.nn.silu(_mm("bsd,df->bsf", h, m["w_gate"], mm_dtype)) \
        * _mm("bsd,df->bsf", h, m["w_up"], mm_dtype)
    return x + _mm("bsf,fd->bsd", u, m["w_down"], mm_dtype)


def loss(params, tokens, labels, cfg, mm_dtype=None, q_block=256,
         tok_block=512):
    """Mean next-token cross entropy over every token of the batch."""
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    layers = params["dense_layers"]
    body = jax.checkpoint(partial(_layer, cfg=cfg, mm_dtype=mm_dtype,
                                  q_block=q_block))
    for i in range(cfg["num_hidden_layers"]):
        x = body(x, jax.tree.map(lambda w: w[i], layers))
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    b, s, d = x.shape
    tb = min(tok_block, s)
    xs = x.reshape(b, s // tb, tb, d).swapaxes(0, 1)
    ls = labels.reshape(b, s // tb, tb).swapaxes(0, 1)
    pad = jnp.arange(emb.shape[0]) >= cfg["vocab_size"]

    def chunk(xl):
        xc, lc = xl
        logits = _mm("btd,vd->btv", xc, emb, mm_dtype)
        logits = jnp.where(pad, NEG, logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    total = jnp.sum(jax.lax.map(jax.checkpoint(chunk), (xs, ls)))
    return total / (b * s)


# --------------------------------------------------------------------------
# optimizer
# --------------------------------------------------------------------------


def learning_rate(tcfg: dict, total_steps: int, step: int) -> float:
    lr, warm = tcfg["learning_rate"], tcfg["warmup_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    prog = min(max((step - warm) / max(total_steps - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.45 * (1 + math.cos(math.pi * prog)))


def _leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]


def make_step(cfg: dict, tcfg: dict, devices: tuple, rows: int,
              mm_dtype=None):
    """The jitted step; its inputs and outputs keep the split of
    ``shardings`` and ``row_sharding(rows)`` over ``devices``."""
    return _make_step(_cfg_key(cfg) + (("rms_norm_eps", cfg["rms_norm_eps"]),
                                       ("rope_theta", cfg["rope_theta"])),
                      tuple(sorted(tcfg.items())), mm_dtype, devices, rows)


@lru_cache(maxsize=None)
def _make_step(cfg_key: tuple, tcfg_key: tuple, mm_dtype, devices, rows):
    cfg, tcfg = dict(cfg_key), dict(tcfg_key)
    b1, b2, eps = tcfg["beta1"], tcfg["beta2"], tcfg["eps"]
    wd, clip = tcfg["weight_decay"], tcfg["grad_clip_norm"]

    def step(params, m, v, count, lr, tokens, labels):
        with jax.default_matmul_precision("highest"):
            val, g = jax.value_and_grad(loss)(params, tokens, labels, cfg,
                                              mm_dtype)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, clip / jnp.maximum(gnorm, 1e-9)), g)
        c1 = 1 - b1 ** count
        c2 = 1 - b2 ** count
        m = jax.tree.map(lambda mi, gi: b1 * mi + (1 - b1) * gi, m, g)
        v = jax.tree.map(lambda vi, gi: b2 * vi + (1 - b2) * gi * gi, v, g)
        params = jax.tree.map(
            lambda p, mi, vi: p - lr * ((mi / c1) / (jnp.sqrt(vi / c2) + eps)
                                        + wd * p), params, m, v)
        return params, m, v, val, _leaf_norms(g)

    sh = shardings(cfg, devices)
    one = NamedSharding(_mesh(devices), P())
    tok = row_sharding(rows, devices)
    return jax.jit(step, donate_argnums=(0, 1, 2),
                   in_shardings=(sh, sh, sh, one, one, tok, tok),
                   out_shardings=(sh, sh, sh, one, one))


def run(cfg: dict, tcfg: dict, seed: int, batch_size: int, seq_len: int,
        total_steps: int, steps: int = 3, mm_dtype=None,
        rows: Optional[slice] = None, log=None, devices=None) -> dict:
    """The first ``steps`` steps from the seed.  Returns the losses, the
    per-leaf norms of the first (clipped) gradient and the parameters
    after the last step, placed over ``devices``."""
    devs = _devices(devices)
    n_rows = len(range(batch_size)[rows]) if rows is not None else batch_size
    params, m, v = init_state(cfg, seed, devs)
    step_fn = make_step(cfg, tcfg, devs, n_rows, mm_dtype)
    losses, grad_norms = [], None
    for i in range(steps):
        tokens, labels = batch(cfg, seed, i, batch_size, seq_len)
        if rows is not None:
            tokens, labels = tokens[rows], labels[rows]
        lr = learning_rate(tcfg, total_steps, i)
        params, m, v, val, gn = step_fn(
            params, m, v, jnp.float32(i + 1), jnp.float32(lr), tokens, labels)
        losses.append(float(val))
        if log is not None:
            log(f"reference step {i + 1} done")
        if grad_norms is None:
            grad_norms = [float(x) for x in gn]
    del m, v
    return {"losses": losses, "grad_norms": grad_norms, "params": params}


def diff_norms(params_ref, params_other, devices=None) -> list:
    """Per-leaf norm of (reference parameters - another run's leaves, in
    the reference's order; host arrays are fine), in one jitted call, the
    other run's leaves placed as ``place`` does."""
    other = place(params_other, devices)
    return [float(x) for x in _diff(jax.tree.leaves(params_ref), other)]


@jax.jit
def _diff(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x - y.astype(jnp.float32))))
            for x, y in zip(a, b)]


def change_norms(cfg: dict, seed: int, params_after, devices=None) -> list:
    """Per-leaf norm of (params_after - the seed's initial params), the
    initial leaves remade on the device(s), in one jitted call;
    ``params_after`` may be host arrays in the reference's layout, and is
    placed as ``place`` does."""
    leaves = place(jax.tree.leaves(params_after), devices)
    return [float(x) for x in _change(leaves, seed, _cfg_key(cfg))]


def _cfg_key(cfg: dict) -> tuple:
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "vocab_pad_to")
    return tuple((k, cfg[k]) for k in keys if k in cfg)


@partial(jax.jit, static_argnums=(2,))
def _change(leaves, seed, cfg_key):
    cfg = dict(cfg_key)
    return [jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32)
                                        - init_leaf(seed, path, desc))))
            for p, (path, desc) in zip(leaves, leaf_paths(cfg))]
