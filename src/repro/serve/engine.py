"""Serving layer: jitted prefill/decode steps + a batched request engine.

``make_serve_fns`` builds the jit-able prefill and decode step functions;
:class:`ServingEngine` is the runnable engine used by the serving example — batched greedy decoding
with per-request and per-step metrics emitted to the LMS (time-to-first-token,
decode throughput), so a *serving* job is monitored exactly like a training
job (paper's "jobs" are agnostic to what runs inside).
"""

from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.transformer import forward, init_cache


def make_serve_fns(cfg: ModelConfig, *, pc=None, donate_cache: bool = True):
    """Returns (prefill_fn, decode_fn), both jit-able.

    prefill(params, tokens, cache, extras) -> (last_logits, cache)
    decode(params, cache, tokens, pos, extras) -> (logits, cache)

    The logits keep the padded vocab width; the padding columns (ids the
    tokenizer does not have) are masked to -inf so no sampler picks them.
    """

    def last(logits):
        lg = logits[:, -1]
        if cfg.vocab_padded == cfg.vocab_size:
            return lg
        pad = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
        return jnp.where(pad, -jnp.inf, lg)

    def prefill(params, tokens, cache, extras=None):
        logits, cache, _ = forward(params, cfg, tokens=tokens,
                                   mode="prefill", cache=cache, pc=pc,
                                   extras=extras or {})
        return last(logits), cache

    def decode(params, cache, tokens, pos, extras=None):
        logits, cache, _ = forward(params, cfg, tokens=tokens, mode="decode",
                                   cache=cache, pos=pos, pc=pc,
                                   extras=extras or {})
        return last(logits), cache

    return prefill, decode


def greedy_next(logits, pos):
    """The greedy choice of each row as the next decode step's input, a
    (B, 1) int32 token column, and that step's position ``pos + 1``: both
    stay on the device, so the next step needs nothing from the host."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None], pos + 1


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 16
    submitted_at: float = field(default_factory=time.monotonic)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    output: list = field(default_factory=list)


class ServingEngine:
    """Static-batch engine: collect up to ``max_batch`` requests, left-pad
    prompts to a common length, batched prefill, batched greedy decode.

    Padding note: prompts are right-aligned so every row's *last* prompt
    token lands at position plen-1 (where the first sampled logit is read);
    the left padding is BOS (token 0) and is attended — the demo-engine
    simplification vs. per-row attention masks, documented here.

    Lagged readback: each step's greedy token and position stay on the
    device (``greedy_next``) as the next decode step's input, and the host
    reads step *s*'s tokens only after it has dispatched step *s+1*.  The
    host runs one step ahead of the device, never more, so the device
    always holds the next decode step in its queue while the host waits
    on a read and runs the row loop.  Prefill's first token is still read
    at once: it is the time to first token.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_len: int = 256, usermetric=None, markers=None,
                 jit: bool = True):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.um = usermetric
        # marker regions (repro.core.marker) for the request phases —
        # default to the usermetric's session so serving phases land in
        # the same per-region roofline view as training
        self.markers = markers if markers is not None else (
            usermetric.markers if usermetric is not None else None)
        self._queue: list = []
        self._next_rid = 0
        prefill, decode = make_serve_fns(cfg)
        self.prefill = jax.jit(prefill) if jit else prefill
        self.decode = jax.jit(decode, donate_argnums=(1,)) if jit else decode
        self.greedy_next = jax.jit(greedy_next) if jit else greedy_next

    # -- request api -----------------------------------------------------------

    def submit(self, prompt_tokens, max_new_tokens: int = 16) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(rid, np.asarray(prompt_tokens,
                                                   np.int32),
                                   max_new_tokens))
        return rid

    def _metric(self, name, value, **tags):
        if self.um is not None:
            self.um.metric(name, value, tags=tags or None)

    @staticmethod
    def _read_tokens(reqs, tokens) -> float:
        """Appends one step's tokens (B, 1) to the rows still short of
        their answer; returns the seconds the host waited for them."""
        t0 = time.monotonic()
        tk = np.asarray(tokens)
        waited = time.monotonic() - t0
        for i, r in enumerate(reqs):
            if len(r.output) < r.max_new_tokens:
                r.output.append(int(tk[i, 0]))
        return waited

    # -- batch step ---------------------------------------------------------------

    def run_batch(self) -> list:
        """Serve one batch from the queue; returns finished Requests."""
        if not self._queue:
            return []
        reqs = self._queue[:self.max_batch]
        self._queue = self._queue[self.max_batch:]
        b = len(reqs)
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(reqs):                 # right-align prompts
            toks[i, plen - len(r.prompt):] = r.prompt

        m = self.markers
        t0 = time.monotonic()
        with (m.region("serve:prefill",
                       counters={"tokens": float(b * plen)})
              if m else nullcontext()):
            cache = init_cache(self.cfg, b, self.max_len)
            last_logits, cache = self.prefill(self.params,
                                              jnp.asarray(toks), cache)
            tok, pos = self.greedy_next(last_logits, jnp.int32(plen - 1))
            tk0 = np.asarray(tok)            # sync: real prefill time
        prefill_s = time.monotonic() - t0
        self._metric("serve_prefill", {"batch": b, "prompt_len": plen,
                                       "prefill_time_s": prefill_s})
        now = time.monotonic()
        for i, r in enumerate(reqs):
            r.first_token_at = now
            r.output.append(int(tk0[i, 0]))

        max_new = max(r.max_new_tokens for r in reqs)
        t_dec = time.monotonic()
        wait_s = 0.0
        dec_region = m.region("serve:decode") if m else nullcontext()
        with dec_region:
            lagged = None           # the step before's tokens, not yet read
            for _ in range(max_new - 1):
                logits, cache = self.decode(self.params, cache, tok, pos)
                tok, pos = self.greedy_next(logits, pos)
                if lagged is not None:
                    wait_s += self._read_tokens(reqs, lagged)
                lagged = tok
            if lagged is not None:
                wait_s += self._read_tokens(reqs, lagged)
            n_tok = sum(len(r.output) for r in reqs)
            if m:
                dec_region.add(tokens=float(n_tok - b))
        decode_s = time.monotonic() - t_dec
        self._metric("serve_decode", {
            "batch": b, "new_tokens": n_tok,
            "decode_time_s": decode_s, "readback_wait_s": wait_s,
            "tokens_per_s": n_tok / max(decode_s, 1e-9)})
        done = []
        now = time.monotonic()
        for r in reqs:
            r.finished_at = now
            self._metric("serve_request", {
                "ttft_s": r.first_token_at - r.submitted_at,
                "latency_s": r.finished_at - r.submitted_at,
                "new_tokens": len(r.output)}, rid=str(r.rid))
            if m:
                # externally-timed: a request's latency spans queueing,
                # not a code block on this thread
                m.record("serve:request", r.finished_at - r.submitted_at,
                         counters={"tokens": float(len(r.output))})
            done.append(r)
        return done

    def run_until_empty(self) -> list:
        out = []
        while self._queue:
            out.extend(self.run_batch())
        return out
