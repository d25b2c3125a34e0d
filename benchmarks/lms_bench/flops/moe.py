"""Operations a top-k mixture-of-experts GQA decoder needs per token.

Per token and layer: the attention projections, the router, and the
SwiGLU FFN of the ``num_experts_per_tok`` experts it is routed to (not of
all experts, and not of the empty capacity a dispatch computes).  Causal
attention counts the keys each query really attends.  The LM head covers
the real vocabulary.  Counted from the configuration's widths alone.

``decode_step_bytes`` counts the other side of a decode step: the bytes it
must read from HBM.
"""

from __future__ import annotations

from benchmarks.lms_bench.flops import dense


def matmul_params_per_token_layer(cfg: dict) -> int:
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    router = d * cfg["num_local_experts"]
    experts = cfg["num_experts_per_tok"] * 3 * d * cfg["intermediate_size"]
    return attn + router + experts


def forward_flops(cfg: dict, positions) -> float:
    """Forward operations for one token at each given absolute position
    (its own context is every earlier position of its sequence)."""
    positions = list(positions)
    n = cfg["num_hidden_layers"] * matmul_params_per_token_layer(cfg) \
        + dense.head_params(cfg)
    return 2.0 * n * len(positions) + dense.attention_flops(cfg, positions)


def request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """The useful work of serving one request: its prompt once, then one
    forward step per delivered token after the first (which prefill
    gives); positions are the request's own, with no padding."""
    return forward_flops(cfg, range(prompt_len + max(new_tokens - 1, 0)))


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_params(cfg: dict) -> int:
    """Every weight one layer holds: attention, router, all experts, and
    its two norms."""
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    experts = cfg["num_local_experts"] * 3 * d * cfg["intermediate_size"]
    return attn + d * cfg["num_local_experts"] + experts + 2 * d


def decode_step_bytes(cfg: dict, batch: int, pos: int) -> float:
    """Bytes one decode step of ``batch`` rows must read when ``pos``
    tokens are already in the cache: every held layer's weights (all
    experts: a batch's top-k routes reach each of them), the final norm
    and the LM head over the real vocabulary, the batch's embedding rows,
    and each row's keys and values at positions 0..pos of every layer."""
    w = DTYPE_BYTES[cfg["weight_dtype"]]
    d = cfg["hidden_size"]
    weights = cfg["num_hidden_layers"] * layer_params(cfg) + d \
        + dense.head_params(cfg) + batch * d
    kv = 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * (pos + 1) * batch
    return float(w * weights + DTYPE_BYTES[cfg["compute_dtype"]] * kv)
