"""Operations a dense GQA decoder needs, counted from its widths.

Counts are model operations (a multiply-add is 2): what the algorithm
requires, not what the program computes.  Recomputation under remat, the
masked half of causal attention and the padded vocabulary rows are left
out.  Nothing here reads the compiled program.
"""

from __future__ import annotations


def matmul_params_per_layer(cfg: dict) -> int:
    """Weights that multiply every token in one layer (attention + MLP)."""
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    return attn + mlp_params_per_layer(cfg)


def mlp_params_per_layer(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg: dict) -> int:
    """The LM head over the real vocabulary (tied or not)."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: dict, q_positions) -> float:
    """Forward score and value products for queries at the given absolute
    positions, each attending to itself and every earlier position:
    2 (QK and PV) x 2 x heads x head_dim x keys."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    keys = sum(p + 1 for p in q_positions)
    return 4.0 * h * hd * keys * cfg["num_hidden_layers"]


def forward_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """One causal forward pass over ``batch`` sequences of ``seq_len``."""
    n = cfg["num_hidden_layers"] * matmul_params_per_layer(cfg) \
        + head_params(cfg)
    tokens = batch * seq_len
    # sum over positions 0..S-1 of (p + 1) keys, per sequence
    keys = seq_len * (seq_len + 1) // 2
    attn = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * keys \
        * cfg["num_hidden_layers"] * batch
    return 2.0 * n * tokens + attn


def train_step_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """Forward plus backward (twice the forward) of one training step."""
    return 3.0 * forward_flops(cfg, batch, seq_len)
