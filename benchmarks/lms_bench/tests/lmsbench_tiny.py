"""Tiny cells for the CPU tests: the real cells' traffic and limits with the
program's smoke-sized model, so that a whole run fits in a test."""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.lms_bench import bench  # noqa: E402

TRAIN = {"name": "tiny-dense", "arch": "granite-3-8b", "smoke": True,
         "family": "dense", "flops": "dense", "hidden_size": 64,
         "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
         "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
         "tie_word_embeddings": True, "param_dtype": "float32",
         "compute_dtype": "bfloat16",
         "train": {"learning_rate": 3e-4, "weight_decay": 0.1, "beta1": 0.9,
                   "beta2": 0.95, "eps": 1e-8, "grad_clip_norm": 1.0,
                   "warmup_steps": 1, "remat_policy": "minimal"}}

SERVE = {"name": "tiny-moe", "arch": "mixtral-8x7b", "smoke": True,
         "family": "moe", "flops": "moe", "hidden_size": 64,
         "intermediate_size": 64, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
         "vocab_size": 512, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
         "tie_word_embeddings": False, "num_local_experts": 4,
         "num_experts_per_tok": 2, "sliding_window": 16,
         "weight_dtype": "bfloat16", "compute_dtype": "bfloat16",
         "moe_capacity_factor": 2.0}

SERVE_BATCHES = [
    {"prompts": [24, 16, 16, 8, 8, 4, 4, 4], "new": [2, 2, 4, 4, 6, 6, 8, 8]},
    {"prompts": [16, 16, 8, 8, 8, 4, 4, 4], "new": [2, 2, 4, 4, 6, 6, 8, 8]}]


def cell(name: str) -> bench.Cell:
    c = bench.load_cell(name)
    c.traffic = json.loads(json.dumps(c.traffic))
    if c.traffic["generator"] == "train":
        c.config = json.loads(json.dumps(TRAIN))
        c.traffic.update(batch=2, seq_len=32, trace_s=1.0)
        if c.traffic.get("readers"):
            c.traffic["readers"].update(count=2, board_refresh_s=0.5,
                                        roofline_window_s=2)
    else:
        c.config = json.loads(json.dumps(SERVE))
        c.traffic.update(max_len=64, batches=SERVE_BATCHES,
                         sample_requests=4, trace_s=1.0)
    return c


def run(c: bench.Cell, seed: int = 2 ** 33 + 7, seconds: float = 2.0):
    import jax
    gen = bench.generator_module(c.traffic["generator"])
    return gen.run(c, seed, seconds, False, time.monotonic(), jax.devices())
