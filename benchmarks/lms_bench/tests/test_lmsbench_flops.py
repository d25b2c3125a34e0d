"""The FLOP counts against hand arithmetic, on the configuration files."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT)]

from benchmarks.lms_bench.flops import dense, moe  # noqa: E402

CONFIGS = ROOT / "benchmarks" / "lms_bench" / "configs"


def _conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_granite_two_layer_step_is_6NT_plus_attention():
    c = _conf("granite-3-8b-l2")
    d, ff, v = 4096, 12800, 49155
    per_layer = (4096 * 32 * 128 + 2 * 4096 * 8 * 128 + 32 * 128 * 4096
                 + 3 * d * ff)
    n = 2 * per_layer + d * v                  # the tied head, real vocab
    assert n == 599_797_760
    b, s = 2, 2048
    attn_fwd = 2 * 2 * 32 * 128 * (s * (s + 1) // 2) * 2 * b
    want = 6 * n * b * s + 3 * attn_fwd
    assert dense.train_step_flops(c, b, s) == pytest.approx(want, rel=1e-12)
    assert dense.train_step_flops(c, b, s) == pytest.approx(15.15e12,
                                                            rel=2e-3)


def test_count_grows_with_depth():
    """XLA's cost analysis counts a scanned layer stack's body once (2 and
    4 layers read the same); the benchmark's count adds every layer."""
    c2 = _conf("granite-3-8b-l2")
    c4 = dict(c2, num_hidden_layers=4)
    f2, f4 = (dense.train_step_flops(c, 2, 2048) for c in (c2, c4))
    head = 6 * 4096 * 49155 * 2 * 2048
    assert f4 - f2 == pytest.approx(f2 - head, rel=1e-12)
    assert f4 > f2 + 9.9e12


def test_moe_counts_only_routed_experts():
    c = _conf("mixtral-8x7b-l2")
    d, ff = 4096, 14336
    attn = 4096 * 32 * 128 + 2 * 4096 * 8 * 128 + 32 * 128 * 4096
    per_tok = 2 * (2 * (attn + d * 8 + 2 * 3 * d * ff) + d * 32000)
    # one token at position 0 attends one key in each of the 2 layers
    assert moe.forward_flops(c, [0]) == per_tok + 4 * 32 * 128 * 1 * 2
    # a request: its prompt once, then one step per token after the first
    assert moe.request_flops(c, 10, 3) == moe.forward_flops(c, range(12))
    c4 = dict(c, num_hidden_layers=4)
    assert moe.request_flops(c4, 10, 3) > 1.9 * moe.request_flops(c, 10, 3) \
        - 2 * d * 32000 * 12


def test_mixtral_decode_step_bytes_at_published_widths():
    """Every held weight in bfloat16 (all 8 experts), the head over the
    real vocabulary, 8 embedding rows, and 8 rows' keys and values at
    positions 0..1023 of both layers."""
    c = _conf("mixtral-8x7b-l2")
    d, ff = 4096, 14336
    attn = 4096 * 32 * 128 + 2 * 4096 * 8 * 128 + 32 * 128 * 4096
    layer = attn + d * 8 + 8 * 3 * d * ff + 2 * d
    assert layer == 1_451_270_144
    weights = 2 * layer + d + d * 32000 + 8 * d
    kv = 2 * 2 * 8 * 128 * 1024 * 8
    assert moe.decode_step_bytes(c, 8, 1023) == 2 * (weights + kv)
    assert moe.decode_step_bytes(c, 8, 1023) == 6_134_407_168
    # one position more adds each row's key and value in both layers
    assert moe.decode_step_bytes(c, 8, 1024) - \
        moe.decode_step_bytes(c, 8, 1023) == 2 * 2 * 2 * 8 * 128 * 8
