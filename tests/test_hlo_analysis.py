"""HLO cost walker: trip counts, dot FLOPs, collective accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import (HloAnalyzer, analyze_hlo,
                                       parse_computations)


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_dot_flops_exact():
    def f(a, b):
        return a @ b
    c = _compile(f, jax.ShapeDtypeStruct((64, 32), jnp.float32),
                 jax.ShapeDtypeStruct((32, 16), jnp.float32))
    got = analyze_hlo(c.as_text())["per_device"]["flops"]
    assert got == pytest.approx(2 * 64 * 32 * 16, rel=0.01)


def test_scan_trip_count_multiplies():
    def body(x, _):
        return jnp.tanh(x @ x), None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    c = _compile(f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
    res = analyze_hlo(c.as_text())
    assert list(res["trip_counts"].values()) == [7.0]
    # 7 iterations x 2*32^3 dot flops (+ elementwise)
    assert res["per_device"]["flops"] >= 7 * 2 * 32**3
    assert res["per_device"]["flops"] < 1.3 * 7 * 2 * 32**3
    # vs. the uncorrected cost_analysis, which counts the body once
    assert c.cost_analysis()["flops"] < 2 * 2 * 32**3 + 5000


def test_nested_scan_trip_counts():
    def inner(x, _):
        return x @ x, None

    def outer(x, _):
        y, _ = jax.lax.scan(inner, x, None, length=3)
        return y, None

    def f(x):
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y
    c = _compile(f, jax.ShapeDtypeStruct((16, 16), jnp.float32))
    res = analyze_hlo(c.as_text())
    assert res["per_device"]["flops"] >= 15 * 2 * 16**3


_KERNEL_HLO = """HloModule m

ENTRY %main (x: bf16[256,4096]) -> bf16[256,4096] {
  %x = bf16[256,4096]{1,0} parameter(0)
  ROOT %k = bf16[256,4096]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"TUzv","cost_estimate":{"flops":"12345","transcendentals":"7","bytes_accessed":"999","remote_bytes_transferred":"0"}}}
}
"""


def test_kernel_custom_call_declared_cost():
    """A Mosaic kernel is a custom call: the walk takes its flops from the
    cost estimate the kernel declares, and its operand + result bytes."""
    per = analyze_hlo(_KERNEL_HLO)["per_device"]
    assert per["flops"] == 12345.0
    assert per["transcendentals"] == 7.0
    assert per["bytes"] == per["bytes_fused"] == 2 * 256 * 4096 * 2


def test_bytes_reasonable():
    def f(a):
        return a * 2.0
    c = _compile(f, jax.ShapeDtypeStruct((1024,), jnp.float32))
    b = analyze_hlo(c.as_text())["per_device"]["bytes"]
    # one read + one write = 8 KiB
    assert 4096 <= b <= 4 * 8192


def test_parse_computations_shapes():
    text = """HloModule m, num_partitions=4

%foo (p: f32[2,3]) -> f32[2,3] {
  %p = f32[2,3]{1,0} parameter(0)
  ROOT %t = f32[2,3]{1,0} tanh(%p)
}

ENTRY %main (a: f32[2,3]) -> f32[2,3] {
  %a = f32[2,3]{1,0} parameter(0)
  ROOT %c = f32[2,3]{1,0} fusion(%a), kind=kLoop, calls=%foo
}
"""
    comps, np_ = parse_computations(text)
    assert np_ == 4
    assert set(comps) == {"foo", "main"}
    an = HloAnalyzer(text)
    cost = an.analyze()
    assert cost.flops == pytest.approx(5 * 6)      # tanh = 5 flops/elem


def test_collective_accounting_sharded():
    """psum over an 8-partition mesh (requires >1 device via sub-mesh trick:
    single-device fallback just checks zero collectives)."""
    ndev = len(jax.devices())
    if ndev == 1:
        def f(x):
            return x + 1
        c = _compile(f, jax.ShapeDtypeStruct((8,), jnp.float32))
        res = analyze_hlo(c.as_text())
        assert res["per_device"]["collective_operand_bytes"] == 0
    else:
        pytest.skip("multi-device path covered by test_multidevice")


# What the TPU backend emits (trimmed from a v5e compile of the granite
# train step): matmuls as convolutions inside fusions, a batched matmul as
# a convolution over the batch dims with lhs_dilate = window size, and a
# scan loop without ``known_trip_count``.
_TPU_STYLE = """HloModule m

%fused_mm (p0: bf16[2,2048,4096], p1: bf16[4096,12800,1]) -> bf16[2,2048,12800] {
  %p0 = bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[4096,12800,1]{1,0,2:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.30 = bf16[2,2048,12800]{2,1,0:T(8,128)(2,1)} convolution(%p0, %p1), window={size=1}, dim_labels=0bf_io0->0bf
}

%fused_bmm (q: bf16[2,8,4,2048,128], k: bf16[2,8,2048,128,1]) -> f32[2,8,4,2048,2048] {
  %q = bf16[2,8,4,2048,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %k = bf16[2,8,2048,128,1]{2,3,4,1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution-base-dilated.33 = f32[2,8,4,2048,2048]{3,4,2,1,0:T(8,128)} convolution(%q, %k), window={size=2x8x1 stride=1x7x1 lhs_dilate=2x8x1}, dim_labels=012bf_01oi2->012bf
}

%body (t: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %t = (s32[]{:T(128)}, f32[64,64]{1,0:T(8,128)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%t), index=0
  %x = f32[64,64]{1,0:T(8,128)} get-tuple-element(%t), index=1
  %one = s32[]{:T(128)} constant(1)
  %i2 = s32[]{:T(128)} add(%i, %one)
  %y = f32[64,64]{1,0:T(8,128)} convolution(%x, %x), dim_labels=bf_io->bf
  ROOT %r = (s32[]{:T(128)}, f32[64,64]{1,0:T(8,128)}) tuple(%i2, %y)
}

%cond (t2: (s32[], f32[64,64])) -> pred[] {
  %n = s32[]{:T(128)} constant(%N%)
  %t2 = (s32[]{:T(128)}, f32[64,64]{1,0:T(8,128)}) parameter(0)
  %i3 = s32[]{:T(128)} get-tuple-element(%t2), index=0
  ROOT %lt = pred[]{:T(512)} compare(%i3, %n), direction=LT
}

ENTRY %main (a: bf16[2,2048,4096], w: bf16[4096,12800,1], q: bf16[2,8,4,2048,128], k: bf16[2,8,2048,128,1], x: f32[64,64]) -> f32[64,64] {
  %a = bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %w = bf16[4096,12800,1]{1,0,2:T(8,128)(2,1)} parameter(1)
  %q = bf16[2,8,4,2048,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(2)
  %k = bf16[2,8,2048,128,1]{2,3,4,1,0:T(8,128)(2,1)} parameter(3)
  %x0 = f32[64,64]{1,0:T(8,128)} parameter(4)
  %mm = bf16[2,2048,12800]{2,1,0:T(8,128)(2,1)} fusion(%a, %w), kind=kOutput, calls=%fused_mm
  %bmm = f32[2,8,4,2048,2048]{3,4,2,1,0:T(8,128)} fusion(%q, %k), kind=kOutput, calls=%fused_bmm
  %zero = s32[]{:T(128)} constant(%START%)
  %z = s32[]{:T(128)} copy(%zero)
  %init = (s32[]{:T(128)}, f32[64,64]{1,0:T(8,128)}) tuple(%z, %x0)
  %loop = (s32[]{:T(128)}, f32[64,64]{1,0:T(8,128)}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[64,64]{1,0:T(8,128)} get-tuple-element(%loop), index=1
}
"""


@pytest.mark.parametrize("start,n", [(0, 5), (2, 5), (0, 40)])
def test_tpu_convolutions_and_untagged_loop(start, n):
    """Convolution FLOPs count only the taps that land on real input (a
    batched matmul's dilated window has one per output), and a loop the
    TPU backend leaves untagged runs ``N - start`` times, read off its
    ``counter < N`` condition and its initial tuple."""
    text = _TPU_STYLE.replace("%N%", str(n)).replace("%START%", str(start))
    res = analyze_hlo(text)
    assert res["trip_counts"] == {"loop": float(n - start)}
    mlp = 2 * 2 * 2048 * 4096 * 12800           # [4096, 2048x2] @ [., 12800]
    scores = 2 * (2 * 8 * 4) * 2048 * 2048 * 128  # 64 heads of S x S x 128
    # a trip: the matmul, the counter's add and the condition's compare
    loop = (n - start) * (2 * 64 ** 3 + 2)
    assert res["per_device"]["flops"] == mlp + scores + loop
