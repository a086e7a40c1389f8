"""Trace reduction on a small cut of a chip trace (tests/data): one layer of
a width-128 step of the chat cell on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace as T

CUT = Path(__file__).resolve().parent / "data" / "qwen2_chat_trace_cut.json"


@pytest.fixture(scope="module")
def reduced():
    fx = json.loads(CUT.read_text())
    return fx, T.reduce(fx, T.kernel_classes(), window=tuple(fx["window"]))


def test_busy_idle_and_kernel_times(reduced):
    fx, r = reduced
    assert r["window_s"] == pytest.approx(0.0256)
    assert r["busy_s"] == pytest.approx(0.025399413)
    assert r["class_s"]["lut"] == pytest.approx(0.023930506)
    assert r["class_s"]["attn"] == pytest.approx(0.000540636)
    assert [op for op, _ in r["device_ops"][:3]] == [
        "lut_matmul_fused_multi", "lut_matmul_fused", "paged_pool_attention"]
    label, gap = r["idle_gaps"][0]
    assert label == "bench.step" and gap == pytest.approx(0.0002)


def test_kernel_time_is_the_clipped_sum_of_its_ops(reduced):
    fx, r = reduced
    w0, w1 = fx["window"]
    lut = sum(min(s + d, w1) - max(s, w0) for name, _, s, d in fx["device"][0]
              if name.startswith("%lut_matmul_fused"))
    assert r["class_s"]["lut"] == pytest.approx(lut * 1e-9)
    # ops nest in no other op once the loop container is left out
    assert r["busy_s"] <= r["window_s"]
    assert "while" not in [op for op, _ in r["device_ops"]]


def test_op_names_and_classes():
    cls = T.kernel_classes()
    name = "%lut_matmul_fused_multi_gemv.19 = f32[16,17920] custom-call(x)"
    assert T.op_name(name) == "lut_matmul_fused_multi_gemv"
    assert T.classify(name, "", cls) == "lut"
    assert T.classify("%paged_pool_attention.6 = bf16[16]", "", cls) == "attn"
    assert T.classify("%fusion.3 = bf16[2]", "", cls) is None
