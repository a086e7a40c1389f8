"""Percentiles, window rates and the roofline's bytes and flops against
hand counts."""
import math
import types

import pytest

from bench import roofline
from bench import run as R


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert R.percentile(xs, 95) == 95
    assert R.percentile(xs[:10], 99) == 10
    assert R.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert R.percentile([1.0, math.inf], 95) == math.inf


def _run(tracks, open_t=10.0, seconds=10.0, steps=()):
    return types.SimpleNamespace(
        tracks=tracks, seconds=seconds, steps=list(steps), open_t=open_t,
        close_t=open_t + seconds,
        in_window=lambda t: open_t <= t < open_t + seconds)


def test_window_rate_and_gaps():
    tok_s = R.reader("tokens_per_s")
    itl = R.reader("itl_p99_ms")
    ttft = R.reader("ttft_mean_ms")
    tr = [types.SimpleNamespace(times=[9.5, 10.5, 11.0, 19.9, 20.1],
                                due=9.0),
          types.SimpleNamespace(times=[12.0, 12.25], due=11.0),
          types.SimpleNamespace(times=[], due=15.0)]
    # iterations [9, 9.5] [9.5, 10.5] [10.5, 20.1] [20.1, 21]: the window's
    # whole iterations run from 10.5 (the end of the last one that began
    # before it opened) to 20.1 (the end of the last one that began before
    # it closed), and emit the tokens at 11.0, 12.0, 12.25, 19.9 and 20.1
    steps = [R.Step(a, b, False) for a, b in
             [(9.0, 9.5), (9.5, 10.5), (10.5, 20.1), (20.1, 21.0)]]
    run = _run(tr, steps=steps)
    assert tok_s(run) == pytest.approx(5 / 9.6)
    # gaps ending in the window: 1.0, 0.5, 8.9, 0.25 s
    assert itl(run) == pytest.approx(8900.0)
    # due in the window: 1000 ms and a request that never started
    assert ttft(run) == 1e9
    tr[2].times = [17.0]
    assert ttft(run) == pytest.approx((1000.0 + 2000.0) / 2)


def test_lut_bytes_and_flops_by_hand():
    groups = roofline.lut_groups([
        ("blocks.attn.wq", (28, 1536, 2048), 4),
        ("blocks.attn.wk", (28, 1536, 256), 4),
        ("blocks.attn.wv", (28, 1536, 256), 4),
        ("blocks.attn.wo", (28, 2048, 1536), 4)])
    qkv = [g for g in groups if g.k == 1536][0]
    assert qkv.ns == (2048, 256, 256) and qkv.layers == 28
    launches = roofline.lut_call([qkv], rows=16)
    assert len(launches) == 28
    flops, byts = launches[0]
    assert flops == 2 * 16 * 1536 * 2560
    assert byts == (768 * 2560 + 3 * 64 + 3 * 4 * 1536 + 16 * 1536 * 2
                    + 16 * 2560 * 4)
    assert roofline.packed_bytes(6144, 24576, 2) == 1536 * 24576
    assert roofline.bound_s([(2e12, 1e9)], 1e12, 1e9) == 2.0
    assert roofline.bound_s([(1e9, 3e9)], 1e12, 1e9) == 3.0


def test_attention_bytes_and_flops_by_hand():
    # one slot with 40 cached tokens feeding 1, one empty slot; 16 served
    # query heads over 2 kv heads: 8 rows per kv head at width 1
    (flops, byts), = roofline.attn_call(
        [(40, 1, 1)], 1, 1, layers=1, kv_heads=2, q_heads=16, head_dim=128,
        block_size=16)
    blocks = 3 + 1
    assert byts == blocks * 16 * 2 * 128 * 2 * 2 + 2 * 2 * 2 * 8 * 128 * 2
    assert flops == blocks * 2 * 2 * 2 * 8 * 16 * 128


def test_model_flops_by_hand():
    spec = {"hidden_size": 8, "intermediate_size": 16, "head_dim": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "num_hidden_layers": 3, "vocab_size": 10, "mlp": "swiglu"}
    per_token = 2 * 3 * (2 * 8 * 8 + 2 * 8 * 4 + 3 * 8 * 16)
    # 2 tokens fed after 5 cached (positions 6 and 7), one emitted
    want = 2 * per_token + 4 * 3 * 4 * 2 * (6 + 7) + 2 * 8 * 10
    assert roofline.model_flops(spec, [(5, 2, 1)]) == want
