"""The engine's own counters and spans as bench/phases.py reads them: its
row counters against the Recorder's rebuild on a CPU run of the tiny cell,
gap naming on synthetic and recorded events, and the readers of host time
and admission wait against hand counts."""
import json
import types
from pathlib import Path

import pytest

from bench import phases as P
from bench import run as R
from bench import trace as T
from bench.tests import tiny

CUT = Path(__file__).resolve().parent / "data" / "qwen2_chat_trace_cut.json"


def test_row_counters_match_the_recorder_on_the_tiny_cell():
    out = P.measure(tiny.cell(), 2**33 + 7, 1.0, require_chip=False)
    assert out["steps"] > 0 and out["mixed_steps"] > 0
    rows, fed = out["rows_fed"]["counters"]
    assert (rows, fed) == tuple(out["rows_fed"]["recorder"])
    assert 0 < fed < rows
    share = out["pad_row_share"]
    assert share["counters"] == pytest.approx(share["reader"], abs=1e-12)
    host = out["host"]
    assert set(host["by_phase"]) == {"step", "admit", "reserve", "pack",
                                     "dispatch", "harvest"}
    assert host["host_ms"] == pytest.approx(sum(host["by_phase"].values()))
    assert host["wait_ms"] > 0 and out["admit_wait_ms"] >= 0
    assert out["admit_wait_ms"] <= out["ttft_mean_ms"]
    # time to first token in three parts: the first two are the wait
    # before admission, all three the whole
    reqs = out["requests"]
    assert reqs and min(min(r[1:]) for r in reqs) >= 0
    assert sum(r[1] + r[2] for r in reqs) / len(reqs) == pytest.approx(
        out["admit_wait_ms"])
    assert sum(sum(r[1:]) for r in reqs) / len(reqs) == pytest.approx(
        out["ttft_mean_ms"])


def _span(name, a, b):
    return [name, float(a), float(b - a)]


def test_gap_is_named_by_the_innermost_span_covering_most_of_it():
    spans = [_span("bench.step", 0, 100), _span("engine.step", 1, 97),
             _span("engine.pack", 10, 20), _span("engine.dispatch", 20, 30),
             _span("engine.wait", 30, 90), _span("bench.submit", 100, 106),
             _span("bench.step", 106, 200)]
    ops = [(0, 12), (16, 17), (26, 91), (93, 95), (106, 200)]
    device = [[[f"%fusion.{i} = f32[] fusion(x)", "", float(a), float(b - a)]
               for i, (a, b) in enumerate(ops)]]
    assert P.innermost(spans) == [
        _span("bench.step", 0, 1), _span("engine.step", 1, 10),
        _span("engine.pack", 10, 20), _span("engine.dispatch", 20, 30),
        _span("engine.wait", 30, 90), _span("engine.step", 90, 97),
        _span("bench.step", 97, 100), _span("bench.submit", 100, 106),
        _span("bench.step", 106, 200)]
    out = P.name_gaps(device, spans)
    assert [(label, round(d * 1e9)) for label, d in out["idle_gaps"]] == [
        ("bench.submit", 11),       # [95, 106): 6 of it in bench.submit
        ("engine.dispatch", 9),     # [17, 26): 3 in pack, 6 in dispatch
        ("engine.pack", 4),         # [12, 16), inside pack
        ("engine.step", 2)]         # [91, 93): the step's own time
    assert out["window_s"] - out["busy_s"] == pytest.approx(26e-9)
    assert out["window_s"] == pytest.approx(200e-9)


def test_recorded_trace_keeps_its_bench_labels():
    fx = json.loads(CUT.read_text())
    want = T.reduce(fx, T.kernel_classes())["idle_gaps"]
    got = P.name_gaps(fx["device"], fx["host"])["idle_gaps"]
    assert [g[0] for g in got] == [g[0] for g in want]
    assert [g[1] for g in got] == pytest.approx([g[1] for g in want])
    assert got[0][0] == "bench.step"


def test_host_ms_and_pairing_by_hand():
    steps = [R.Step(1.0, 2.0, True), R.Step(2.0, 2.5, False)]
    records = [(1.1, 1.9, {"host_s.step": 0.001, "host_s.pack": 0.002,
                           "host_s.wait": 0.5, "rows.w4": 8,
                           "rows_fed.w4": 3}),
               (2.1, 2.4, {"host_s.step": 0.003, "host_s.pack": 0.0,
                           "host_s.wait": 0.1, "rows.w1": 2,
                           "rows_fed.w1": 2})]
    deltas = P.deltas_of(records, steps)
    assert deltas == [r[2] for r in records]
    host = P.host_ms(deltas)
    assert host["host_ms"] == pytest.approx((3.0 + 3.0) / 2)
    assert host["by_phase"] == pytest.approx({"step": 2.0, "pack": 1.0})
    assert host["wait_ms"] == pytest.approx(300.0)
    assert P.rows_fed(deltas) == (10, 5)
    with pytest.raises(ValueError):
        P.deltas_of(records[:1], steps)


def test_admit_wait_by_hand():
    read = R.reader("admit_wait_ms")
    req = types.SimpleNamespace

    def run(tracks, trace_span=None):
        return types.SimpleNamespace(
            tracks=tracks, trace_span=trace_span,
            in_window=lambda t: 10.0 <= t < 20.0)

    tracks = [types.SimpleNamespace(due=9.0, request=req(admit_t=9.5)),
              types.SimpleNamespace(due=11.0, request=req(admit_t=11.25)),
              types.SimpleNamespace(due=12.0, request=req(admit_t=12.75)),
              types.SimpleNamespace(due=15.0, request=req(admit_t=17.0)),
              types.SimpleNamespace(due=None, request=req(admit_t=1.0))]
    assert read(run(tracks)) == pytest.approx((250.0 + 750.0 + 2000.0) / 3)
    # due while the profiler ran, from 14 s to its stop at 16 s: left out
    assert read(run(tracks, (14.0, 16.0))) == pytest.approx(
        (250.0 + 750.0) / 2)
    assert read(run(tracks, (10.0, 16.0))) is None
    tracks[2].request.admit_t = None            # due, never admitted
    assert read(run(tracks, (14.0, 16.0))) == 1e9
    # a program whose requests carry no admission stamp reads nothing
    assert read(run([types.SimpleNamespace(due=11.0, request=req())])) is None
    assert read(run(tracks[:1])) is None        # nothing due in the window
