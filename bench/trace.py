"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps. `load` turns the `.xplane.pb` the JAX profiler writes into plain
events; `reduce` works on those alone, so a small recorded cut of a chip
trace (tests/data) checks it without a chip.

Device ops are the events of the `XLA Ops` line of each `/device:TPU:<i>`
plane; host spans are the benchmark's own `bench.*` annotations. Kernels
are classed by the regular expressions of bench/kernels.json, matched
against an op's name and its `long_name`.
"""
from __future__ import annotations

import glob
import json
import re
from pathlib import Path
from typing import Dict, Optional

HERE = Path(__file__).resolve().parent
SPAN_PREFIX = "bench."
# container ops span their children and are not device work of their own
CONTROL = {"while", "conditional", "call"}


def op_name(name: str) -> str:
    """'lut_matmul_fused' for an event named
    '%lut_matmul_fused.13 = f32[...] custom-call(...)'."""
    m = re.match(r"%?([^\s=]+?)(?:\.\d+)?\s*=", name)
    return m.group(1) if m else re.sub(r"\.\d+$", "", name)


def load(trace_dir: str, chips: int) -> dict:
    """{"device": [[ [name, long_name, start_ns, dur_ns], ...] per chip],
    "host": [[name, start_ns, dur_ns], ...]} from the newest trace in
    `trace_dir`."""
    import jax

    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    device: Dict[int, list] = {}
    host = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < chips:
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    stats = dict(ev.stats)
                    evs.append([ev.name, str(stats.get("long_name", "")),
                                float(ev.start_ns), float(ev.duration_ns)])
            device[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"device": [device.get(i, []) for i in range(chips)],
            "host": sorted(host, key=lambda e: e[1])}


def kernel_classes(path: Path = HERE / "kernels.json") -> Dict[str, list]:
    with open(path) as f:
        return {k: [re.compile(p) for p in v]
                for k, v in json.load(f)["classes"].items()}


def classify(name: str, long_name: str, classes) -> Optional[str]:
    for cls, pats in classes.items():
        if any(p.search(name) or p.search(long_name) for p in pats):
            return cls
    return None


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: dict, classes, window=None, top: int = 10) -> dict:
    """Busy and idle time over the window (default: from the first to the
    last host span), seconds per kernel class, the ops that took most
    device time, and the longest idle gaps named by the host span that
    covers most of each. Times are averaged over chips."""
    host = events["host"]
    if window is None:
        window = (min(e[1] for e in host), max(e[1] + e[2] for e in host))
    w0, w1 = window
    chips = events["device"]
    busy, per_class, per_op, gaps = 0.0, {}, {}, []
    longs = {}
    for evs in chips:
        ivs = []
        for name, long_name, s, d in evs:
            s0, e0 = max(s, w0), min(s + d, w1)
            key = op_name(name)
            if e0 <= s0 or key in CONTROL:
                continue
            ivs.append((s0, e0))
            cls = classify(name, long_name, classes)
            if cls is not None:
                per_class[cls] = per_class.get(cls, 0.0) + (e0 - s0)
            per_op[key] = per_op.get(key, 0.0) + (e0 - s0)
            longs.setdefault(key, ((long_name or name)[:200], cls))
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    n = max(len(chips), 1)
    window_ns = w1 - w0

    def host_label(s, e):
        best, label = 0.0, "no host span"
        for name, hs, hd in host:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best:
                best, label = ov, name
        return label

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": window_ns * 1e-9,
        "class_s": {k: v / n * 1e-9 for k, v in sorted(per_class.items())},
        "device_ops": [[k, v / n * 1e-9] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_label(s, e), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
        "ops": [[k, v / n * 1e-9, *longs[k]] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:40]],
    }


def cut(events: dict, start_ns: float, length_ns: float) -> dict:
    """The events that start in [start, start + length): a small trace to
    keep as a test fixture."""
    end = start_ns + length_ns
    return {"device": [[e for e in evs if start_ns <= e[2] < end]
                       for evs in events["device"]],
            "host": [e for e in events["host"] if start_ns <= e[1] < end]}
