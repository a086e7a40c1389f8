"""Split a cell's scheduler iterations into the engine's phases, on the chip:

    python3 -m bench.phases --workload <cell> --seed <n> --seconds <s>

Runs the cell as `bench.run --trace 1` does (weights from the seed, the
engine, the mix's pre-roll, then the window with its first 8 s profiled)
and prints one JSON line read from the engine's own instrumentation
(DESIGN.md §5 "Spans and counters"):

- `host_ms`: the mean, over the window's scheduler iterations, of the
  engine's host self time outside the device wait (the iteration's change
  of every `host_s.*` counter but `host_s.wait`), and its split by phase;
- `rows_fed` and `pad_row_share`: the rows the window's step programs
  computed and the rows that fed a token, and the share that fed none,
  from the iterations' `rows.w*` / `rows_fed.w*` counters and, beside
  them, as bench/metrics/pad_row_share.py rebuilds them from slot state;
- `trace`: trace.reduce's busy time and longest device idle gaps over the
  traced window, each gap named by the innermost `bench.*` or `engine.*`
  host span covering most of it;
- `admit_wait_ms` and `ttft_mean_ms`, by their readers in bench/metrics,
  and `requests`: per request due in the window, when it was due (s after
  the opening) and its time to first token in three parts (ms): due to
  submitted (the generator waits for the iteration under way), submitted
  to admitted, admitted to first token; with `trace_s`, the stretch of
  the window the profiler covered (s after the opening).

No reference check runs: this is a reading for PERF.md, not a result
line. Exits 2 off a TPU, as bench.run does. What it reads goes into
bench/run.py and bench/trace.py in a benchmark change (PERF.md section 7,
item 6), which deletes this file.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import math
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

from bench import run as R
from bench import trace as T

SPAN_PREFIXES = ("bench.", "engine.")


def count_steps(engine, clock=time.perf_counter) -> list:
    """Wrap `engine.step` so that every call appends (start, end, the
    change of each counter) to the list returned."""
    records = []
    step = engine.step

    def counted():
        before, t0 = dict(engine.counters), clock()
        done = step()
        records.append((t0, clock(), {k: v - before.get(k, 0)
                                      for k, v in engine.counters.items()}))
        return done

    engine.step = counted
    return records


def deltas_of(records, steps) -> List[dict]:
    """The counter change of each of the Recorder's `steps`: the record
    whose call lies inside the step (the Recorder's clock reads bracket
    the engine's call)."""
    starts = [r[0] for r in records]
    out = []
    for s in steps:
        i = bisect.bisect_left(starts, s.t0)
        if i == len(records) or records[i][1] > s.t1:
            raise ValueError(f"no engine call inside the step at {s.t0}")
        out.append(records[i][2])
    return out


def host_ms(deltas: List[dict]) -> Optional[dict]:
    """Mean host ms per iteration outside the device wait, by phase and in
    all, and the mean device wait."""
    if not deltas:
        return None
    phases: Dict[str, float] = {}
    for d in deltas:
        for k, v in d.items():
            if k.startswith("host_s."):
                phases[k[len("host_s."):]] = phases.get(
                    k[len("host_s."):], 0.0) + 1e3 * v / len(deltas)
    wait = phases.pop("wait", 0.0)
    return {"host_ms": sum(phases.values()), "by_phase": phases,
            "wait_ms": wait}


def rows_fed(deltas: List[dict]) -> tuple:
    """(rows computed, rows that fed a token) of the iterations, from the
    engine's `rows.w*` / `rows_fed.w*` counters."""
    return (sum(v for d in deltas for k, v in d.items()
                if k.startswith("rows.w")),
            sum(v for d in deltas for k, v in d.items()
                if k.startswith("rows_fed.w")))


def recorded_rows_fed(calls) -> tuple:
    """The same two counts as bench/metrics/pad_row_share.py rebuilds them
    from the Recorder's calls."""
    return (sum((len(c.slots) + c.n_empty) * c.width for c in calls),
            sum(n for c in calls for _, n, _ in c.slots))


def host_spans(trace_dir: str, prefixes=SPAN_PREFIXES) -> list:
    """[[name, start_ns, dur_ns], ...] of the host spans whose names start
    with one of `prefixes`, from the newest trace in `trace_dir`."""
    import jax

    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    return sorted(([ev.name, float(ev.start_ns), float(ev.duration_ns)]
                   for plane in pd.planes if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith(prefixes)), key=lambda e: e[1])


def innermost(spans: list) -> list:
    """Host spans [[name, start, dur], ...], nested, cut into disjoint
    pieces each named by the innermost span over it; trace.reduce, which
    names a gap by the host span covering most of it, then names it by the
    innermost span covering most of it."""
    pieces, stack, t = [], [], -math.inf      # stack: open [name, end]

    def advance(x):
        nonlocal t
        while stack and stack[-1][1] <= x:
            name, end = stack.pop()
            if end > t:
                pieces.append([name, t, end - t])
                t = end
        if stack and x > t:
            pieces.append([stack[-1][0], t, x - t])
        t = max(t, x)

    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        advance(s)
        stack.append([name, s + d])
    advance(math.inf)
    return pieces


def name_gaps(device: list, spans: list) -> dict:
    """trace.reduce over the window from the first to the last `bench.*`
    span, its longest idle gaps named by the innermost host span."""
    bench = [sp for sp in spans if sp[0].startswith("bench.")]
    window = (min(sp[1] for sp in bench), max(sp[1] + sp[2] for sp in bench))
    out = T.reduce({"device": device, "host": innermost(spans)},
                   T.kernel_classes(), window=window)
    return {k: out[k] for k in ("window_s", "busy_s", "idle_gaps")}


def measure(cell: R.Cell, seed: int, seconds: float,
            trace_dir: Optional[Path] = None,
            require_chip: bool = True) -> dict:
    """One run of the cell, read from the engine's counters and spans."""
    import jax

    from bench import traffic
    from repro.utils import enable_compile_cache

    if require_chip:
        devs, peaks = R.device_or_refuse(cell.chips)
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    else:
        devs, peaks = jax.devices()[:cell.chips], {}
    engine, _, _ = R.build(cell, seed, require_kernels=require_chip,
                           devs=devs)
    items = traffic.generate(cell.mix, seed, cell.spec["vocab_size"], seconds)
    records = count_steps(engine)
    profiler = R.Profiler(trace_dir) if trace_dir is not None else None
    rec, tracks, open_t, close_t, span, _ = R.drive(
        engine, cell, items, seconds, profiler=profiler)
    run = R.Run(cell, seconds, open_t, close_t, tracks, rec.steps,
                rec.calls, open_t - R.T_PROCESS, peaks, [], trace_span=span)
    steps = run.window_steps()
    deltas = deltas_of(records, steps)
    out = {"workload": cell.workload, "seed": seed,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)},
           "steps": len(steps), "mixed_steps": sum(s.mixed for s in steps),
           "host": host_ms(deltas),
           "rows_fed": {"counters": rows_fed(deltas),
                        "recorder": recorded_rows_fed(run.window_calls())},
           "pad_row_share": {"reader": R.reader("pad_row_share")(run)},
           "preempted": sum(d.get("preempted", 0) for d in deltas)}
    rows, fed = out["rows_fed"]["counters"]
    out["pad_row_share"]["counters"] = (100.0 * (1.0 - fed / rows)
                                        if rows else None)
    for name in ("admit_wait_ms", "ttft_mean_ms"):
        out[name] = R.reader(name)(run)
    out["requests"] = [
        [t.due - open_t, 1e3 * (t.submitted - t.due),
         1e3 * (t.request.admit_t - t.submitted),
         1e3 * (t.times[0] - t.request.admit_t)]
        for t in tracks if t.due is not None and run.in_window(t.due)
        and t.times]
    out["trace_s"] = None if span is None else [x - open_t for x in span]
    if profiler is not None:
        out["trace"] = name_gaps(T.load(str(trace_dir), cell.chips)["device"],
                                 host_spans(str(trace_dir)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(R.ROOT / "src"))
    try:
        cell = R.load_cell(args.workload)
        out = measure(cell, args.seed, args.seconds,
                      R.BENCH / ".runs" / f"{cell.workload}.{args.seed}"
                      ".phases.trace")
    except R.Refused as e:
        R.log(f"bench.phases: {e.msg}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
