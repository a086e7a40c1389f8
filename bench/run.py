"""Run one benchmark cell once:

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json `workloads`) names a configuration
(bench/configs/<config>.json) and a traffic mix (bench/traffic/<mix>.json).
Set-up draws the weights on the device from the seed (bench/weights.py),
builds the engine with `build_engine` and the configuration's engine
settings over the cell's chips, compiles its two step widths, and runs the
mix for the mix's `preroll_s`, at least one request's life, so the slots
are at steady state when the window opens (the detail file records the
occupancy there).
The window then drives `ServingEngine.submit` / `ServingEngine.step` for
`--seconds`. Afterwards a sample of the finished requests is compared with
the float32 reference (bench/check.py) and the last stdout line is the
result: `correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` `breakdown`, and `check` (each number compared beside its
limit). With `--trace 0` the metrics are the cell's end-to-end metrics,
with `--trace 1` its per-layer ones, each computed by
bench/metrics/<name>.py. A detail file is written under bench/.runs/.

Exits nonzero with no result line off a TPU, with fewer chips than the cell
asks for, on a device kind missing from bench/peaks.json, or when the
serving dispatch does not resolve to the compiled kernels.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import itertools
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

T_PROCESS = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECK_TOKENS = 384            # served tokens the check compares at least
TRACE_SECONDS = 8.0           # longest stretch of the window a trace covers
FOLLOW_UP_S = 150.0           # longest the run goes on after the window for
                              # requests to finish for the check


class Refused(SystemExit):
    """Exit with code 2 and a message, before any result line."""

    def __init__(self, msg: str):
        super().__init__(2)
        self.msg = msg

    def __str__(self) -> str:
        return self.msg


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    workload: str
    chips: int
    spec: dict                      # bench/configs/<config>.json
    mix: dict                       # bench/traffic/<mix>.json
    end_to_end: List[dict]          # metric entries that apply to the cell
    per_layer: List[dict]
    limits: dict                    # bench/checks/<workload>.json


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    from bench import check, traffic

    with open(root / "BENCHMARK.json") as f:
        bj = json.load(f)
    cells = {w["name"]: w for w in bj["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bj["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        spec = json.load(f)
    return Cell(workload, int(w["chips"]), spec,
                traffic.load(w["traffic"], root / "bench"),
                [m for m in bj["end_to_end"] if _applies(m, workload)],
                [m for m in bj["per_layer"] if _applies(m, workload)],
                check.limits(workload, root / "bench"))


def device_or_refuse(chips: int):
    """(devices, peaks) of the chip the run holds; refuses anything else."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips; JAX found "
                      f"{len(devs)}")
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    if devs[0].device_kind not in peaks:
        raise Refused(f"no peaks for device kind {devs[0].device_kind!r} in "
                      f"bench/peaks.json")
    return devs[:chips], peaks[devs[0].device_kind]


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def program_config(spec: dict):
    """The program's ModelConfig for the configuration: the registered
    arch, with the file's layer count and widths."""
    from repro.models.config import get_config, register

    base = get_config(spec["arch"])
    want = dict(n_layers=spec["num_hidden_layers"],
                d_model=spec["hidden_size"], d_ff=spec["intermediate_size"],
                n_heads=spec["num_attention_heads"],
                n_kv_heads=spec["num_key_value_heads"],
                head_dim=spec["head_dim"], vocab=spec["vocab_size"],
                dtype=spec["torch_dtype"])
    if any(getattr(base, k) != v for k, v in want.items()):
        tag = "-".join(f"{v}" for v in want.values())
        base = register(dataclasses.replace(
            base, arch_id=f"{spec['arch']}@{tag}", **want))
    fixed = dict(norm=spec["norm"], mlp=spec["mlp"],
                 qkv_bias=spec["qkv_bias"],
                 n_heads_eff=spec["served"]["heads"],
                 rope_theta=float(spec["rope_theta"]))
    wrong = {k: (getattr(base, k), v) for k, v in fixed.items()
             if getattr(base, k) != v}
    if wrong:
        raise ValueError(f"program config {base.arch_id} disagrees with "
                         f"{spec['name']}: {wrong}")
    return base


def geometry(spec: dict, mix: dict) -> dict:
    """Engine geometry: the configuration's serving knobs, with a block pool
    that holds every slot at the mix's longest request."""
    from bench import traffic

    served = spec["served"]
    bs = served["block_size"]
    per_slot = -(-traffic.longest(mix) // bs)
    return dict(num_slots=served["num_slots"], block_size=bs,
                prefill_chunk=served["prefill_chunk"],
                max_blocks_per_slot=per_slot,
                num_blocks=served["num_slots"] * per_slot)


def engine_config(cell: Cell, arch_id: str):
    """The EngineConfig of the cell: the geometry, then every EngineConfig
    field the configuration names under `served.engine` (kv_dtype,
    prefix_cache, speculative_k, model_parallel, ...). A cell on several
    chips shards the model over all of them unless `served.engine` lays the
    mesh out itself."""
    from repro.launch.engine import EngineConfig

    eng = dict(cell.spec["served"].get("engine", {}))
    if cell.chips > 1 and not {"model_parallel", "data_parallel"} & set(eng):
        eng["model_parallel"] = cell.chips
    lcd = cell.spec.get("lcd")
    return EngineConfig(**geometry(cell.spec, cell.mix),
                        weight_bits=lcd["nbits"] if lcd else 4,
                        arch=arch_id, **eng)


def mesh_of(ecfg, devs):
    """The (data, model) serving mesh over the cell's own chips."""
    from repro.launch.mesh import auto_mesh

    n = len(devs)
    mp = ecfg.model_parallel or (n // ecfg.data_parallel
                                 if ecfg.data_parallel else 1)
    return auto_mesh((n // mp, mp), ("data", "model"), devices=devs)


def build(cell: Cell, seed: int, require_kernels: bool = True, devs=None):
    """(engine, params, timings): weights drawn on the device, the engine
    built on them over the cell's chips, both step widths compiled."""
    import jax

    from bench import weights
    from repro.kernels.ops import _resolve_mode
    from repro.kernels.paged_attention import resolved_paged_attention_mode
    from repro.launch.engine import build_engine
    from repro.models.registry import get_model

    if require_kernels:
        modes = sorted({_resolve_mode(None), resolved_paged_attention_mode()})
        if modes != ["kernel"]:
            raise Refused(f"serving dispatch resolves to {modes}, not the "
                          f"compiled kernels")
    times = {}
    cfg = program_config(cell.spec)
    t = time.perf_counter()
    params = weights.build(get_model(cfg), cell.spec, seed)
    jax.block_until_ready(params)
    times["weights_s"] = time.perf_counter() - t
    ecfg = engine_config(cell, cfg.arch_id)
    devs = jax.devices()[:cell.chips] if devs is None else devs
    t = time.perf_counter()
    engine, _ = build_engine(cfg.arch_id, use_reduced=False,
                             lcd=bool(cell.spec.get("lcd")), params=params,
                             ecfg=ecfg, mesh=mesh_of(ecfg, devs))
    times["engine_s"] = time.perf_counter() - t
    # both widths: a prompt one token past a chunk runs the wide program
    # twice, then its answer runs the width-1 program
    t = time.perf_counter()
    engine.submit(np.zeros(ecfg.prefill_chunk + 1, np.int32), 2)
    engine.run()
    engine.finished.clear()
    times["warm_up_s"] = time.perf_counter() - t
    return engine, params, times


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Track:
    """One submitted request as the benchmark follows it."""
    prompt: np.ndarray
    max_new: int
    due: Optional[float]            # clock time it was due (open loop)
    submitted: float
    request: Any = None
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Call:
    """One run of a step program: its width and, per occupied slot,
    (cached length, tokens fed, tokens emitted)."""
    width: int
    slots: List[tuple]
    n_empty: int
    t0: float                       # start of the step it belongs to


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    mixed: bool                     # a slot was prefilling
    queued: int = 0                 # requests waiting after the step
    active: int = 0                 # slots occupied after the step


class Recorder:
    """Drives `engine.step()` and reads, from the scheduler's public state
    around it, which step programs ran and what each slot fed."""

    def __init__(self, engine, clock):
        self.engine, self.clock = engine, clock
        self.steps: List[Step] = []
        self.calls: List[Call] = []
        self.spans = contextlib.nullcontext

    def step(self):
        eng = self.engine
        before = {r.rid: (r.fed, r.prefilling, len(r.out_tokens))
                  for r in eng.slots if r is not None}
        t0 = self.clock()
        with self.spans("bench.step"):
            done = eng.step()
        t1 = self.clock()
        after = [r for r in eng.slots if r is not None] + done
        if not after:
            return done
        # (cached length, fed, emitted) per request, by the program it ran in
        wide, narrow = [], []
        for r in after:
            fed0, pre, out0 = before.get(r.rid, (0, True, 0))
            row = (fed0, r.fed - fed0, len(r.out_tokens) - out0)
            (wide if pre else narrow).append(row)
        empty = eng.ecfg.num_slots - len(after)
        if wide:
            self.calls.append(Call(eng.ecfg.prefill_chunk, wide + [
                (ln, 0, 0) for ln, _, _ in narrow], empty, t0))
        if narrow:
            self.calls.append(Call(1, narrow + [
                (ln + n, 0, 0) for ln, n, _ in wide], empty, t0))
        self.steps.append(Step(t0, t1, bool(wide), len(eng.queue),
                               sum(r is not None for r in eng.slots)))
        return done


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or below it."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    return xs[max(int(math.ceil(q / 100.0 * len(xs))) - 1, 0)]


@dataclasses.dataclass
class Run:
    """What one run measured: the readers in bench/metrics take it."""
    cell: Cell
    seconds: float
    open_t: float                   # window opens (clock time)
    close_t: float
    tracks: List[Track]
    steps: List[Step]
    calls: List[Call]
    setup_s: float
    peaks: dict
    lut_groups: list
    trace: Optional[dict] = None
    trace_span: Optional[tuple] = None   # clock times the trace covered

    def in_window(self, t: float) -> bool:
        return self.open_t <= t < self.close_t

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.in_window(s.t0)]

    def window_calls(self) -> List[Call]:
        return [c for c in self.calls if self.in_window(c.t0)]

    def traced_calls(self) -> List[Call]:
        if self.trace_span is None:
            return []
        a, b = self.trace_span
        return [c for c in self.calls if a <= c.t0 < b]


class Profiler:
    """jax.profiler over part of the window, with the benchmark's host
    spans written into the same trace."""

    def __init__(self, directory: Path):
        self.dir = directory
        self.on = False

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self):
        import jax
        jax.profiler.start_trace(str(self.dir))
        self.on = True

    def stop(self):
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False


def drive(engine, cell: Cell, items, seconds: float, clock=time.perf_counter,
          profiler: Optional[Profiler] = None, check_tokens: int = 0):
    """Run the mix: pre-roll (the mix's `preroll_steps` iterations or
    `preroll_s` seconds), the window, then the follow-up. Items with a due
    time are released at it (open loop); the others top the queue up to the
    mix's `backlog`. After the window the run goes on until every
    request due in the window has its first token (at most the mix's
    `grace_s`), then, with no more arrivals, until finished requests hold
    `check_tokens` served tokens (at most FOLLOW_UP_S). Returns (recorder,
    tracks, open_t, close_t, trace_span, late)."""
    mix = cell.mix
    rec = Recorder(engine, clock)
    if profiler is not None:
        rec.spans = profiler.span
    span = rec.spans
    tracks: List[Track] = []
    late: List[float] = []

    def submit(item, due):
        tr = Track(item.prompt, item.max_new, due, clock())
        tr.request = engine.submit(
            item.prompt, item.max_new,
            on_token=lambda r, tok, times=tr.times: times.append(clock()))
        tracks.append(tr)
        if due is not None:
            late.append(tr.submitted - due)

    dated = sorted((it for it in items if it.due is not None),
                   key=lambda it: it.due)
    undated = [it for it in items if it.due is None]
    pool = itertools.cycle(undated) if undated else iter(())
    backlog = mix.get("backlog", 0)

    def top_up():
        while len(engine.queue) < backlog:
            submit(next(pool), None)

    # a backlog's pre-roll is a fixed number of scheduler iterations, so its
    # window opens at the same point of the (deterministic) step sequence
    # on every run; an open loop's is wall-clock time, as its arrivals are
    for _ in range(mix.get("preroll_steps", 0)):
        top_up()
        rec.step()
    start = clock()
    open_t = start + mix.get("preroll_s", 0.0)
    close_t = open_t + seconds
    end = close_t + mix.get("grace_s", 0.0)
    trace_span = None
    tracing = False

    def trace_edge(now):
        nonlocal tracing, trace_span
        if profiler is None:
            return
        if not tracing and trace_span is None and now >= open_t:
            profiler.start()
            tracing, trace_span = True, (clock(), None)
        elif tracing and (now >= close_t
                          or now >= trace_span[0] + TRACE_SECONDS):
            profiler.stop()
            tracing, trace_span = False, (trace_span[0], clock())

    due = [open_t + it.due for it in dated]
    window_due = [i for i, d in enumerate(due) if open_t <= d < close_t]
    tracked = {}                    # index into `dated` -> its Track
    nxt = 0
    while True:
        now = clock()
        trace_edge(now)
        with span("bench.submit"):
            while nxt < len(dated) and due[nxt] <= now:
                submit(dated[nxt], due[nxt])
                tracked[nxt] = tracks[-1]
                nxt += 1
            if now < close_t:
                top_up()
        if now >= close_t and all(i in tracked and tracked[i].times
                                  for i in window_due):
            break
        if now >= end:
            break
        if engine.busy:
            rec.step()
        else:
            # idle: nothing queued, the next arrival (if any) not yet due
            wake = due[nxt] if nxt < len(dated) else end
            with span("bench.wait"):
                time.sleep(min(max(wake - clock(), 0.0), 0.01))
    if tracing:
        profiler.stop()
        trace_span = (trace_span[0], clock())
    end = clock() + FOLLOW_UP_S
    while engine.busy and clock() < end and sum(
            len(t.request.out_tokens) for t in tracks
            if t.request.done) < check_tokens:
        rec.step()
    return rec, tracks, open_t, close_t, trace_span, late


# ---------------------------------------------------------------------------
# Metrics, check, result
# ---------------------------------------------------------------------------

def reader(name: str, root: Path = BENCH):
    """bench/metrics/<name>.py, else bench/metrics/<name before '.'>.py."""
    for stem in (name, name.split(".", 1)[0]):
        path = root / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{root / 'metrics'}")


def metrics_of(run: Run, entries: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in entries:
        v = reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def occupancy(run: Run, num_slots: int) -> dict:
    """Slots occupied and requests queued at the window's opening and
    close, and the slots occupied on average (by time) over the window."""
    before = [s for s in run.steps if s.t1 <= run.open_t]
    w = run.window_steps()
    t = sum(s.t1 - s.t0 for s in w)
    return {"slots": num_slots,
            "at_open": before[-1].active if before else 0,
            "window_mean": (sum(s.active * (s.t1 - s.t0) for s in w) / t
                            if t else None),
            "queued_at_open": before[-1].queued if before else 0,
            "queued_at_close": w[-1].queued if w else None}


def lut_groups_of(params):
    import jax

    from bench import roofline
    from bench.weights import leaf_name
    from repro.core.api import is_clustered

    flat = jax.tree_util.tree_flatten_with_path(params, is_leaf=is_clustered)[0]
    return roofline.lut_groups([(leaf_name(p), tuple(x.codes.shape), x.nbits)
                                for p, x in flat if is_clustered(x)])


def compare(cell: Cell, params, tracks: List[Track], seed: int):
    """(readings {name: {"value", "limit"}}, whether they pass). Frees
    nothing itself; call after the program's state is released."""
    from bench import check, weights
    from bench.reference import Reference

    finished = [(t.prompt, list(t.request.out_tokens)) for t in tracks
                if t.request is not None and t.request.done]
    pick = check.sample(finished, seed, CHECK_TOKENS)
    pub = weights.published(params, cell.spec)
    ref = Reference(cell.spec, pub)
    worst, n = check.worst_gap(ref, [finished[i] for i in pick])
    lim = cell.limits["max_gap"]["limit"]
    need = min(CHECK_TOKENS, sum(len(o) for _, o in finished))
    readings = {"max_gap": {"value": worst, "limit": lim},
                "tokens_compared": {"value": n, "limit": need}}
    return readings, bool(pick) and worst <= lim and n >= need


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, out_dir: Path = BENCH / ".runs",
             t_process: float = T_PROCESS) -> dict:
    """One run of the cell; returns the result line's object. Off the chip
    (`require_chip=False`, the CPU tests) it takes whatever devices JAX has,
    the dispatch the program picks there, and no compile cache."""
    import jax

    from bench import trace as trace_mod
    from bench import traffic
    from repro.utils import enable_compile_cache

    if not require_chip:
        devs = jax.devices()[:cell.chips]
        with open(BENCH / "peaks.json") as f:
            peaks = next(iter(json.load(f).values()))
    else:
        devs, peaks = device_or_refuse(cell.chips)
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    engine, params, times = build(cell, seed, require_kernels=require_chip,
                                  devs=devs)
    num_slots = engine.ecfg.num_slots
    items = traffic.generate(cell.mix, seed, cell.spec["vocab_size"], seconds)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append((time.perf_counter(), ev))
        if "compile" in ev else None)
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{cell.workload}.{seed}.t{int(trace)}"
    profiler = Profiler(out_dir / f"{tag}.trace") if trace else None
    t_drive = time.perf_counter()
    rec, tracks, open_t, close_t, span, late = drive(
        engine, cell, items, seconds, profiler=profiler,
        check_tokens=CHECK_TOKENS)
    times["after_window_s"] = time.perf_counter() - close_t
    setup_s = open_t - t_process
    times["preroll_s"] = open_t - t_drive
    engine.assert_bounded_traces()
    in_window = [c for c in compiles if open_t <= c[0] < close_t]
    mem = [d.memory_stats() or {} for d in devs]
    peak_mem = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)

    due_in = [t for t in tracks
              if t.due is not None and open_t <= t.due < close_t]
    # requests due in the window fail without a first token; a backlog's
    # requests count where they were queued or served during the window
    attempted = len(due_in) + sum(
        1 for t in tracks if t.due is None and t.submitted < close_t
        and not (t.request.done and t.times and t.times[-1] < open_t))
    failed = sum(1 for t in due_in if not t.times)
    run = Run(cell, seconds, open_t, close_t, tracks, rec.steps, rec.calls,
              setup_s, peaks, lut_groups_of(params), trace_span=span)
    breakdown = None
    if trace:
        events = trace_mod.load(str(profiler.dir), cell.chips)
        h0 = min((e[1] for e in events["host"]), default=0.0)
        run.trace = trace_mod.reduce(events, trace_mod.kernel_classes())
        with open(out_dir / f"{tag}.events.json", "w") as f:
            json.dump(trace_mod.cut(events, h0, 0.2e9), f)
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}

    metrics = metrics_of(run, cell.per_layer if trace else cell.end_to_end)

    # the program's state goes before the reference runs
    del engine, rec
    gc.collect()
    t = time.perf_counter()
    readings, ok = compare(cell, params, tracks, seed)
    times["check_s"] = time.perf_counter() - t
    correct = ok and failed == 0 and not in_window

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_mem)}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = readings

    wsteps = run.window_steps()
    detail = {
        "workload": cell.workload, "seed": seed, "seconds": seconds,
        "trace": trace, "setup": {**times, "setup_s": setup_s},
        "generator_late_s": {"p50": percentile(late, 50) if late else None,
                             "max": max(late) if late else None,
                             "n": len(late)},
        "requests": {"attempted": attempted, "failed": failed,
                     "submitted": len(tracks),
                     "finished": sum(1 for t in tracks
                                     if t.request.done),
                     "preempted": sum(t.request.preemptions
                                      for t in tracks)},
        "samples": {"ttft": len(due_in),
                    "itl": sum(1 for t in tracks for a, b in
                               zip(t.times, t.times[1:]) if run.in_window(b)),
                    "steps": len(wsteps),
                    "mixed_steps": sum(s.mixed for s in wsteps),
                    "calls": len(run.window_calls()),
                    "traced_calls": len(run.traced_calls())},
        "occupancy": occupancy(run, num_slots),
        "compiles_in_window": len(in_window),
        "memory_peak_bytes": int(peak_mem),
        "memory_stats": mem[0],
        "check": readings, "correct": correct,
        "metrics": metrics,
        "trace": run.trace,
    }
    with open(out_dir / f"{tag}.json", "w") as f:
        json.dump(detail, f, indent=1, default=str)
    log(f"detail: {out_dir / f'{tag}.json'}")
    log(f"compiles in window: {len(in_window)} (must be 0); requests "
        f"attempted {attempted}, failed {failed}")
    for k, v in readings.items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"bench: the program is missing: no {src / 'repro'}")
        return 2
    sys.path.insert(0, str(src))
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        log(f"bench: {e.msg}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
