"""Find the knee of an open-loop cell once, by a sweep on the chip:

    python3 -m bench.sweep --workload <cell> --seeds 1,2 --seconds 90 \
        --rates 0.15,0.2,0.25,0.3

One process builds the cell's engine and runs its mix at each rate and seed
in turn (the mix's pre-roll, then a window), draining the engine between
them. Per rate and seed it prints one JSON line: requests due in the
window, the queue and the occupied slots at the window's opening and close,
the requests due in the window with no first token by its close, and the
count and mean TTFT of the requests due in each third of the window
(infinite where one had no first token by the close). A rate is sustained
when, on every seed, the queue does not grow through the window, every
request due has its first token by the close, and the later thirds' TTFT
stays near the first's. Not part of a benchmark run: the cell's traffic
file records the rate chosen from it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time

from bench import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=90.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(R.ROOT / "src"))
    from bench import traffic

    cell = R.load_cell(args.workload)
    R.device_or_refuse(cell.chips)
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    engine, _, times = R.build(cell, seeds[0])
    R.log(f"set-up {times}")
    for rate in [float(r) for r in args.rates.split(",")]:
        for seed in seeds:
            mix = {**cell.mix, "rate_per_s": rate, "grace_s": 0.0}
            c = dataclasses.replace(cell, mix=mix)
            items = traffic.generate(mix, seed, cell.spec["vocab_size"],
                                     args.seconds)
            t = time.perf_counter()
            rec, tracks, open_t, close_t, _, late = R.drive(
                engine, c, items, args.seconds)
            run = R.Run(c, args.seconds, open_t, close_t, tracks, rec.steps,
                        rec.calls, 0.0, {}, [])
            occ = R.occupancy(run, engine.ecfg.num_slots)
            thirds = []
            for i in range(3):
                a = open_t + args.seconds * i / 3
                b = open_t + args.seconds * (i + 1) / 3
                ttft = [(t.times[0] - t.due) * 1e3 if t.times else math.inf
                        for t in tracks
                        if t.due is not None and a <= t.due < b]
                thirds.append([len(ttft),
                               sum(ttft) / len(ttft) if ttft else None])
            due = [t for t in tracks
                   if t.due is not None and open_t <= t.due < close_t]
            for tr in tracks:               # drain before the next point
                if not tr.request.done:
                    engine.cancel(tr.request)
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "due": len(due),
                "no_first_token": sum(1 for t in due if not t.times),
                "occupancy": occ, "ttft_ms_mean_by_third": thirds,
                "steps": len(rec.steps), "wall_s": time.perf_counter() - t,
                "late_max_s": max(late) if late else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
