"""Readings that set a cell's check limit, on the chip:

    python3 -m bench.control --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 20

For each seed, in one process: the cell's weights and engine from the seed,
a short window of the cell's own traffic (pre-roll, window, then on until
finished requests hold the run's CHECK_TOKENS served tokens), and the same
sample a run compares. It prints one JSON line per seed with the program's
reading (the worst gap of its served tokens against the float32 reference)
and, for the control seeds, the control's: the reference itself computed in
the next precision below the configuration's (float8 matmul inputs, for
bfloat16), read as the reference's gap of the token the control ranks first
at the same positions. The lower reading is the largest program reading
over the seeds, the upper the smallest control reading; PERF.md gives both
and the limit set between them in bench/checks/<cell>.json.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from bench import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(R.ROOT / "src"))
    from bench import check, traffic, weights
    from bench.reference import Reference
    from repro.utils import enable_compile_cache

    cell = R.load_cell(args.workload)
    R.device_or_refuse(cell.chips)
    enable_compile_cache()
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        engine, params, _ = R.build(cell, seed)
        items = traffic.generate(cell.mix, seed, cell.spec["vocab_size"],
                                 args.seconds)
        _, tracks, *_ = R.drive(engine, cell, items, args.seconds,
                                check_tokens=R.CHECK_TOKENS)
        del engine
        gc.collect()
        finished = [(tr.prompt, list(tr.request.out_tokens)) for tr in tracks
                    if tr.request.done]
        sample = [finished[i] for i in
                  check.sample(finished, seed, R.CHECK_TOKENS)]
        pub = weights.published(params, cell.spec)
        ref = Reference(cell.spec, pub)
        worst, n = check.worst_gap(ref, sample)
        line = {"seed": seed, "program": worst, "tokens": n,
                "requests": len(sample)}
        if seed in ctl_seeds:
            ctl = Reference(cell.spec, pub, precision="fp8")
            line["control"], _ = check.control_gap(ref, ctl, sample)
        line["wall_s"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
        del params, pub, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
