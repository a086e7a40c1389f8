"""Whole step's share of the chip's bf16 peak: model FLOPs of the tokens
the window's calls fed, at published widths and heads (bench/roofline.py
model_flops), over the host time of the window's scheduler iterations
times the peak."""
from bench import roofline


def read(run):
    steps = run.window_steps()
    t = sum(s.t1 - s.t0 for s in steps)
    if not t:
        return None
    flops = sum(roofline.model_flops(run.cell.spec, c.slots)
                for c in run.window_calls())
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
