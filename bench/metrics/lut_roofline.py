"""LUT kernels' share of their roofline over the traced calls: the least
time the chip needs for every LUT launch of those calls (bench/roofline.py:
packed codes, codebooks, inv_scale, activations in and out, 2 M K N flops;
each launch at the slower of its HBM and bf16 bounds) over the device time
of the ops bench/kernels.json classes as `lut`."""
from bench import roofline


def read(run):
    t = (run.trace or {}).get("class_s", {}).get("lut")
    calls = run.traced_calls()
    if not t or not calls:
        return None
    pk = run.peaks
    need = sum(roofline.bound_s(
        roofline.lut_call(run.lut_groups, (len(c.slots) + c.n_empty) * c.width),
        pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"]) for c in calls)
    return 100.0 * need / t
