"""Paged attention kernel's share of its roofline over the traced calls:
the least time for the K/V blocks each slot's kernel program reads, q and
output, and the q.k and p.v flops of every live block (bench/roofline.py),
over the device time of the ops bench/kernels.json classes as `attn`."""
from bench import roofline


def read(run):
    t = (run.trace or {}).get("class_s", {}).get("attn")
    calls = run.traced_calls()
    if not t or not calls:
        return None
    spec, pk = run.cell.spec, run.peaks
    bs = run.cell.spec["served"]["block_size"]
    need = sum(roofline.bound_s(
        roofline.attn_call(c.slots, c.n_empty, c.width,
                           layers=spec["num_hidden_layers"],
                           kv_heads=spec["num_key_value_heads"],
                           q_heads=spec["served"]["heads"],
                           head_dim=spec["head_dim"], block_size=bs),
        pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"]) for c in calls)
    return 100.0 * need / t
