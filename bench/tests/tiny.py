"""A cell small enough for the CPU: the qwen2 configuration file with tiny
widths, two layers and a short chat mix. The harness then runs its whole
path on the gather/jnp dispatch the program takes off a TPU."""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent


def spec(nbits: int = 4, centroids: int = 8) -> dict:
    with open(BENCH / "configs" / "qwen2-1.5b-lcd4.json") as f:
        s = json.load(f)
    s.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             vocab_size=512, tie_word_embeddings=False)
    s["lcd"] = {"nbits": nbits, "centroids": centroids}
    s["served"].update(heads=16, num_slots=4, prefill_chunk=16)
    return s


def mix() -> dict:
    with open(BENCH / "traffic" / "chat.json") as f:
        m = json.load(f)
    m.update(rate_per_s=12.0, preroll_s=0.3, grace_s=10.0)
    m["prompt"].update(median=20, min=4, max=40)
    m["output"].update(median=10, min=4, max=24)
    return m


def cell():
    from bench import check
    from bench import run as R

    with open(BENCH.parent / "BENCHMARK.json") as f:
        bj = json.load(f)
    wl = "qwen2-1.5b-lcd4.chat"
    return R.Cell(wl, 1, spec(), mix(),
                  [m for m in bj["end_to_end"] if R._applies(m, wl)],
                  [m for m in bj["per_layer"] if R._applies(m, wl)],
                  check.limits(wl))
