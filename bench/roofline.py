"""Operations and bytes of the served step, from shapes: the yardstick the
roofline and MFU readers divide by. Every count here is a lower bound on
what the chip must do for the call as the program was given it, so a share
computed from it can only be understated.

A device call is one run of a step program: `width` tokens for each of the
engine's `num_slots` slots, every layer. Its slots are given as
`[(cached_length, n_new, emitted), ...]` for the occupied slots, plus the
number of empty ones.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

Slot = Tuple[int, int, int]       # (cached length, tokens fed, tokens emitted)

CODEBOOK_BYTES = 16 * 4           # the kernel's padded f32 codebook


@dataclasses.dataclass(frozen=True)
class LutGroup:
    """Clustered projections one LUT launch may serve: same input width,
    same parent module. Merging launches can only lower the bound: the sum
    of max(flops, bytes) terms is at least the max of the sums."""
    k: int                        # input width
    ns: Tuple[int, ...]           # output width of each projection
    nbits: Tuple[int, ...]
    layers: int


def packed_bytes(k: int, n: int, nbits: int) -> int:
    """Bytes of one projection's packed codes (whole packing groups)."""
    g = {2: 4, 3: 8, 4: 2}[nbits]
    return -(-k // g) * g * nbits // 8 * n


def lut_groups(named_shapes: Sequence[Tuple[str, Tuple[int, ...], int]]):
    """Group clustered leaves [(name, (L, d_in, d_out), nbits)] by parent
    module and input width."""
    groups = {}
    for name, shape, nbits in named_shapes:
        parent = name.rsplit(".", 1)[0]
        layers = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
        key = (parent, shape[-2], layers)
        groups.setdefault(key, []).append((shape[-1], nbits))
    return [LutGroup(k, tuple(n for n, _ in v), tuple(b for _, b in v), layers)
            for (_, k, layers), v in sorted(groups.items())]


def lut_call(groups: List[LutGroup], rows: int, act_bytes: int = 2,
             out_bytes: int = 4):
    """(flops, [(flops, bytes) per launch]) of the LUT kernels in one device
    call of `rows` activation rows (num_slots * width)."""
    launches = []
    for g in groups:
        n = sum(g.ns)
        flops = 2 * rows * g.k * n
        byts = (sum(packed_bytes(g.k, ni, b) for ni, b in zip(g.ns, g.nbits))
                + len(g.ns) * (CODEBOOK_BYTES + 4 * g.k)     # inv_scale f32
                + rows * g.k * act_bytes + rows * n * out_bytes)
        launches += [(flops, byts)] * g.layers
    return launches


def attn_call(slots: Sequence[Slot], n_empty: int, width: int, *, layers: int,
              kv_heads: int, q_heads: int, head_dim: int, block_size: int,
              kv_bytes: int = 2, act_bytes: int = 2):
    """[(flops, bytes)] per layer of the paged attention kernel in one call.
    Per slot it reads its live K and V blocks (at least one, as the kernel's
    clamped index does for an empty slot) and computes q.k and p.v for every
    (padded) query row of each kv head over each live block."""
    gt = -(-(q_heads // kv_heads) * width // 8) * 8
    lives = [max(-(-(ln + n) // block_size), 1) for ln, n, _ in slots]
    lives += [1] * n_empty
    blocks = sum(lives)
    n_slots = len(lives)
    byts = (blocks * block_size * kv_heads * head_dim * 2 * kv_bytes
            + 2 * n_slots * kv_heads * gt * head_dim * act_bytes)
    flops = blocks * kv_heads * 2 * 2 * gt * block_size * head_dim
    return [(flops, byts)] * layers


def bound_s(launches, peak_flops: float, peak_bytes: float) -> float:
    """Least time of a list of launches: each at the slower of its two
    roofs."""
    return sum(max(f / peak_flops, b / peak_bytes) for f, b in launches)


def model_flops(spec: dict, slots: Sequence[Slot]) -> float:
    """Model FLOPs of the tokens a call fed, at the published widths and
    heads: weight matmuls 2 * params per token, causal attention
    4 * heads * head_dim * (position + 1) per token and layer, and the vocab
    head for each token emitted. Padded rows, padded heads and idle slots
    do not count."""
    d, f, hd = spec["hidden_size"], spec["intermediate_size"], spec["head_dim"]
    h, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    layers, vocab = spec["num_hidden_layers"], spec["vocab_size"]
    mlp = (3 if spec["mlp"] == "swiglu" else 2) * d * f
    per_token = 2 * layers * (2 * d * h * hd + 2 * d * kv * hd + mlp)
    total = 0.0
    for ln, n, emitted in slots:
        ctx = n * ln + n * (n + 1) / 2        # sum of (position + 1)
        total += n * per_token + 4 * layers * h * hd * ctx
        total += emitted * 2 * d * vocab
    return total
