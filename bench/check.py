"""The comparison that decides `correct`: served tokens against the plain
float32 reference (bench/reference.py) of the same weights.

After the window closes, a sample of the finished requests, drawn from the
seed and always holding the longest one, is replayed through the reference
as prompt + served tokens. At every served position the number read is how
far the served token's reference logit lies below the reference's best,
as a share of the largest reference logit magnitude at that position. The
cell's number is the worst such gap over the sample; its limit is in
bench/checks/<workload>.json, with the readings it was set from.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, Sequence, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent


def limits(workload: str, root: Path = HERE) -> dict:
    with open(root / "checks" / f"{workload}.json") as f:
        return json.load(f)["limits"]


def sample(finished: Sequence[Tuple[np.ndarray, List[int]]], seed: int,
           min_tokens: int) -> List[int]:
    """Indices into `finished` [(prompt, served tokens)]: the request with
    the most tokens, then others in an order drawn from `seed`, until the
    sample holds `min_tokens` served tokens."""
    if not finished:
        return []
    size = [len(p) + len(o) for p, o in finished]
    first = int(np.argmax(size))
    rest = [i for i in np.random.default_rng(int(seed)).permutation(
        len(finished)) if i != first]
    picked, served = [first], len(finished[first][1])
    for i in rest:
        if served >= min_tokens:
            break
        picked.append(int(i))
        served += len(finished[i][1])
    return picked


def gaps(logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """Per row: (best logit - logit of the token) / max |logit|."""
    best = logits.max(axis=1)
    tok = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return (best - tok) / np.abs(logits).max(axis=1)


def served_rows(ref, prompt: np.ndarray, out: Sequence[int]):
    """The reference's logits at the positions that predicted each served
    token."""
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])[:-1]
    x = ref.hidden(seq)
    return ref.logits(x[len(prompt) - 1:])


def worst_gap(ref, requests) -> Tuple[float, int]:
    """(worst gap, served tokens compared) of [(prompt, served)]."""
    worst, n = 0.0, 0
    for prompt, out in requests:
        g = gaps(served_rows(ref, prompt, out), out)
        worst, n = max(worst, float(g.max())), n + len(out)
    return worst, n


def control_gap(ref, ctl, requests) -> Tuple[float, int]:
    """The control's reading: at the same positions of the same prompts and
    served tokens, the reference's gap of the token the control ranks
    first."""
    worst, n = 0.0, 0
    for prompt, out in requests:
        lr = served_rows(ref, prompt, out)
        lc = served_rows(ctl, prompt, out)
        g = gaps(lr, lc.argmax(axis=1))
        worst, n = max(worst, float(g.max())), n + len(out)
    return worst, n
