"""The one traffic generator. A mix is a data file, bench/traffic/<mix>.json,
of one of two kinds:

  open_loop  independent users: arrivals at `rate_per_s` on the wall clock,
             due times fixed before the run starts, from `preroll_s` before
             the window opens until `grace_s` after it closes. Gaps are
             gamma distributed with coefficient of variation `burst_cv`
             (default 1: Poisson; above 1: bursts).
  backlog    an offline batch: a queue topped up to `backlog` waiting
             requests whenever it runs low, drawn in order from a pool of
             `pool` requests, after a pre-roll of `preroll_steps`
             scheduler iterations.

Lengths are `{"median", "sigma", "min", "max"}` (lognormal, clipped) or
`{"uniform": [lo, hi]}`. `shared_prefix: {"groups": g, "len": <lengths>}`
starts every prompt with one of g prefixes (its length counts in the
prompt's), the traffic a prefix cache serves.

The work is fixed by the mix and the run seed orders it. Lengths come from
the mix's own `size_seed`. An open loop splits the run into phases (pre-roll,
window, after), each holding `rate_per_s` x its length requests, rounded: a
fixed set of lengths per phase, which the run seed deals out in its own
order at arrival times it draws within the phase (for Poisson arrivals, a
Poisson process given its count). So every seed serves the same requests in
each phase, in another order and on another schedule. A backlog keeps the
pool's order with `"order": "fixed"` and deals it out in the seed's order
with `"order": "seeded"`. The run seed also draws the token ids (and, in
bench/weights.py, the weights).

A mix may instead name `"generator": "<name>"`: bench/traffic/<name>.py,
whose `generate(mix, seed, vocab, seconds)` returns the list of Items.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
from pathlib import Path
from typing import List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Item:
    prompt: np.ndarray          # (P,) int32 token ids
    max_new: int
    due: Optional[float]        # seconds from the window's opening; None: backlog


def load(name: str, root: Path = HERE) -> dict:
    with open(root / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    mix["_dir"] = str(root / "traffic")
    return mix


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if "uniform" in dist:
        lo, hi = dist["uniform"]
        return rng.integers(lo, hi + 1, n)
    x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def longest(mix: dict) -> int:
    """The most tokens one request of the mix can hold in the cache."""
    def top(d):
        return d["uniform"][1] if "uniform" in d else d["max"]
    return top(mix["prompt"]) + top(mix["output"])


def phases(mix: dict, seconds: float):
    """[(start, end, count)] of an open loop, in seconds from the window's
    opening: pre-roll, window, after."""
    edges = [-mix["preroll_s"], 0.0, seconds, seconds + mix["grace_s"]]
    return [(a, b, int(round(mix["rate_per_s"] * (b - a))))
            for a, b in zip(edges, edges[1:])]


def _arrivals(rng, n: int, a: float, b: float, cv: float) -> np.ndarray:
    """n due times in [a, b): the first n of n + 1 gamma gaps, scaled to the
    phase. With cv 1 they are n uniform points, sorted."""
    shape = 1.0 / (cv * cv)
    g = np.cumsum(rng.gamma(shape, 1.0, n + 1))
    return a + (b - a) * g[:n] / g[n]


def _prompts(mix, p_len, rng, vocab):
    sp = mix.get("shared_prefix")
    if sp is None:
        return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in p_len]
    fixed = np.random.default_rng([mix["size_seed"], 9])
    pre_len = lengths(sp["len"], sp["groups"], fixed)
    prefixes = [rng.integers(0, vocab, int(n)).astype(np.int32)
                for n in pre_len]
    out = []
    for n in p_len:
        pre = prefixes[rng.integers(0, len(prefixes))][:max(int(n) - 1, 0)]
        tail = rng.integers(0, vocab, int(n) - len(pre)).astype(np.int32)
        out.append(np.concatenate([pre, tail]))
    return out


def generate(mix: dict, seed: int, vocab: int, seconds: float) -> List[Item]:
    """The run's requests in the order they are due (open loop) or drawn
    (backlog). The same (mix, seed, seconds) gives the same list."""
    if "generator" in mix:
        path = Path(mix["_dir"]) / f"{mix['generator']}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_traffic_{mix['generator']}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.generate(mix, seed, vocab, seconds)
    rng = np.random.default_rng(int(seed))

    def fixed(*stream):     # lengths of the mix, whatever the run seed
        return np.random.default_rng([mix["size_seed"], *stream])

    if mix["kind"] == "backlog":
        n = mix["pool"]
        p_len = lengths(mix["prompt"], n, fixed(0))
        o_len = lengths(mix["output"], n, fixed(1))
        if mix.get("stagger_first"):
            # the first `backlog` requests stand for a batch already
            # running: each has some of its answer left, so they finish
            # one by one
            k = mix["backlog"]
            left = fixed(3).uniform(0, 1, k)
            o_len[:k] = np.maximum(1, np.ceil(left * o_len[:k]))
        if mix.get("order", "fixed") == "seeded":
            perm = rng.permutation(n)
            p_len, o_len = p_len[perm], o_len[perm]
        due = [None] * n
    else:
        p_len, o_len, due = [], [], []
        for i, (a, b, n) in enumerate(phases(mix, seconds)):
            perm = rng.permutation(n)
            p_len.append(lengths(mix["prompt"], n, fixed(i, 0))[perm])
            o_len.append(lengths(mix["output"], n, fixed(i, 1))[perm])
            due.extend(float(t) for t in _arrivals(
                rng, n, a, b, mix.get("burst_cv", 1.0)))
        p_len, o_len = np.concatenate(p_len), np.concatenate(o_len)
    prompts = _prompts(mix, p_len, rng, vocab)
    return [Item(p, int(o), d) for p, o, d in zip(prompts, o_len, due)]
