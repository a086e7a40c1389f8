"""Mesh-layout selection for sharded serving (DESIGN.md §14).

Given a device count, `choose_layout` enumerates every (data, model)
factorization, lowers ONE representative serving step per candidate — the
width-1 pure-decode step, the shape a deployment spends its life in — with
the engine's real parameter/cache shardings attached, and scores the
SPMD-partitioned module with the trip-count-aware HLO cost model
(`distributed/hlo_cost.py`). The score is a static roofline time:

    t = flops / PEAK_FLOPS  +  bytes / HBM_BW  +  coll_bytes / ICI_BW

where flops/bytes come from the per-device (post-partitioning) program, so
a candidate that shards a projection pays 1/model of its FLOPs but buys the
row-parallel all-reduce the collective term charges. The constants are one
v5e-class chip — the RATIOS drive the argmin, not the absolute times, and
the same constants rank layouts on the CPU CI lane (where wall-clock would
measure the host, not the partitioning).

`serving_shardings` is the shared helper: the engine places its live params
and block pools with it, and the chooser attaches the same shardings to the
abstract avals it lowers — so the scored program IS the served program.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.hlo_cost import analyze_text
from repro.launch.mesh import auto_mesh
from repro.distributed.sharding import (auto_shard, named_sharding,
                                        parse_names, use_rules)

# one v5e-class chip: peak bf16 FLOP/s, HBM bytes/s, per-link ICI bytes/s.
# Scoring constants, not measurements — only their ratios matter.
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 45e9


def candidate_layouts(n_devices: int):
    """Every (data, model) factorization of `n_devices`, pure-DP first."""
    out = []
    for model in range(1, n_devices + 1):
        if n_devices % model == 0:
            out.append((n_devices // model, model))
    return out


def cache_shardings(model, caches, sr=None):
    """NamedShardings for an engine cache dict ({kind: pytree}) from the
    family's declared logical names (models/registry.py seq_caches). Keys a
    family adds beyond its declared names (e.g. calibration smoothing
    vectors) replicate."""
    out: Dict[str, Any] = {}
    for kind, cache in caches.items():
        nm = dict(model.seq_caches[kind].names)
        out[kind] = {
            k: named_sharding(
                v.shape,
                parse_names(nm[k]) if k in nm else (None,) * len(v.shape),
                sr)
            for k, v in cache.items()}
    return out


def serving_shardings(model, params, caches, sr=None):
    """(param_shardings, cache_shardings) for a serving deployment: params
    through the ClusteredTensor-aware `auto_shard`, pools through the
    family's cache names. Call under `use_rules(mesh, fsdp=False)`."""
    return auto_shard(params, model.names(), sr), cache_shardings(
        model, caches, sr)


def _abstract(tree, shardings):
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def score_layout(model, params, ecfg, mesh) -> Dict[str, float]:
    """Roofline-score one mesh candidate from the compiled width-1 step."""
    cfg = model.cfg
    with use_rules(mesh, fsdp=False):
        caches = jax.eval_shape(lambda: model.init_seq_caches(
            num_blocks=ecfg.num_blocks, block_size=ecfg.block_size,
            num_slots=ecfg.num_slots, max_seq=ecfg.max_seq,
            kv_dtype=ecfg.kv_dtype))
        pshard, cshard = serving_shardings(model, params, caches)

        def step(params, caches, tokens, lengths, n_new, block_tables):
            logits, caches = model.serving_step(
                params, caches, tokens, lengths, n_new, block_tables)
            return jnp.argmax(logits[..., :cfg.vocab], axis=-1), caches

        s = ecfg.num_slots
        i32 = jnp.int32
        compiled = jax.jit(step).lower(
            _abstract(params, pshard), _abstract(caches, cshard),
            jax.ShapeDtypeStruct((s, 1), i32),
            jax.ShapeDtypeStruct((s,), i32),
            jax.ShapeDtypeStruct((s,), i32),
            jax.ShapeDtypeStruct((s, ecfg.max_blocks_per_slot), i32),
        ).compile()
    cost = analyze_text(compiled.as_text())
    t = (cost.flops / PEAK_FLOPS + cost.bytes / HBM_BW
         + cost.total_coll_bytes / ICI_BW)
    return {"flops": cost.flops, "bytes": cost.bytes,
            "coll_bytes": cost.total_coll_bytes,
            "coll_counts": dict(cost.coll_counts), "t_model_s": t}


def choose_layout(model, params, ecfg, *,
                  devices=None) -> Tuple[Any, Dict[str, Any]]:
    """(mesh, report): the roofline-cheapest (data, model) mesh over
    `devices` (default: all). The report records every candidate's score —
    `BENCH_serving.json:tp.layout` ships it so a deployment's layout choice
    is auditable."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    report: Dict[str, Any] = {"devices": n, "candidates": {}}
    best: Optional[Tuple[float, Any, str]] = None
    for data, mp in candidate_layouts(n):
        mesh = auto_mesh((data, mp), ("data", "model"), devices=devices)
        row = score_layout(model, params, ecfg, mesh)
        key = f"{data}x{mp}"
        report["candidates"][key] = {
            k: (round(v, 9) if isinstance(v, float) else v)
            for k, v in row.items()}
        if best is None or row["t_model_s"] < best[0]:
            best = (row["t_model_s"], mesh, key)
    assert best is not None
    report["chosen"] = best[2]
    return best[1], report
