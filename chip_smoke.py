#!/usr/bin/env python3
"""Bring-up check: the LCD continuous-batching engine on TPU at the published
widths of qwen2-1.5b (28 layers, d_model 1536, 12 heads served as 16, 2 kv
heads, head_dim 128, d_ff 8960, vocab 151936, bf16), random weights from
--seed, LCD-compressed at the serving CLI's default width (4-bit, 8
centroids). Everything goes through the user entry points:
build_engine(..., use_reduced=False, lcd=True) and ServingEngine.submit/step.

    python chip_smoke.py             # one chip: the served path end to end
    python chip_smoke.py --chips 4   # tensor parallel on 4 chips vs 1 chip

One chip: warm up both step widths, serve 6 staggered requests, then check
(a) every request's tokens equal the same request served alone, and (b) the
kernel-dispatched logits of a prefill plus 4 cached decode steps against the
same ClusteredTensors served through the gather/jnp reference in float32.
Four chips (depth cut to TP_LAYERS; every layer has one shape): the same
requests through model_parallel=4 and through one chip, then check (b) for
both, and that each request's tokens either are equal or part only where the
float32 reference sees two tokens within the logits bound of each other.

Exits nonzero, before the result line, off a TPU, when any dispatch resolves
to something other than the compiled kernels, or when any check fails. The
last stdout line is {"ok": true, "device": {platform, kind, count}}.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-1.5b"
# 16 slots (the chat cell's) x 32 blocks of 16 tokens: a 512-token window
# per request, with room for every slot at the longest prompt; prefill in
# 128-token chunks, so the wide step runs the chat cell's 2048 rows
GEOMETRY = dict(num_slots=16, block_size=16, num_blocks=512,
                max_blocks_per_slot=32, prefill_chunk=128)
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 6, (192, 384), 32
TP_LAYERS = 2
# logits check (b): max|kernel - ref| / max|ref| over the vocab, per step.
# The kernel path keeps activations, the residual stream and the KV pool in
# bf16 (8-bit mantissa, 2^-9 relative rounding per op) across 28 layers of
# ~10 rounded ops each; as a random walk that is ~sqrt(280) * 2^-9 ~ 3e-2 at
# worst of the logits' scale. A decode or packing fault moves it to O(1).
LOGITS_BOUND = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def prompts(cfg, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, int(rng.integers(*PROMPT_LENS)))
            .astype(np.int32) for _ in range(N_REQUESTS)]


def serve(engine, reqs):
    """Submit one request every other scheduler step; drive to completion."""
    pending, out = list(reqs), []
    t0 = time.perf_counter()
    while pending or engine.busy:
        if pending and engine.steps % 2 == 0:
            out.append(engine.submit(pending.pop(0), NEW_TOKENS))
        if engine.busy:
            engine.step()
        else:
            engine.steps += 1
    return out, time.perf_counter() - t0


def warm_up(engine):
    """Compile both step widths (prefill chunk and 1) on one request longer
    than a chunk, with the launch log open for the process's first trace."""
    from repro.kernels.ops import track_lut_launches
    t0 = time.perf_counter()
    with track_lut_launches() as launches:
        engine.submit(np.arange(GEOMETRY["prefill_chunk"] + 8,
                                dtype=np.int32), 2)
        engine.run()
    return launches, time.perf_counter() - t0


class Slot:
    """One request slot served straight through `model.serving_step` on
    `mesh` (the engine's step computation, without its scheduler):
    `feed(tokens)` appends them to the slot's paged cache, a prompt in
    prefill_chunk-wide pieces and a single token at width 1, and returns
    the logits after the last one. Kernel or reference dispatch is whatever
    mode is active when a Slot first traces."""
    _steps: dict = {}

    def __init__(self, model, params, mesh):
        import jax
        import jax.numpy as jnp
        from repro.distributed.layout import cache_shardings
        from repro.distributed.sharding import use_rules

        self.model, self.params, self.mesh = model, params, mesh
        bs, nbt = GEOMETRY["block_size"], GEOMETRY["max_blocks_per_slot"]
        with use_rules(mesh, fsdp=False):
            caches = model.init_seq_caches(num_blocks=nbt, block_size=bs,
                                           num_slots=1, max_seq=bs * nbt,
                                           kv_dtype="float")
            self.caches = jax.device_put(caches,
                                         cache_shardings(model, caches))
        self.step = self._steps.setdefault(
            (id(model), mesh), jax.jit(model.serving_step))
        self.bt = jnp.arange(nbt, dtype=jnp.int32)[None]
        self.length = 0

    def feed(self, tokens) -> np.ndarray:
        import jax.numpy as jnp
        from repro.distributed.sharding import use_rules

        t = 1 if len(tokens) == 1 else GEOMETRY["prefill_chunk"]
        with use_rules(self.mesh, fsdp=False):
            for i in range(0, len(tokens), t):
                piece = tokens[i:i + t]
                tok = np.zeros((1, t), np.int32)
                tok[0, :len(piece)] = piece
                lg, self.caches = self.step(
                    self.params, self.caches, jnp.asarray(tok),
                    jnp.asarray([self.length], jnp.int32),
                    jnp.asarray([len(piece)], jnp.int32), self.bt)
                self.length += len(piece)
        return np.asarray(lg[0, :self.model.cfg.vocab], np.float32)


@contextlib.contextmanager
def ref_modes():
    """Gather/jnp reference dispatch at highest matmul precision."""
    import jax
    from repro.kernels.ops import lut_serving
    from repro.kernels.paged_attention import paged_attention_mode

    with lut_serving("ref"), paged_attention_mode("ref"), \
            jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def reference(engine):
    """(model, params) serving `engine`'s ClusteredTensors in float32, for
    use under `ref_modes()` on `engine.mesh`."""
    import jax
    import jax.numpy as jnp
    from repro.core.api import is_clustered
    from repro.models.registry import get_model

    model32 = get_model(dataclasses.replace(engine.model.cfg,
                                            dtype="float32"))
    params32 = jax.tree_util.tree_map(
        lambda x: x if is_clustered(x) or not jnp.issubdtype(
            x.dtype, jnp.floating) else x.astype(jnp.float32),
        engine.params, is_leaf=is_clustered)
    return model32, params32


def logits_check(engines, prompt, steps: int = 4):
    """Prefill + `steps` cached decode steps of one prompt: each engine's
    kernel-dispatched logits against the first engine's ClusteredTensors
    through the float32 reference, all fed the reference's greedy tokens.
    Returns {name: per-step kernel logits}."""
    with ref_modes():
        slot = Slot(*reference(engines[0][1]), engines[0][1].mesh)
        ref = [slot.feed(prompt)]
        for _ in range(steps):
            ref.append(slot.feed([int(np.argmax(ref[-1]))]))
    feed = [[int(np.argmax(r))] for r in ref[:-1]]
    out = {}
    for name, engine in engines:
        slot = Slot(engine.model, engine.params, engine.mesh)
        ker = out[name] = [slot.feed(tok) for tok in [prompt] + feed]
        worst = 0.0
        for i, (r, k) in enumerate(zip(ref, ker)):
            scale = float(np.max(np.abs(r)))
            rel = float(np.max(np.abs(k - r))) / scale
            worst = max(worst, rel)
            top = np.argsort(r)[-2:]
            margin = float(r[top[1]] - r[top[0]]) / scale
            agree = int(np.argmax(k)) == int(top[1])
            log(f"  {name} logits step {i}: rel_err={rel!r} "
                f"top1_agree={agree} ref_top2_margin={margin!r}")
            if rel > LOGITS_BOUND:
                raise SystemExit(f"{name} logits step {i}: relative error "
                                 f"{rel!r} exceeds the bound {LOGITS_BOUND}")
            # an error within the bound can only flip a near-tie
            if not agree and margin >= LOGITS_BOUND:
                raise SystemExit(
                    f"{name} logits step {i}: top-1 differs although the "
                    f"reference's top-2 margin {margin!r} is not below the "
                    f"bound {LOGITS_BOUND}")
            if not agree:
                log(f"  {name} logits step {i}: top-1 flip accepted: "
                    f"reference top-2 margin {margin!r} < bound "
                    f"{LOGITS_BOUND}")
        log(f"logits: {name} kernels vs float32 reference, worst "
            f"rel_err={worst!r} (bound {LOGITS_BOUND}: bf16 activations, "
            f"residual and KV over the layers)")
    return out


def one_chip(args) -> None:
    from repro.kernels.ops import _resolve_mode
    from repro.kernels.paged_attention import resolved_paged_attention_mode
    from repro.launch.engine import EngineConfig, ServingEngine, build_engine
    from repro.utils import human_bytes, tree_size_bytes

    modes = sorted({_resolve_mode(None), resolved_paged_attention_mode()})
    log(f"dispatch: modes={modes}")
    if modes != ["kernel"]:
        raise SystemExit(f"dispatch resolved to {modes}, not the kernels")

    ecfg = EngineConfig(**GEOMETRY, weight_bits=4, arch=ARCH)
    t0 = time.perf_counter()
    engine, params = build_engine(ARCH, use_reduced=False, lcd=True,
                                  target_centroids=8, ecfg=ecfg,
                                  seed=args.seed)
    log(f"build+compress: {time.perf_counter() - t0!r} s | "
        f"{engine.compress_report.summary()}")
    cfg = engine.model.cfg
    log(f"model: {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads_eff} kv={cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} dtype={cfg.dtype}")
    log(f"memory: params {human_bytes(tree_size_bytes(engine.params))}, "
        f"kv pool {human_bytes(tree_size_bytes(engine.caches))} "
        f"({tree_size_bytes(engine.caches)} bytes, {GEOMETRY})")

    launches, t_warm = warm_up(engine)
    log(f"warm-up: {t_warm!r} s, traces {engine.traces}")
    log(f"lut launches per layer (both widths): {list(launches)}")
    for key, blocks in sorted(launches.blocks.items()):
        variant, m, k, n = key
        log(f"  blocks {variant} m={m} k={k} n={n}: (bm, bn, bk)={blocks}, "
            f"rows_per_decode={launches.rows_per_decode[key]}")
    if not any(tag.startswith("fused") for tag in launches):
        raise SystemExit("no fused LUT launch was traced")

    reqs, wall = serve(engine, prompts(cfg, args.seed))
    engine.assert_bounded_traces()
    n_tok = sum(len(r.out_tokens) for r in reqs)
    log(f"served {len(reqs)} requests, {n_tok} tokens in {wall!r} s, "
        f"{engine.steps} steps, traces {engine.traces} (bounded)")

    solo = ServingEngine(engine.model, engine.params, ecfg, mesh=engine.mesh)
    for r in reqs:
        alone = solo.submit(r.prompt, r.max_new_tokens)
        solo.run()
        if alone.out_tokens != r.out_tokens:
            raise SystemExit(f"request {r.rid}: engine tokens {r.out_tokens}"
                             f" != served alone {alone.out_tokens}")
    solo.assert_bounded_traces()
    log(f"parity: {len(reqs)}/{len(reqs)} requests token-equal to serving "
        f"alone")

    logits_check([("1 chip", engine)], prompts(cfg, args.seed + 1)[0][:100])


def four_chips(args) -> None:
    import jax
    from repro.launch.engine import EngineConfig, build_engine
    from repro.launch.mesh import auto_mesh
    from repro.models.config import get_config, register

    arch = f"{ARCH}@{TP_LAYERS}L"
    register(dataclasses.replace(get_config(ARCH), arch_id=arch,
                                 n_layers=TP_LAYERS))
    devs = jax.devices()[:4]
    ecfg1 = EngineConfig(**GEOMETRY, weight_bits=4, arch=arch)
    t0 = time.perf_counter()
    one, params = build_engine(
        arch, use_reduced=False, lcd=True, target_centroids=8, ecfg=ecfg1,
        seed=args.seed,
        mesh=auto_mesh((1, 1), ("data", "model"), devices=devs[:1]))
    log(f"build+compress ({TP_LAYERS} layers): "
        f"{time.perf_counter() - t0!r} s")
    tp, _ = build_engine(
        arch, use_reduced=False, lcd=True, ecfg=dataclasses.replace(
            ecfg1, data_parallel=1, model_parallel=4), params=params)
    log(f"meshes: one chip {dict(one.mesh.shape)} on {devs[0]}, "
        f"tensor parallel {dict(tp.mesh.shape)}")
    wq = tp.params["blocks"]["attn"]["wq"].packed
    log(f"tp placement: wq.packed {wq.sharding.spec} over "
        f"{len(wq.sharding.device_set)} devices; kv pool "
        f"{tp.caches['paged']['k'].sharding.spec}")
    reqs = prompts(one.model.cfg, args.seed)
    out = {}
    for name, engine in (("1 chip", one), ("model_parallel=4", tp)):
        done, wall = serve(engine, reqs)
        engine.assert_bounded_traces()
        out[name] = [r.out_tokens for r in done]
        log(f"{name}: {len(done)} requests in {wall!r} s, traces "
            f"{engine.traces}")

    ker = logits_check([("1 chip", one), ("model_parallel=4", tp)],
                       prompts(one.model.cfg, args.seed + 1)[0][:100])
    for i, (a, b) in enumerate(zip(*ker.values())):
        log(f"  model_parallel=4 vs 1 chip logits step {i}: rel_diff="
            f"{float(np.max(np.abs(b - a))) / float(np.max(np.abs(a)))!r}")

    # Both programs are within the logits bound of the float32 reference,
    # but the row-parallel psum adds the f32 partial sums in another order
    # than one chip's K loop, so a bf16 rounding can differ and flip a
    # greedy pick where two tokens nearly tie. Where a request's tokens
    # part, the reference at the common prefix must see such a tie.
    equal = 0
    for n, (r, a, b) in enumerate(zip(reqs, *out.values())):
        if a == b:
            equal += 1
            continue
        j = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
        with ref_modes():
            lg = Slot(*reference(one), one.mesh).feed(list(r) + a[:j])
        gap = abs(float(lg[a[j]] - lg[b[j]])) / float(np.max(np.abs(lg)))
        log(f"  request {n}: parts at token {j} "
            f"({a[j]} vs {b[j]}), reference gap {gap!r}")
        if gap >= LOGITS_BOUND:
            raise SystemExit(
                f"request {n}: tensor-parallel token {b[j]} "
                f"!= one chip's {a[j]} at token {j}, and the reference "
                f"gap {gap!r} is not below the bound {LOGITS_BOUND}")
    log(f"tp tokens: {equal}/{len(reqs)} requests token-equal to one chip; "
        f"the others part only at a near-tie of the float32 reference")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the tensor-parallel check on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from repro.utils import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devs[0].platform!r} devices")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but only "
                         f"{len(devs)} devices")
    cache_dir = enable_compile_cache()
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} compile_cache={cache_dir}")
    if args.chips == 4:
        four_chips(args)
    else:
        one_chip(args)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
