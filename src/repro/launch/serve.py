"""Serving CLI — a thin command-line front-end over `repro.launch.engine`.

Static batch (PR 1's scan-compiled path; one batch starts/finishes together):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --lcd --tokens 32 --batch 4

Continuous batching (DESIGN.md §5; staggered requests, paged KV cache):

    PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --reduced \
        --lcd --continuous --requests 6 --tokens 16

Self-speculative decoding (DESIGN.md §8; the model's own 2-bit clustering
drafts k tokens per verify round, output bit-equal to plain greedy):

    PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --reduced \
        --continuous --speculative 3 --requests 6 --tokens 16

All engine logic — the two-trace static path (`serve`, `build_decode_fns`)
and the slot/block continuous engine (`ServingEngine`) — lives in
`repro.launch.engine`; this module only parses flags and reports. The names
`serve` and `build_decode_fns` are re-exported here for compatibility with
existing imports (benchmarks/decode_bench.py, tests/test_decode_engine.py).
"""
from __future__ import annotations

import argparse

import numpy as np

# re-exported API (the engine module is the implementation); __all__ marks
# the compatibility names so the lint gate doesn't read them as unused
from repro.launch.engine import (BlockAllocator, EngineConfig, Request,
                                 ServingEngine, build_decode_fns,
                                 build_engine, serve)
from repro.utils import enable_compile_cache, logger

__all__ = ["BlockAllocator", "EngineConfig", "Request", "ServingEngine",
           "build_decode_fns", "build_engine", "serve", "main"]


def _describe(engine) -> None:
    """Deployment inventory (DESIGN.md §10): per-layer packing width and
    centroid count of the compressed target (and the speculative draft), so
    a deployed mixed-precision model is inspectable from the CLI."""
    from repro.core.clustered_params import packed_weight_bytes
    if engine.compress_report is None:
        logger.info("describe: params are not LCD-compressed (run with --lcd)")
    else:
        logger.info("target bits assignment:\n"
                    + engine.compress_report.bits_table())
        logger.info(f"target packed weight bytes: "
                    f"{packed_weight_bytes(engine.params)}")
    if engine.draft_report is not None:
        logger.info("draft bits assignment:\n"
                    + engine.draft_report.bits_table())
        logger.info(f"draft packed weight bytes: "
                    f"{packed_weight_bytes(engine.draft_params)} "
                    f"(int4 layout would be "
                    f"{packed_weight_bytes(engine.draft_params, nbits=4)})")
    logger.info(f"kv_dtype: {engine.kv_dtype}")


def _run_continuous(args) -> None:
    # arch= binds the config to the architecture's capability set
    # (DESIGN.md §13): a slot-state arch with --speculative/--prefix-cache/
    # --kv-dtype int8 fails HERE with the missing capability named, before
    # any params are built.
    ecfg = EngineConfig(num_slots=args.slots, block_size=args.block_size,
                        num_blocks=args.blocks,
                        max_blocks_per_slot=args.blocks_per_slot,
                        prefill_chunk=args.prefill_chunk,
                        speculative_k=args.speculative,
                        draft_centroids=args.draft_centroids,
                        kv_dtype=args.kv_dtype,
                        weight_bits=args.bits,
                        bits_budget=args.bits_budget,
                        prefix_cache=args.prefix_cache,
                        chunked_prefill=args.chunked_prefill,
                        scheduler="priority" if args.priority else "fcfs",
                        data_parallel=args.data_parallel,
                        model_parallel=args.model_parallel,
                        arch=args.arch)
    engine, _ = build_engine(args.arch, use_reduced=args.reduced,
                             lcd=args.lcd, target_centroids=args.centroids,
                             ecfg=ecfg,
                             fused_projections=args.fused_projections)
    if args.describe:
        _describe(engine)
        return
    rng = np.random.default_rng(0)
    cfg = engine.model.cfg
    # encoder-decoder archs (whisper): every request carries a synthetic
    # frame buffer; admission runs the encoder once per request (the
    # engine's "encode" trace) and decoding reads the per-slot cross-KV
    audio = cfg.family == "audio"

    def _frames():
        if not audio:
            return None
        return rng.normal(size=(1, cfg.enc_seq, cfg.d_model)).astype(
            cfg.jnp_dtype)
    # staggered submissions: a fresh request every other scheduler step, with
    # varying prompt lengths — the continuous-batching case the static path
    # cannot serve without padding everyone to the slowest request
    shared = rng.integers(0, cfg.vocab, max(4, args.prompt_len // 2))
    pending = []
    for i in range(args.requests):
        tail = rng.integers(0, cfg.vocab, rng.integers(4, args.prompt_len + 1))
        # with --prefix-cache, every other request opens with the same
        # "system prompt" so the demo actually exercises block reuse
        prompt = (np.concatenate([shared, tail])
                  if args.prefix_cache and i % 2 == 0 else tail)
        pending.append((prompt, {"tenant": f"tenant{i % 2}",
                                 "priority": i % 3} if args.priority else {}))
    finished = []
    while pending or engine.busy:
        if pending and engine.steps % 2 == 0:
            prompt, kw = pending.pop(0)
            engine.submit(prompt, max_new_tokens=args.tokens,
                          frames=_frames(), **kw)
        if engine.busy:
            finished.extend(engine.step())
        else:
            engine.steps += 1          # idle tick: let the next arrival land
    engine.assert_bounded_traces()
    for r in finished:
        logger.info(f"request {r.rid}: prompt {len(r.prompt)} -> "
                    f"{len(r.out_tokens)} tokens "
                    f"(latency {r.finish_t - r.submit_t:.2f}s, "
                    f"preemptions {r.preemptions})")
    logger.info(f"continuous engine: {len(finished)} requests in "
                f"{engine.steps} steps, traces {engine.traces}")
    logger.info(f"counters: {engine.counters_summary()}")
    if args.speculative:
        logger.info(f"speculative: {engine.acceptance_summary()}")
    if args.prefix_cache:
        logger.info(f"prefix cache: {engine.prefix_cache_report()}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--lcd", action="store_true")
    ap.add_argument("--centroids", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    # continuous-batching mode
    ap.add_argument("--continuous", action="store_true",
                    help="run the paged continuous-batching engine with "
                         "staggered requests instead of one static batch")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--blocks", type=int, default=48)
    ap.add_argument("--blocks-per-slot", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="draft K tokens per verify round through the "
                         "model's own 2-bit clustering (continuous mode "
                         "only; 0 = off)")
    ap.add_argument("--draft-centroids", type=int, default=4,
                    help="centroid count of the self-draft (4 = 2-bit)")
    ap.add_argument("--kv-dtype", choices=("float", "int8"), default=None,
                    help="paged KV block-pool dtype (DESIGN.md §9): int8 "
                         "stores smoothed codes + per-(block-slot, kv-head) "
                         "scales for ~3.5x the admissible slots per f32 "
                         "pool byte; default follows the model config "
                         "(continuous mode only)")
    ap.add_argument("--bits", type=int, choices=(2, 3, 4), default=4,
                    help="uniform LCD weight packing width (DESIGN.md §10): "
                         "2-bit streams half the weight bytes of the int4 "
                         "layout on the decode GEMV")
    ap.add_argument("--bits-budget", type=float, default=None,
                    help="per-layer mixed precision under a global "
                         "element-weighted mean-bits cap (e.g. 3.0): "
                         "empirical-Fisher scores keep sensitive layers at "
                         "4-bit and drop the rest to 3/2 (overrides --bits)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-hashed prefix caching with copy-on-write "
                         "block tables (DESIGN.md §12): requests sharing a "
                         "prompt prefix share physical KV blocks, bit-equal "
                         "to cache-off (continuous mode only)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="admit long prompts with one prefill chunk's worth "
                         "of blocks instead of the whole prompt's, so they "
                         "start decoding behind a busy pool (DESIGN.md §12)")
    ap.add_argument("--priority", action="store_true",
                    help="priority/weighted-fair multi-tenant admission in "
                         "place of FCFS (DESIGN.md §12); the demo tags "
                         "requests with alternating tenants and priorities")
    ap.add_argument("--data-parallel", type=int, default=None, metavar="N",
                    help="pin the serving mesh's data axis (DESIGN.md §14); "
                         "the model axis is derived from the visible device "
                         "count. Default: the hlo_cost layout search on "
                         "multi-device hosts (continuous mode only)")
    ap.add_argument("--model-parallel", type=int, default=None, metavar="N",
                    help="pin the serving mesh's model (tensor-parallel) "
                         "axis; ClusteredTensor codes/scales and the paged "
                         "pool's kv heads shard across it (DESIGN.md §14; "
                         "continuous mode only)")
    ap.add_argument("--no-fused-projections", dest="fused_projections",
                    action="store_false",
                    help="serve same-input projection groups (QKV; gate+up) "
                         "through per-projection LUT kernel launches instead "
                         "of the fused multi-projection GEMV (DESIGN.md §15);"
                         " bit-equal, for perf triage only")
    ap.add_argument("--describe", action="store_true",
                    help="print the deployment inventory (per-layer bits "
                         "assignment, packed weight bytes, kv dtype) and "
                         "exit without serving (continuous mode)")
    args = ap.parse_args()
    logger.info(f"compile cache: {enable_compile_cache()}")
    if args.speculative and not args.continuous:
        ap.error("--speculative requires --continuous")
    if args.kv_dtype and not args.continuous:
        ap.error("--kv-dtype applies to the paged engine; add --continuous")
    if args.describe and not args.continuous:
        ap.error("--describe inspects the paged engine; add --continuous")
    for flag, name in ((args.prefix_cache, "--prefix-cache"),
                       (args.chunked_prefill, "--chunked-prefill"),
                       (args.data_parallel is not None, "--data-parallel"),
                       (args.model_parallel is not None, "--model-parallel"),
                       (args.priority, "--priority")):
        if flag and not args.continuous:
            ap.error(f"{name} applies to the paged engine; add --continuous")
    if args.continuous:
        _run_continuous(args)
    else:
        serve(args.arch, use_reduced=args.reduced, lcd=args.lcd,
              target_centroids=args.centroids, batch=args.batch,
              prompt_len=args.prompt_len, gen_tokens=args.tokens,
              weight_bits=args.bits, bits_budget=args.bits_budget,
              fused_projections=args.fused_projections)


if __name__ == "__main__":
    main()
