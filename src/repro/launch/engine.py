"""LCD serving engines: the scan-compiled static-batch path (PR 1) and the
continuous-batching engine with a paged KV cache (DESIGN.md §5).

`launch/serve.py` is the CLI over both; this module is the importable API.

Static batch (`serve`, `build_decode_fns`)
    One batch of identical-length prompts starts and finishes together:
    exactly TWO traced computations per generation (one batched prefill + one
    lax.scan decode with a donated (L, B, S, KV, D) cache).

Continuous batching (`ServingEngine`)
    Real traffic is requests with different prompt lengths, arrival times and
    completion times. The engine holds a fixed number of request SLOTS and a
    pool of fixed-size KV BLOCKS:

      * a free-list `BlockAllocator` hands blocks to slots on demand, so a
        finishing short request frees exactly its blocks for a queued long one
        (the whole cache no longer lives or dies together);
      * each scheduler `step()` packs prefilling slots (a prompt chunk),
        decoding slots (one token) and idle slots (nothing) into ONE traced
        computation — per-slot position/length/activity are data, not shapes;
      * the traced step therefore comes in exactly TWO shapes: token-window
        width `prefill_chunk` (any slot prefilling) and width 1 (pure decode).
        `assert_bounded_traces()` enforces the contract; per-slot math is
        independent, so engine output is bit-equal to a single-request run
        (tests/test_serving_engine.py).

    Out-of-block pressure is resolved by recompute preemption: the youngest
    running request is evicted back to the queue (its blocks freed) and later
    re-prefills its prompt plus the tokens it had already generated.

    The block pool stores either the model dtype (`EngineConfig.kv_dtype =
    "float"` — engine output exactly equals single-request decoding) or
    smoothed int8 codes with per-(block-slot, kv-head) scale pools ("int8",
    DESIGN.md §9): ~3.5x the admissible slots per f32 pool byte (~2x vs
    bf16), with the smoothing vectors calibrated through core/smoothing.py
    (`calibrate_kv_smooth`) and reads served by the fused dequantizing
    Pallas kernel on TPU (kernels/paged_attention.py). The default (None)
    follows the model's cfg.kv_cache_dtype.

Self-speculative decoding (`EngineConfig.speculative_k > 0`, DESIGN.md §8)
    The model's own 2-bit LCD clustering drafts `k` tokens per round through
    the cheap serving path; the target model verifies all of them in ONE
    batched forward over the paged cache and accepts the longest matching
    prefix, so greedy output stays bit-equal to target-only decoding while
    each target dispatch advances every slot by 1..k+1 tokens. Rejected
    tokens roll back by bookkeeping alone: cache entries past `lengths` are
    unobservable, so not advancing `lengths` IS the rollback.

Prefix caching + production scheduler (DESIGN.md §12)
    `EngineConfig.prefix_cache=True` turns the BlockAllocator into a
    refcounted, content-hash-indexed cache: completed full blocks are
    published under position-0-anchored chain hashes (salted with kv dtype
    and layer config), `submit`-ed prompts share their longest cached
    block-aligned prefix read-only instead of re-prefilling it, and a write
    into a shared tail block copies-on-write first — output stays bit-equal
    to a cache-off run within a kv dtype. `chunked_prefill` admits long
    prompts on first-chunk blocks (prefill interleaves with decode either
    way); `scheduler="priority"` replaces FCFS with per-tenant token
    budgets + weighted-fair pick; `submit(on_token=...)` streams tokens and
    `cancel()` frees a request's slot/blocks through the refcounts.

Capability-typed cache protocols (DESIGN.md §13)
    The engine is written against models/registry.py's cache protocols, not
    against transformers. A family serves through a `PagedSeqCache` (the
    block-table pool everything above describes), a `SlotStateCache`
    (fixed-size per-slot recurrent state — rwkv6, linear-attention GLA,
    whisper; the slot swap IS the allocator, so admission needs only a free
    slot and no block arithmetic), or BOTH (zamba2 threads its shared-
    attention KV pool and its mamba ssm/conv state through one step fn).
    `self.caches` holds every instantiated cache keyed by kind; the traced
    step donates the whole dict. Prefix caching, COW, speculation and int8
    KV are capabilities a family must advertise — a config that asks for
    one on a family without it fails eagerly (EngineConfig(arch=...) at
    construction, ServingEngine at init). Preemption SNAPSHOTS slot state
    where the family declares `snapshot` (rwkv/GLA/whisper: `preempt()`
    saves the per-slot rows and re-admission restores them — no recompute)
    and falls back to recompute eviction otherwise. Whisper's encoder runs
    once per request at admission (the "encode" trace) and parks cross-
    attention KV in per-slot state, so encoder-decoder requests batch with
    the same scheduler. Slot ops add at most four traced shapes
    ("slot_reset", "snapshot", "restore", "encode") — slot indices are
    data — and `assert_bounded_traces` bounds them per capability.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import warnings
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import compress_model, is_clustered
from repro.distributed.sharding import use_rules
from repro.launch.mesh import auto_mesh, make_host_mesh
from repro.models.config import get_config, reduced
from repro.models.registry import (CAP_ENCODER, CAP_INT8_KV, CAP_PAGED,
                                   CAP_PREFIX_CACHE, CAP_SLOT_STATE,
                                   CAP_SPECULATIVE, Model, arch_capabilities,
                                   get_model)
from repro.utils import human_bytes, logger, tree_size_bytes


# ---------------------------------------------------------------------------
# Static-batch path (PR 1): one prefill + one scan decode, 2 traces
# ---------------------------------------------------------------------------

def build_decode_fns(model, cfg, gen_tokens: int):
    """(prefill_fn, decode_fn, trace_counts): the engine's two traced
    computations. trace_counts is mutated at TRACE time (a Python side effect
    inside the jitted functions), so after a full generation it records how
    many computations were actually compiled — asserted to be {1, 1} by
    benchmarks/decode_bench.py and tests/test_decode_engine.py."""
    traces = {"prefill": 0, "decode": 0}

    @partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, prompt):
        traces["prefill"] += 1
        logits, cache = model.decode(
            params, cache, {"tokens": prompt, "pos": jnp.asarray(0, jnp.int32)})
        tok = jnp.argmax(logits[..., :cfg.vocab], axis=-1)[:, None]
        return tok.astype(jnp.int32), cache

    @partial(jax.jit, donate_argnums=(1,))
    def decode(params, cache, first_tok):
        traces["decode"] += 1

        def body(carry, _):
            tok, cache = carry
            logits, cache = model.decode(
                params, cache, {"tokens": tok, "pos": cache["pos"]})
            nxt = jnp.argmax(logits[..., :cfg.vocab], axis=-1)[:, None]
            return (nxt.astype(jnp.int32), cache), tok[:, 0]

        (_, cache), toks = jax.lax.scan(
            body, (first_tok, cache), None, length=gen_tokens)
        return toks.swapaxes(0, 1), cache       # (B, gen_tokens)

    return prefill, decode, traces


def serve(arch: str, *, use_reduced: bool = True, lcd: bool = False,
          target_centroids: int = 8, batch: int = 4, prompt_len: int = 16,
          gen_tokens: int = 32, seed: int = 0, params=None, greedy=True,
          stats: Optional[Dict[str, Any]] = None, weight_bits: int = 4,
          bits_budget: Optional[float] = None,
          fused_projections: bool = True):
    """Static-batch generation: `gen_tokens` per sequence for one batch of
    identical prompts; returns (tokens (B, gen), params).

    Pass a dict as `stats` to receive timing/trace telemetry (tokens/s,
    prefill/decode wall time, trace counts) — benchmarks/decode_bench.py uses
    it to track the serving-speedup trajectory. `weight_bits` / `bits_budget`
    set the LCD packing policy (DESIGN.md §10): a uniform sub-byte width or a
    Fisher-scored per-layer mix under a global mean. For staggered
    multi-request traffic use `ServingEngine` instead.
    """
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg, dtype="float32")
    if cfg.fused_projections != fused_projections:
        # escape hatch (launch/serve.py --no-fused-projections): serve the
        # per-projection kernel path; bit-equal to fused, so a toggle, not a
        # numerics knob (DESIGN.md §15)
        cfg = dataclasses.replace(cfg, fused_projections=fused_projections)
    model = get_model(cfg)
    mesh = make_host_mesh()

    with use_rules(mesh, fsdp=False):
        if params is None:
            params = model.init(jax.random.key(seed))
        dense_bytes = tree_size_bytes(params)
        if lcd and not any(is_clustered(l) for l in jax.tree_util.tree_leaves(
                params, is_leaf=is_clustered)):
            kcap = 1 << weight_bits
            params, report = compress_model(
                params, target_centroids=min(target_centroids, kcap),
                nbits=weight_bits, bits_budget=bits_budget)
            logger.info("LCD: " + report.summary())
            logger.info(f"weights: {human_bytes(dense_bytes)} dense -> "
                        f"{human_bytes(tree_size_bytes(params))} clustered "
                        f"(packed sub-byte codes first-class)")
            if stats is not None:
                stats["bits_assignment"] = dict(report.bits_assignment)
                stats["mean_packed_bits"] = report.mean_packed_bits

        max_seq = prompt_len + gen_tokens
        cache = model.init_cache(batch, max_seq)
        rng = np.random.default_rng(seed)
        prompt = jnp.asarray(rng.integers(0, cfg.vocab, (batch, prompt_len)),
                             jnp.int32)

        prefill, decode, traces = build_decode_fns(model, cfg, gen_tokens)

        t0 = time.perf_counter()
        first_tok, cache = prefill(params, cache, prompt)
        jax.block_until_ready(first_tok)
        t1 = time.perf_counter()
        gen, cache = decode(params, cache, first_tok)
        gen = np.asarray(jax.block_until_ready(gen))
        t2 = time.perf_counter()

        dt = t2 - t0
        tok_s = gen.shape[1] * batch / max(t2 - t1, 1e-9)
        logger.info(f"{arch}{' +LCD' if lcd else ''}: generated "
                    f"{gen.shape[1]} tokens x {batch} seqs in {dt:.2f}s "
                    f"(prefill {t1 - t0:.2f}s, decode {t2 - t1:.2f}s, "
                    f"{tok_s:.1f} tok/s) — traces: {traces}")
        if stats is not None:
            stats.update(tokens_per_s=tok_s, prefill_s=t1 - t0,
                         decode_s=t2 - t1, total_s=dt, traces=dict(traces),
                         gen_tokens=int(gen.shape[1]), batch=batch)
        return gen, params


# ---------------------------------------------------------------------------
# Paged-block allocator (refcounted, content-hash-indexed — DESIGN.md §12)
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Refcounted free-list allocator over the physical KV block pool, with a
    content-hash index for prefix caching.

    Invariants (DESIGN.md §5, §12; pinned by tests/test_block_allocator.py):

      * every block id is either on the free list (refcount 0) or referenced
        (refcount >= 1) — `num_free + referenced == num_blocks` always;
      * a reference is a slot's block-table entry OR the hash index's own
        entry, so `refcount(b) == holders(b) + (1 if b is indexed)` and a
        hash-index entry can NEVER point at a freed block (the index's
        reference keeps it allocated);
      * `alloc` is all-or-nothing (no partial grants) and may reclaim
        cache-only blocks (refcount 1, held solely by the index) in LRU
        order to satisfy a grant;
      * `free` decrements; a block returns to the free list exactly when its
        refcount hits zero, exactly once. Freeing an unallocated block or an
        out-of-range id raises `ValueError` naming the block id (the PR 5/6
        assert→ValueError pattern: survives `python -O`, messages pinned in
        tests).
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free: collections.deque = collections.deque(range(num_blocks))
        self._refcount: List[int] = [0] * num_blocks
        # content hash -> block id; the index HOLDS one reference per entry.
        # An OrderedDict doubles as the LRU order for cache-only reclaim
        # (move_to_end on every hit/registration).
        self._hash_index: "collections.OrderedDict" = collections.OrderedDict()
        self._block_hash: List[Optional[int]] = [None] * num_blocks

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_cached(self) -> int:
        return len(self._hash_index)

    def refcount(self, b: int) -> int:
        return self._refcount[b]

    def _check_id(self, op: str, b) -> None:
        if not isinstance(b, (int, np.integer)) or not 0 <= b < self.num_blocks:
            raise ValueError(
                f"BlockAllocator.{op}: block id {b!r} out of range "
                f"[0, {self.num_blocks})")

    def _reclaimable(self) -> int:
        """Cache-only blocks (refcount 1, sole holder is the index) that
        `alloc` may evict from the prefix cache to satisfy a grant."""
        return sum(1 for h, b in self._hash_index.items()
                   if self._refcount[b] == 1)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free) + self._reclaimable():
            return None
        while len(self._free) < n:
            self._evict_cached()
        out = []
        for _ in range(n):
            b = self._free.popleft()
            self._refcount[b] = 1
            out.append(b)
        return out

    def share(self, b: int) -> int:
        """Add a reference to an allocated block (read-only sharing across
        slots — prefix caching's grant path). Returns the new refcount."""
        self._check_id("share", b)
        if self._refcount[b] == 0:
            raise ValueError(
                f"BlockAllocator.share: block {b} is free — only an "
                f"allocated block can be shared")
        self._refcount[b] += 1
        return self._refcount[b]

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per block id; a block whose refcount hits zero
        returns to the free list. Raises ValueError (naming the id) on an
        out-of-range id or a refcount underflow (double free / free of a
        never-allocated block)."""
        for b in blocks:
            self._check_id("free", b)
            if self._refcount[b] == 0:
                raise ValueError(
                    f"BlockAllocator.free: block {b} is not allocated "
                    f"(double free or refcount underflow)")
            self._refcount[b] -= 1
            if self._refcount[b] == 0:
                # cannot still be hash-indexed: the index holds a reference,
                # so an indexed block bottoms out at refcount 1
                self._free.append(b)

    # -- prefix-cache index --------------------------------------------------

    def register(self, b: int, h: int) -> bool:
        """Publish allocated block `b` under content hash `h`. The index
        takes its own reference, so the entry keeps the block alive after
        every slot lets go. First writer wins: an already-indexed hash is
        left pointing at its existing block (returns False)."""
        self._check_id("register", b)
        if self._refcount[b] == 0:
            raise ValueError(
                f"BlockAllocator.register: block {b} is free — only an "
                f"allocated block can enter the hash index")
        if h in self._hash_index:
            self._hash_index.move_to_end(h)
            return False
        if self._block_hash[b] is not None:
            # block already published under some other hash — a second entry
            # would take a second index reference and orphan the first one
            # (leaving the block permanently unreclaimable); first
            # publication wins
            return False
        self._hash_index[h] = b
        self._block_hash[b] = h
        self._refcount[b] += 1
        return True

    def lookup(self, h: int) -> Optional[int]:
        """Block id cached under hash `h`, or None. A hit refreshes the
        entry's LRU position (it just proved useful)."""
        b = self._hash_index.get(h)
        if b is not None:
            self._hash_index.move_to_end(h)
        return b

    def _evict_cached(self) -> bool:
        """Drop the least-recently-used cache-only index entry, returning its
        block to the free list. Blocks a slot still holds (refcount > 1) are
        never touched."""
        for h, b in self._hash_index.items():
            if self._refcount[b] == 1:
                del self._hash_index[h]
                self._block_hash[b] = None
                self._refcount[b] = 0
                self._free.append(b)
                return True
        return False


# ---------------------------------------------------------------------------
# Requests and engine configuration
# ---------------------------------------------------------------------------

QUEUED, RUNNING, FINISHED, CANCELLED = ("queued", "running", "finished",
                                        "cancelled")

# the counters `prefix_cache_report` reads (BENCH_serving.json prefix_cache):
# prompt tokens served from the cache, block grants satisfied by sharing and
# by alloc, copy-on-write block copies, blocks published to the hash index
PREFIX_CACHE_COUNTERS = ("cached_tokens", "shared_block_grants",
                         "fresh_block_grants", "cow_copies",
                         "registered_blocks")


def _named(fn, name: str):
    """`fn` renamed before jit, so its program is module `jit_<name>` in the
    device trace (DESIGN.md §5 "Spans and counters")."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (P,) int32
    max_new_tokens: int
    state: str = QUEUED
    slot: Optional[int] = None
    blocks: List[int] = dataclasses.field(default_factory=list)
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    fed: int = 0                       # tokens of `feed` already in the cache
    preemptions: int = 0
    submit_t: float = 0.0
    # first admission into a slot; a re-admission after preemption keeps it,
    # so admit_t - submit_t is the wait before the request first ran
    admit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    # multi-tenant scheduling (DESIGN.md §12): admission weight/budget key
    # and the intra-tenant priority (higher admits first)
    tenant: str = "default"
    priority: int = 0
    # prefix caching (DESIGN.md §12): prompt tokens served from shared cached
    # blocks instead of re-prefilled, and the chain hashes of this request's
    # full blocks registered so far (position-0-anchored, block-granular)
    cached_tokens: int = 0
    hash_chain: List[int] = dataclasses.field(default_factory=list)
    # streaming: called as on_token(request, token) for every emitted token
    on_token: Optional[Any] = None
    # tokens counted against this request's tenant budget while admitted
    inflight_tokens: int = 0
    # speculative decoding: draft tokens accepted AND emitted per verify
    # round (0..k each; round i emits accept_lens[i] + 1 tokens — a round
    # whose acceptance overshoots the token budget records the capped count)
    accept_lens: List[int] = dataclasses.field(default_factory=list)
    # encoder-decoder (CAP_ENCODER, DESIGN.md §13): precomputed frame
    # embeddings (1, enc_seq, d_model), encoded ONCE at admission into the
    # slot's cross-attention state
    frames: Optional[np.ndarray] = None
    # snapshot preemption (CAP_SNAPSHOT, DESIGN.md §13): the per-slot state
    # rows saved by `preempt()` and the readable length they cover;
    # re-admission restores both instead of re-prefilling
    snapshot: Optional[Any] = None
    snap_len: int = 0

    # tokens to (re)prefill this running stint, SNAPSHOTTED at admission:
    # the prompt plus anything generated before a preemption. Tokens decoded
    # after admission are fed one at a time, not appended here — otherwise a
    # decoding request would look permanently "prefilling" and pin the step
    # at the wide trace shape.
    feed: Optional[np.ndarray] = None

    @property
    def done(self) -> bool:
        return len(self.out_tokens) >= self.max_new_tokens

    @property
    def prefilling(self) -> bool:
        return self.feed is not None and self.fed < len(self.feed)

    def resume_feed(self) -> np.ndarray:
        """prompt + already-generated tokens — after a recompute preemption
        the generated tokens are re-ingested as prompt so greedy decoding
        resumes where it left off."""
        if not self.out_tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.out_tokens, np.int32)])


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 4                # concurrent sequences per traced step
    block_size: int = 16              # tokens per KV block
    num_blocks: int = 64              # physical pool size (all slots share it)
    max_blocks_per_slot: int = 16     # block-table width (max seq / block_size)
    prefill_chunk: int = 16           # token-window width of the mixed step
    # speculative decoding (DESIGN.md §8): tokens drafted by the 2-bit LCD
    # draft per verify round; 0 = off. The verify window is speculative_k + 1.
    speculative_k: int = 0
    draft_centroids: int = 4          # 2-bit self-draft (build_engine default)
    # KV block-pool dtype (DESIGN.md §9): "float" keeps blocks in the model
    # dtype (engine output exactly equals single-request decoding); "int8"
    # stores smoothed int8 codes + per-(block-slot, kv-head) scale pools —
    # ~3.5x the admissible slots per f32 pool byte (~2x vs a bf16 pool),
    # engine-vs-solo parity still exact WITHIN the dtype, int8-vs-float
    # parity at the documented logit tolerance. None follows the model's
    # cfg.kv_cache_dtype, so a config that quantizes its plain decode cache
    # pages quantized too.
    kv_dtype: Optional[str] = None
    # weight bit-width policy (DESIGN.md §10), applied by build_engine when it
    # LCD-compresses: weight_bits is the uniform packing width; bits_budget,
    # when set, overrides it with Fisher-scored per-layer mixed precision
    # under that global element-weighted mean (compress_model(bits_budget=)).
    weight_bits: int = 4
    bits_budget: Optional[float] = None
    # prefix caching (DESIGN.md §12): content-hashed block reuse with
    # copy-on-write block tables. Off by default — with it on, a submitted
    # prompt's longest block-aligned prefix already present in the hash
    # index is shared read-only instead of re-prefilled, and output stays
    # bit-equal to a cache-off run within a kv dtype.
    prefix_cache: bool = False
    # chunked-prefill admission (DESIGN.md §12): admit a long prompt once
    # blocks for its FIRST prefill chunk are grantable (later chunks grow the
    # block table lazily, interleaved with decode) instead of demanding the
    # whole feed's blocks up front. Prefill is always chunk-interleaved with
    # decode; this knob only lowers the admission bar.
    chunked_prefill: bool = False
    # admission policy (DESIGN.md §12): "fcfs" is strict arrival order;
    # "priority" picks by (priority desc, weighted-fair tenant share asc,
    # arrival) among tenants under their token budget.
    scheduler: str = "fcfs"
    # tenant -> fair-share weight (unlisted tenants weigh 1.0); only
    # consulted by the "priority" scheduler
    tenant_weights: Optional[Dict[str, float]] = None
    # max concurrently admitted tokens (feed + generation budget) per
    # tenant; None = unbounded. Only enforced by the "priority" scheduler.
    tenant_token_budget: Optional[int] = None
    # architecture binding (DESIGN.md §13): when set, capability-dependent
    # knobs are validated EAGERLY against the arch's family capabilities at
    # config construction — speculation, prefix cache and int8 KV are
    # paged-family features, so a slot-state arch fails here with the
    # capability named, not deep inside engine init.
    arch: Optional[str] = None
    # mesh layout (DESIGN.md §14): requested (data, model) axis sizes of the
    # serving mesh. None = let build_engine pick (the hlo_cost layout search
    # on multi-device hosts, the trivial 1-device mesh otherwise). A knob
    # that disagrees with the mesh an engine is actually constructed on
    # fails at engine init with both values named.
    data_parallel: Optional[int] = None
    model_parallel: Optional[int] = None

    def __post_init__(self):
        """Eager validation: a bad knob fails at config construction with the
        allowed values spelled out, not deep inside cache init or the first
        compress call."""
        from repro.core.lut import SUPPORTED_NBITS
        if self.kv_dtype not in (None, "float", "int8"):
            raise ValueError(
                f"EngineConfig.kv_dtype must be None (follow the model "
                f"config), 'float' or 'int8'; got {self.kv_dtype!r}")
        if self.weight_bits not in SUPPORTED_NBITS:
            raise ValueError(
                f"EngineConfig.weight_bits must be one of {SUPPORTED_NBITS}; "
                f"got {self.weight_bits!r}")
        if self.bits_budget is not None and not (
                min(SUPPORTED_NBITS) <= self.bits_budget <= max(SUPPORTED_NBITS)):
            raise ValueError(
                f"EngineConfig.bits_budget must lie in "
                f"[{min(SUPPORTED_NBITS)}, {max(SUPPORTED_NBITS)}] (global "
                f"mean packed bits); got {self.bits_budget!r}")
        if self.speculative_k < 0:
            raise ValueError(
                f"EngineConfig.speculative_k must be >= 0; got "
                f"{self.speculative_k}")
        if not 2 <= self.draft_centroids <= 16:
            raise ValueError(
                f"EngineConfig.draft_centroids must lie in [2, 16] (sub-byte "
                f"codes); got {self.draft_centroids}")
        if self.num_blocks < self.max_blocks_per_slot:
            raise ValueError(
                f"EngineConfig.num_blocks ({self.num_blocks}) must be >= "
                f"max_blocks_per_slot ({self.max_blocks_per_slot}) or no "
                f"request can ever be fully admitted")
        if self.scheduler not in ("fcfs", "priority"):
            raise ValueError(
                f"EngineConfig.scheduler must be 'fcfs' or 'priority'; got "
                f"{self.scheduler!r}")
        if self.tenant_token_budget is not None and self.tenant_token_budget <= 0:
            raise ValueError(
                f"EngineConfig.tenant_token_budget must be positive (max "
                f"concurrently admitted tokens per tenant); got "
                f"{self.tenant_token_budget!r}")
        if self.tenant_weights is not None and any(
                w <= 0 for w in self.tenant_weights.values()):
            raise ValueError(
                f"EngineConfig.tenant_weights must all be positive; got "
                f"{self.tenant_weights!r}")
        for knob in ("data_parallel", "model_parallel"):
            v = getattr(self, knob)
            if v is not None and (not isinstance(v, int) or v < 1):
                raise ValueError(
                    f"EngineConfig.{knob} must be a positive int (mesh axis "
                    f"size) or None (auto layout); got {v!r}")
        if self.arch is not None:
            caps = arch_capabilities(self.arch)  # ValueError when unknown
            if self.speculative_k and CAP_SPECULATIVE not in caps:
                raise ValueError(
                    f"EngineConfig.speculative_k > 0 needs the 'speculative' "
                    f"capability; arch {self.arch!r} has {sorted(caps)}")
            if self.prefix_cache and CAP_PREFIX_CACHE not in caps:
                raise ValueError(
                    f"EngineConfig.prefix_cache=True needs the 'prefix_cache' "
                    f"capability; arch {self.arch!r} has {sorted(caps)}")
            if self.kv_dtype == "int8" and CAP_INT8_KV not in caps:
                raise ValueError(
                    f"EngineConfig.kv_dtype='int8' needs the 'int8_kv' "
                    f"capability; arch {self.arch!r} has {sorted(caps)}")

    @property
    def max_seq(self) -> int:
        return self.max_blocks_per_slot * self.block_size


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------

class ServingEngine:
    """Continuous-batching scheduler over the paged decode path.

    Slot lifecycle (DESIGN.md §5): submit -> QUEUED -> (admit: slot + prompt
    blocks granted) -> RUNNING prefill (chunked) -> RUNNING decode (1 token
    per step, blocks allocated lazily at block-size boundaries) -> FINISHED
    (slot and blocks freed, immediately reusable by the queue).

        engine = ServingEngine(model, params, EngineConfig(...))
        engine.submit(prompt, max_new_tokens=32)
        finished = engine.run()          # drive until queue + slots drain
        engine.assert_bounded_traces()   # bounded set of compiled step shapes

    Speculative mode (ecfg.speculative_k > 0) additionally takes the 2-bit
    draft clustering as `draft_params` (core/clustered_params.py
    make_draft_params) and a second block pool mirrors the target's: the
    draft cache reuses the SAME block tables and allocator grants, so one
    reservation covers both fidelities.
    """

    def __init__(self, model: Model, params, ecfg: Optional[EngineConfig] = None,
                 mesh=None, clock=time.perf_counter, draft_params=None,
                 kv_smooth=None):
        # default constructed per engine, not evaluated once in the signature
        # (EngineConfig is frozen today, so the shared instance was inert —
        # this hardens against any future mutable field)
        ecfg = EngineConfig() if ecfg is None else ecfg
        caps = model.capabilities
        assert CAP_PAGED in caps or CAP_SLOT_STATE in caps, (
            f"family '{model.cfg.family}' publishes no serving cache "
            f"protocol (needs 'paged' or 'slot_state', DESIGN.md §13)")
        self.has_paged = CAP_PAGED in caps
        self.has_slot = CAP_SLOT_STATE in caps
        # kv_dtype / block geometry are validated eagerly by
        # EngineConfig.__post_init__; only engine-level coupling lives here.
        # the RESOLVED pool dtype: an explicit knob wins, else follow the
        # model config (the pre-§9 engine raised NotImplementedError here
        # for int8 configs — resolving beats silently serving full precision).
        # Families without the int8_kv capability always pool in the model
        # dtype; asking them for int8 is a config error, not a silent float.
        if CAP_INT8_KV in caps:
            self.kv_dtype = ecfg.kv_dtype or (
                "int8" if model.cfg.kv_cache_dtype == "int8" else "float")
        else:
            if ecfg.kv_dtype == "int8":
                raise ValueError(
                    f"EngineConfig.kv_dtype='int8' needs the 'int8_kv' "
                    f"capability; family '{model.cfg.family}' has "
                    f"{sorted(caps)}")
            self.kv_dtype = "float"
        assert kv_smooth is None or self.kv_dtype == "int8", (
            "kv_smooth only applies to the int8 KV cache")
        if ecfg.prefix_cache:
            assert CAP_PREFIX_CACHE in caps, (
                f"EngineConfig.prefix_cache=True needs the 'prefix_cache' "
                f"capability; family '{model.cfg.family}' has {sorted(caps)}")
        self.model, self.params, self.ecfg = model, params, ecfg
        self.spec_k = ecfg.speculative_k
        self.draft_params = draft_params
        if self.spec_k:
            assert CAP_SPECULATIVE in caps, (
                f"EngineConfig.speculative_k > 0 needs the 'speculative' "
                f"capability; family '{model.cfg.family}' has {sorted(caps)}")
            assert draft_params is not None, (
                "speculative decoding needs draft_params (see "
                "core/clustered_params.py make_draft_params)")
        self.mesh = mesh if mesh is not None else make_host_mesh()
        # mesh-knob agreement (DESIGN.md §14): a config that requested axis
        # sizes must match the mesh this engine actually serves on — a silent
        # mismatch would mean the deployment is NOT running the layout the
        # operator asked for (messages pinned by tests/test_sharded_serving).
        axes = dict(self.mesh.shape)
        for knob, axis in (("data_parallel", "data"),
                           ("model_parallel", "model")):
            want = getattr(ecfg, knob)
            if want is not None and want != axes.get(axis, 1):
                raise ValueError(
                    f"EngineConfig.{knob}={want} does not match the engine "
                    f"mesh's '{axis}' axis ({axes.get(axis, 1)}); mesh shape "
                    f"is {axes}")
        self.clock = clock
        self.alloc = BlockAllocator(ecfg.num_blocks)
        self.slots: List[Optional[Request]] = [None] * ecfg.num_slots
        # unallocated entries point at block 0; reads there are masked by
        # lengths, writes by n_new — never observable
        self.block_tables = np.zeros(
            (ecfg.num_slots, ecfg.max_blocks_per_slot), np.int32)
        self.lengths = np.zeros(ecfg.num_slots, np.int32)
        self.queue: collections.deque = collections.deque()
        self.finished: List[Request] = []
        # prefix cache (DESIGN.md §12): chain hashes are salted with the kv
        # dtype and the layer config, so two engines only ever share content
        # computed by an identical paged stack — the (token-chunk hash,
        # kv_dtype, layer config) key of the hash index
        self._prefix_salt = hash((self.kv_dtype, repr(model.cfg)))
        self._cow_fn = None
        # tenant accounting for the "priority" scheduler: tokens currently
        # admitted (feed + generation budget) and total tokens served, per
        # tenant — the weighted-fair share is served/weight
        self._tenant_inflight: Dict[str, int] = {}
        self._tenant_served: Dict[str, int] = {}
        # cumulative counters (DESIGN.md §5 "Spans and counters"): the
        # prefix-cache keys (PREFIX_CACHE_COUNTERS), scheduler counts, and
        # per-width `calls.w<t>` / `rows.w<t>` / `rows_fed.w<t>` and
        # per-phase `host_s.<phase>` keys added as they first occur
        self.counters: Dict[str, float] = dict.fromkeys(
            PREFIX_CACHE_COUNTERS + ("iterations", "tokens_emitted",
                                     "admitted", "preempted"), 0)
        self._phase_s = 0.0            # host seconds of every phase so far
        with use_rules(self.mesh, fsdp=False):
            # every cache the family declared, keyed by kind ("paged" block
            # pool and/or "slot" per-slot state, DESIGN.md §13)
            self.caches = model.init_seq_caches(
                num_blocks=ecfg.num_blocks, block_size=ecfg.block_size,
                num_slots=ecfg.num_slots, max_seq=ecfg.max_seq,
                kv_dtype=self.kv_dtype if self.has_paged else None)
            # the draft's own K/V pool (draft weights produce different K/V),
            # same geometry, block ids and kv dtype as the target's
            self.draft_caches = (model.init_seq_caches(
                num_blocks=ecfg.num_blocks, block_size=ecfg.block_size,
                num_slots=ecfg.num_slots, max_seq=ecfg.max_seq,
                kv_dtype=self.kv_dtype) if self.spec_k else None)
        if kv_smooth is not None:
            # calibrated smoothing vectors (calibrate_kv_smooth); the draft
            # pool uses the same VALUES — its K/V track the target's closely
            # enough, and smoothing is a quantization-quality knob, not a
            # correctness requirement (identity vectors are always valid).
            # Each cache gets its own buffers: both pytrees are donated into
            # the traced steps, and donating one shared array twice would
            # leave the second tree holding a deleted buffer.
            k_sm, v_sm = kv_smooth
            for c in (self.caches, self.draft_caches):
                if c is not None:
                    c["paged"]["k_smooth"] = jnp.array(k_sm, jnp.float32,
                                                       copy=True)
                    c["paged"]["v_smooth"] = jnp.array(v_sm, jnp.float32,
                                                       copy=True)
        # trace bookkeeping: width T -> count in normal mode; (role, width) ->
        # count in speculative mode ("prefill" / "draft" / "verify"); slot
        # ops add at most {"slot_reset", "snapshot", "restore", "encode"}
        self.traces: Dict[Any, int] = {}
        self._step_fns: Dict[Any, Any] = {}
        self._slot_fns: Dict[str, Any] = {}
        self._next_rid = 0
        self.steps = 0
        self.spec_rounds = 0
        # deployment inventory (DESIGN.md §10): build_engine attaches the
        # CompressReports here so --describe can print the bits assignment
        self.compress_report = None
        self.draft_report = None
        # layout audit trail: build_engine attaches the hlo_cost layout search
        # report here when it chose the mesh (DESIGN.md §14)
        self.layout_report = None
        self._place_sharded()

    def _place_sharded(self):
        """Commit params and cache pools to the mesh (DESIGN.md §14).

        Weights get the dense logical names (ClusteredTensor leaves expand via
        `auto_shard`: codes/packed shard like the dense weight, smoothing
        vectors like its d_in dims, LUTs replicate); pools get the family's
        declared cache names — kv heads on the model axis, so each chip holds
        its kv-head shard of EVERY block and block tables stay valid
        everywhere. With the arrays committed, every jitted step partitions
        under GSPMD and the all-reduces land only where the row-parallel
        projections (wo, w_down) demand them."""
        from repro.distributed.layout import cache_shardings
        from repro.distributed.sharding import auto_shard
        with use_rules(self.mesh, fsdp=False):
            names = self.model.names()
            self.params = jax.device_put(
                self.params, auto_shard(self.params, names))
            if self.draft_params is not None:
                self.draft_params = jax.device_put(
                    self.draft_params, auto_shard(self.draft_params, names))
            self.caches = jax.device_put(
                self.caches, cache_shardings(self.model, self.caches))
            if self.draft_caches is not None:
                self.draft_caches = jax.device_put(
                    self.draft_caches,
                    cache_shardings(self.model, self.draft_caches))

    # -- deprecated pre-§13 cache aliases -----------------------------------

    @property
    def cache(self):
        warnings.warn(
            "ServingEngine.cache is deprecated; use engine.caches['paged'] "
            "(DESIGN.md §13)", DeprecationWarning, stacklevel=2)
        return self.caches.get("paged")

    @property
    def draft_cache(self):
        warnings.warn(
            "ServingEngine.draft_cache is deprecated; use "
            "engine.draft_caches['paged'] (DESIGN.md §13)",
            DeprecationWarning, stacklevel=2)
        return (None if self.draft_caches is None
                else self.draft_caches.get("paged"))

    # -- public API ---------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *, tenant: str = "default",
               priority: int = 0, on_token=None, frames=None) -> Request:
        """Queue a request. `tenant`/`priority` feed the "priority" scheduler
        (DESIGN.md §12); `on_token(request, token)` streams every emitted
        token as it is decoded (speculative rounds stream each accepted
        token individually, in order). Encoder-decoder families
        (CAP_ENCODER) REQUIRE `frames`, the request's precomputed frame
        embeddings (enc_seq, d_model) or (1, enc_seq, d_model) — encoded
        once at admission into the slot's cross-attention state."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.model.supports(CAP_ENCODER):
            assert frames is not None, (
                f"family '{self.model.cfg.family}' is encoder-decoder: "
                f"submit() needs `frames` (1, enc_seq, d_model)")
            frames = np.asarray(frames, self.model.cfg.jnp_dtype)
            if frames.ndim == 2:
                frames = frames[None]
            want = (1, self.model.cfg.enc_seq, self.model.cfg.d_model)
            assert frames.shape == want, (
                f"frames must be {want} (one request's encoder input); got "
                f"{frames.shape}")
        else:
            assert frames is None, (
                f"family '{self.model.cfg.family}' has no encoder; submit() "
                f"got unexpected `frames`")
        # speculative rounds write up to k tokens past the accepted length
        # before rolling back, so a request needs k tokens of cache headroom
        need = len(prompt) + max_new_tokens + self.spec_k
        assert need <= self.ecfg.max_seq, (
            f"request needs {need} tokens (incl. speculative headroom "
            f"{self.spec_k}); engine max_seq is {self.ecfg.max_seq} "
            f"(max_blocks_per_slot * block_size)")
        r = Request(self._next_rid, prompt, max_new_tokens,
                    submit_t=self.clock(), tenant=tenant, priority=priority,
                    on_token=on_token, frames=frames)
        self._next_rid += 1
        self.queue.append(r)
        return r

    def cancel(self, r: Request) -> bool:
        """Abort a queued or running request. A running request's slot and
        blocks are released immediately — frees go through the refcounted
        allocator, so blocks other slots (or the prefix-cache index) still
        reference survive untouched (DESIGN.md §12). Returns False if the
        request already finished or was already cancelled."""
        if r.state == QUEUED:
            self.queue.remove(r)
            r.state = CANCELLED
            return True
        if r.state == RUNNING:
            s = r.slot
            self.alloc.free(r.blocks)
            self._tenant_release(r)
            r.blocks, r.slot, r.feed = [], None, None
            r.state, r.finish_t = CANCELLED, self.clock()
            self.slots[s] = None
            self.lengths[s] = 0
            self.block_tables[s] = 0
            return True
        return False

    def preempt(self, r: Request) -> None:
        """Preempt a RUNNING request back to the queue front (DESIGN.md §13).

        Families whose SlotStateCache declares `snapshot` (rwkv, GLA,
        whisper) save the request's per-slot state rows — including any
        cross-attention KV — and re-admission RESTORES them, so the request
        resumes exactly where it stopped without recomputing a single token.
        Everything else (paged pools whose blocks must be surrendered,
        zamba2's non-snapshot hybrid state) falls back to recompute
        preemption, identical to block-pressure eviction."""
        assert r.state == RUNNING, f"cannot preempt a {r.state!r} request"
        proto = self.model.seq_caches.get("slot")
        if proto is None or not proto.snapshot or self.has_paged:
            self._evict(r)
            return
        s = r.slot
        logger.info(f"engine: snapshot-preempting request {r.rid} "
                    f"({len(r.out_tokens)}/{r.max_new_tokens} tokens done)")
        with use_rules(self.mesh, fsdp=False):
            r.snapshot = self._slot_fn("snapshot")(
                self.caches["slot"], jnp.asarray(s, jnp.int32))
        r.snap_len = int(self.lengths[s])
        self._tenant_release(r)
        # feed/fed are KEPT: a mid-prefill request resumes its feed from the
        # restored state; a decoding one keeps its pending token
        r.slot = None
        r.state, r.preemptions = QUEUED, r.preemptions + 1
        self._count("preempted", 1)
        self.slots[s] = None
        self.lengths[s] = 0
        self.queue.appendleft(r)

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)

    def run(self, max_steps: int = 100_000) -> List[Request]:
        """Drive `step()` until every submitted request finishes."""
        done: List[Request] = []
        for _ in range(max_steps):
            if not self.busy:
                return done
            done.extend(self.step())
        raise RuntimeError(f"engine did not drain in {max_steps} steps")

    def assert_bounded_traces(self) -> None:
        """The bounded-trace contract: no matter how requests arrive or
        interleave, the engine compiles a FIXED set of computations, each
        exactly once. Normal mode: at most two step widths — prefill_chunk
        and 1. Speculative mode: at most three computations — the combined
        two-model prefill step (width prefill_chunk), the scan-compiled
        k-token draft, and the width-(k+1) verify (DESIGN.md §8)."""
        if self.spec_k:
            allowed = {("prefill", self.ecfg.prefill_chunk),
                       ("draft", self.spec_k),
                       ("verify", self.spec_k + 1)}
        else:
            allowed = {1, self.ecfg.prefill_chunk}
        if self.ecfg.prefix_cache:
            # the copy-on-write block copy is one extra traced computation
            # (block ids are data), shared by every COW this engine performs
            allowed = allowed | {"cow"}
        if self.has_slot:
            # per-slot state ops (DESIGN.md §13): slot indices are data, so
            # each op is one traced shape no matter how many slots it touches
            allowed = allowed | {"slot_reset"}
            if self.model.seq_caches["slot"].snapshot:
                allowed = allowed | {"snapshot", "restore"}
        if self.model.supports(CAP_ENCODER):
            allowed = allowed | {"encode"}
        assert set(self.traces) <= allowed, (
            f"unexpected step shapes {set(self.traces)} (allowed {allowed})")
        assert all(c == 1 for c in self.traces.values()), (
            f"a step shape retraced: {self.traces}")

    def acceptance_summary(self) -> Dict[str, Any]:
        """Accepted-length accounting over every request this engine has
        seen. `accepted_len` counts tokens emitted per verify round (the
        accepted draft prefix + the target's correction/bonus token), so its
        mean is the speculative speed multiplier on target dispatches."""
        live = [x for x in self.slots if x is not None] + list(self.queue)
        entries = [a for r in self.finished + live for a in r.accept_lens]
        hist: Dict[int, int] = {}
        for a in entries:
            hist[a + 1] = hist.get(a + 1, 0) + 1
        return {
            # engine-level verify dispatches vs per-slot accept entries: one
            # round serves every decoding slot, so entries >= rounds
            "spec_rounds": self.spec_rounds,
            "accept_entries": len(entries),
            "mean_accepted_len": (float(np.mean([a + 1 for a in entries]))
                                  if entries else 0.0),
            "accepted_len_hist": {str(n): c for n, c in sorted(hist.items())},
        }

    def prefix_cache_report(self) -> Dict[str, Any]:
        """Prefix-cache telemetry (DESIGN.md §12): the prefix-cache counters
        plus the derived block-reuse rate (shared grants over all grants —
        the BENCH_serving.json `prefix_cache.cache_on.block_reuse_rate`
        headline) and the index's current size."""
        out: Dict[str, Any] = {k: self.counters[k]
                               for k in PREFIX_CACHE_COUNTERS}
        grants = out["shared_block_grants"] + out["fresh_block_grants"]
        out["block_reuse_rate"] = (
            round(out["shared_block_grants"] / grants, 4) if grants else 0.0)
        out["cached_blocks_now"] = self.alloc.num_cached
        return out

    def counters_summary(self) -> Dict[str, Any]:
        """The operator's digest of `counters` (the prefix-cache keys are
        `prefix_cache_report`'s): scheduler iterations, program calls and
        the share of computed rows (num_slots x width per call) that fed no
        token per program width, host ms per iteration outside the device
        wait, tokens emitted, admissions and preemptions."""
        c = self.counters
        widths = sorted(int(k[len("rows.w"):]) for k in c
                        if k.startswith("rows.w"))
        host_s = sum(v for k, v in c.items()
                     if k.startswith("host_s.") and k != "host_s.wait")
        return {
            "iterations": c["iterations"],
            "calls": {f"w{w}": c[f"calls.w{w}"] for w in widths},
            "pad_row_share": {f"w{w}": round(
                1.0 - c[f"rows_fed.w{w}"] / c[f"rows.w{w}"], 4)
                for w in widths},
            "host_ms_per_step": (round(1e3 * host_s / c["iterations"], 3)
                                 if c["iterations"] else 0.0),
            "tokens_emitted": c["tokens_emitted"],
            "admitted": c["admitted"],
            "preempted": c["preempted"],
        }

    # -- scheduler ----------------------------------------------------------

    def _count(self, key: str, n) -> None:
        self.counters[key] = self.counters.get(key, 0) + n

    @contextlib.contextmanager
    def _phase(self, name: str, annotation=jax.profiler.TraceAnnotation,
               **meta):
        """Span `engine.<name>` on the profiler's clock (recorded only while
        a profiler session is open) around one scheduler phase, and its
        host self time (minus the phases nested in it) into
        `counters["host_s.<name>"]`, on `self.clock`."""
        t0, inner = self.clock(), self._phase_s
        with annotation(f"engine.{name}", **meta):
            yield
        dt = self.clock() - t0
        self._count(f"host_s.{name}", dt - (self._phase_s - inner))
        self._phase_s = inner + dt

    def step(self) -> List[Request]:
        """One scheduler iteration: admit from the queue, advance every
        active slot once, harvest finished requests. Returns the requests
        that finished during this step.

        Prefilling slots advance by one chunk through the width-
        `prefill_chunk` computation; decoding slots advance one token
        through the width-1 computation, in a second traced step when both
        kinds are active. A decode token is therefore always computed by the
        same program whether or not another request is prefilling: the two
        widths compile to different programs, on a v5e they were seen to
        round the same row differently, and a token must not depend on its
        neighbours (the engine-vs-solo contract, DESIGN.md §5).

        In speculative mode a pure-decode step becomes a draft/verify ROUND
        (`_spec_round`): k draft tokens from the 2-bit model, one batched
        verify from the target. Steps with a prefilling slot keep the mixed
        prefill shape — decoding slots still advance one plain token there,
        through the combined step that feeds both caches."""
        with self._phase("step", jax.profiler.StepTraceAnnotation,
                         step_num=self.steps):
            self._count("iterations", 1)
            with self._phase("admit"):
                self._admit()
            active = [(s, r) for s, r in enumerate(self.slots)
                      if r is not None]
            if not active:
                return []
            if self.spec_k and not any(r.prefilling for _, r in active):
                return self._spec_round(active)
            prefilling = {r.rid for _, r in active if r.prefilling}
            if self.spec_k or len(prefilling) in (0, len(active)):
                done = self._step_width(
                    self.ecfg.prefill_chunk if prefilling else 1, None)
            else:
                decoding = {r.rid for _, r in active} - prefilling
                done = self._step_width(self.ecfg.prefill_chunk, prefilling)
                done += self._step_width(1, decoding)
            self.steps += 1
            return done

    def _step_width(self, t: int, rids) -> List[Request]:
        """One traced step of width `t` over the active slots whose request
        id is in `rids` (None: every active slot); the others feed nothing."""
        ecfg = self.ecfg
        active = [(s, r) for s, r in enumerate(self.slots)
                  if r is not None and (rids is None or r.rid in rids)]

        # pass 1 — reserve blocks (paged families only: slot state is
        # fixed-size, so slot-only families never starve or evict here).
        # Reservation may EVICT other active slots (recompute preemption),
        # so it must complete before any tokens are packed: a slot evicted
        # here simply drops out of pass 2.
        def want(r):
            return min(len(r.feed) - r.fed, t) if r.prefilling else 1
        with self._phase("reserve"):
            if self.has_paged:
                for s, r in active:
                    if self.slots[s] is not r:
                        continue       # evicted by an earlier reservation
                    self._ensure_blocks(r, int(self.lengths[s]) + want(r))

            # pass 1.5 — copy-on-write (DESIGN.md §12): a slot about to
            # write into a block prefix caching granted read-only (refcount
            # > 1) gets a private copy first. Like pass 1 this can evict, so
            # it completes before any tokens are packed.
            if ecfg.prefix_cache:
                for s, r in enumerate(self.slots):
                    if r is None or (rids is not None and r.rid not in rids):
                        continue
                    w = want(r)
                    if (len(r.blocks) * ecfg.block_size
                            >= int(self.lengths[s]) + w):
                        self._cow_for_write(r, s, w)

        # pass 2 — pack the surviving slots into one traced batch (a slot
        # whose tail block is still shared — COW could not get a block —
        # waits this step, like starvation)
        with self._phase("pack"):
            tokens = np.zeros((ecfg.num_slots, t), np.int32)
            n_new = np.zeros(ecfg.num_slots, np.int32)
            active = [(s, r) for s, r in enumerate(self.slots)
                      if r is not None and (rids is None or r.rid in rids)]
            for s, r in active:
                w = want(r)
                if self.has_paged and (len(r.blocks) * ecfg.block_size
                                       < int(self.lengths[s]) + w):
                    continue           # starved of blocks: waits this step
                if self._write_shared(r, s, w):
                    continue           # COW starved: waits this step
                if r.prefilling:
                    tokens[s, :w] = r.feed[r.fed:r.fed + w]
                else:
                    tokens[s, 0] = r.out_tokens[-1]
                n_new[s] = w
            self._count(f"calls.w{t}", 1)
            self._count(f"rows.w{t}", ecfg.num_slots * t)
            self._count(f"rows_fed.w{t}", int(n_new.sum()))

        with (self._phase("dispatch", width=t),
              use_rules(self.mesh, fsdp=False)):
            if self.spec_k:
                # combined step: the draft cache ingests the same tokens so
                # it stays in lockstep with the target's accepted prefix
                next_tok, self.caches, self.draft_caches = self._spec_prefill_fn(t)(
                    self.params, self.draft_params, self.caches,
                    self.draft_caches, jnp.asarray(tokens),
                    jnp.asarray(self.lengths), jnp.asarray(n_new),
                    jnp.asarray(self.block_tables))
            else:
                next_tok, self.caches = self._step_fn(t)(
                    self.params, self.caches, jnp.asarray(tokens),
                    jnp.asarray(self.lengths), jnp.asarray(n_new),
                    jnp.asarray(self.block_tables))
        with self._phase("wait", width=t):
            next_tok = np.asarray(next_tok)

        done: List[Request] = []
        with self._phase("harvest"):
            for s, r in active:
                if self.slots[s] is not r or not n_new[s]:
                    continue           # evicted by _ensure_blocks, or starved
                r.fed += int(n_new[s])
                self.lengths[s] += int(n_new[s])
                if self.ecfg.prefix_cache:
                    self._register_blocks(s, r)
                if not r.prefilling:   # last valid token's logits are usable
                    if r.first_token_t is None:
                        r.first_token_t = self.clock()
                    self._emit(r, int(next_tok[s]))
                    if r.done:
                        self._finish(r)
                        done.append(r)
        return done

    # -- speculative round (DESIGN.md §8) ------------------------------------

    def _spec_round(self, active) -> List[Request]:
        """One draft/verify round over every decoding slot.

        1. RESERVE: a round writes K/V up to `lengths + k` (the pending token
           plus k drafts) before any rollback, so each slot's block table must
           cover lengths + k + 1 tokens up front — a slot that cannot be
           covered sits the round out (n_new = 0 masks it everywhere).
        2. DRAFT: one scan-compiled dispatch of the 2-bit model generates k
           greedy tokens per slot (width-1 steps inside lax.scan; the draft
           cache advances k positions).
        3. VERIFY: one width-(k+1) target forward over [pending, d_1..d_k]
           returns the target's argmax AFTER every fed token. The longest
           prefix of drafts matching those argmaxes is accepted; the round
           emits accepted + 1 tokens (the +1 is the target's own next token —
           the correction on mismatch, the bonus token on full acceptance).
        4. ROLLBACK: `lengths` advances by exactly the emitted count, so the
           K/V written for rejected drafts stays past the readable horizon
           and is overwritten by the next round. The draft cache rolls back
           the same way — both pools share block tables and `lengths`.
        """
        ecfg, k = self.ecfg, self.spec_k
        with self._phase("reserve", role="draft"):
            for s, r in active:
                if self.slots[s] is not r:
                    continue           # evicted by an earlier reservation
                self._ensure_blocks(r, int(self.lengths[s]) + k + 1)

            # copy-on-write pass (DESIGN.md §12): a round writes the span
            # [lengths, lengths + k + 1), so a shared tail block must be
            # copied first; like reservations this can evict, so it runs to
            # completion before participation is decided
            if ecfg.prefix_cache:
                for s, r in enumerate(self.slots):
                    if r is None:
                        continue
                    if (len(r.blocks) * ecfg.block_size
                            >= int(self.lengths[s]) + k + 1):
                        self._cow_for_write(r, s, k + 1)

            # participation is decided after ALL reservations: a reservation
            # may have evicted a slot that reserved earlier
            live: List[tuple] = []
            for s, r in enumerate(self.slots):
                if r is None:
                    continue
                if (len(r.blocks) * ecfg.block_size
                        >= int(self.lengths[s]) + k + 1):
                    assert r.out_tokens, (
                        "decoding slot must have a pending token")
                    if self._write_shared(r, s, k + 1):
                        continue       # COW starved: waits this round
                    live.append((s, r))
        if not live:
            self.steps += 1            # starved round: everyone waits
            return []

        with self._phase("pack", role="draft"):
            pend = np.zeros((ecfg.num_slots, 1), np.int32)
            n_one = np.zeros(ecfg.num_slots, np.int32)
            for s, r in live:
                pend[s, 0] = r.out_tokens[-1]
                n_one[s] = 1

        with use_rules(self.mesh, fsdp=False):
            with self._phase("dispatch", role="draft"):
                drafts, self.draft_caches = self._draft_fn()(
                    self.draft_params, self.draft_caches, jnp.asarray(pend),
                    jnp.asarray(self.lengths), jnp.asarray(n_one),
                    jnp.asarray(self.block_tables))
            with self._phase("wait", role="draft"):
                drafts = np.asarray(drafts)                  # (S, k)

            with self._phase("pack", role="verify"):
                vtokens = np.zeros((ecfg.num_slots, k + 1), np.int32)
                n_ver = np.zeros(ecfg.num_slots, np.int32)
                for s, r in live:
                    vtokens[s, 0] = r.out_tokens[-1]
                    vtokens[s, 1:] = drafts[s]
                    n_ver[s] = k + 1
            with self._phase("dispatch", role="verify", width=k + 1):
                target, self.caches = self._verify_fn()(
                    self.params, self.caches, jnp.asarray(vtokens),
                    jnp.asarray(self.lengths), jnp.asarray(n_ver),
                    jnp.asarray(self.block_tables))
        with self._phase("wait", role="verify", width=k + 1):
            target = np.asarray(target)                      # (S, k+1)
        self.steps += 1
        self.spec_rounds += 1

        done: List[Request] = []
        with self._phase("harvest", role="verify"):
            for s, r in live:
                accepted = 0
                while (accepted < k
                       and target[s, accepted] == drafts[s, accepted]):
                    accepted += 1
                emit = [int(t) for t in target[s, :accepted + 1]]
                emit = emit[:r.max_new_tokens - len(r.out_tokens)]
                # record the REALIZED advance (budget cap included), so the
                # mean accepted length is the true target-dispatch multiplier
                r.accept_lens.append(len(emit) - 1)
                for tok in emit:
                    self._emit(r, tok)
                # the rollback: only the emitted prefix becomes readable cache
                self.lengths[s] += len(emit)
                if self.ecfg.prefix_cache:
                    self._register_blocks(s, r)
                if r.done:
                    self._finish(r)
                    done.append(r)
        return done

    # -- internals ----------------------------------------------------------

    def _step_fn(self, t: int):
        if t not in self._step_fns:
            model, cfg = self.model, self.model.cfg

            def step(params, caches, tokens, lengths, n_new, block_tables):
                self.traces[t] = self.traces.get(t, 0) + 1   # trace-time only
                logits, caches = model.serving_step(
                    params, caches, tokens, lengths, n_new, block_tables)
                nxt = jnp.argmax(logits[..., :cfg.vocab], axis=-1)
                return nxt.astype(jnp.int32), caches

            self._step_fns[t] = jax.jit(_named(step, f"serve_step_w{t}"),
                                        donate_argnums=(1,))
        return self._step_fns[t]

    def _spec_prefill_fn(self, t: int):
        """Speculative-mode mixed step: ONE traced computation feeds the same
        token window through BOTH models so the draft cache tracks the target
        cache through prefill (and through the one-token decode a non-
        prefilling slot does while others prefill). The target's logits pick
        the next token; the draft's head output is dead code XLA removes."""
        key = ("prefill", t)
        if key not in self._step_fns:
            model, cfg = self.model, self.model.cfg

            def step(params, dparams, caches, dcaches, tokens, lengths, n_new,
                     block_tables):
                self.traces[key] = self.traces.get(key, 0) + 1
                logits, caches = model.serving_step(
                    params, caches, tokens, lengths, n_new, block_tables)
                _, dcaches = model.serving_step(
                    dparams, dcaches, tokens, lengths, n_new, block_tables)
                nxt = jnp.argmax(logits[..., :cfg.vocab], axis=-1)
                return nxt.astype(jnp.int32), caches, dcaches

            self._step_fns[key] = jax.jit(
                _named(step, f"serve_prefill_w{t}"), donate_argnums=(2, 3))
        return self._step_fns[key]

    def _draft_fn(self):
        """k greedy draft tokens per slot in ONE dispatch: width-1 draft
        steps scan-compiled (the §2 static-decode structure applied to the
        2-bit model), draft cache donated through the loop.

        The scan runs k+1 feeds, not k: the last feed pushes d_k through the
        draft so its K/V lands at position lengths+k BEFORE acceptance is
        known. Without it a fully-accepted round (lengths += k+1) would leave
        a permanent hole in the draft cache at d_k's position — the draft
        would attend stale zeros there forever after, and acceptance would
        silently collapse a few rounds into every long generation. The
        (k+1)-th output token is discarded; rejected feeds roll back by the
        same lengths masking as everything else (DESIGN.md §8)."""
        key = ("draft", self.spec_k)
        if key not in self._step_fns:
            model, cfg, k = self.model, self.model.cfg, self.spec_k

            def draft(dparams, dcaches, tok0, lengths, n_one, block_tables):
                self.traces[key] = self.traces.get(key, 0) + 1

                def body(carry, _):
                    tok, dcaches, dlen = carry
                    logits, dcaches = model.serving_step(
                        dparams, dcaches, tok, dlen, n_one, block_tables)
                    nxt = jnp.argmax(logits[..., :cfg.vocab], axis=-1)
                    nxt = nxt.astype(jnp.int32)
                    return (nxt[:, None], dcaches, dlen + n_one), nxt

                (_, dcaches, _), toks = jax.lax.scan(
                    body, (tok0, dcaches, lengths), None, length=k + 1)
                return toks.swapaxes(0, 1)[:, :k], dcaches   # (S, k)

            self._step_fns[key] = jax.jit(_named(draft, f"serve_draft_k{k}"),
                                          donate_argnums=(1,))
        return self._step_fns[key]

    def _verify_fn(self):
        """Target verification: one width-(k+1) forward whose argmax at every
        fed position is the target's next-token choice there (bit-equal to
        what k+1 sequential width-1 steps would pick)."""
        key = ("verify", self.spec_k + 1)
        if key not in self._step_fns:
            model, cfg = self.model, self.model.cfg

            def verify(params, caches, tokens, lengths, n_new, block_tables):
                self.traces[key] = self.traces.get(key, 0) + 1
                logits, caches = model.serving_verify(
                    params, caches, tokens, lengths, n_new, block_tables)
                nxt = jnp.argmax(logits[..., :cfg.vocab], axis=-1)
                return nxt.astype(jnp.int32), caches

            self._step_fns[key] = jax.jit(
                _named(verify, f"serve_verify_w{self.spec_k + 1}"),
                donate_argnums=(1,))
        return self._step_fns[key]

    # -- per-slot state ops (SlotStateCache, DESIGN.md §13) ------------------

    def _slot_fn(self, name: str):
        """One jitted per-slot state op per kind — the slot index arrives as
        DATA (a traced int32), so "slot_reset"/"snapshot"/"restore"/"encode"
        each cost exactly one traced shape no matter which or how many slots
        they touch (every SlotStateCache leaf carries the slot on axis 1)."""
        if name not in self._slot_fns:
            model = self.model
            if name == "slot_reset":
                def reset(state, slot):
                    self.traces["slot_reset"] = (
                        self.traces.get("slot_reset", 0) + 1)
                    return jax.tree_util.tree_map(
                        lambda a: a.at[:, slot].set(0), state)
                jitted = jax.jit(_named(reset, "serve_slot_reset"),
                                 donate_argnums=(0,))
            elif name == "snapshot":
                # NOT donated: the engine state stays live for other slots
                def take(state, slot):
                    self.traces["snapshot"] = (
                        self.traces.get("snapshot", 0) + 1)
                    return jax.tree_util.tree_map(lambda a: a[:, slot], state)
                jitted = jax.jit(_named(take, "serve_snapshot"))
            elif name == "restore":
                def put(state, snap, slot):
                    self.traces["restore"] = self.traces.get("restore", 0) + 1
                    return jax.tree_util.tree_map(
                        lambda a, b: a.at[:, slot].set(b.astype(a.dtype)),
                        state, snap)
                jitted = jax.jit(_named(put, "serve_restore"),
                                 donate_argnums=(0,))
            else:                      # "encode": encoder prefill -> cross KV
                def encode(params, state, frames, slot):
                    self.traces["encode"] = self.traces.get("encode", 0) + 1
                    ck, cv = model.encode_prefill(params, frames)
                    out = dict(state)
                    out["ck"] = state["ck"].at[:, slot].set(
                        ck.astype(state["ck"].dtype))
                    out["cv"] = state["cv"].at[:, slot].set(
                        cv.astype(state["cv"].dtype))
                    return out
                jitted = jax.jit(_named(encode, "serve_encode"),
                                 donate_argnums=(1,))
            self._slot_fns[name] = jitted
        return self._slot_fns[name]

    def _slot_reset(self, s: int) -> None:
        """Zero slot `s`'s state rows: a fresh stint must not read the
        previous occupant's recurrence."""
        with use_rules(self.mesh, fsdp=False):
            self.caches["slot"] = self._slot_fn("slot_reset")(
                self.caches["slot"], jnp.asarray(s, jnp.int32))

    def _slot_encode(self, s: int, frames: np.ndarray) -> None:
        """Run the encoder ONCE for the request admitted into slot `s` and
        park its cross-attention KV in the slot's state (CAP_ENCODER) — the
        encoder is a second prefill shape, fixed at (1, enc_seq, d_model)."""
        with use_rules(self.mesh, fsdp=False):
            self.caches["slot"] = self._slot_fn("encode")(
                self.params, self.caches["slot"], jnp.asarray(frames),
                jnp.asarray(s, jnp.int32))

    def _slot_restore(self, s: int, r: Request) -> None:
        """Put a preemption snapshot back into slot `s` (CAP_SNAPSHOT)."""
        with use_rules(self.mesh, fsdp=False):
            self.caches["slot"] = self._slot_fn("restore")(
                self.caches["slot"], r.snapshot, jnp.asarray(s, jnp.int32))

    def _admit(self) -> None:
        """Admission (DESIGN.md §12): pick the next queued request under the
        configured policy — "fcfs" is strict arrival order; "priority" picks
        by (priority desc, weighted-fair tenant share asc, arrival) among
        tenants under their token budget — then grant blocks all-or-nothing
        for its feed (with `chunked_prefill`, only its first chunk: later
        chunks grow the table lazily through _ensure_blocks). With
        `prefix_cache` on, the feed's longest block-aligned prefix already
        in the hash index is shared read-only instead of re-prefilled; at
        least the feed's last token is always re-fed, because its logits
        seed the first generated token.

        Slot-state families (DESIGN.md §13) skip block accounting entirely —
        a free slot is the only admission requirement; the slot's state rows
        are zeroed (and, for encoder-decoder requests, the encoder runs into
        them). A snapshot-preempted request restores its saved state and
        resumes mid-stream instead of re-prefilling."""
        ecfg = self.ecfg
        for s in range(ecfg.num_slots):
            if self.slots[s] is not None or not self.queue:
                continue
            r = self._pick_next()
            if r is None:
                return                 # nothing admissible this step
            if r.snapshot is not None:
                self.queue.remove(r)
                self._slot_restore(s, r)
                r.state, r.slot = RUNNING, s
                self.slots[s] = r
                self.lengths[s] = r.snap_len
                self.block_tables[s] = 0
                r.snapshot, r.snap_len = None, 0
                self._tenant_acquire(r)
                self._admitted(r)
                continue
            feed = r.resume_feed()
            shared, hashes = ([], [])
            if ecfg.prefix_cache:
                shared, hashes = self._match_prefix(feed)
            cached_len = len(shared) * ecfg.block_size
            if shared and cached_len >= len(feed):
                cached_len = len(feed) - 1
            # protect matched blocks from alloc()'s cache reclaim by taking
            # our reference BEFORE allocating the fresh remainder
            for b in shared:
                self.alloc.share(b)
            blocks: List[int] = []
            if self.has_paged:
                need_tokens = len(feed)
                if ecfg.chunked_prefill:
                    need_tokens = min(len(feed),
                                      cached_len + ecfg.prefill_chunk)
                need = -(-need_tokens // ecfg.block_size) - len(shared)
                blocks = self.alloc.alloc(max(need, 0))
                if blocks is None:
                    self.alloc.free(shared)  # undo the shares; r stays queued
                    return             # all-or-nothing: don't starve the pick
            self.queue.remove(r)
            self._count("cached_tokens", cached_len)
            self._count("shared_block_grants", len(shared))
            self._count("fresh_block_grants", len(blocks))
            r.cached_tokens = cached_len
            r.hash_chain = hashes
            r.feed = feed
            r.blocks = shared + blocks
            r.state, r.slot, r.fed = RUNNING, s, cached_len
            self.slots[s] = r
            self.lengths[s] = cached_len
            self.block_tables[s] = 0
            self.block_tables[s, :len(r.blocks)] = r.blocks
            if self.has_slot:
                self._slot_reset(s)
                if r.frames is not None:
                    self._slot_encode(s, r.frames)
            self._tenant_acquire(r)
            self._admitted(r)

    def _admitted(self, r: Request) -> None:
        self._count("admitted", 1)
        if r.admit_t is None:
            r.admit_t = self.clock()

    # -- prefix cache, copy-on-write and tenant accounting (DESIGN.md §12) --

    def _chunk_hash(self, prev: int, chunk: np.ndarray) -> int:
        """Chain hash of one full token block given the chain value of
        everything before it — position-0-anchored, so equal hashes mean the
        ENTIRE prefix up to this block matches, not just the chunk."""
        return hash((prev, np.ascontiguousarray(chunk, np.int32).tobytes()))

    def _match_prefix(self, feed: np.ndarray):
        """(shared_blocks, chain_hashes): the longest prefix of `feed`'s full
        blocks present in the hash index, at block granularity."""
        bs = self.ecfg.block_size
        shared: List[int] = []
        hashes: List[int] = []
        h = self._prefix_salt
        for i in range(len(feed) // bs):
            h = self._chunk_hash(h, feed[i * bs:(i + 1) * bs])
            b = self.alloc.lookup(h)
            if b is None:
                break
            shared.append(b)
            hashes.append(h)
        return shared, hashes

    def _register_blocks(self, s: int, r: Request) -> None:
        """Publish every newly COMPLETED full block of slot `s` (its end is
        below the accepted `lengths` — speculative overwrites past `lengths`
        never reach a registered block) to the hash index. The index takes
        its own reference, so the entry outlives the request."""
        bs = self.ecfg.block_size
        full = int(self.lengths[s]) // bs
        if full <= len(r.hash_chain):
            return
        stream = (np.concatenate([r.prompt,
                                  np.asarray(r.out_tokens, np.int32)])
                  if r.out_tokens else r.prompt)
        for i in range(len(r.hash_chain), full):
            prev = r.hash_chain[-1] if r.hash_chain else self._prefix_salt
            h = self._chunk_hash(prev, stream[i * bs:(i + 1) * bs])
            if self.alloc.register(r.blocks[i], h):
                self._count("registered_blocks", 1)
            r.hash_chain.append(h)

    def _touched_blocks(self, r: Request, s: int, w: int):
        """Logical block indices the next `w`-token write at `lengths[s]`
        lands in (clipped to the table)."""
        bs = self.ecfg.block_size
        start = int(self.lengths[s])
        return range(start // bs,
                     min(-(-(start + w) // bs), len(r.blocks)))

    def _write_shared(self, r: Request, s: int, w: int) -> bool:
        """True if any block the next write touches is still shared —
        writing would corrupt another reference's read-only view."""
        if not self.ecfg.prefix_cache:
            return False
        return any(self.alloc.refcount(r.blocks[i]) > 1
                   for i in self._touched_blocks(r, s, w))

    def _cow_for_write(self, r: Request, s: int, w: int) -> bool:
        """Copy-on-write (DESIGN.md §12): give `r` a private copy of every
        shared block its next `w`-token write touches, before `quantize_kv`
        appends through the traced step. The copy is ONE extra traced
        computation (block ids are data; both KV pools in speculative mode).
        Under pool exhaustion the youngest other running request is evicted,
        exactly like _ensure_blocks; False means `r` waits this step."""
        for i in self._touched_blocks(r, s, w):
            old = r.blocks[i]
            if self.alloc.refcount(old) <= 1:
                continue               # sole owner: write in place
            got = self.alloc.alloc(1)
            while got is None:
                victim = self._youngest_running(exclude=r)
                if victim is None:
                    return False       # nothing to evict; r waits this step
                self._evict(victim)
                got = self.alloc.alloc(1)
            new = self._count_fresh(got)[0]
            with use_rules(self.mesh, fsdp=False):
                self.caches["paged"] = self._cow_copy_fn()(
                    self.caches["paged"], jnp.asarray(old, jnp.int32),
                    jnp.asarray(new, jnp.int32))
                if self.draft_caches is not None:
                    self.draft_caches["paged"] = self._cow_copy_fn()(
                        self.draft_caches["paged"], jnp.asarray(old, jnp.int32),
                        jnp.asarray(new, jnp.int32))
            r.blocks[i] = new
            self.block_tables[s, i] = new
            self.alloc.free([old])     # drop our reference; sharers keep it
            self._count("cow_copies", 1)
        return True

    def _cow_copy_fn(self):
        """One jitted pool-row copy shared by every COW: block ids arrive as
        data, so the bounded-trace contract gains exactly one "cow" shape
        (target and draft caches share the treedef, hence the trace)."""
        if self._cow_fn is None:
            def copy(cache, src, dst):
                self.traces["cow"] = self.traces.get("cow", 0) + 1
                out = dict(cache)
                for name in ("k", "v", "k_scale", "v_scale"):
                    if name in cache:
                        out[name] = cache[name].at[:, dst].set(
                            cache[name][:, src])
                return out
            self._cow_fn = jax.jit(_named(copy, "serve_cow"),
                                   donate_argnums=(0,))
        return self._cow_fn

    def _pick_next(self) -> Optional[Request]:
        """The admission pick. "fcfs": the queue head. "priority": among
        requests whose tenant stays under `tenant_token_budget`, the highest
        priority wins; ties go to the tenant with the smallest weighted fair
        share (tokens served / weight), then to arrival order."""
        if not self.queue:
            return None
        if self.ecfg.scheduler == "fcfs":
            return self.queue[0]
        budget = self.ecfg.tenant_token_budget
        weights = self.ecfg.tenant_weights or {}

        def admissible(r: Request) -> bool:
            if budget is None:
                return True
            need = len(r.resume_feed()) + r.max_new_tokens - len(r.out_tokens)
            return self._tenant_inflight.get(r.tenant, 0) + need <= budget

        eligible = [r for r in self.queue if admissible(r)]
        if not eligible:
            return None
        return min(eligible, key=lambda r: (
            -r.priority,
            self._tenant_served.get(r.tenant, 0) / weights.get(r.tenant, 1.0),
            r.rid))

    def _tenant_acquire(self, r: Request) -> None:
        r.inflight_tokens = len(r.feed) + r.max_new_tokens - len(r.out_tokens)
        self._tenant_inflight[r.tenant] = (
            self._tenant_inflight.get(r.tenant, 0) + r.inflight_tokens)

    def _tenant_release(self, r: Request) -> None:
        self._tenant_inflight[r.tenant] = (
            self._tenant_inflight.get(r.tenant, 0) - r.inflight_tokens)
        r.inflight_tokens = 0

    def _emit(self, r: Request, tok: int) -> None:
        """Append one generated token: bookkeeping + streaming callback."""
        r.out_tokens.append(tok)
        self._count("tokens_emitted", 1)
        self._tenant_served[r.tenant] = (
            self._tenant_served.get(r.tenant, 0) + 1)
        if r.on_token is not None:
            r.on_token(r, tok)

    def _ensure_blocks(self, r: Request, tokens_needed: int) -> bool:
        """Grow `r`'s block table to cover `tokens_needed` cached tokens.
        On pool exhaustion, evict the youngest other running request
        (recompute preemption) and retry; False if `r` itself was evicted or
        still cannot be served this step."""
        while True:
            need = -(-tokens_needed // self.ecfg.block_size) - len(r.blocks)
            if need <= 0:
                return True
            got = self.alloc.alloc(need)
            if got is not None:
                self._count_fresh(got)
                self.block_tables[r.slot, len(r.blocks):len(r.blocks) + len(got)] = got
                r.blocks.extend(got)
                continue
            victim = self._youngest_running(exclude=r)
            if victim is None:
                return False           # nothing to evict; r waits this step
            self._evict(victim)
            if victim is r:            # cannot happen (excluded), but be safe
                return False

    def _count_fresh(self, got: List[int]) -> List[int]:
        self._count("fresh_block_grants", len(got))
        return got

    def _youngest_running(self, exclude: Request) -> Optional[Request]:
        running = [r for r in self.slots
                   if r is not None and r is not exclude]
        return max(running, key=lambda r: r.rid) if running else None

    def _evict(self, r: Request) -> None:
        """Recompute preemption: return `r` to the FRONT of the queue with its
        blocks freed; on re-admission it re-prefills prompt + generated."""
        logger.info(f"engine: preempting request {r.rid} "
                    f"({len(r.out_tokens)}/{r.max_new_tokens} tokens done)")
        s = r.slot
        self.alloc.free(r.blocks)      # refcounted: sharers keep theirs
        self._tenant_release(r)
        r.blocks, r.slot, r.fed, r.feed = [], None, 0, None
        r.hash_chain = []
        r.state, r.preemptions = QUEUED, r.preemptions + 1
        self._count("preempted", 1)
        self.slots[s] = None
        self.lengths[s] = 0
        self.block_tables[s] = 0
        self.queue.appendleft(r)

    def _finish(self, r: Request) -> None:
        s = r.slot
        self.alloc.free(r.blocks)      # hash-indexed blocks stay cached
        self._tenant_release(r)
        r.blocks, r.slot, r.feed = [], None, None
        r.state, r.finish_t = FINISHED, self.clock()
        self.slots[s] = None
        self.lengths[s] = 0
        self.block_tables[s] = 0
        self.finished.append(r)


# ---------------------------------------------------------------------------
# int8 KV cache: smoothing calibration + capacity accounting (DESIGN.md §9)
# ---------------------------------------------------------------------------

def calibrate_kv_smooth(model: Model, params, *, n_tokens: int = 64,
                        batch: int = 4, seed: int = 0):
    """Per-(layer, kv-head, channel) smoothing vectors for the int8 paged KV
    cache, picked from the paper's Eq. 9 candidate family
    (core/smoothing.py candidate_vectors: identity, scalar strengths,
    SmoothQuant-style alpha vectors) — the same calibration machinery that
    arms the fused GEMM's activation quantization, pointed at K/V instead.
    Candidates are scored under the DEPLOYMENT quantizer — per-(token,
    kv-head) absmax int8, `models/layers.py quantize_kv` — not Eq. 9's
    per-tensor scale, so the winner is the winner at serving time (identity
    is in the family, so calibration never hurts).

    A short prefill of random tokens through the PLAIN decode path captures
    every layer's K and V (the (L, B, S, KV, D) cache is the capture — no
    instrumentation). Returns (k_smooth, v_smooth), both (L, KV, D) float32 —
    pass as `ServingEngine(..., kv_smooth=...)`."""
    from repro.core.smoothing import candidate_vectors
    cfg = model.cfg
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (batch, n_tokens)),
                         jnp.int32)
    cache = model.init_cache(batch, n_tokens)
    _, cache = model.decode(
        params, cache, {"tokens": tokens, "pos": jnp.asarray(0, jnp.int32)})

    def roundtrip_mse(x: np.ndarray, s: np.ndarray) -> float:
        xs = x / s                                     # (n_tokens, D)
        scale = np.maximum(np.abs(xs).max(axis=1, keepdims=True), 1e-6) / 127.0
        q = np.clip(np.round(xs / scale), -127, 127)
        return float(np.mean((q * scale * s - x) ** 2))

    def smooth_of(key: str) -> jnp.ndarray:
        kv = np.asarray(cache[key], np.float32)        # (L, B, S, KV, D)
        if cache[key].dtype == jnp.int8:               # int8 plain cache
            kv = kv * np.asarray(cache[key + "_scale"], np.float32)[..., None]
        n_l, _, _, n_kv, d = kv.shape
        out = np.ones((n_l, n_kv, d), np.float32)
        for li in range(n_l):
            for h in range(n_kv):
                x = kv[li, :, :, h].reshape(-1, d)
                cands = candidate_vectors(np.abs(x).max(axis=0))
                out[li, h] = min(
                    (s for _, s in cands), key=lambda s: roundtrip_mse(x, s))
        return jnp.asarray(out)

    return smooth_of("k"), smooth_of("v")


def paged_kv_bytes_per_block(cfg, block_size: int, kv_dtype: str) -> int:
    """HBM bytes ONE physical block costs across all layers: the k + v pools,
    plus the two scale pools for int8. The (L, KV, D) smoothing vectors are
    per engine, not per block, and are excluded."""
    elems = cfg.n_layers * block_size * cfg.n_kv_heads * cfg.hd
    if kv_dtype == "int8":
        scales = cfg.n_layers * block_size * cfg.n_kv_heads * 4
        return 2 * (elems + scales)
    return 2 * elems * jnp.dtype(cfg.jnp_dtype).itemsize


def kv_capacity_report(cfg, ecfg: EngineConfig,
                       tokens_per_request: int) -> Dict[str, Any]:
    """The admission arithmetic behind BENCH_serving.json's kv-dtype axis:
    at a FIXED pool byte budget (what this geometry's float pool costs), how
    many blocks each kv dtype buys and how many requests of
    `tokens_per_request` tokens (prompt + generation + speculative headroom)
    are admissible concurrently."""
    budget = ecfg.num_blocks * paged_kv_bytes_per_block(
        cfg, ecfg.block_size, "float")
    bpr = -(-tokens_per_request // ecfg.block_size)
    out: Dict[str, Any] = {"pool_bytes_budget": budget,
                           "tokens_per_request": tokens_per_request}
    for dt in ("float", "int8"):
        bb = paged_kv_bytes_per_block(cfg, ecfg.block_size, dt)
        blocks = budget // bb
        out[dt] = {"bytes_per_block": bb, "blocks_in_budget": int(blocks),
                   "blocks_per_request": bpr,
                   "max_admissible_slots": int(blocks // bpr)}
    out["slots_ratio_int8_vs_float"] = round(
        out["int8"]["max_admissible_slots"]
        / max(out["float"]["max_admissible_slots"], 1), 2)
    return out


# ---------------------------------------------------------------------------
# Convenience constructor shared by the CLI, benchmarks and examples
# ---------------------------------------------------------------------------

def build_engine(arch: str, *, use_reduced: bool = True, lcd: bool = False,
                 target_centroids: int = 8, ecfg: Optional[EngineConfig] = None,
                 seed: int = 0, params=None, draft_params=None,
                 kv_smooth=None, mesh=None, fused_projections: bool = True):
    """(engine, params): model + (optionally LCD-compressed) params wrapped in
    a ready ServingEngine.

    With `ecfg.speculative_k > 0` and no `draft_params`, the 2-bit self-draft
    is built here by re-clustering the target's weights
    (core/clustered_params.py make_draft_params — genuinely 2-bit-packed, so
    the draft streams half the int4 layout's weight bytes). With
    `ecfg.kv_dtype == "int8"` and no `kv_smooth`, the cache smoothing vectors
    are calibrated here (calibrate_kv_smooth). `ecfg.weight_bits` /
    `ecfg.bits_budget` set the LCD packing policy (DESIGN.md §10); the
    resulting CompressReports land on the engine as `compress_report` /
    `draft_report` so a deployment stays inspectable
    (launch/serve.py --describe).

    Mesh selection (DESIGN.md §14): pass `mesh=` to serve on an explicit
    mesh; otherwise `ecfg.data_parallel` / `ecfg.model_parallel` pin the
    layout (the missing factor is derived from the visible device count, a
    non-factoring request raises eagerly), and with neither, multi-device
    hosts get the hlo_cost layout search (`distributed/layout.choose_layout`,
    report attached as `engine.layout_report`) while 1-device hosts take the
    trivial mesh."""
    ecfg = EngineConfig() if ecfg is None else ecfg
    if ecfg.arch is None:
        # bind the config to the arch so capability-dependent knobs fail
        # eagerly with the capability named (DESIGN.md §13)
        ecfg = dataclasses.replace(ecfg, arch=arch)
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg, dtype="float32")
    if cfg.fused_projections != fused_projections:
        cfg = dataclasses.replace(cfg, fused_projections=fused_projections)
    model = get_model(cfg)
    # params are built/compressed/calibrated on a provisional host mesh; the
    # engine commits them to the serving mesh at init (_place_sharded)
    build_mesh = mesh if mesh is not None else make_host_mesh()
    compress_report = draft_report = None
    with use_rules(build_mesh, fsdp=False):
        if params is None:
            params = model.init(jax.random.key(seed))
        if lcd and not any(is_clustered(l) for l in jax.tree_util.tree_leaves(
                params, is_leaf=is_clustered)):
            kcap = 1 << ecfg.weight_bits
            params, compress_report = compress_model(
                params, target_centroids=min(target_centroids, kcap),
                nbits=ecfg.weight_bits, bits_budget=ecfg.bits_budget)
            logger.info("LCD: " + compress_report.summary())
        if ecfg.speculative_k and draft_params is None:
            from repro.core.clustered_params import make_draft_params
            draft_params, draft_report = make_draft_params(
                params, draft_centroids=ecfg.draft_centroids)
            logger.info("LCD draft: " + draft_report.summary())
        resolved_kv = ecfg.kv_dtype or (
            "int8" if cfg.kv_cache_dtype == "int8" else "float")
        if (resolved_kv == "int8" and kv_smooth is None
                and model.supports(CAP_INT8_KV)):
            kv_smooth = calibrate_kv_smooth(model, params, seed=seed)
            logger.info("int8 KV cache: smoothing calibrated "
                        "(Eq. 9 candidate search per layer x kv-head)")
    layout_report = None
    if mesh is None:
        n = len(jax.devices())
        dp, mp = ecfg.data_parallel, ecfg.model_parallel
        if dp is not None or mp is not None:
            # derive the unpinned factor from the visible device count
            if dp is None:
                dp = n // mp if mp and n % mp == 0 else 0
            if mp is None:
                mp = n // dp if dp and n % dp == 0 else 0
            if dp < 1 or mp < 1 or dp * mp != n:
                raise ValueError(
                    f"build_engine: data_parallel x model_parallel must "
                    f"factor the {n} visible device(s); got "
                    f"{ecfg.data_parallel} x {ecfg.model_parallel}")
            mesh = auto_mesh((dp, mp), ("data", "model"))
        elif n > 1:
            from repro.distributed.layout import choose_layout
            mesh, layout_report = choose_layout(model, params, ecfg)
            logger.info("mesh layout: chose %s over %d device(s)",
                        layout_report["chosen"], n)
        else:
            mesh = build_mesh
    engine = ServingEngine(model, params, ecfg, mesh=mesh,
                           draft_params=draft_params, kv_smooth=kv_smooth)
    engine.compress_report = compress_report
    engine.draft_report = draft_report
    engine.layout_report = layout_report
    return engine, params
