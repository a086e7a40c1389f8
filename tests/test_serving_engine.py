"""Continuous-batching engine tests (DESIGN.md §5): block allocator
invariants, slot reuse with block free/realloc, bit-for-bit parity between
multi-request and single-request decoding, the bounded-trace contract, and
the LCD fused path through the engine (Pallas interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import compress_model
from repro.kernels.ops import lut_serving
from repro.launch.engine import BlockAllocator, EngineConfig, ServingEngine
from repro.models.config import ModelConfig
from repro.models.registry import get_model


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(arch_id="tiny-engine", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab=256, head_dim=16, dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _prompt(seed, n, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _solo_tokens(model, params, prompt, gen, ecfg):
    """Single-request run through a FRESH engine with the same geometry —
    the per-request reference the engine's outputs must match exactly."""
    eng = ServingEngine(model, params, ecfg)
    r = eng.submit(prompt, gen)
    eng.run()
    return list(r.out_tokens)


class TestEngineConfigDefault:
    def test_default_config_constructed_per_engine(self, tiny):
        """Hardening: `ecfg: EngineConfig = EngineConfig()` in the signature
        evaluated ONCE at import, sharing one instance across every engine
        built without a config (inert while EngineConfig is frozen, a
        footgun the moment it grows a mutable field); the default is now
        constructed per engine inside __init__."""
        cfg, model, params = tiny
        e1 = ServingEngine(model, params)
        e2 = ServingEngine(model, params)
        assert e1.ecfg == EngineConfig()
        assert e1.ecfg is not e2.ecfg


class TestEngineConfigValidation:
    """Eager __post_init__ validation: a bad knob fails at CONFIG
    construction with the allowed values spelled out, not deep inside cache
    init (kv_dtype used to surface as an engine-time assert) or the first
    compress call (the bits policy)."""

    def test_kv_dtype_validated_with_allowed_values(self):
        with pytest.raises(ValueError) as ei:
            EngineConfig(kv_dtype="int4")
        msg = str(ei.value)
        assert "kv_dtype" in msg and "int8" in msg and "float" in msg

    def test_valid_kv_dtypes_accepted(self):
        for dt in (None, "float", "int8"):
            assert EngineConfig(kv_dtype=dt).kv_dtype == dt

    def test_weight_bits_validated(self):
        with pytest.raises(ValueError, match=r"weight_bits.*\(2, 3, 4\)"):
            EngineConfig(weight_bits=5)
        assert EngineConfig(weight_bits=2).weight_bits == 2

    def test_bits_budget_validated(self):
        with pytest.raises(ValueError, match="bits_budget"):
            EngineConfig(bits_budget=1.0)
        with pytest.raises(ValueError, match="bits_budget"):
            EngineConfig(bits_budget=7.5)
        assert EngineConfig(bits_budget=2.5).bits_budget == 2.5

    def test_geometry_and_speculation_validated(self):
        with pytest.raises(ValueError, match="num_blocks"):
            EngineConfig(num_blocks=4, max_blocks_per_slot=8)
        with pytest.raises(ValueError, match="speculative_k"):
            EngineConfig(speculative_k=-1)
        with pytest.raises(ValueError, match="draft_centroids"):
            EngineConfig(draft_centroids=32)


class TestBlockAllocator:
    def test_all_or_nothing_and_reuse(self):
        a = BlockAllocator(4)
        got = a.alloc(3)
        assert sorted(got) == [0, 1, 2] and a.num_free == 1
        assert a.alloc(2) is None and a.num_free == 1   # no partial grant
        a.free([1])
        assert sorted(a.alloc(2)) == [1, 3]             # freed block reused
        assert a.num_free == 0

    def test_double_free_rejected(self):
        """Hardening (PR 7): freeing a free block is a ValueError naming the
        block id, not a bare assert (python -O-proof; message pinned in
        tests/test_block_allocator.py alongside the rest of the surface)."""
        a = BlockAllocator(2)
        blocks = a.alloc(1)
        a.free(blocks)
        with pytest.raises(ValueError, match=r"block 0 is not allocated"):
            a.free(blocks)


class TestSlotAndBlockReuse:
    def test_finishing_request_frees_blocks_for_queued_one(self, tiny):
        """The paged cache's reason to exist: with a pool too small for all
        three requests at once, the queued request must wait for blocks, be
        granted physical blocks the short request freed, and its tokens must
        still equal a single-request run of the same prompt bit-for-bit."""
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=3, block_size=4, num_blocks=6,
                            max_blocks_per_slot=4, prefill_chunk=16)
        eng = ServingEngine(model, params, ecfg)
        short = eng.submit(_prompt(1, 6), 2)      # 8 tokens  = 2 blocks
        long1 = eng.submit(_prompt(2, 8), 8)      # 16 tokens -> 4 blocks
        queued = eng.submit(_prompt(3, 9), 7)     # needs 3 blocks up front

        eng.step()
        short_blocks = set(short.blocks)
        assert short_blocks and long1.blocks
        # a slot is free, but the POOL can't cover the queued prompt yet
        assert queued.slot is None and queued.state == "queued"

        while short.state != "finished":
            eng.step()
        assert queued.state == "queued"           # still blocked on blocks

        while queued.slot is None and eng.busy:
            eng.step()
        # the queued request was served out of physical blocks the short
        # request returned to the free list
        assert set(queued.blocks) & short_blocks

        eng.run()
        assert queued.state == "finished"
        # every request's tokens match its single-request run exactly
        for r, (s, n, g) in ((short, (1, 6, 2)), (long1, (2, 8, 8)),
                             (queued, (3, 9, 7))):
            assert r.out_tokens == _solo_tokens(model, params, _prompt(s, n),
                                                g, ecfg), r.rid
        assert eng.alloc.num_free == ecfg.num_blocks

    def test_slot_reuse_after_finish(self, tiny):
        """With ONE slot, the second request runs only after the first frees
        it, in the same physical blocks (free-list reuse, no compaction)."""
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=1, block_size=4, num_blocks=2,
                            max_blocks_per_slot=2, prefill_chunk=8)
        eng = ServingEngine(model, params, ecfg)
        a = eng.submit(_prompt(4, 4), 3)
        b = eng.submit(_prompt(5, 5), 3)
        eng.step()
        a_blocks = set(a.blocks)
        assert b.slot is None
        while a.state != "finished":
            eng.step()
        while b.slot is None and eng.busy:
            eng.step()
        assert b.slot == 0                         # the slot a vacated
        assert set(b.blocks) <= a_blocks | {0, 1}  # same 2-block pool
        eng.run()
        assert b.state == "finished"
        assert eng.alloc.num_free == ecfg.num_blocks
        assert b.out_tokens == _solo_tokens(model, params, _prompt(5, 5), 3,
                                            ecfg)


class TestMultiRequestParity:
    def test_staggered_requests_match_single_request_bitwise(self, tiny):
        """>= 4 requests arriving mid-flight, different prompt lengths: every
        request's greedy tokens equal its single-request run EXACTLY. Per-slot
        math is independent (masks, not shapes), so sharing the traced step
        with other requests must not perturb anyone's output."""
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=3, block_size=4, num_blocks=24,
                            max_blocks_per_slot=6, prefill_chunk=8)
        eng = ServingEngine(model, params, ecfg)
        specs = [(10, 5, 6), (11, 9, 5), (12, 3, 7), (13, 12, 4), (14, 7, 6)]
        reqs = []
        pending = list(specs)
        while pending or eng.busy:
            if pending and eng.steps % 2 == 0:   # staggered arrivals
                s, n, g = pending.pop(0)
                reqs.append((eng.submit(_prompt(s, n), g), s, n, g))
            if eng.busy:
                eng.step()
        eng.assert_bounded_traces()
        for r, s, n, g in reqs:
            assert r.state == "finished"
            solo = _solo_tokens(model, params, _prompt(s, n), g, ecfg)
            assert r.out_tokens == solo, (r.rid, r.out_tokens, solo)

    def test_parity_with_static_scan_engine(self, tiny):
        """The paged engine and PR 1's static-batch scan path produce the
        same greedy tokens for the same prompt (the two serving paths agree,
        so the docs can present them as one system)."""
        from repro.launch.engine import build_decode_fns
        cfg, model, params = tiny
        p_len, gen = 6, 5
        prompt = _prompt(21, p_len)

        prefill, decode, _ = build_decode_fns(model, cfg, gen)
        cache = model.init_cache(1, p_len + gen)
        tok, cache = prefill(params, cache, jnp.asarray(prompt[None]))
        static_out, _ = decode(params, cache, tok)
        static_toks = [int(x) for x in np.asarray(static_out)[0]]

        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=8,
                            max_blocks_per_slot=4, prefill_chunk=8)
        paged_toks = _solo_tokens(model, params, prompt, gen, ecfg)
        assert paged_toks == static_toks


class TestBoundedTraces:
    def test_two_step_shapes_total(self, tiny):
        """However requests arrive, the engine compiles at most TWO step
        computations — width prefill_chunk and width 1 — each once."""
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=16,
                            max_blocks_per_slot=4, prefill_chunk=4)
        eng = ServingEngine(model, params, ecfg)
        eng.submit(_prompt(31, 6), 6)
        eng.run()                       # prefill chunks then pure decode
        eng.submit(_prompt(32, 5), 4)   # second request: NO new traces
        eng.submit(_prompt(33, 3), 4)
        eng.run()
        eng.assert_bounded_traces()
        assert set(eng.traces) == {1, ecfg.prefill_chunk}
        assert sum(eng.traces.values()) == 2

    def test_decode_rows_never_ride_the_prefill_width(self, tiny):
        """An iteration with one slot decoding and one prefilling runs the
        prefill chunk at width prefill_chunk and the decode token at width
        1, as two steps: a decode token is computed by the same program
        whether or not a neighbour is prefilling (the two widths round
        differently on TPU, DESIGN.md §5)."""
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=16,
                            max_blocks_per_slot=4, prefill_chunk=4)
        eng = ServingEngine(model, params, ecfg)
        a = eng.submit(_prompt(41, 3), 6)
        eng.step()                      # a prefills (one chunk) and decodes
        assert not a.prefilling
        calls = []
        run = eng._step_width
        eng._step_width = lambda t, rids: (
            calls.append((t, None if rids is None else set(rids)))
            or run(t, rids))
        b = eng.submit(_prompt(42, 9), 2)
        steps = eng.steps
        eng.step()
        assert calls == [(ecfg.prefill_chunk, {b.rid}), (1, {a.rid})]
        assert eng.steps == steps + 1
        eng.run()
        assert a.out_tokens == _solo_tokens(model, params, _prompt(41, 3), 6,
                                            ecfg)

    def test_retrace_is_detected(self, tiny):
        cfg, model, params = tiny
        eng = ServingEngine(model, params, EngineConfig())
        eng.traces = {1: 1, 7: 1}       # simulate an off-contract width
        with pytest.raises(AssertionError):
            eng.assert_bounded_traces()


class TestPreemption:
    def test_eviction_requeues_and_completes(self, tiny):
        """Pool pressure mid-decode: the youngest request is evicted
        (recompute preemption), re-prefills prompt + generated tokens, and
        still completes with its full token budget."""
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=2, block_size=2, num_blocks=8,
                            max_blocks_per_slot=8, prefill_chunk=4)
        eng = ServingEngine(model, params, ecfg)
        r1 = eng.submit(_prompt(41, 4), 10)    # grows to 14 tokens = 7 blocks
        r2 = eng.submit(_prompt(42, 4), 10)    # both cannot fit (14 > 8 blocks)
        eng.run()
        eng.assert_bounded_traces()
        assert r1.state == r2.state == "finished"
        assert len(r1.out_tokens) == len(r2.out_tokens) == 10
        assert r1.preemptions + r2.preemptions >= 1
        assert eng.alloc.num_free == ecfg.num_blocks   # everything returned


class TestLCDThroughEngine:
    def test_fused_interpret_serving_matches_ref(self, tiny):
        """Two staggered requests through the LCD fused kernels (interpret
        mode) == the gather-contraction engine run, token for token — the
        continuous engine and the fused GEMM compose."""
        cfg, model, params = tiny
        cparams, _ = compress_model(params, target_centroids=8)
        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=8,
                            max_blocks_per_slot=4, prefill_chunk=8)

        def run_two():
            eng = ServingEngine(model, cparams, ecfg)
            a = eng.submit(_prompt(51, 6), 3)
            eng.step()                      # a mid-prefill when b arrives
            b = eng.submit(_prompt(52, 4), 3)
            eng.run()
            eng.assert_bounded_traces()
            return a.out_tokens, b.out_tokens

        ref = run_two()
        with lut_serving("interpret"):
            fused = run_two()
        assert ref == fused


# ---------------------------------------------------------------------------
# PR 7: prefix caching + production scheduler (DESIGN.md §12)
# ---------------------------------------------------------------------------

_PFX = _prompt(99, 8)                     # the shared "system prompt"


def _with_prefix(seed, extra):
    if extra == 0:
        return _PFX.copy()
    return np.concatenate([_PFX, _prompt(seed, extra)])


class TestPrefixCacheParity:
    """The hard contract: prefix-cache-on output is bit-equal to cache-off
    for EVERY request within a kv dtype. Sharing and COW are pure
    bookkeeping — the traced step never learns caching exists."""

    def _run(self, model, params, ecfg, specs, stagger=2):
        eng = ServingEngine(model, params, ecfg)
        reqs, pending = [], list(specs)
        while pending or eng.busy:
            if pending and eng.steps % stagger == 0:
                s, extra, g = pending.pop(0)
                reqs.append(eng.submit(_with_prefix(s, extra), g))
            if eng.busy:
                eng.step()
            else:
                eng.steps += 1        # idle tick: let the next arrival land
        eng.assert_bounded_traces()
        return eng, reqs, [r.out_tokens for r in reqs]

    def test_staggered_shared_prefix_bit_equal(self, tiny):
        cfg, model, params = tiny
        base = dict(num_slots=3, block_size=4, num_blocks=32,
                    max_blocks_per_slot=8, prefill_chunk=8)
        specs = [(1, 3, 5), (2, 0, 5), (3, 6, 4), (4, 1, 5), (5, 0, 4)]
        _, _, off = self._run(model, params, EngineConfig(**base), specs)
        eng, _, on = self._run(model, params,
                               EngineConfig(**base, prefix_cache=True), specs)
        assert on == off                   # bit-equal, request for request
        rep = eng.prefix_cache_report()
        assert rep["cached_tokens"] > 0 and rep["block_reuse_rate"] > 0

    def test_block_aligned_resubmit_hits_cow(self, tiny):
        """Resubmitting an exactly block-aligned cached prompt re-feeds its
        last token into a SHARED tail block: the write must copy-on-write,
        and tokens still match the cache-off run."""
        cfg, model, params = tiny
        base = dict(num_slots=2, block_size=4, num_blocks=32,
                    max_blocks_per_slot=8, prefill_chunk=8)
        specs = [(1, 0, 4), (1, 0, 4)]     # identical 8-token (2-block) prompt
        _, _, off = self._run(model, params, EngineConfig(**base), specs,
                              stagger=50)  # sequential: second hits the index
        eng, _, on = self._run(model, params,
                               EngineConfig(**base, prefix_cache=True), specs,
                               stagger=50)
        assert on == off
        assert eng.counters["cow_copies"] >= 1
        assert "cow" in eng.traces         # COW compiled exactly once
        eng.assert_bounded_traces()

    def test_eviction_of_sharer_leaves_other_sharers_intact(self, tiny):
        """Pool pressure evicts a request holding SHARED blocks: refcounts
        keep the survivor's blocks alive, both requests complete, and both
        match the cache-off run bit-for-bit."""
        cfg, model, params = tiny
        base = dict(num_slots=2, block_size=2, num_blocks=8,
                    max_blocks_per_slot=8, prefill_chunk=4)
        specs = [(0, 0, 6), (0, 0, 6)]     # 8-token prompt grows to 7 blocks
        # (14 tokens each: two full requests need 14 of the 8 blocks, so the
        # younger sharer must be evicted mid-decode)
        _, _, off = self._run(model, params, EngineConfig(**base), specs)
        eng, reqs, on = self._run(model, params,
                                  EngineConfig(**base, prefix_cache=True),
                                  specs)
        assert on == off
        assert sum(r.preemptions for r in reqs) >= 1
        # every non-cached block returned; the hash index holds the rest
        assert eng.alloc.num_free + eng.alloc.num_cached == base["num_blocks"]

    def test_speculative_composes_with_prefix_cache(self, tiny):
        from repro.core.clustered_params import make_draft_params
        cfg, model, params = tiny
        draft, _ = make_draft_params(params, draft_centroids=4)
        base = dict(num_slots=2, block_size=4, num_blocks=32,
                    max_blocks_per_slot=8, prefill_chunk=8, speculative_k=2)
        specs = [(1, 0, 5), (2, 3, 5)]

        def run(ecfg):
            eng = ServingEngine(model, params, ecfg, draft_params=draft)
            out = []
            for s, extra, g in specs:
                r = eng.submit(_with_prefix(s, extra), g)
                eng.run()
                out.append(r.out_tokens)
            eng.assert_bounded_traces()
            return eng, out

        _, off = run(EngineConfig(**base))
        eng, on = run(EngineConfig(**base, prefix_cache=True))
        assert on == off
        assert eng.prefix_cache_report()["cached_tokens"] > 0

    def test_cache_salted_by_kv_dtype(self, tiny):
        """Same tokens under a different kv dtype hash to different index
        entries — an int8 pool must never serve a float request's blocks."""
        cfg, model, params = tiny
        e_f = ServingEngine(model, params, EngineConfig(prefix_cache=True))
        e_i = ServingEngine(model, params,
                            EngineConfig(prefix_cache=True, kv_dtype="int8"))
        assert e_f._prefix_salt != e_i._prefix_salt


class TestChunkedPrefill:
    def test_chunked_matches_whole_prefill(self, tiny):
        cfg, model, params = tiny
        base = dict(num_slots=2, block_size=4, num_blocks=16,
                    max_blocks_per_slot=8, prefill_chunk=4)
        p = _prompt(7, 20)
        whole = _solo_tokens(model, params, p, 6, EngineConfig(**base))
        chunked = _solo_tokens(model, params, p, 6,
                               EngineConfig(**base, chunked_prefill=True))
        assert chunked == whole

    def test_long_prompt_admitted_under_pool_pressure(self, tiny):
        """Chunked prefill admits with one chunk's worth of blocks instead
        of the whole prompt's — a long prompt starts while a hog still owns
        most of the pool, instead of stalling in the queue."""
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=8,
                            max_blocks_per_slot=8, prefill_chunk=4,
                            chunked_prefill=True)
        eng = ServingEngine(model, params, ecfg)
        eng.submit(_prompt(6, 8), 9)       # grows to 5 of the 8 blocks
        eng.step()
        late = eng.submit(_prompt(7, 20), 4)   # whole prompt would need 5
        eng.step()
        assert late.slot is not None       # admitted on chunk-sized grant
        eng.run()
        solo = _solo_tokens(model, params, _prompt(7, 20), 4,
                            EngineConfig(num_slots=2, block_size=4,
                                         num_blocks=8, max_blocks_per_slot=8,
                                         prefill_chunk=4))
        assert late.out_tokens == solo


class TestSchedulerAndStreaming:
    def test_priority_beats_arrival_order(self, tiny):
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=1, block_size=4, num_blocks=16,
                            max_blocks_per_slot=4, prefill_chunk=8,
                            scheduler="priority")
        eng = ServingEngine(model, params, ecfg)
        first = eng.submit(_prompt(1, 4), 3)
        eng.step()                          # occupies the only slot
        low = eng.submit(_prompt(2, 4), 3, priority=0)
        high = eng.submit(_prompt(3, 4), 3, priority=5)
        eng.run()
        assert high.finish_t < low.finish_t

    def test_tenant_budget_defers_admission(self, tiny):
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=32,
                            max_blocks_per_slot=8, prefill_chunk=8,
                            scheduler="priority", tenant_token_budget=12)
        eng = ServingEngine(model, params, ecfg)
        a = eng.submit(_prompt(1, 4), 6, tenant="t")   # 10 inflight tokens
        b = eng.submit(_prompt(2, 4), 6, tenant="t")   # would exceed 12
        c = eng.submit(_prompt(3, 4), 6, tenant="u")   # other tenant: fine
        eng.step()
        assert a.slot is not None and c.slot is not None
        assert b.slot is None               # over t's budget, must wait
        eng.run()
        assert b.state == "finished"        # admitted once a released tokens

    def test_streaming_callback_sees_every_token_in_order(self, tiny):
        cfg, model, params = tiny
        eng = ServingEngine(model, params, EngineConfig())
        seen = []
        r = eng.submit(_prompt(8, 5), 6,
                       on_token=lambda req, tok: seen.append((req.rid, tok)))
        eng.run()
        assert seen == [(r.rid, t) for t in r.out_tokens]
        assert len(seen) == 6

    def test_cancel_queued_and_running(self, tiny):
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=1, block_size=4, num_blocks=16,
                            max_blocks_per_slot=4, prefill_chunk=8)
        eng = ServingEngine(model, params, ecfg)
        running = eng.submit(_prompt(1, 4), 8)
        queued = eng.submit(_prompt(2, 4), 8)
        eng.step()
        assert eng.cancel(queued) and queued.state == "cancelled"
        assert eng.cancel(running) and running.state == "cancelled"
        assert running.slot is None
        assert eng.alloc.num_free == ecfg.num_blocks   # blocks all returned
        assert not eng.cancel(running)      # idempotent: already terminal
        eng.run()
        assert not eng.busy


class TestSpansAndCounters:
    """The scheduler's own instrumentation (DESIGN.md §5 "Spans and
    counters"): profiler spans per phase, cumulative counters, the
    admission stamp and the step programs' stable names."""

    PHASES = ["engine.admit", "engine.reserve", "engine.pack",
              "engine.dispatch", "engine.wait", "engine.harvest"]

    def test_phase_spans_nest_in_step_in_order(self, tiny, tmp_path):
        import glob
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=16,
                            max_blocks_per_slot=4, prefill_chunk=4)
        eng = ServingEngine(model, params, ecfg)
        eng.submit(_prompt(51, 3), 4)
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(3):          # prefill (width 4), then 2 decodes
                eng.step()
        path = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")[0]
        pd = jax.profiler.ProfileData.from_file(path)
        spans = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                         dict(ev.stats))
                        for plane in pd.planes
                        if plane.name.startswith("/host:")
                        for line in plane.lines for ev in line.events
                        if ev.name.startswith("engine.")),
                       key=lambda e: (e[0], -e[1]))
        steps = [e for e in spans if e[2] == "engine.step"]
        assert [e[3]["step_num"] for e in steps] == [0, 1, 2]
        for (s0, s1, _, _), width in zip(steps, (4, 1, 1)):
            inner = [e for e in spans if e[2] != "engine.step"
                     and s0 <= e[0] and e[1] <= s1]
            assert [e[2] for e in inner] == self.PHASES
            assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))
            assert [e[3]["width"] for e in inner
                    if e[2] in ("engine.dispatch", "engine.wait")] == [
                        width, width]
        assert len(spans) == 3 * (1 + len(self.PHASES))

    def test_counters_count_rows_tokens_and_host_time(self, tiny):
        import itertools
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=16,
                            max_blocks_per_slot=4, prefill_chunk=4)
        ticks = itertools.count()
        eng = ServingEngine(model, params, ecfg,
                            clock=lambda: float(next(ticks)))
        reqs = [eng.submit(_prompt(61, 6), 3), eng.submit(_prompt(62, 2), 5)]
        inside = 0.0
        while eng.busy:
            c0 = eng.clock()
            eng.step()
            inside += eng.clock() - c0 - 2   # minus the two reads around
        c = eng.counters
        assert c["tokens_emitted"] == sum(len(r.out_tokens) for r in reqs)
        assert c["admitted"] == 2 and c["preempted"] == 0
        assert c["iterations"] == eng.steps
        # prompt tokens through the wide program, one token per decode
        assert c["rows_fed.w4"] == 6 + 2
        assert c["rows_fed.w1"] == sum(len(r.out_tokens) - 1 for r in reqs)
        for w in (1, 4):
            assert c[f"rows.w{w}"] == c[f"calls.w{w}"] * ecfg.num_slots * w
        host = {k: v for k, v in c.items() if k.startswith("host_s.")}
        assert set(host) == {"host_s.step"} | {
            f"host_s.{p.split('.')[1]}" for p in self.PHASES}
        # self times: they partition the time inside step()
        assert min(host.values()) >= 0 and sum(host.values()) == inside
        summary = eng.counters_summary()
        assert summary["pad_row_share"]["w4"] == round(
            1 - 8 / c["rows.w4"], 4)
        assert summary["host_ms_per_step"] == round(
            1e3 * (inside - host["host_s.wait"]) / eng.steps, 3)
        assert summary["calls"] == {"w1": c["calls.w1"],
                                    "w4": c["calls.w4"]}
        assert (summary["iterations"], summary["tokens_emitted"],
                summary["admitted"], summary["preempted"]) == (
                    eng.steps, c["tokens_emitted"], 2, 0)
        assert eng.prefix_cache_report() == {
            "cached_tokens": 0, "shared_block_grants": 0,
            "fresh_block_grants": c["fresh_block_grants"], "cow_copies": 0,
            "registered_blocks": 0, "block_reuse_rate": 0.0,
            "cached_blocks_now": 0}

    def test_admit_t_is_the_first_admission(self, tiny):
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=2, block_size=2, num_blocks=8,
                            max_blocks_per_slot=8, prefill_chunk=4)
        eng = ServingEngine(model, params, ecfg)
        r1 = eng.submit(_prompt(41, 4), 10)    # the preemption test's pair:
        r2 = eng.submit(_prompt(42, 4), 10)    # both cannot fit the pool
        assert r1.admit_t is None
        eng.step()
        first = {r.rid: r.admit_t for r in (r1, r2)}
        eng.run()
        preempted = [r for r in (r1, r2) if r.preemptions]
        assert preempted
        for r in (r1, r2):
            assert r.admit_t == first[r.rid]
            assert r.submit_t <= r.admit_t <= r.first_token_t
        n = sum(r.preemptions for r in (r1, r2))
        assert eng.counters["preempted"] == n
        assert eng.counters["admitted"] == 2 + n

    def test_step_programs_carry_stable_names(self, tiny):
        cfg, model, params = tiny
        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=16,
                            max_blocks_per_slot=4, prefill_chunk=8)
        eng = ServingEngine(model, params, ecfg)
        for t in (1, ecfg.prefill_chunk):
            text = eng._step_fn(t).lower(
                eng.params, eng.caches, jnp.zeros((2, t), jnp.int32),
                jnp.asarray(eng.lengths), jnp.zeros(2, jnp.int32),
                jnp.asarray(eng.block_tables)).as_text()
            assert f"module @jit_serve_step_w{t} " in text
