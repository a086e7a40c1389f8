"""Autotuner contract tests (DESIGN.md §11): key normalization, the
candidate grid's invariants (heuristic-first, MXU alignment, VMEM budget),
the persistent cache round-trip (a reloaded winner is served WITHOUT
re-measurement), corrupt/empty cache-file recovery, and the deterministic
interpret fallback — `pick_blocks` under the interpreter must be bit-for-bit
the seed's `_pick_blocks` heuristic, so CPU CI behaves as before the tuner
existed. Measurement is injected as counting fakes; no kernel runs here."""
import json
import os

import pytest

from repro.kernels import autotune, ops
from repro.kernels.autotune import (AutotuneCache, VMEM_BUDGET,
                                    candidate_blocks, flash_heuristic,
                                    heuristic_blocks, m_chunk,
                                    normalize_key, paged_heuristic,
                                    pick_blocks, pick_flash_blocks,
                                    pick_paged_pad, vmem_bytes)


@pytest.fixture
def cache(tmp_path):
    """A fresh cache on a throwaway path; restores the process cache after."""
    c = autotune.reset_cache(str(tmp_path / "autotune.json"))
    yield c
    autotune.reset_cache()


class TestKeyNormalization:
    def test_decode_gemvs_share_a_bucket(self):
        # every m in 1..8 pads to the same sublane block -> one cache entry
        keys = {normalize_key(m, 4096, 4096, 4, "lut_fused_gemv", "tpu v5")
                for m in range(1, 9)}
        assert len(keys) == 1
        assert "m8," in keys.pop()

    def test_matmul_rounds_to_lane_tiles(self):
        a = normalize_key(130, 4000, 4001, 4, "lut_f32", "cpu")
        b = normalize_key(256, 4096, 4096, 4, "lut_f32", "cpu")
        assert a == b == "lut_f32|cpu|m256,k4096,n4096|b4"

    def test_axes_are_disjoint(self):
        # backend, variant and nbits each split the key space
        base = dict(m=8, k=4096, n=4096)
        assert normalize_key(**base, nbits=4, variant="lut_fused",
                             backend="tpu") != \
            normalize_key(**base, nbits=4, variant="lut_fused", backend="cpu")
        assert normalize_key(**base, nbits=2, variant="lut_fused",
                             backend="tpu") != \
            normalize_key(**base, nbits=4, variant="lut_fused", backend="tpu")
        assert normalize_key(**base, nbits=4, variant="lut_int8",
                             backend="tpu") != \
            normalize_key(**base, nbits=4, variant="lut_fused", backend="tpu")

    def test_attention_geometry_is_exact(self):
        # flash/paged keys must NOT round: block validity depends on exact
        # divisibility of the sequence geometry
        assert normalize_key(384, 640, 64, 0, "flash", "tpu") == \
            "flash|tpu|m384,k640,n64|b0"


class TestCandidateGrid:
    def test_heuristic_is_first_candidate(self):
        for (m, k, n) in ((1, 4096, 4096), (128, 2048, 2048),
                          (512, 11008, 4096)):
            for nbits in (2, 3, 4):
                cands = candidate_blocks(m, k, n, nbits)
                assert cands[0] == heuristic_blocks(m, k, n)

    def test_grid_respects_vmem_budget_and_packing(self):
        for nbits in (2, 3, 4):
            for bm, bn, bk in candidate_blocks(256, 4096, 4096, nbits)[1:]:
                assert vmem_bytes(bm, bn, bk, nbits) <= VMEM_BUDGET
                assert (bk * nbits) % 8 == 0
                assert bm % 8 == 0 and bn % 128 == 0

    def test_narrower_packing_admits_deeper_bk(self):
        # a 2-bit tile is half the bytes of int4 -> the 2-bit grid can only
        # be a superset along bk
        deep4 = max(bk for _, _, bk in candidate_blocks(256, 8192, 4096, 4))
        deep2 = max(bk for _, _, bk in candidate_blocks(256, 8192, 4096, 2))
        assert deep2 >= deep4

    def test_gemv_grid_pins_bm(self):
        for bm, _, _ in candidate_blocks(3, 4096, 4096, 4, "lut_fused_gemv"):
            assert bm == 8

    def test_flash_heuristic_clamps_to_geometry(self):
        assert flash_heuristic(512, 1024) == (256, 512)
        assert flash_heuristic(64, 128) == (64, 128)


# the wide (prefill_chunk) step's LUT GEMM launches of both benchmark cells:
# 16 slots x 128 (qwen2-1.5b, 4-bit) and 32 slots x 64 (starcoder2-15b,
# 2-bit) rows; (k, projection widths, nbits)
WIDE_M = 2048
WIDE_LAUNCHES = {
    "qwen2.qkv": (1536, (2048, 256, 256), 4),
    "qwen2.gate_up": (1536, (8960, 8960), 4),
    "qwen2.wo": (2048, (1536,), 4),
    "qwen2.w_down": (8960, (1536,), 4),
    "starcoder2.qkv": (6144, (6144, 512, 512), 2),
    "starcoder2.wo": (6144, (6144,), 2),
    "starcoder2.w_up": (6144, (24576,), 2),
    "starcoder2.w_down": (24576, (6144,), 2),
}


class TestMChunk:
    @pytest.mark.parametrize("launch", sorted(WIDE_LAUNCHES))
    def test_cells_wide_launches_decode_once(self, launch):
        """Both cells' 2048-row launches fit one chunk: each weight tile is
        decoded once per launch, within the VMEM budget."""
        k, widths, nbits = WIDE_LAUNCHES[launch]
        bm, bn, bk = heuristic_blocks(WIDE_M, k, widths[0])
        rows = m_chunk(WIDE_M, bm, bn, bk, nbits, len(widths))
        assert rows == WIDE_M
        assert vmem_bytes(bm, bn, bk, nbits, len(widths),
                          rows=rows) <= VMEM_BUDGET

    @pytest.mark.parametrize("m", [4096, 8192, 16384])
    def test_chunk_shrinks_past_the_budget(self, m):
        bm, bn, bk = heuristic_blocks(m, 1536, 2048)
        rows = m_chunk(m, bm, bn, bk, 4, 3)
        assert bm <= rows < m and m % rows == 0 and rows % bm == 0
        assert vmem_bytes(bm, bn, bk, 4, 3, rows=rows) <= VMEM_BUDGET
        assert vmem_bytes(bm, bn, bk, 4, 3, rows=2 * rows) > VMEM_BUDGET

    def test_chunk_divides_m_and_never_drops_under_one_block(self):
        # 19 blocks (a prime count over the budget): only 1 or 19 divide
        assert m_chunk(19 * 128, 128, 256, 512) == 128
        # 24 blocks at a width whose budget holds 18: the largest divisor
        assert m_chunk(24 * 128, 128, 256, 512) == 12 * 128
        # one M block, and a block too wide for any chunk: bm itself
        assert m_chunk(128, 128, 256, 512) == 128
        assert m_chunk(1024, 128, 8192, 512) == 128

    def test_gemm_candidates_run_at_one_block(self):
        # every GEMM candidate fits at one M block, the least chunk
        for bm, bn, bk in candidate_blocks(2048, 6144, 24576, 2)[1:]:
            assert vmem_bytes(bm, bn, bk, 2, rows=bm) <= VMEM_BUDGET
            assert m_chunk(2048, bm, bn, bk, 2) >= bm


class TestInterpretFallback:
    def test_interpret_is_exactly_the_seed_heuristic(self, cache):
        # ops._pick_blocks is the seed heuristic (aliased); the tuner under
        # the interpreter must return exactly its choice for any geometry
        for (m, k, n) in ((1, 4096, 4096), (7, 4096, 11008),
                          (128, 2048, 2048), (513, 4000, 4001)):
            for variant in autotune.LUT_VARIANTS:
                assert pick_blocks(m, k, n, variant=variant,
                                   interpret=True) == ops._pick_blocks(m, k, n)

    def test_interpret_never_measures(self, cache):
        calls = []
        out = pick_blocks(8, 4096, 4096, interpret=True,
                          measure=lambda *b: calls.append(b) or 1.0)
        assert out == heuristic_blocks(8, 4096, 4096)
        assert calls == []

    def test_disabled_tuning_falls_back(self, cache, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE", "0")
        calls = []
        out = pick_blocks(8, 4096, 4096, interpret=False,
                          measure=lambda *b: calls.append(b) or 1.0)
        assert out == heuristic_blocks(8, 4096, 4096)
        assert calls == []

    def test_no_measure_fn_falls_back(self, cache):
        assert pick_blocks(8, 4096, 4096, interpret=False) == \
            heuristic_blocks(8, 4096, 4096)


class TestMeasuredTuning:
    def test_argmin_wins_and_heuristic_bounds_it(self, cache):
        # fake timer: deeper bk is faster -> winner must be the deepest
        # candidate, and never slower than the heuristic's fake time
        times = {}

        def measure(bm, bn, bk):
            times[(bm, bn, bk)] = 1.0 / bk
            return 1.0 / bk

        won = pick_blocks(8, 4096, 4096, interpret=False, measure=measure)
        assert won in times
        assert times[won] == min(times.values())
        assert times[won] <= times[heuristic_blocks(8, 4096, 4096)]

    def test_cache_hit_never_remeasures(self, cache):
        calls = []

        def measure(*b):
            calls.append(b)
            return 1.0

        first = pick_blocks(8, 4096, 4096, interpret=False, measure=measure)
        assert calls, "first sight must measure"
        n_first = len(calls)
        again = pick_blocks(8, 4096, 4096, interpret=False, measure=measure)
        assert again == first
        assert len(calls) == n_first, "cache hit re-measured"
        # the hit also beats the fallback when measurement is gone entirely
        assert pick_blocks(8, 4096, 4096, interpret=False) == first

    def test_rejecting_candidates_lose(self, cache):
        heur = heuristic_blocks(128, 4096, 4096)

        def measure(bm, bn, bk):
            if (bm, bn, bk) == heur:
                raise RuntimeError("backend rejected")
            return float(bk)

        won = pick_blocks(128, 4096, 4096, interpret=False, measure=measure)
        assert won != heur

    def test_all_candidates_failing_falls_back(self, cache):
        # a grid the backend refuses outright is a kernel fault: it raises
        # with the refusal chained, and nothing lands in the cache, rather
        # than silently serving the heuristic
        def measure(*b):
            raise RuntimeError("no backend")

        with pytest.raises(RuntimeError, match="candidates refused") as ei:
            pick_blocks(8, 4096, 4096, interpret=False, measure=measure)
        assert "no backend" in str(ei.value.__cause__)
        assert cache.entries == {}

    def test_flash_and_paged_share_the_contract(self, cache):
        # nothing measures the attention kernels: a cached winner on a
        # compiled backend, else the heuristic; the interpreter always gets
        # the heuristic
        assert pick_flash_blocks(512, 1024, 64, interpret=False) == \
            flash_heuristic(512, 1024)
        assert pick_paged_pad(4, 512, 64, interpret=False) == \
            paged_heuristic()[0]
        backend = autotune.jax.default_backend()
        cache.put(normalize_key(512, 1024, 64, 0, "flash", backend),
                  (128, 256), 1.0)
        cache.put(normalize_key(4, 512, 64, 8, "paged", backend), (256,),
                  1.0)
        assert pick_flash_blocks(512, 1024, 64, interpret=False) == (128, 256)
        assert pick_paged_pad(4, 512, 64, interpret=False) == 256
        assert pick_flash_blocks(512, 1024, 64, interpret=True) == \
            flash_heuristic(512, 1024)
        assert pick_paged_pad(4, 512, 64, interpret=True) == \
            paged_heuristic()[0]


class TestPersistentCache:
    def test_default_path_is_fixed_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert autotune.cache_path() == os.path.join(root, ".autotune.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "/elsewhere/at.json")
        assert autotune.cache_path() == "/elsewhere/at.json"

    def test_roundtrip_reload_hits_without_measuring(self, tmp_path):
        path = str(tmp_path / "autotune.json")
        c1 = autotune.reset_cache(path)
        won = pick_blocks(8, 4096, 4096, interpret=False,
                          measure=lambda bm, bn, bk: 1.0 / bk, cache=c1)
        assert os.path.exists(path)
        # a NEW process (fresh cache object off the same file) must serve the
        # winner from disk with measurement entirely unavailable
        c2 = AutotuneCache(path)
        calls = []
        assert pick_blocks(8, 4096, 4096, interpret=False,
                           measure=lambda *b: calls.append(b) or 99.0,
                           cache=c2) == won
        assert calls == []
        autotune.reset_cache()

    def test_corrupt_file_recovers_empty(self, tmp_path):
        path = tmp_path / "autotune.json"
        for payload in ("", "{not json", '{"version": 99, "entries": {}}',
                        '[1, 2, 3]',
                        '{"version": 1, "entries": {"k": {"blocks": "bad"}}}'):
            path.write_text(payload)
            c = AutotuneCache(str(path))
            assert c.entries == {}
            # and the empty cache still resolves deterministically
            assert pick_blocks(8, 4096, 4096, interpret=True, cache=c) == \
                heuristic_blocks(8, 4096, 4096)

    def test_schema_bump_discards_old_entries(self, tmp_path):
        """Winners stored under the per-M-block GEMM layout (schema v2) are
        not served: the file reads as empty and the heuristic resolves."""
        assert autotune.CACHE_SCHEMA_VERSION > 2
        path = tmp_path / "autotune.json"
        key = normalize_key(2048, 1536, 1536, 4, "lut_fused", "tpu")
        path.write_text(json.dumps({"version": 2, "entries": {
            key: {"blocks": [256, 512, 1024], "us": 1.0,
                  "source": "measured"}}}))
        c = AutotuneCache(str(path))
        assert c.entries == {}
        assert c.get(key) is None
        c.put(key, (128, 256, 512), 1.0)
        assert AutotuneCache(str(path)).get(key) == (128, 256, 512)

    def test_save_is_versioned_and_sorted(self, tmp_path):
        path = str(tmp_path / "sub" / "autotune.json")
        c = AutotuneCache(path)
        c.put("b|key", (8, 256, 512), 12.3456)
        c.put("a|key", (128, 256, 512), 1.0)
        doc = json.load(open(path))
        assert doc["version"] == autotune.CACHE_SCHEMA_VERSION
        assert list(doc["entries"]) == sorted(doc["entries"])
        assert doc["entries"]["b|key"]["blocks"] == [8, 256, 512]
        assert AutotuneCache(path).get("b|key") == (8, 256, 512)

    def test_snapshot_matches_entries(self, tmp_path):
        c = AutotuneCache(str(tmp_path / "autotune.json"))
        c.put("k1", (8, 256, 512), 1.0)
        assert c.snapshot() == {"k1": [8, 256, 512]}
