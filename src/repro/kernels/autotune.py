"""Measured block-shape autotuner for the Pallas kernels (DESIGN.md §11).

The serving kernels are tiled: every entry point streams its operands through
VMEM in (bm, bn, bk)-shaped blocks (or (bq, bk) query/key blocks for the
attention kernels), and the static heuristic `heuristic_blocks` — the seed's
`_pick_blocks` — guesses one shape per problem. That guess is fine for the
interpreter but leaves measured throughput on the table on real hardware,
where the best tile shape depends on the (m, k, n) geometry, the packing
width (a 2-bit tile is half the VMEM bytes of an int4 one, so deeper bk fits)
and the backend generation. AQLM ships per-shape tuned LUT kernels for
exactly this reason (PAPERS.md: Egiazarian et al., 2024).

This module is the single place block shapes come from:

  key        (variant, backend, normalized (m, k, n), nbits) — normalization
             rounds the problem to the shapes the kernels actually run after
             padding, so e.g. a (1, 4096, 4096) and a (7, 4096, 4096) decode
             GEMV share one entry (both pad M to 8).
  candidates the MXU-aligned grid per variant, always containing the
             heuristic choice, filtered by the VMEM working-set budget the
             heuristic enforces (`vmem_bytes` ≤ VMEM_BUDGET) — the tuner can
             never propose a spec the kernel could not run.
  measure    a caller-supplied `measure(*blocks) -> seconds`, run outside
             any trace (benchmarks/common.py `timeit_p50` is the discipline:
             warmup, then p50 of repeats fenced by `block_until_ready`).
  cache      in-process dict backed by a persistent JSON store
             (`<checkout>/.autotune.json`, override with
             $REPRO_AUTOTUNE_CACHE; versioned schema, corrupt-file tolerant).
             A cache hit NEVER re-measures (asserted in tests/test_autotune).

Fallback contract (deterministic, no timing dependence): in interpret mode,
and on a cache miss with measurement unavailable or disabled
($REPRO_AUTOTUNE=0), `pick_blocks` returns exactly `heuristic_blocks`'s
choice — CPU CI and the interpret benches behave precisely as before the
tuner existed.

The kernel wrappers never pass a `measure` fn: they run while a `jax.jit`
is tracing, where a timed call is staged into the trace instead of running,
so the traced serving path always resolves to a cache hit or the
heuristic. Measuring is for a caller outside any trace: the compiled lane
of `benchmarks/kernel_bench.py` on TPU measures the LUT GEMM's grid and
fills the cache. Nothing measures the attention kernels, so
`pick_flash_blocks` and `pick_paged_pad` serve a cache hit or their
heuristic.
"""
from __future__ import annotations

import json
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.utils import REPO_ROOT, logger, round_up

# ---------------------------------------------------------------------------
# Heuristic (the seed's _pick_blocks — kept verbatim as the deterministic
# fallback and as the always-present candidate)
# ---------------------------------------------------------------------------

VMEM_BUDGET = 8 * 1024 * 1024  # the working-set bound _pick_blocks was sized to

# v2: the fused multi-projection variants (lut_fused_multi[_gemv]) key their
# own entries and the VMEM formula became P-aware (`n_ops`); v1 entries could
# alias a multi call onto a single-projection winner that blows the budget.
# v3: the fused GEMMs decode each weight tile once per M chunk (`m_chunk`),
# so a winner timed under the per-M-block layout is stale. Old caches are
# discarded wholesale rather than migrated.
CACHE_SCHEMA_VERSION = 3
_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
_ENV_ENABLE = "REPRO_AUTOTUNE"

LUT_VARIANTS = ("lut_f32", "lut_int8", "lut_fused", "lut_fused_gemv",
                "lut_fused_multi", "lut_fused_multi_gemv")

# variants whose M dimension is one resident decode block (N-major grid)
GEMV_VARIANTS = ("lut_fused_gemv", "lut_fused_multi_gemv")


def heuristic_blocks(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """MXU-aligned blocks whose working set at one M block (`vmem_bytes`)
    stays well under VMEM_BUDGET, which leaves the fused GEMM room for a
    chunk of many M blocks (`m_chunk`).

    GEMV-aware: decode-shaped calls (m < 128) collapse M into one
    sublane-aligned block (multiple of 8 for f32) consumed by the N-major
    fused GEMV kernel instead of padding M up to a full MXU tile."""
    bm = round_up(m, 8) if m < 128 else 128
    bn = 256 if n % 256 == 0 else 128
    bk = 512 if k % 512 == 0 else 256
    return bm, bn, bk


def vmem_bytes(bm: int, bn: int, bk: int, nbits: int = 4,
               n_ops: int = 1, rows: int = None) -> int:
    """VMEM working-set bytes of one LUT-matmul launch. `rows` is the M rows
    resident at once: the fused GEMM's `m_chunk`, else (and by default) bm.

      f32 accumulator           rows x bn x 4
      output block, two buffers rows x bn x 4 x 2
      x block, two buffers      bm x bk x 4 x 2  (counted at f32)
      decoded f32 weight tile   bk x bn x 4
      packed code tiles         n_ops x bk x bn x nbits/8 x 2

    Pallas double-buffers every blocked operand. `n_ops` is the projection
    count of a fused multi call (lut_matmul_fused_multi): every
    projection's current packed tile stays resident — Pallas holds one
    block per operand — so that term scales with P even though one tile is
    read per grid step."""
    rows = rows or bm
    return (rows * bn * 4 * 3 + bm * bk * 4 * 2 + bk * bn * 4
            + n_ops * (bk * bn * nbits // 8) * 2)


def m_chunk(m: int, bm: int, bn: int, bk: int, nbits: int = 4,
            n_ops: int = 1) -> int:
    """Rows of a fused GEMM launch that share one decoded weight tile: the
    largest whole number of bm-blocks that divides M (already padded to a
    bm multiple) and whose working set (`vmem_bytes` at rows=m_chunk) fits
    VMEM_BUDGET; bm when no larger chunk does. The kernel then decodes
    every tile M / m_chunk times per launch."""
    nblk = m // bm
    for c in range(nblk, 1, -1):
        if nblk % c == 0 and vmem_bytes(bm, bn, bk, nbits, n_ops,
                                        rows=c * bm) <= VMEM_BUDGET:
            return c * bm
    return bm


def candidate_blocks(m: int, k: int, n: int, nbits: int = 4,
                     variant: str = "lut_fused",
                     n_ops: int = 1) -> List[Tuple[int, int, int]]:
    """The measured grid: MXU-aligned (bm, bn, bk) triples that (a) never pad
    the problem beyond one block of slack, (b) cover whole packing groups
    (bk·nbits ≡ 0 mod 8), and (c) fit the VMEM budget — P-aware for the
    fused multi variants (`n_ops` resident code tiles) at one M block of
    rows, the least `m_chunk` gives, so every candidate runs and the GEMM
    picks its own chunk for it. The heuristic's choice is always first, so
    the tuner's argmin can only match or beat it."""
    heur = heuristic_blocks(m, k, n)
    if variant in GEMV_VARIANTS or m < 128:
        bms: Sequence[int] = (round_up(m, 8),)  # one resident M block
    else:
        bms = [b for b in (128, 256) if b <= round_up(m, 128)]
    bns = [b for b in (128, 256, 512) if b <= round_up(n, 128)]
    bks = [b for b in (128, 256, 512, 1024) if b <= round_up(k, 128)]
    out = [heur]
    for bm in bms:
        for bn in bns:
            for bk in bks:
                cand = (bm, bn, bk)
                if cand == heur or cand in out:
                    continue
                if (bk * nbits) % 8:
                    continue
                if vmem_bytes(bm, bn, bk, nbits, n_ops) > VMEM_BUDGET:
                    continue
                out.append(cand)
    return out


def flash_heuristic(sq: int, sk: int) -> Tuple[int, int]:
    """The flash kernel's historical defaults, clamped to the problem."""
    return min(256, sq), min(512, sk)


def paged_heuristic() -> Tuple[int]:
    """Lane-alignment multiple for the gathered KV length (the seed padded
    to 128 lanes unconditionally)."""
    return (128,)


# ---------------------------------------------------------------------------
# Key normalization
# ---------------------------------------------------------------------------

def normalize_key(m: int, k: int, n: int, nbits: int, variant: str,
                  backend: str, n_ops: int = 1) -> str:
    """Canonical cache key: the problem rounded to the shape the kernel runs
    after padding. Decode GEMVs (m < 128) bucket M to the sublane multiple;
    larger M, and K/N always, round to the 128-lane tile. Two calls that pad
    to the same kernel problem share one entry. Fused multi calls
    additionally key on the projection count (`n_ops`): a 2-way and a 3-way
    fusion at the same concatenated N have different VMEM residency."""
    if variant in GEMV_VARIANTS or (variant in LUT_VARIANTS and m < 128):
        m_n = round_up(max(m, 1), 8)
    elif variant in LUT_VARIANTS:
        m_n = round_up(m, 128)
    else:
        m_n = m                       # attention: sq / gt are exact geometry
    k_n = round_up(k, 128) if variant in LUT_VARIANTS else k
    n_n = round_up(n, 128) if variant in LUT_VARIANTS else n
    key = f"{variant}|{backend}|m{m_n},k{k_n},n{n_n}|b{nbits}"
    if n_ops > 1:
        key += f"|p{n_ops}"
    return key


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------

def cache_path() -> str:
    """$REPRO_AUTOTUNE_CACHE when set, else one fixed file in the checkout
    (git-ignored) — the compile cache's rule (utils.enable_compile_cache)."""
    return os.environ.get(_ENV_CACHE, str(REPO_ROOT / ".autotune.json"))


class AutotuneCache:
    """In-process {key: entry} map backed by a JSON file.

    entry = {"blocks": [ints], "us": float, "source": "measured"}.

    The file is versioned ({"version": 1, "entries": {...}}); a missing,
    empty, corrupt, or wrong-version file is treated as an empty cache — the
    tuner re-measures rather than crashing serving (tests/test_autotune pins
    this recovery)."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or cache_path()
        self.entries: Dict[str, dict] = {}
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                doc = json.load(f)
            if (isinstance(doc, dict)
                    and doc.get("version") == CACHE_SCHEMA_VERSION
                    and isinstance(doc.get("entries"), dict)):
                self.entries = {
                    k: v for k, v in doc["entries"].items()
                    if isinstance(v, dict) and isinstance(v.get("blocks"), list)
                    and all(isinstance(b, int) for b in v["blocks"])}
        except (OSError, ValueError):
            pass                      # absent/corrupt file -> empty cache

    def save(self) -> None:
        doc = {"version": CACHE_SCHEMA_VERSION, "entries": self.entries}
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass                      # read-only FS: stay in-process only

    def get(self, key: str) -> Optional[Tuple[int, ...]]:
        ent = self.entries.get(key)
        return tuple(ent["blocks"]) if ent else None

    def put(self, key: str, blocks: Sequence[int], us: float) -> None:
        self.entries[key] = {"blocks": [int(b) for b in blocks],
                             "us": round(float(us), 3), "source": "measured"}
        self.save()

    def snapshot(self) -> Dict[str, List[int]]:
        """key -> winning blocks, for the BENCH_trajectory.json record."""
        return {k: list(v["blocks"]) for k, v in sorted(self.entries.items())}


_CACHE: Optional[AutotuneCache] = None


def get_cache() -> AutotuneCache:
    global _CACHE
    if _CACHE is None:
        _CACHE = AutotuneCache()
    return _CACHE


def reset_cache(path: Optional[str] = None) -> AutotuneCache:
    """Drop the in-process cache (tests; or after changing $REPRO_AUTOTUNE_CACHE)."""
    global _CACHE
    _CACHE = AutotuneCache(path)
    return _CACHE


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def tuning_enabled() -> bool:
    return os.environ.get(_ENV_ENABLE, "1") != "0"


def _tune(key: str, candidates, measure, cache: AutotuneCache):
    """Measure every candidate and store the fastest. A candidate the
    backend refuses loses, with its error logged; when every candidate is
    refused the first refusal is raised — the grid is VMEM-filtered, so
    that is a kernel fault, never a reason to serve the heuristic."""
    best, best_t, refused = None, float("inf"), []
    for cand in candidates:
        try:
            t = measure(*cand)
        except Exception as e:        # noqa: BLE001 — re-raised below
            logger.warning("autotune %s: candidate %s refused: %s",
                           key, cand, e)
            refused.append(e)
            continue
        if t < best_t:
            best, best_t = cand, t
    if best is None:
        raise RuntimeError(
            f"autotune {key}: all {len(refused)} candidates refused"
        ) from (refused[0] if refused else None)
    cache.put(key, best, best_t * 1e6)
    return tuple(best)


def pick_blocks(m: int, k: int, n: int, *, nbits: int = 4,
                variant: str = "lut_fused", interpret: bool = True,
                measure: Optional[Callable[..., float]] = None,
                cache: Optional[AutotuneCache] = None,
                n_ops: int = 1) -> Tuple[int, int, int]:
    """(bm, bn, bk) for a LUT matmul problem — cached winner, else measured,
    else the deterministic heuristic.

    Resolution order (the §11 contract):
      1. cache hit for the normalized key  -> the stored winner, NO measuring;
      2. miss + measurement available      -> time `candidate_blocks`, store;
      3. miss + interpret / disabled / no
         measure fn                        -> exactly `heuristic_blocks`.

    `measure(bm, bn, bk) -> seconds` is injected by a caller outside any
    trace (benchmarks/kernel_bench.py on TPU; tests inject counters).
    Shape args must be the post-group-padding problem the kernel will run.
    """
    backend = "interpret" if interpret else jax.default_backend()
    cache = cache or get_cache()
    key = normalize_key(m, k, n, nbits, variant, backend, n_ops)
    hit = cache.get(key)
    if hit is not None:
        return hit                    # cache hit: never re-measure
    if interpret or measure is None or not tuning_enabled():
        return heuristic_blocks(m, k, n)
    return _tune(key, candidate_blocks(m, k, n, nbits, variant, n_ops),
                 measure, cache)


def pick_flash_blocks(sq: int, sk: int, d: int, *, interpret: bool = True,
                      cache: Optional[AutotuneCache] = None
                      ) -> Tuple[int, int]:
    """(bq, bk) for the flash-attention kernel: a cached winner on a compiled
    backend, else `flash_heuristic`. Nothing measures the attention kernels
    yet. Key geometry: (m=sq, k=sk, n=d), nbits=0 (no packing)."""
    if interpret:
        return flash_heuristic(sq, sk)
    cache = cache or get_cache()
    hit = cache.get(normalize_key(sq, sk, d, 0, "flash",
                                  jax.default_backend()))
    return hit if hit is not None else flash_heuristic(sq, sk)


def pick_paged_pad(gt: int, l: int, d: int, *, interpret: bool = True,
                   cache: Optional[AutotuneCache] = None) -> int:
    """Lane-padding multiple for the paged dequant-attention kernel's gathered
    KV length: a cached winner on a compiled backend, else
    `paged_heuristic`. Key geometry: (m=gt, k=l, n=d)."""
    if interpret:
        return paged_heuristic()[0]
    cache = cache or get_cache()
    hit = cache.get(normalize_key(gt, l, d, 8, "paged",
                                  jax.default_backend()))
    return hit[0] if hit is not None else paged_heuristic()[0]
