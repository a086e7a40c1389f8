"""Clustering primitives for LCD.

Implements the paper's §3.1 Density-Based Centroid Initialization (DBCI) and the
cluster-state machinery the distillation loop (distill.py) operates on.

Key observation exploited here: LLM weights are *scalars*, so DBSCAN over a weight
tensor is a 1-D problem. On sorted data, 1-D DBSCAN is exact and linear-time:
a point is a core point iff its eps-window (found by two binary searches) holds at
least MinPts points, and clusters are maximal chains of eps-reachable core points,
which on a sorted axis are contiguous runs. We run the *same algorithm* as the
paper, just with the optimal 1-D implementation (recorded in DESIGN.md §7).

All distillation-time operations (assignment, weighted refresh, merge, objective)
are pure-jnp and jittable with a fixed K_max + active mask, so the whole per-layer
LCD loop can live inside one jit.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# Maximum number of centroids the fixed-size cluster state can hold. DBCI
# empirically yields 15-20 (paper §3.1); 32 leaves headroom for speculative
# re-initialisation at larger eps.
K_MAX = 32


# ---------------------------------------------------------------------------
# DBCI — Density-Based Centroid Initialization (paper §3.1, steps 1-6)
# ---------------------------------------------------------------------------

def estimate_sigma(w_sorted: np.ndarray) -> float:
    """Paper Eq. (1): sigma from the +-68.27/95.44/99.74 percentile weights.

    For a centred Gaussian the weight at the q-th percentile of the positive tail
    sits at k*sigma for k=1,2,3, so (sum of the six |values|)/12 estimates sigma
    robustly even with outliers (which only perturb the 3-sigma terms).
    """
    n = w_sorted.shape[0]

    def at(frac: float) -> float:
        idx = min(max(int(round(frac * (n - 1))), 0), n - 1)
        return float(w_sorted[idx])

    # percentile of the *signed* distribution corresponding to +-k sigma
    # (CDF of N(0,1) at +-1/2/3 sigma).
    pos = [at(0.84135), at(0.97725), at(0.99865)]   # +1, +2, +3 sigma
    neg = [at(0.15865), at(0.02275), at(0.00135)]   # -1, -2, -3 sigma
    sigma = (sum(pos) - sum(neg)) / 12.0
    return max(sigma, 1e-12)


@dataclasses.dataclass
class DBCIResult:
    centroids: np.ndarray          # (k,) sorted float32 centroids
    eps: float
    min_pts: int
    sigma: float
    n_noise: int                   # points labelled noise (absorbed post-hoc)


def _dbscan_1d_sorted(ws: np.ndarray, eps: float, min_pts: int) -> Tuple[np.ndarray, int]:
    """Exact DBSCAN on sorted 1-D data.

    Returns (cluster_id per point, with -1 = noise, ids contiguous from 0), n_clusters.
    A point is core iff #points within [w-eps, w+eps] >= min_pts; clusters are
    maximal runs of points chained through core points within eps.
    """
    n = ws.shape[0]
    lo = np.searchsorted(ws, ws - eps, side="left")
    hi = np.searchsorted(ws, ws + eps, side="right")
    core = (hi - lo) >= min_pts

    labels = np.full(n, -1, dtype=np.int64)
    cid = -1
    i = 0
    while i < n:
        if not core[i]:
            i += 1
            continue
        # start a new cluster at core point i; extend right while the chain holds
        cid += 1
        j = i
        labels[i] = cid
        # border points to the left of the first core point of the run
        k = i - 1
        while k >= 0 and labels[k] == -1 and ws[i] - ws[k] <= eps:
            labels[k] = cid
            k -= 1
        while j + 1 < n:
            if ws[j + 1] - ws[j] <= eps and (core[j] or core[j + 1]):
                j += 1
                labels[j] = cid
            else:
                break
        i = j + 1
    return labels, cid + 1


def dbci_init(
    w: np.ndarray,
    *,
    max_centroids: int = 20,
    min_centroids: int = 2,
    subsample: int = 1 << 17,
    eps_scale: float = 1.0,
    seed: int = 0,
) -> DBCIResult:
    """Density-Based Centroid Initialization (paper §3.1).

    eps_scale multiplies the derived eps — the speculative optimizer (paper §3.3)
    re-enters with eps_scale=2.0 then 1.5.
    """
    flat = np.asarray(w, dtype=np.float64).reshape(-1)
    flat = flat[np.isfinite(flat)]
    if flat.size == 0:
        raise ValueError("dbci_init: empty/namid weight tensor")
    if flat.size > subsample:
        rng = np.random.default_rng(seed)
        flat = rng.choice(flat, size=subsample, replace=False)
    ws = np.sort(flat)
    n = ws.shape[0]

    # Steps 1-2: sigma from percentiles.
    sigma = estimate_sigma(ws)

    # Step 3: the two most extreme points seed sigma-radius core neighbourhoods.
    lo_cnt = int(np.searchsorted(ws, ws[0] + sigma, side="right"))
    hi_cnt = int(n - np.searchsorted(ws, ws[-1] - sigma, side="left"))

    # Step 4: MinPts = smaller count; eps = sigma / MinPts.
    min_pts = max(int(min(lo_cnt, hi_cnt)), 2)
    eps = eps_scale * sigma / min_pts
    # Guard: for near-degenerate layers eps can underflow the float grid.
    eps = max(eps, 1e-9 * max(abs(float(ws[0])), abs(float(ws[-1])), 1e-30))

    # Step 5: standard DBSCAN on the (sorted) points.
    labels, k = _dbscan_1d_sorted(ws, eps, min_pts)

    # Adaptive guard: if eps over-segments far beyond the budget, widen it.
    tries = 0
    while k > 4 * max_centroids and tries < 40:
        eps *= 1.6
        labels, k = _dbscan_1d_sorted(ws, eps, min_pts)
        tries += 1

    # Step 6 (budgeted): DBSCAN over a *continuous* weight distribution yields a
    # handful of density regions (the Gaussian bulk + outlier tails + noise); a
    # single L1 median per region cannot represent the bulk. We therefore spend
    # the centroid budget across density regions proportionally to their mass
    # and place the per-region centroids at within-region quantile medians
    # (each is the L1 minimizer of its sub-cluster — step 6 of the paper applied
    # at the budget's granularity). eps_scale > 1 (speculative search) coarsens
    # the regions AND shrinks the budget, so re-initialisation explores fewer
    # centroids exactly as §3.3 intends.
    n_noise = int((labels == -1).sum())
    budget = max(min_centroids, int(round(max_centroids / eps_scale)))
    regions: list[np.ndarray] = [ws[labels == c] for c in range(k)]
    if n_noise:
        noise = ws[labels == -1]
        regions.append(noise)
    regions = [r for r in regions if r.size > 0]
    if not regions:
        regions = [ws]
    masses = np.array([r.size for r in regions], np.float64)
    # proportional allocation, >=1 each, largest-remainder rounding
    raw = masses / masses.sum() * budget
    alloc = np.maximum(np.floor(raw).astype(int), 1)
    while alloc.sum() > budget and (alloc > 1).any():
        alloc[np.argmax(alloc - raw)] -= 1
    rem = budget - alloc.sum()
    if rem > 0:
        order = np.argsort(-(raw - alloc))
        for i in order[:rem]:
            alloc[i] += 1
    cents_list = []
    for r, m in zip(regions, alloc):
        m = min(int(m), r.size)
        qs = (np.arange(m) + 0.5) / m
        cents_list.append(np.quantile(r, qs))
    cents = np.unique(np.concatenate(cents_list))
    return DBCIResult(cents.astype(np.float32), float(eps), min_pts, float(sigma), n_noise)


# ---------------------------------------------------------------------------
# Fixed-size jittable cluster state
# ---------------------------------------------------------------------------

class ClusterState(NamedTuple):
    """Fixed-size (K_MAX) cluster state so merges stay jit-compatible.

    centroids : (K_MAX,) f32 — sorted ascending over the *active* prefix;
                inactive slots hold +inf so nearest-centroid never picks them.
    active    : (K_MAX,) bool
    counts    : (K_MAX,) f32 — H-weighted member mass (used by merge, Eq. 8).
    """
    centroids: jax.Array
    active: jax.Array
    counts: jax.Array

    @property
    def k(self) -> jax.Array:
        return jnp.sum(self.active.astype(jnp.int32))


_INACTIVE = jnp.inf


def make_state(centroids: np.ndarray) -> ClusterState:
    c = np.sort(np.asarray(centroids, np.float32).reshape(-1))
    k = c.shape[0]
    if k > K_MAX:
        # keep K_MAX evenly spaced representatives
        idx = np.linspace(0, k - 1, K_MAX).round().astype(int)
        c, k = c[idx], K_MAX
    cent = np.full((K_MAX,), np.inf, np.float32)
    cent[:k] = c
    act = np.zeros((K_MAX,), bool)
    act[:k] = True
    return ClusterState(jnp.asarray(cent), jnp.asarray(act), jnp.zeros((K_MAX,), jnp.float32))


# --- assignment -------------------------------------------------------------

@jax.jit
def assign(w: jax.Array, state: ClusterState) -> jax.Array:
    """Nearest-active-centroid assignment. H-weighting does not change the argmin
    (the per-weight importance multiplies every candidate distance equally), so
    assignment is plain nearest — the weighting enters refresh/objective."""
    d = jnp.abs(w[..., None] - state.centroids)          # (..., K_MAX); inf slots lose
    return jnp.argmin(d, axis=-1).astype(jnp.int32)


def lookup(codes: jax.Array, table: jax.Array) -> jax.Array:
    """table[codes] for a K_MAX-entry table, as a select-sum over the
    entries: exactly one term is nonzero, so it equals the gather, and it
    lowers to fused elementwise work where a TPU gather of a tiny table
    over millions of indices is slow."""
    hit = codes[..., None] == jnp.arange(K_MAX, dtype=codes.dtype)
    return jnp.sum(jnp.where(hit, table, jnp.zeros((), table.dtype)), axis=-1)


@jax.jit
def dequant(codes: jax.Array, state: ClusterState) -> jax.Array:
    safe = jnp.where(state.active, state.centroids, 0.0)
    return lookup(codes, safe)


# --- objective (paper Eq. 4, normalized) -------------------------------------

@jax.jit
def objective(w: jax.Array, codes: jax.Array, state: ClusterState, h: jax.Array) -> jax.Array:
    """Normalized H-weighted distortion  J = sum h (w-c)^2 / sum h w^2.

    The paper's Eq. 4 is sum |w - C| / (2 H^-1) = 0.5 * sum H|w - C|; we use the
    squared form (the second-order expansion Eq. 2 is quadratic) normalized so a
    single threshold theta works across layers of different scale.
    """
    c = dequant(codes, state)
    num = jnp.sum(h * (w - c) ** 2)
    den = jnp.sum(h * w ** 2) + 1e-30
    return num / den


# --- H-weighted centroid refresh (Eq. 7 realized as weighted re-estimation) ---

@jax.jit
def refresh(w: jax.Array, codes: jax.Array, state: ClusterState, h: jax.Array) -> ClusterState:
    """Recompute each active centroid as the H-weighted mean of its members.

    Eq. 7 accumulates per-cluster increments (own members + reclassified-in
    members); with reclassification already folded into `codes`, summing
    increments and re-normalizing is exactly the weighted mean below. The
    weighted mean minimizes the quadratic Eq. 4 objective for fixed assignment.
    """
    flat_w = w.reshape(-1)
    flat_h = h.reshape(-1)
    hit = codes.reshape(-1)[:, None] == jnp.arange(K_MAX, dtype=codes.dtype)
    # per-cluster sums as a masked reduction, not a scatter-add: on TPU a
    # scatter of millions of updates into K_MAX bins serializes on the
    # colliding indices (~0.1 s per call at a 1536x8960 layer)
    mass = jnp.sum(jnp.where(hit, flat_h[:, None], 0.0), axis=0)
    wsum = jnp.sum(jnp.where(hit, (flat_h * flat_w)[:, None], 0.0), axis=0)
    new = jnp.where(mass > 0, wsum / jnp.maximum(mass, 1e-30), state.centroids)
    new = jnp.where(state.active, new, _INACTIVE)
    return ClusterState(new, state.active, mass)


# --- progressive merge (paper Eq. 8) -----------------------------------------

@partial(jax.jit, static_argnames=("rule",))
def merge_closest(state: ClusterState, rule: str = "salience") -> ClusterState:
    """Merge two adjacent *active* centroids into their count-weighted average.

    C_new = (n_b C_a + n_a C_b) / (n_a + n_b)   — note the paper's cross-weighting;
    we implement the standard mass-weighted mean (n_a C_a + n_b C_b)/(n_a+n_b),
    which preserves the cluster mass centroid (the paper's Eq. 8 appears to have
    the subscripts crossed; the mass-preserving form is the one consistent with
    its own 'weights proportional to the number of points' description).

    rule="closest"  : the paper's pair choice — smallest centroid gap.
    rule="salience" : beyond-paper — smallest *distortion increase*
                      n_a n_b/(n_a+n_b) * gap^2 (the exact SSE increase of merging
                      two point masses), which protects heavy clusters separated
                      by small gaps. Benchmarked in EXPERIMENTS.md.
    """
    c = state.centroids
    # centroids are kept sorted over the active prefix -> adjacent gaps suffice
    pair_ok = state.active[1:] & state.active[:-1]
    gaps = jnp.where(pair_ok, c[1:] - c[:-1], jnp.inf)
    if rule == "closest":
        score = gaps
    else:  # salience: SSE increase of merging the two mass points
        na_, nb_ = state.counts[:-1], state.counts[1:]
        mass = jnp.where(na_ + nb_ > 0, na_ * nb_ / jnp.maximum(na_ + nb_, 1e-30), 1.0)
        score = jnp.where(pair_ok, mass * gaps ** 2, jnp.inf)
    i = jnp.argmin(score)  # merge slots i, i+1
    na = state.counts[i]
    nb = state.counts[i + 1]
    tot = jnp.maximum(na + nb, 1e-30)
    merged = (na * c[i] + nb * c[i + 1]) / tot
    # guard: if counts are both zero (fresh state), plain midpoint
    merged = jnp.where(na + nb > 0, merged, 0.5 * (c[i] + c[i + 1]))

    cent = c.at[i].set(merged).at[i + 1].set(_INACTIVE)
    act = state.active.at[i + 1].set(False)
    cnt = state.counts.at[i].set(na + nb).at[i + 1].set(0.0)
    # compact: keep active prefix sorted by re-sorting with inactives at +inf
    order = jnp.argsort(cent)
    return ClusterState(cent[order], act[order], cnt[order])


def num_active(state: ClusterState) -> int:
    return int(jax.device_get(state.k))


def active_centroids(state: ClusterState) -> np.ndarray:
    c = np.asarray(jax.device_get(state.centroids))
    a = np.asarray(jax.device_get(state.active))
    return c[a]


# ---------------------------------------------------------------------------
# Baselines: k-means (naive init / SKIM-like) — used by benchmarks & ablations
# ---------------------------------------------------------------------------

def kmeans_1d(
    w: np.ndarray,
    k: int,
    *,
    iters: int = 25,
    weights: Optional[np.ndarray] = None,
    seed: int = 0,
) -> np.ndarray:
    """Weighted Lloyd's in 1-D with quantile init. Returns sorted centroids (k,)."""
    flat = np.asarray(w, np.float64).reshape(-1)
    hw = np.ones_like(flat) if weights is None else np.asarray(weights, np.float64).reshape(-1)
    qs = np.linspace(0.5 / k, 1 - 0.5 / k, k)
    cents = np.quantile(flat, qs)
    for _ in range(iters):
        # nearest assignment via boundaries between sorted centroids
        bounds = (cents[1:] + cents[:-1]) / 2
        idx = np.searchsorted(bounds, flat)
        num = np.bincount(idx, weights=hw * flat, minlength=k)
        den = np.bincount(idx, weights=hw, minlength=k)
        new = np.where(den > 0, num / np.maximum(den, 1e-30), cents)
        if np.allclose(new, cents, rtol=0, atol=1e-12):
            cents = new
            break
        cents = np.sort(new)
    return cents.astype(np.float32)


def uniform_grid_centroids(w: np.ndarray, bits: int) -> np.ndarray:
    """'Naive init' baseline from Fig. 7b: a uniform 2^bits grid over the range."""
    flat = np.asarray(w, np.float64).reshape(-1)
    lo, hi = float(flat.min()), float(flat.max())
    k = 2 ** bits
    return np.linspace(lo, hi, k).astype(np.float32)
