"""LCD distillation loop (paper §3.2-§3.3).

Per-layer self-distillation: the full-precision weights are the teacher; the
clustered weights are the student. With the layer-wise quadratic objective
(Eq. 2-4) and diagonal H, one distillation step is:

  1. Hessian-preconditioned weight update (Eq. 5). For the layer-reconstruction
     loss L = E||X W' - X W_t||^2 the gradient is grad = H (W' - W_t), so the
     preconditioned step  W <- W' - eta * grad / diag(H)  =  W' - eta (W' - W_t)
     pulls the *dequantized* weights toward the teacher at a uniform rate — the
     preconditioning exactly cancels the per-channel curvature, which is why the
     paper can drop KL distillation and still converge fast.
  2. Reclassification (Eq. 6): weights whose update crossed the half-distance
     boundary migrate to the neighbouring cluster. With sorted centroids this is
     exactly nearest-centroid re-assignment of the updated weights (a weight
     whose update exceeds d_left/d_right is, by definition, nearer the neighbour).
  3. Centroid refresh (Eq. 7): H-weighted re-estimation from the new members.
  4. Progressive merge (Eq. 8 / §3.3): when the normalized H-weighted distortion
     J drops below theta, merge the two closest centroids.
  5. Speculative search (§3.3): on stagnation, re-run DBCI with doubled eps,
     optimize p steps, keep if within the accuracy threshold Theta, else back
     off eps <- 1.5 eps and retry; bounded by T rounds.

Steps 1-4 are one jitted function (`lcd_step`); step 5 is the Python driver
(`distill_layer`) since it re-enters initialization.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clustering as C


@dataclasses.dataclass
class LCDConfig:
    """Hyper-parameters of the LCD distillation loop (paper notation in comments)."""
    eta: float = 1.0                  # Eq. 5 learning rate. eta=1 is the exact
                                      # Newton step (diag-H cancels the curvature,
                                      # see module docstring) and empirically
                                      # matches weighted Lloyd's fixed point.
    theta: float = 0.04               # progressive-merge distortion threshold (theta)
    merge_rule: str = "salience"      # "closest" (paper Eq. 8 pair choice) | "salience"
    target_centroids: int = 0         # stop merging below this (0 = fully adaptive)
    max_steps: int = 400              # total distillation step budget (T-ish)
    spec_patience: int = 25           # steps without merge before speculative search
    spec_iters: int = 30              # p — iterations granted to a speculative restart
    spec_tolerance: float = 1.08      # Theta — accept if J_new <= tol * J_old
    spec_rounds: int = 3              # T — speculative rounds before giving up
    max_init_centroids: int = 20      # DBCI cap (paper: 15-20 empirically)
    damp_frac: float = 1e-2
    seed: int = 0


@dataclasses.dataclass
class DistillReport:
    """Trajectory of one layer's distillation — feeds Fig. 7 / Fig. 8 benchmarks."""
    centroid_history: List[int]
    objective_history: List[float]
    trace_history: List[float]
    speculative_events: List[Tuple[int, str]]   # (step, accepted/reverted)
    final_centroids: np.ndarray
    final_objective: float


# ---------------------------------------------------------------------------
# One jitted LCD step (Eq. 5-8)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("allow_merge", "merge_rule"))
def lcd_step(
    w_teacher: jax.Array,     # FP teacher weights (the model's own weights — self-distill)
    codes: jax.Array,         # int32, same shape
    state: C.ClusterState,
    h: jax.Array,             # diag Hessian, same shape as w (broadcasted)
    eta: float,
    theta: float,
    min_k: int,
    allow_merge: bool = True,
    merge_rule: str = "salience",
):
    """Returns (codes', state', J', merged?)."""
    w_student = C.dequant(codes, state)

    # (1) Eq. 5 — preconditioned update toward the teacher. grad = H*(W'-Wt);
    # grad/diag(H) = (W'-Wt): curvature cancels (see module docstring).
    w_upd = w_student - eta * (w_student - w_teacher)

    # (2) Eq. 6 — reclassification == nearest re-assignment of updated weights.
    codes2 = C.assign(w_upd, state)

    # (3) Eq. 7 — H-weighted centroid refresh from updated member positions.
    state2 = C.refresh(w_upd, codes2, state, h)
    # Refreshing can unsort centroids in principle; refresh preserves order here
    # because members of sorted clusters stay interval-disjoint after a uniform
    # shrink toward the teacher, but we defensively re-sort (cheap, K_MAX=32).
    order = jnp.argsort(state2.centroids)
    state2 = C.ClusterState(state2.centroids[order], state2.active[order], state2.counts[order])
    codes2 = C.lookup(codes2, jnp.argsort(order).astype(codes2.dtype))

    # Distortion against the *teacher* (the quantity Eq. 4 bounds).
    j = C.objective(w_teacher, codes2, state2, h)

    # (4) progressive merge when distortion is below theta and we may shrink.
    k = state2.k
    do_merge = jnp.logical_and(j < theta, k > min_k) if allow_merge else jnp.array(False)

    def _merged(_):
        s3 = C.merge_closest(state2, merge_rule)
        c3 = C.assign(w_upd, s3)
        s3 = C.refresh(w_upd, c3, s3, h)
        return c3, s3

    def _same(_):
        return codes2, state2

    codes3, state3 = jax.lax.cond(do_merge, _merged, _same, None)
    j3 = C.objective(w_teacher, codes3, state3, h)
    return codes3, state3, j3, do_merge


# ---------------------------------------------------------------------------
# Python driver: progressive + speculative optimization (§3.3)
# ---------------------------------------------------------------------------

def _init_from_dbci(w: np.ndarray, cfg: LCDConfig, eps_scale: float) -> Tuple[C.ClusterState, jax.Array]:
    res = C.dbci_init(
        np.asarray(w),
        max_centroids=cfg.max_init_centroids,
        eps_scale=eps_scale,
        seed=cfg.seed,
    )
    state = C.make_state(res.centroids)
    codes = C.assign(jnp.asarray(w, jnp.float32), state)
    return state, codes


def distill_layer(
    w_teacher: np.ndarray,
    h_diag: np.ndarray,
    cfg: LCDConfig = LCDConfig(),
    *,
    init: str = "dbci",          # dbci | naive4bit | kmeans:<k>  (Fig. 7b ablation)
    progressive: bool = True,    # PO on/off (Fig. 7b ablation)
    speculative: bool = True,    # SO on/off (Fig. 7b ablation)
) -> Tuple[np.ndarray, C.ClusterState, DistillReport]:
    """Run the full LCD loop on one weight tensor.

    Returns (codes int32 ndarray, final ClusterState, DistillReport).
    """
    wt = jnp.asarray(w_teacher, jnp.float32)
    h = jnp.asarray(np.broadcast_to(h_diag, w_teacher.shape), jnp.float32)

    if init == "dbci":
        state, codes = _init_from_dbci(w_teacher, cfg, eps_scale=1.0)
    elif init == "naive4bit":
        state = C.make_state(C.uniform_grid_centroids(w_teacher, 4))
        codes = C.assign(wt, state)
    elif init.startswith("kmeans:"):
        k = int(init.split(":")[1])
        state = C.make_state(C.kmeans_1d(w_teacher, k, seed=cfg.seed))
        codes = C.assign(wt, state)
    else:
        raise ValueError(f"unknown init scheme {init!r}")

    min_k = max(cfg.target_centroids, 2)
    hist_k: List[int] = [C.num_active(state)]
    hist_j: List[float] = []
    hist_tr: List[float] = []
    spec_events: List[Tuple[int, str]] = []

    steps_since_merge = 0
    spec_round = 0
    eps_scale = 2.0
    j_prev = np.inf

    h_sum = jnp.sum(h)           # loop-invariant; one reduction, not one per step
    step = 0
    while step < cfg.max_steps:
        codes, state, j, merged = lcd_step(
            wt, codes, state, h, cfg.eta, cfg.theta, min_k,
            allow_merge=progressive, merge_rule=cfg.merge_rule,
        )
        jf = float(j)
        kf = C.num_active(state)
        hist_j.append(jf)
        hist_k.append(kf)
        hist_tr.append(float(h_sum * jf))  # H-trace-scaled distortion monitor
        step += 1

        if bool(merged):
            steps_since_merge = 0
        else:
            steps_since_merge += 1

        # --- speculative search trigger: stagnation + non-monotone trace ----
        stagnated = steps_since_merge >= cfg.spec_patience
        non_monotone = jf > j_prev - 1e-12
        j_prev = jf
        if speculative and stagnated and non_monotone and spec_round < cfg.spec_rounds:
            spec_round += 1
            snap = (codes, state, jf, kf)   # device arrays are immutable
            try:
                state_s, codes_s = _init_from_dbci(w_teacher, cfg, eps_scale=eps_scale)
            except ValueError:
                break
            # p iterations of progressive-only optimization on the candidate
            js = np.inf
            for _ in range(cfg.spec_iters):
                codes_s, state_s, js, _m = lcd_step(
                    wt, codes_s, state_s, h, cfg.eta, cfg.theta, min_k,
                    allow_merge=True, merge_rule=cfg.merge_rule,
                )
                step += 1
            js = float(js)
            ks = C.num_active(state_s)
            accept = (ks < kf and js <= cfg.spec_tolerance * max(jf, 1e-12)) or (
                ks <= kf and js < jf
            )
            if accept:
                codes, state = codes_s, state_s
                spec_events.append((step, f"accepted k={ks} J={js:.3e} (eps x{eps_scale})"))
                eps_scale = 2.0
                steps_since_merge = 0
            else:
                codes, state = snap[0], snap[1]
                spec_events.append((step, f"reverted (cand k={ks} J={js:.3e}, eps x{eps_scale})"))
                eps_scale = 1.5  # paper: back off 2*eps -> 1.5*eps
        elif stagnated and not speculative:
            break  # PO-only converges (possibly prematurely — Fig. 7b)

        if cfg.target_centroids and kf <= cfg.target_centroids and jf < cfg.theta:
            break

    final_j = float(C.objective(wt, codes, state, h))
    report = DistillReport(
        centroid_history=hist_k,
        objective_history=hist_j,
        trace_history=hist_tr,
        speculative_events=spec_events,
        final_centroids=C.active_centroids(state),
        final_objective=final_j,
    )
    return np.asarray(jax.device_get(codes)), state, report


def distill_layer_to_k(
    w_teacher: np.ndarray,
    h_diag: np.ndarray,
    k: int,
    cfg: Optional[LCDConfig] = None,
    **kw,
) -> Tuple[np.ndarray, C.ClusterState, DistillReport]:
    """Convenience: distill until exactly k centroids remain (Table 1/2 settings
    fix the centroid budget, e.g. 8 centroids == 3 equivalent bits)."""
    cfg = dataclasses.replace(cfg or LCDConfig(), target_centroids=k,
                              theta=np.inf)  # always merge until k reached
    codes, state, rep = distill_layer(w_teacher, h_diag, cfg, **kw)
    # polish at fixed k with merging disabled
    wt = jnp.asarray(w_teacher, jnp.float32)
    h = jnp.asarray(np.broadcast_to(h_diag, w_teacher.shape), jnp.float32)
    cj = jnp.asarray(codes)
    st = state
    for _ in range(30):
        cj, st, j, _ = lcd_step(wt, cj, st, h, cfg.eta, 0.0, k,
                                allow_merge=False, merge_rule=cfg.merge_rule)
    rep.final_objective = float(j)
    rep.final_centroids = C.active_centroids(st)
    return np.asarray(jax.device_get(cj)), st, rep
