"""Public jit'd wrappers around the Pallas kernels: shape padding, block/variant
selection, CPU fallback.

`clustered_linear(x, ct)` is the serving-path entry the models call: on TPU it
runs the fused smooth+quant+LUT GEMM (DESIGN.md §2) streaming the tensor's
first-class packed int4 codes; elsewhere (CPU tests, dry-run lowering on the
host platform) it falls back to the mathematically identical gather
contraction so the whole framework runs everywhere. `lut_serving(mode)` forces
the dispatch — "interpret" runs the real kernels through the Pallas
interpreter, which is how the CPU CI and `benchmarks/decode_bench.py --smoke`
exercise the serving engine end-to-end.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.api import ClusteredTensor, clustered_matmul
from repro.core.lut import pack_codes_jax, packed_rows, padded_d_in
from repro.distributed.sharding import kernel_mesh, logical_to_spec
from repro.kernels import autotune
from repro.kernels.lut_matmul import (KC, lut_matmul_f32, lut_matmul_fused,
                                      lut_matmul_fused_gemv,
                                      lut_matmul_fused_multi,
                                      lut_matmul_fused_multi_gemv,
                                      lut_matmul_int8)
from repro.utils import round_up

# the deterministic fallback the autotuner resolves to on a miss (DESIGN.md
# §11); kept under the historical name — tests pin its GEMV-awareness
_pick_blocks = autotune.heuristic_blocks

def _blocks_for(variant: str, m: int, k: int, n: int, nbits: int,
                interpret: bool):
    """(bm, bn, bk, rows) for one wrapper call: the autotuner's cached winner
    for this key, else exactly `_pick_blocks` (DESIGN.md §11), and the rows
    each decoded weight tile serves (`_rows_per_decode`). Called while the
    wrapper's jit traces, so it never measures."""
    blocks = autotune.pick_blocks(m, k, n, nbits=nbits, variant=variant,
                                  interpret=interpret)
    rows = _rows_per_decode(variant, m, blocks, nbits)
    _log_blocks(variant, m, k, n, blocks, rows)
    return (*blocks, rows)


def _rows_per_decode(variant: str, m: int, blocks, nbits: int,
                     n_ops: int = 1) -> int:
    """The rows that share one decoded weight tile: the fused GEMMs' M chunk
    (`autotune.m_chunk` of M padded to bm), which the wrappers pass to the
    kernel as `m_chunk`; one M block elsewhere (a GEMV's whole M)."""
    bm, bn, bk = blocks
    if variant in ("lut_fused", "lut_fused_multi"):
        return autotune.m_chunk(round_up(m, bm), bm, bn, bk, nbits, n_ops)
    return bm


def pad_for_kernel(x: jax.Array, packed: jax.Array, bm: int, bk: int, bn: int,
                   nbits: int = 4):
    """Pad (x, packed) to block multiples. `x` must already cover the packed
    tensor's group padding (k == padded_d_in), so the extra packed rows are
    exactly (kp - k) * nbits / 8 — whole bytes, because kp - k is a multiple
    of 8 whenever bk is (the packing-group contract in core/lut.py)."""
    m, k = x.shape
    n = packed.shape[1]
    mp, kp, np_ = round_up(m, bm), round_up(k, bk), round_up(n, bn)
    if (mp, kp, np_) != (m, k, n):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
        packed = jnp.pad(packed, ((0, (kp - k) * nbits // 8, ), (0, np_ - n)))
    return x, packed, (m, n)


def pad_codebook(codebook: jax.Array) -> jax.Array:
    """Zero-pad the active centroids up to the kernel's KC=16 capacity.
    Padded slots decode to 0 and are never referenced by valid codes."""
    k = codebook.shape[0]
    if k == KC:
        return codebook.astype(jnp.float32)
    if k > KC:   # ValueError, not assert: must survive `python -O`
        raise ValueError(
            f"pad_codebook: codebook has K={k} centroids but the kernel "
            f"supports K<=KC={KC} (paper: distillation yields <16)")
    return jnp.pad(codebook.astype(jnp.float32), (0, KC - k))


@functools.partial(jax.jit, static_argnames=("interpret", "nbits"))
def lut_gemm(
    x: jax.Array,
    packed_codes: jax.Array,
    codebook: jax.Array,
    *,
    interpret: bool = True,
    nbits: int = 4,
) -> jax.Array:
    """Padded/blocked f32-activation LUT GEMM. interpret=True on CPU."""
    cb = pad_codebook(codebook)
    m, k = x.shape
    n = packed_codes.shape[1]
    kc = padded_d_in(k, nbits)
    if kc != k:  # group padding: packed codes carry zero-code tail rows
        x = jnp.pad(x, ((0, 0), (0, kc - k)))
        k = kc
    bm, bn, bk, _ = _blocks_for("lut_f32", m, k, n, nbits, interpret)
    xp, cp, (m0, n0) = pad_for_kernel(x, packed_codes, bm, bk, bn, nbits)
    y = lut_matmul_f32(xp, cp, cb, bm=bm, bn=bn, bk=bk, interpret=interpret,
                       nbits=nbits)
    return y[:m0, :n0]


@functools.partial(jax.jit, static_argnames=("interpret", "nbits"))
def lut_gemm_int8(
    q: jax.Array,
    packed_codes: jax.Array,
    codebook: jax.Array,
    act_scale: jax.Array,
    *,
    interpret: bool = True,
    nbits: int = 4,
) -> jax.Array:
    cb = pad_codebook(codebook)
    m, k = q.shape
    n = packed_codes.shape[1]
    kc = padded_d_in(k, nbits)
    if kc != k:
        q = jnp.pad(q, ((0, 0), (0, kc - k)))
        k = kc
    bm, bn, bk, _ = _blocks_for("lut_int8", m, k, n, nbits, interpret)
    qp, cp, (m0, n0) = pad_for_kernel(q, packed_codes, bm, bk, bn, nbits)
    y = lut_matmul_int8(qp, cp, cb, act_scale, bm=bm, bn=bn, bk=bk,
                        interpret=interpret, nbits=nbits)
    return y[:m0, :n0]


@functools.partial(jax.jit, static_argnames=("quantize", "interpret", "nbits"))
def lut_gemm_fused(
    x: jax.Array,            # (M, K) RAW activations (smoothing NOT applied)
    inv_scale: jax.Array,    # (K,) f32 — Eq. 11 fused multiplier
    packed_codes: jax.Array, # (packed_rows(K), N) uint8
    codebook: jax.Array,     # (K_active,) f32
    act_scale: jax.Array,    # () f32 s_q (pass 1.0 when quantize=False)
    *,
    quantize: bool = True,
    interpret: bool = True,
    nbits: int = 4,
) -> jax.Array:
    """Single-pass serving GEMM: smooth(+quant) fused into the LUT matmul's
    K loop — no standalone smooth/smooth_quant pass, no intermediate
    activation tensor in HBM. Decode shapes (M < 128) dispatch to the N-major
    GEMV variant (DESIGN.md §2 selection table). `nbits` is the packed
    tensor's width (DESIGN.md §10) — validated against the packed shape
    inside the kernel entry."""
    cb = pad_codebook(codebook)
    m, k = x.shape
    n = packed_codes.shape[1]
    kc = padded_d_in(k, nbits)
    if kc != k:  # group padding: packed codes carry zero-code tail rows
        x = jnp.pad(x, ((0, 0), (0, kc - k)))
        inv_scale = jnp.pad(inv_scale, (0, kc - k))
        k = kc
    variant = "lut_fused_gemv" if m < 128 else "lut_fused"
    bm, bn, bk, rows = _blocks_for(variant, m, k, n, nbits, interpret)
    xp, cp, (m0, n0) = pad_for_kernel(x, packed_codes, bm, bk, bn, nbits)
    invp = jnp.pad(inv_scale.astype(jnp.float32), (0, xp.shape[1] - k))
    if m < 128:
        y = lut_matmul_fused_gemv(xp, invp, cp, cb, quantize=quantize,
                                  bm=xp.shape[0], bn=bn, bk=bk,
                                  interpret=interpret, nbits=nbits)
    else:
        y = lut_matmul_fused(xp, invp, cp, cb, quantize=quantize,
                             bm=bm, bn=bn, bk=bk, m_chunk=rows,
                             interpret=interpret, nbits=nbits)
    y = y[:m0, :n0]
    return y * act_scale if quantize else y


# ---------------------------------------------------------------------------
# Fused multi-projection serving GEMM/GEMV (DESIGN.md §15)
# ---------------------------------------------------------------------------

def _multi_blocks(m: int, k: int, widths, nbits, interpret: bool):
    """(bm, bn, bk, rows) for a fused multi call (rows as in `_blocks_for`),
    or None when the projections' heuristic bn choices disagree (the
    wrapper then falls back to unfused calls so fused-vs-unfused
    bit-equality never depends on a re-tiling).

    Under the interpreter the per-projection heuristic is used directly —
    the SAME (bm, bk) every unfused call gets (they depend only on m and k)
    and the SAME bn (agreement enforced), which is what makes the fused
    output bit-equal to the unfused one on the CPU parity lanes. On a
    compiled backend a cached winner under the multi variant's own key
    (`lut_fused_multi[_gemv]`, P-aware VMEM budget) is served, else the
    same per-projection heuristic. Never measures (called while the
    wrapper's jit traces)."""
    bns = {autotune.heuristic_blocks(m, k, n)[1] for n in widths}
    if len(bns) > 1:
        return None
    bm, _, bk = autotune.heuristic_blocks(m, k, widths[0])
    bn = bns.pop()
    variant = "lut_fused_multi_gemv" if m < 128 else "lut_fused_multi"
    blocks = (bm, bn, bk)
    if not interpret:
        key = autotune.normalize_key(m, k, sum(widths), max(nbits), variant,
                                     jax.default_backend(), len(widths))
        blocks = autotune.get_cache().get(key) or blocks
    rows = _rows_per_decode(variant, m, blocks, max(nbits), len(widths))
    _log_blocks(variant, m, k, sum(widths), blocks, rows)
    return (*blocks, rows)


@functools.partial(
    jax.jit, static_argnames=("quantize", "interpret", "nbits"))
def lut_gemm_fused_multi(
    x: jax.Array,            # (M, K) RAW activations shared by P projections
    inv_stack: jax.Array,    # (P, K) f32 — per-projection Eq. 11 multipliers
    cb_stack: jax.Array,     # (P, KC) f32
    act_stack: jax.Array,    # (P,) f32 s_q per projection (1.0 where unused)
    *packed_list: jax.Array, # P × (packed_rows(K, nbits_p), n_p) uint8
    quantize: tuple,         # P × bool
    interpret: bool = True,
    nbits: tuple = (4,),
):
    """Single-launch multi-projection serving GEMM: every projection's
    smooth(+quant) and LUT contraction fused into ONE kernel walking the
    shared activation once (DESIGN.md §15). The caller guarantees the
    projections' heuristic bn agree (`_multi_blocks`); each projection's
    output segment is then bit-equal to its `lut_gemm_fused` result (same
    bm/bn/bk, same padding, same f32 op sequence per output column).
    Returns a tuple of P (M, n_p) arrays."""
    m, k = x.shape
    n_true = tuple(int(pk.shape[1]) for pk in packed_list)
    blocks = _multi_blocks(m, k, n_true, nbits, interpret)
    if blocks is None:
        raise ValueError("lut_gemm_fused_multi: projections disagree on bn; "
                         "caller must fall back to unfused calls")
    bm, bn, bk, rows = blocks
    # shared K padding: bk is a multiple of every packing group size, so
    # round_up(k, bk) covers each projection's group padding exactly as the
    # unfused wrapper's padded_d_in -> pad_for_kernel chain does
    kp, mp = round_up(k, bk), round_up(m, bm)
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    inv = inv_stack.astype(jnp.float32)
    if kp != k:
        inv = jnp.pad(inv, ((0, 0), (0, kp - k)))
    wps = tuple(round_up(n, bn) for n in n_true)
    padded = [
        jnp.pad(pk, ((0, kp * nb // 8 - pk.shape[0]), (0, wp - pk.shape[1])))
        for pk, wp, nb in zip(packed_list, wps, nbits)]
    kw = dict(widths=wps, quantize=quantize, bn=bn, bk=bk,
              interpret=interpret, nbits=nbits)
    if m < 128:
        y = lut_matmul_fused_multi_gemv(x, inv, cb_stack, *padded,
                                        bm=mp, **kw)
    else:
        y = lut_matmul_fused_multi(x, inv, cb_stack, *padded, bm=bm,
                                   m_chunk=rows, **kw)
    outs, off = [], 0
    for p, (wp, n0) in enumerate(zip(wps, n_true)):
        seg = y[:m, off:off + n0]
        outs.append(seg * act_stack[p] if quantize[p] else seg)
        off += wp
    return tuple(outs)


# ---------------------------------------------------------------------------
# Serving dispatch
# ---------------------------------------------------------------------------

_FORCED_MODE: Optional[str] = None  # None | "kernel" | "interpret" | "ref"


@contextlib.contextmanager
def lut_serving(mode: Optional[str]):
    """Force how clustered_linear dispatches inside the context:

      "kernel"    — compiled Pallas fused path (TPU)
      "interpret" — same kernels through the Pallas interpreter (CPU CI /
                    decode_bench --smoke: real kernel code, no TPU required)
      "ref"       — gather contraction (trainable, runs anywhere)
      None        — auto: kernel on TPU backends, ref elsewhere
    """
    global _FORCED_MODE
    prev, _FORCED_MODE = _FORCED_MODE, mode
    try:
        yield
    finally:
        _FORCED_MODE = prev


def packed_view(ct: ClusteredTensor) -> jax.Array:
    """The tensor's packed sub-byte codes (at ct.nbits per code), without any
    host round-trip.

    Preference order: the first-class `packed` field (computed once at
    compress time — this replaced an id-keyed host-side cache that synced the
    device every call and could alias a freed array's id); codes already
    stored packed (abstract/materialized serving trees); else a device-side
    repack traced into the caller's jit.
    """
    if ct.packed is not None:
        return ct.packed
    d_in = ct.smooth.shape[-1]
    if ct.codes.shape[-2] == packed_rows(d_in, ct.nbits):
        return ct.codes.astype(jnp.uint8)     # stored packed already
    return pack_codes_jax(ct.codes, ct.nbits)


def _transform_params(ct: ClusteredTensor):
    """(inv_scale, act_scale, quantize) for the fused kernel — precomputed
    fields when present, else derived from the smoothing vector alone."""
    quantize = ct.act_scale is not None
    if ct.inv_scale is not None:
        inv = ct.inv_scale
    else:
        inv = 1.0 / ct.smooth
        if quantize:
            inv = inv / ct.act_scale
    act = ct.act_scale if quantize else jnp.float32(1.0)
    return inv.astype(jnp.float32), act, quantize


def _resolve_mode(use_kernel: Optional[bool]) -> str:
    mode = _FORCED_MODE
    if use_kernel is not None:
        mode = "kernel" if use_kernel else "ref"
    if mode is None:
        mode = "kernel" if jax.default_backend() == "tpu" else "ref"
    return mode


# trace-time kernel-launch tracker (benchmarks/decode_bench.py): every LUT
# pallas_call the serving trace would launch per executed step appends a tag
# here while a `track_lut_launches` context is open. Counting happens at
# trace time — jit replays the same launch sequence every step, so one
# traced step IS the per-step launch count.
class LaunchLog(list):
    """The tags of the LUT launches traced in a `track_lut_launches`
    context, plus, for every kernel problem the wrappers resolved there,
    keyed (variant, m, k, n): `blocks`, its (bm, bn, bk), and
    `rows_per_decode`, the rows each decoded weight tile serves — the fused
    GEMMs' M chunk, one M block elsewhere (a GEMV's whole M). The jitted
    wrappers resolve blocks only when they first trace a shape, so open the
    context before the process's first trace of the step to see them all."""

    def __init__(self):
        super().__init__()
        self.blocks: Dict[Tuple[str, int, int, int], Tuple[int, ...]] = {}
        self.rows_per_decode: Dict[Tuple[str, int, int, int], int] = {}


_LAUNCH_LOG: Optional[LaunchLog] = None


@contextlib.contextmanager
def track_lut_launches():
    """Collect the LUT kernel launches of everything traced inside the
    context; yields the LaunchLog of tags (e.g. 'fused_multi[3]', 'fused')."""
    global _LAUNCH_LOG
    prev, _LAUNCH_LOG = _LAUNCH_LOG, LaunchLog()
    try:
        yield _LAUNCH_LOG
    finally:
        _LAUNCH_LOG = prev


def _log_launch(tag: str) -> None:
    if _LAUNCH_LOG is not None:
        _LAUNCH_LOG.append(tag)


def _log_blocks(variant: str, m: int, k: int, n: int, blocks,
                rows: int) -> None:
    if _LAUNCH_LOG is None:
        return
    _LAUNCH_LOG.blocks[(variant, m, k, n)] = tuple(blocks)
    _LAUNCH_LOG.rows_per_decode[(variant, m, k, n)] = rows


# ---------------------------------------------------------------------------
# Kernels on a multi-device serving mesh (DESIGN.md §14)
# ---------------------------------------------------------------------------

def _model_split(split: Optional[str], mesh, k: int, ns) -> Optional[str]:
    """The weight dim the mesh's model axis shards, as `auto_shard` placed
    it: "out" (column-parallel: N of every projection divides), "in"
    (row-parallel: K divides into whole packing groups, so every packed
    shard starts on a group boundary), or None (replicated)."""
    mp = mesh.shape.get("model", 1)
    if mp == 1 or split is None:
        return None
    if split == "out" and all(n % mp == 0 for n in ns):
        return "out"
    if split == "in" and k % mp == 0 and (k // mp) % 8 == 0:
        return "in"
    return None


def _per_device(fn, mesh, split: Optional[str]):
    """Wrap `fn(x2, inv, cb, act, *packed) -> tuple of outputs` to run per
    device of `mesh` under shard_map. The rows of x2 (batch or request-slot
    rows) stay split over the data axes as the rules place them, so each
    device computes only its own rows. Column-parallel weights compute
    their local output columns; row-parallel weights contract their local K
    slice (x2 columns, the last dim of `inv`, packed rows) and psum the f32
    partials over the model axis; other weights run replicated over the
    model axis."""
    def call(x2, inv, cb, act, *packed):
        n_ops = len(packed)
        rows = logical_to_spec(x2.shape[:1], ("batch",))[0]
        if split == "out":
            in_specs = (P(rows), P(), P(), P()) + (P(None, "model"),) * n_ops
            out_specs = (P(rows, "model"),) * n_ops
            body = fn
        elif split == "in":
            k_spec = P(*(None,) * (inv.ndim - 1), "model")
            in_specs = ((P(rows, "model"), k_spec, P(), P())
                        + (P("model", None),) * n_ops)
            out_specs = (P(rows),) * n_ops

            def body(*args):
                return tuple(jax.lax.psum(y, "model") for y in fn(*args))
        else:
            in_specs = (P(rows),) + (P(),) * (3 + n_ops)
            out_specs = (P(rows),) * n_ops
            body = fn
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
            x2, inv, cb, act, *packed)
    return call


def clustered_linear(
    x: jax.Array,
    ct: ClusteredTensor,
    *,
    use_kernel: Optional[bool] = None,
    split: Optional[str] = None,
) -> jax.Array:
    """Model-facing clustered matmul. use_kernel=None auto-selects (see
    lut_serving): the fused Pallas path on TPU backends, the gather
    contraction elsewhere (identical numerics).

    `split` names the weight dim a serving mesh's model axis shards —
    "out" for column-parallel, "in" for row-parallel projections
    (DESIGN.md §14); on a multi-device mesh the kernel then runs per device
    on its local shard (`_per_device`). None runs it replicated there."""
    mode = _resolve_mode(use_kernel)
    if mode == "ref" or ct.codebook.ndim != 1:
        # stacked/expert codebooks take the gather path (vmapped in models)
        return clustered_matmul(x, ct)
    inv, act, quantize = _transform_params(ct)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    packed = packed_view(ct)
    _log_launch("fused")

    def run(x2, inv, cb, act, packed):
        return (lut_gemm_fused(x2, inv, packed, cb, act, quantize=quantize,
                               interpret=(mode == "interpret"),
                               nbits=ct.nbits),)

    mesh = kernel_mesh()
    if mesh is not None:
        run = _per_device(run, mesh, _model_split(
            split, mesh, x2.shape[1], (packed.shape[1],)))
    (y,) = run(x2, inv, ct.codebook, act, packed)
    return y.reshape(*lead, -1).astype(x.dtype)


def clustered_linear_multi(
    x: jax.Array,
    cts,
    *,
    use_kernel: Optional[bool] = None,
):
    """Model-facing MULTI-projection clustered matmul: P projections sharing
    the input x (QKV; gate+up) served by ONE fused kernel launch
    (DESIGN.md §15). Returns a tuple of P outputs, each bit-equal to the
    corresponding `clustered_linear(x, ct)` — per-projection nbits may
    differ (mixed-precision layers fuse too).

    Falls back to per-projection `clustered_linear` calls whenever the
    single-kernel form can't hold the bit-equality contract or the kernel
    path isn't in play: ref mode, stacked/expert codebooks, a single
    projection, or projections whose heuristic bn disagree."""
    cts = tuple(cts)
    mode = _resolve_mode(use_kernel)
    mesh = kernel_mesh()
    fusable = (mode != "ref" and len(cts) > 1
               and all(ct.codebook.ndim == 1 for ct in cts))
    if fusable:
        m = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
        n_true = tuple(int(packed_view(ct).shape[1]) for ct in cts)
        nbits = tuple(ct.nbits for ct in cts)
        split = (None if mesh is None
                 else _model_split("out", mesh, x.shape[-1], n_true))
        if split == "out":       # the kernel sees each device's columns
            n_true = tuple(n // mesh.shape["model"] for n in n_true)
        fusable = _multi_blocks(m, x.shape[-1], n_true, nbits, True) is not None
    if not fusable:
        return tuple(clustered_linear(x, ct, use_kernel=use_kernel,
                                      split="out") for ct in cts)
    params = [_transform_params(ct) for ct in cts]
    inv_stack = jnp.stack([inv for inv, _, _ in params])
    act_stack = jnp.stack([act.astype(jnp.float32) for _, act, _ in params])
    cb_stack = jnp.stack([pad_codebook(ct.codebook) for ct in cts])
    quantize = tuple(qz for _, _, qz in params)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    _log_launch(f"fused_multi[{len(cts)}]")

    def run(x2, inv_stack, cb_stack, act_stack, *packed):
        return lut_gemm_fused_multi(
            x2, inv_stack, cb_stack, act_stack, *packed, quantize=quantize,
            interpret=(mode == "interpret"), nbits=nbits)

    if mesh is not None:
        run = _per_device(run, mesh, split)
    ys = run(x2, inv_stack, cb_stack, act_stack,
             *[packed_view(ct) for ct in cts])
    return tuple(y.reshape(*lead, -1).astype(x.dtype) for y in ys)
