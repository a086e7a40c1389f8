"""Pallas TPU kernel: fused sub-byte-code dequant + matmul (the LCD serving GEMM).

TPU-native translation of the paper's §4 bucket-LUT GEMM (DESIGN.md §2, §10):

  * weights arrive as *packed centroid codes* at a static `nbits` ∈ {2, 3, 4}
    per code (core/lut.py packing contract: 2 codes/byte at 4-bit, 8 codes in
    3 bytes at 3-bit, 4 codes/byte at 2-bit) — ⅛·nbits the HBM bytes of bf16
    (¼ at 4-bit down to ⅛ at 2-bit), which is the entire speedup for
    memory-bound decode GEMVs: the packed stream is the only operand advancing
    with the GEMV grid, so a 2-bit tensor moves HALF the bytes of the int4
    layout per token;
  * the codebook (K ≤ 2^nbits ≤ 16 floats) lives in VMEM/registers for the
    whole kernel;
  * the "table lookup" is realized as a branch-free select-sum
        w[i,j] = Σ_k  c_k * (code[i,j] == k)
    over the 2^nbits codebook entries — the TPU-idiomatic equivalent of a LUT
    read (VPU compare+FMA, no gather, no serialization); narrower widths do
    proportionally fewer selects;
  * the dequantized bf16 tile feeds a standard MXU matmul against the
    activation tile; accumulation in f32 scratch across the K grid dimension.

Four entry points:
  lut_matmul_f32  — float activations (already smoothed), weights = codebook[codes].
  lut_matmul_int8 — int8 activation indices q (Eq. 11 output) with the activation
                    scale folded in at the end: Y = s_q * (q @ codebook[codes]);
                    bit-identical to the paper's signed bucket accumulation.
  lut_matmul_fused      — single-pass serving GEMM (DESIGN.md §2): the Eq. 11
                    input transformation q = clip(round(x · inv_scale)) runs
                    inside the first pipeline stage of every K-step, so the
                    smoothed/quantized activation tile lives only in VMEM and
                    never round-trips HBM (the seed ran smooth-divide,
                    smooth_quant and the LUT GEMM as three HBM-bound passes).
                    M runs innermost in its grid, so each weight tile is
                    decoded once per VMEM-sized chunk of rows and shared by
                    every 128-row M block of it.
  lut_matmul_fused_gemv — decode specialization of the fused kernel for
                    M < 128 (auto-regressive GEMV): the M grid dimension is
                    collapsed into a single sublane-aligned block and the grid
                    becomes N-major (N/bn, K/bk); the Pallas pipeline then
                    double-buffers the packed-code stream — the only HBM-bound
                    operand of a decode step — across consecutive grid steps
                    while the MXU consumes the previous tile.

Block shapes default to MXU-aligned (128 multiples); the K (=d_in) dimension is
streamed, and `autotune.vmem_bytes` counts the VMEM working set a launch holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import SUPPORTED_NBITS
from repro.kernels import autotune

# Codebook capacity the kernel is specialized for: ≤4-bit codes (paper: K < 16
# after distillation -> compact sub-byte representation, §4.2). Codebooks are
# always padded to KC entries; an nbits-wide tensor references the first
# 2^nbits of them. The width set (SUPPORTED_NBITS, imported above) comes from
# the packing contract's single source of truth, core/lut.py.
KC = 16


def _check_packed_shape(k: int, packed_shape, nbits: int, caller: str) -> None:
    """Explicit shape validation for the packed-code operand. A ValueError —
    not a bare assert, which `python -O` strips — naming the packing width
    and the offending shapes, so a 2-bit tensor routed through a 4-bit call
    site fails loudly instead of streaming garbage codes."""
    if nbits not in SUPPORTED_NBITS:
        raise ValueError(
            f"{caller}: nbits must be one of {SUPPORTED_NBITS}; got {nbits}")
    k2 = packed_shape[0]
    if k2 * 8 != k * nbits:
        raise ValueError(
            f"{caller}: packed codes have {k2} rows but K={k} at "
            f"{nbits}-bit packing needs K*nbits/8 = {k * nbits / 8:g} "
            f"(packed shape {tuple(packed_shape)}); did the activation and "
            f"the packed tensor disagree on the packing width?")


def _decode_tile(packed_ref, codebook, bk: int, bn: int, out_dtype,
                 nbits: int = 4):
    """Unpack a (bk*nbits//8, bn) uint8 tile -> (bk, bn) codes -> dequantized
    tile, at a static packing width (core/lut.py layout contract).

    Select-sum over the 2^nbits codebook entries; compare+FMA on the VPU. The
    interleaves use stack/reshape which lower to cheap vector shuffles; the
    3-bit variant first splices each 3-byte group into one 24-bit word.
    """
    # widen before any shift or mask: Mosaic cannot legalize shifts on
    # uint8 vectors, and the int32 fields are the same bits
    packed = packed_ref[...].astype(jnp.int32)            # (bk*nbits//8, bn)
    if nbits == 4:
        lo = packed & 0xF
        hi = packed >> 4
        codes = jnp.stack([lo, hi], axis=1).reshape(bk, bn)  # row 2i->lo, 2i+1->hi
    elif nbits == 2:
        parts = [(packed >> (2 * j)) & 0x3 for j in range(4)]
        codes = jnp.stack(parts, axis=1).reshape(bk, bn)  # row 4i+j -> field j
    else:  # nbits == 3: rows [3g, 3g+1, 3g+2] are one 24-bit little-endian word
        grp = packed.reshape(bk // 8, 3, bn)
        word = grp[:, 0] | (grp[:, 1] << 8) | (grp[:, 2] << 16)
        parts = [(word >> (3 * j)) & 0x7 for j in range(8)]
        codes = jnp.stack(parts, axis=1).reshape(bk, bn)  # row 8g+j -> field j
    w = jnp.zeros((bk, bn), jnp.float32)
    for k in range(1 << nbits):
        w += jnp.where(codes == k, codebook[k], 0.0)
    return w.astype(out_dtype)


def _lut_matmul_kernel(x_ref, packed_ref, cb_ref, o_ref, acc_ref, *, bk: int, bn: int,
                       nsteps: int, int8_act: bool, nbits: int):
    """grid = (M/bm, N/bn, K/bk); K innermost so acc_ref carries partials."""
    ks = pl.program_id(2)

    @pl.when(ks == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cb = cb_ref[...]                                      # (KC,) f32 in SMEM/VMEM
    w = _decode_tile(packed_ref, cb, bk, bn, jnp.float32, nbits)
    x = x_ref[...]
    if int8_act:
        x = x.astype(jnp.float32)                         # int8 -> f32 for MXU input
    acc_ref[...] += jnp.dot(
        x.astype(jnp.float32), w, preferred_element_type=jnp.float32
    )

    @pl.when(ks == nsteps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _resolve_blocks(m, k, n, nbits, variant, interpret, bm, bn, bk):
    """Fill in None block args from the autotuner (DESIGN.md §11): the cached
    measured winner for this (shape, nbits, backend) key when one exists,
    else the deterministic heuristic. No measurement happens at this layer —
    the ops.py wrappers own the measure closure; explicit block args always
    win (tests sweep them)."""
    if bm is not None and bn is not None and bk is not None:
        return bm, bn, bk
    tb = autotune.pick_blocks(m, k, n, nbits=nbits, variant=variant,
                              interpret=interpret)
    return bm or tb[0], bn or tb[1], bk or tb[2]


def _check_blocks(m, k, n, bm, bk, bn, nbits, caller):
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"{caller}: pad shapes to block multiples: {(m, k, n)} vs "
            f"{(bm, bk, bn)}")
    if (bk * nbits) % 8:
        raise ValueError(
            f"{caller}: bk={bk} must cover whole packing groups at "
            f"{nbits}-bit (bk*nbits divisible by 8)")


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype", "nbits")
)
def lut_matmul_f32(
    x: jax.Array,            # (M, K) float (bf16/f32) — pre-smoothed activations
    packed_codes: jax.Array, # (K*nbits//8, N) uint8 — packed centroid codes
    codebook: jax.Array,     # (KC,) f32 — padded with zeros beyond the active K
    *,
    bm: int = None,          # None -> autotuned cache / heuristic (DESIGN.md §11)
    bn: int = None,
    bk: int = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
    nbits: int = 4,
) -> jax.Array:
    """Y = x @ codebook[codes]  with codes streamed packed at `nbits`/code."""
    m, k = x.shape
    n = packed_codes.shape[1]
    bm, bn, bk = _resolve_blocks(m, k, n, nbits, "lut_f32", interpret,
                                 bm, bn, bk)
    _check_packed_shape(k, packed_codes.shape, nbits, "lut_matmul_f32")
    if codebook.shape != (KC,):
        raise ValueError(f"codebook must be padded to ({KC},); got "
                         f"{codebook.shape}")
    _check_blocks(m, k, n, bm, bk, bn, nbits, "lut_matmul_f32")
    nsteps = k // bk
    grid = (m // bm, n // bn, nsteps)
    kernel = functools.partial(
        _lut_matmul_kernel, bk=bk, bn=bn, nsteps=nsteps, int8_act=False,
        nbits=nbits,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk * nbits // 8, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((KC,), lambda i, j, s: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, packed_codes, codebook)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype", "nbits")
)
def lut_matmul_int8(
    q: jax.Array,            # (M, K) int8 — Eq. 11 activation indices
    packed_codes: jax.Array, # (K*nbits//8, N) uint8
    codebook: jax.Array,     # (KC,) f32 centroids of the smoothed weights
    act_scale: jax.Array,    # scalar f32 — s_q
    *,
    bm: int = None,          # None -> autotuned cache / heuristic (DESIGN.md §11)
    bn: int = None,
    bk: int = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
    nbits: int = 4,
) -> jax.Array:
    """Y = s_q * (q @ codebook[codes]) — the paper's bucket accumulation."""
    m, k = q.shape
    n = packed_codes.shape[1]
    bm, bn, bk = _resolve_blocks(m, k, n, nbits, "lut_int8", interpret,
                                 bm, bn, bk)
    _check_packed_shape(k, packed_codes.shape, nbits, "lut_matmul_int8")
    if codebook.shape != (KC,):
        raise ValueError(f"codebook must be padded to ({KC},); got "
                         f"{codebook.shape}")
    _check_blocks(m, k, n, bm, bk, bn, nbits, "lut_matmul_int8")
    nsteps = k // bk
    grid = (m // bm, n // bn, nsteps)
    kernel = functools.partial(
        _lut_matmul_kernel, bk=bk, bn=bn, nsteps=nsteps, int8_act=True,
        nbits=nbits,
    )
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk * nbits // 8, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((KC,), lambda i, j, s: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(q, packed_codes, codebook)
    return (y * act_scale).astype(out_dtype)


# ---------------------------------------------------------------------------
# Fused smooth+quant+LUT serving GEMM (Eq. 11 folded into the K loop)
# ---------------------------------------------------------------------------

def _transform_tile(x_ref, inv_ref, quantize: bool):
    """Eq. 11 input transformation on one (bm, bk) VMEM tile.

    quantize=True : q = clip(round(x · inv), ±127) with inv = 1/(s_m·s_q) —
                    symmetric clip so |q| ≤ 127 (the bucket-table contract,
                    core/lut.py); q stays f32 in VMEM (values are exact ints).
    quantize=False: xs = x · inv with inv = 1/s_m — the smoothing divide only,
                    for uncalibrated tensors (no activation scale known).
    """
    x = x_ref[...].astype(jnp.float32)
    inv = inv_ref[...].astype(jnp.float32)           # (1, bk), broadcasts rows
    xs = x * inv
    if quantize:
        xs = jnp.clip(jnp.round(xs), -127.0, 127.0)
    return xs


def _grid_pos(gemm: bool, bm: int):
    """(N block j, K step, M block i of the chunk, accumulator rows of
    block i) of the current grid step.

    GEMV grid (N/bn, K/bk): one resident M block owns the whole accumulator.
    GEMM grid (M/m_chunk, N/bn, K/bk, m_chunk/bm): M innermost, so the
    accumulator and the output block hold a chunk of m_chunk rows, and
    block i owns its rows i*bm .. i*bm + bm."""
    if not gemm:
        return pl.program_id(0), pl.program_id(1), 0, slice(None)
    i = pl.program_id(3)
    return (pl.program_id(1), pl.program_id(2), i,
            pl.ds(pl.multiple_of(i * bm, bm), bm))


def _decoded_tile(decode, w_ref, i):
    """The grid step's (bk, bn) f32 weight tile. Without the scratch `w_ref`
    (a GEMV, or a GEMM chunk of one M block) it is decoded in place. With
    it, it is decoded into `w_ref` at the chunk's first M block and read
    back by every M block of the chunk, so a launch decodes each tile once
    per chunk, not once per bm rows."""
    if w_ref is None:
        return decode()

    @pl.when(i == 0)
    def _decode():
        w_ref[...] = decode()

    return w_ref[...]


def _fused_kernel(x_ref, inv_ref, packed_ref, cb_ref, o_ref, acc_ref,
                  w_ref=None, *, gemm: bool, bm: int, bk: int, bn: int,
                  nsteps: int, quantize: bool, nbits: int):
    """One body for both fused variants (grids in `_grid_pos`); a GEMM with
    more than one M block per chunk passes the decoded-tile scratch `w_ref`
    (`_gemm_layout`). K advances outside M, so each M block's accumulator
    rows carry its partials across the K steps. Every output row sees the
    same f32 op sequence in both layouts and at any chunk size:
    acc += dot(transform(x), w) over the K steps in order, at one
    (bm, bk) x (bk, bn) shape."""
    _, ks, i, rows = _grid_pos(gemm, bm)

    @pl.when(ks == 0)
    def _init():
        acc_ref[rows, :] = jnp.zeros((bm, bn), jnp.float32)

    xs = _transform_tile(x_ref, inv_ref, quantize)
    w = _decoded_tile(lambda: _decode_tile(packed_ref, cb_ref[...], bk, bn,
                                           jnp.float32, nbits), w_ref, i)
    acc_ref[rows, :] += jnp.dot(xs, w, preferred_element_type=jnp.float32)

    @pl.when(ks == nsteps - 1)
    def _done():
        o_ref[rows, :] = acc_ref[rows, :].astype(o_ref.dtype)


def _check_chunk(m: int, bm: int, m_chunk: int) -> None:
    if m_chunk % bm or m % m_chunk:
        raise ValueError(
            f"m_chunk={m_chunk} must be a multiple of bm={bm} that divides "
            f"M={m}")


def _gemm_layout(m: int, n: int, k: int, bm: int, bn: int, bk: int,
                 m_chunk: int):
    """Grid, activation and output BlockSpecs and scratch of the GEMM
    variants (axes (c, j, s, i) as in `_grid_pos`): an x block of bm rows
    per grid step, an output block and f32 accumulator of m_chunk rows that
    stay resident across the chunk's K steps and M blocks, and, when the
    chunk holds more than one M block, the (bk, bn) decoded-tile scratch
    they share. A chunk of one block (m_chunk == bm) is the per-M-block
    layout: grid (M/bm, N/bn, K/bk, 1), each tile decoded in place."""
    _check_chunk(m, bm, m_chunk)
    nblk = m_chunk // bm
    grid = (m // m_chunk, n // bn, k // bk, nblk)
    x_spec = pl.BlockSpec((bm, bk), lambda c, j, s, i: (c * nblk + i, s))
    out_spec = pl.BlockSpec((m_chunk, bn), lambda c, j, s, i: (c, j))
    scratch = [pltpu.VMEM((m_chunk, bn), jnp.float32)]
    if nblk > 1:
        scratch.append(pltpu.VMEM((bk, bn), jnp.float32))
    return grid, x_spec, out_spec, scratch


@functools.partial(
    jax.jit,
    static_argnames=("quantize", "bm", "bn", "bk", "interpret", "out_dtype",
                     "nbits", "m_chunk")
)
def lut_matmul_fused(
    x: jax.Array,            # (M, K) float — RAW activations (not smoothed)
    inv_scale: jax.Array,    # (K,) f32 = 1/(s_m·s_q) (quantize) or 1/s_m
    packed_codes: jax.Array, # (K*nbits//8, N) uint8 — packed centroid codes
    codebook: jax.Array,     # (KC,) f32 — padded with zeros beyond the active K
    *,
    quantize: bool = True,
    bm: int = None,          # None -> autotuned cache / heuristic (DESIGN.md §11)
    bn: int = None,
    bk: int = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
    nbits: int = 4,
    m_chunk: int = None,     # rows sharing one decoded tile; None -> bm
) -> jax.Array:
    """Y = transform(x) @ codebook[codes], transform fused into every K-step.

    Grid (M/m_chunk, N/bn, K/bk, m_chunk/bm), M innermost: each (bk, bn)
    weight tile is decoded once per chunk of m_chunk rows into VMEM and
    every bm-row block of the chunk multiplies against it, so a wide call
    decodes the weights M/m_chunk times, not M/bm times (`_grid_pos`). The
    ops.py wrapper passes the chunk the VMEM budget allows
    (`autotune.m_chunk`).

    The caller applies the trailing s_q rescale (quantize=True); XLA fuses that
    scalar multiply into the output copy, so the pipeline is one kernel + one
    epilogue — no intermediate activation tensor in HBM.
    """
    m, k = x.shape
    n = packed_codes.shape[1]
    bm, bn, bk = _resolve_blocks(m, k, n, nbits, "lut_fused", interpret,
                                 bm, bn, bk)
    _check_packed_shape(k, packed_codes.shape, nbits, "lut_matmul_fused")
    if inv_scale.shape != (k,):
        raise ValueError(f"inv_scale must be ({k},); got {inv_scale.shape}")
    if codebook.shape != (KC,):
        raise ValueError(f"codebook must be padded to ({KC},); got "
                         f"{codebook.shape}")
    _check_blocks(m, k, n, bm, bk, bn, nbits, "lut_matmul_fused")
    nsteps = k // bk
    kernel = functools.partial(
        _fused_kernel, gemm=True, bm=bm, bk=bk, bn=bn, nsteps=nsteps,
        quantize=quantize, nbits=nbits,
    )
    grid, x_spec, out_spec, scratch = _gemm_layout(m, n, k, bm, bn, bk,
                                                   m_chunk or bm)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            x_spec,
            pl.BlockSpec((1, bk), lambda c, j, s, i: (0, s)),
            pl.BlockSpec((bk * nbits // 8, bn), lambda c, j, s, i: (s, j)),
            pl.BlockSpec((KC,), lambda c, j, s, i: (0,)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, inv_scale[None, :], packed_codes, codebook)


@functools.partial(
    jax.jit,
    static_argnames=("quantize", "bm", "bn", "bk", "interpret", "out_dtype",
                     "nbits")
)
def lut_matmul_fused_gemv(
    x: jax.Array,            # (M, K), M = bm < 128 (decode micro-batch, padded to 8)
    inv_scale: jax.Array,    # (K,) f32
    packed_codes: jax.Array, # (K*nbits//8, N) uint8
    codebook: jax.Array,     # (KC,) f32
    *,
    quantize: bool = True,
    bm: int = None,          # None -> M (one resident block); bn/bk autotuned
    bn: int = None,
    bk: int = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
    nbits: int = 4,
) -> jax.Array:
    """Decode-specialized fused GEMV: one M block, N-major grid (N/bn, K/bk).

    For M < 128 the general kernel wastes an entire grid dimension and pads M
    to the MXU tile; here M collapses to a single sublane-aligned block kept
    resident in VMEM for the whole call while packed codes stream through —
    the only operand advancing with the grid, which the Pallas pipeline
    double-buffers (next (s, j) tile DMA overlaps the current tile's
    decode+FMA) — the memory-bound regime where sub-byte codes buy the
    paper's 6.2x, and where a 2-bit tensor streams HALF the bytes per token
    of the int4 layout (DESIGN.md §10). Same kernel body as the GEMM variant
    (without the decoded-tile scratch), so the two stay numerically locked
    together.
    """
    m, k = x.shape
    n = packed_codes.shape[1]
    if bm is None:
        bm = m
    _, bn, bk = _resolve_blocks(m, k, n, nbits, "lut_fused_gemv", interpret,
                                bm, bn, bk)
    if m != bm or bm > 128:
        raise ValueError(
            f"lut_matmul_fused_gemv: M ({m}) must equal bm ({bm}) <= 128")
    _check_packed_shape(k, packed_codes.shape, nbits, "lut_matmul_fused_gemv")
    if inv_scale.shape != (k,):
        raise ValueError(f"inv_scale must be ({k},); got {inv_scale.shape}")
    if codebook.shape != (KC,):
        raise ValueError(f"codebook must be padded to ({KC},); got "
                         f"{codebook.shape}")
    _check_blocks(bm, k, n, bm, bk, bn, nbits, "lut_matmul_fused_gemv")
    nsteps = k // bk
    grid = (n // bn, nsteps)
    kernel = functools.partial(
        _fused_kernel, gemm=False, bm=bm, bk=bk, bn=bn, nsteps=nsteps,
        quantize=quantize, nbits=nbits,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, s: (0, s)),
            pl.BlockSpec((1, bk), lambda j, s: (0, s)),
            pl.BlockSpec((bk * nbits // 8, bn), lambda j, s: (s, j)),
            pl.BlockSpec((KC,), lambda j, s: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, s: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, inv_scale[None, :], packed_codes, codebook)


# ---------------------------------------------------------------------------
# Fused MULTI-projection serving GEMM/GEMV (QKV, gate+up share one input)
# ---------------------------------------------------------------------------

def _fused_multi_kernel(x_ref, inv_ref, cb_ref, *rest, gemm: bool, bm: int,
                        bk: int, bn: int, nsteps: int, quantize, nbits,
                        bounds):
    """One body for both fused multi variants: P projections sharing the
    activation tile, concatenated along N. `bounds[p] = (s0, nblk)` is
    projection p's N-block segment (static — projection widths are shapes).

    Each grid step serves exactly one projection: the one whose segment the
    N-block index `j` falls in. Its Eq. 11 transform and select-sum decode
    run under a `pl.when` guard, so the activation tile is transformed with
    that projection's inv row and accumulated against that projection's
    codes — per output column this is the identical f32 op sequence the
    single-projection `_fused_kernel` performs at the same (bk, bn), which
    is what makes the fused path bit-equal to the unfused one. Dead
    projections' packed operands hold a frozen block index (their index map
    clamps), so Pallas never re-DMAs them.

    `quantize` and `nbits` are per-projection tuples: a mixed-precision
    layer (wq at 4-bit, wk/wv demoted to 2-bit) still fuses into ONE kernel
    launch — each packed operand unpacks at its own static width.

    A GEMM with more than one M block per chunk passes the decoded-tile
    scratch after the accumulator, and decodes only the live projection's
    tile, once per M chunk, as `_fused_kernel` does.
    """
    n_proj = len(bounds)
    packed_refs = rest[:n_proj]
    o_ref, acc_ref, *w_ref = rest[n_proj:]
    w_ref = w_ref[0] if w_ref else None
    j, ks, i, rows = _grid_pos(gemm, bm)

    @pl.when(ks == 0)
    def _init():
        acc_ref[rows, :] = jnp.zeros((bm, bn), jnp.float32)

    x = x_ref[...].astype(jnp.float32)
    inv = inv_ref[...].astype(jnp.float32)                # (P, bk)
    cb = cb_ref[...]                                      # (P, KC)
    for p, (s0, nblk) in enumerate(bounds):
        @pl.when((j >= s0) & (j < s0 + nblk))
        def _proj(p=p):
            xs = x * inv[p][None, :]
            if quantize[p]:
                xs = jnp.clip(jnp.round(xs), -127.0, 127.0)
            w = _decoded_tile(
                lambda: _decode_tile(packed_refs[p], cb[p], bk, bn,
                                     jnp.float32, nbits[p]), w_ref, i)
            acc_ref[rows, :] += jnp.dot(xs, w,
                                        preferred_element_type=jnp.float32)

    @pl.when(ks == nsteps - 1)
    def _done():
        o_ref[rows, :] = acc_ref[rows, :].astype(o_ref.dtype)


def _multi_segments(widths, bn: int):
    """(s0, nblk) N-block segment per projection of the concatenated output."""
    bounds, s0 = [], 0
    for w in widths:
        nblk = w // bn
        bounds.append((s0, nblk))
        s0 += nblk
    return tuple(bounds)


def _check_multi(x, inv_stack, cb_stack, packed_list, widths, quantize,
                 nbits, bm, bn, bk, caller):
    m, k = x.shape
    n_proj = len(packed_list)
    if not (len(widths) == len(quantize) == len(nbits) == n_proj > 0):
        raise ValueError(
            f"{caller}: {n_proj} packed operands but widths={widths}, "
            f"quantize={quantize}, nbits={nbits}")
    if inv_stack.shape != (n_proj, k):
        raise ValueError(f"{caller}: inv_stack must be ({n_proj}, {k}); got "
                         f"{inv_stack.shape}")
    if cb_stack.shape != (n_proj, KC):
        raise ValueError(f"{caller}: cb_stack must be ({n_proj}, {KC}); got "
                         f"{cb_stack.shape}")
    for p in range(n_proj):
        _check_packed_shape(k, packed_list[p].shape, nbits[p], caller)
        if packed_list[p].shape[1] != widths[p]:
            raise ValueError(
                f"{caller}: projection {p} packed N={packed_list[p].shape[1]}"
                f" != width {widths[p]}")
        if widths[p] % bn:
            raise ValueError(
                f"{caller}: projection {p} width {widths[p]} must be a "
                f"multiple of bn={bn} (the wrapper pads each projection)")
        if (bk * nbits[p]) % 8:
            raise ValueError(
                f"{caller}: bk={bk} must cover whole packing groups at "
                f"{nbits[p]}-bit (bk*nbits divisible by 8)")
    if m % bm or k % bk:
        raise ValueError(
            f"{caller}: pad shapes to block multiples: {(m, k)} vs "
            f"{(bm, bk)}")


def _packed_multi_spec(s0: int, nblk: int, rows: int, bn: int, gemv: bool):
    """BlockSpec for one projection's packed codes in the multi grid: inside
    its N segment the K-block index advances with the grid; outside it the
    index FREEZES at (0, nearest-edge) so the dead operand is never
    re-DMA'd (Pallas skips the copy when the block index repeats)."""
    if gemv:
        def imap(j, s):
            live = (j >= s0) & (j < s0 + nblk)
            return (jnp.where(live, s, 0), jnp.clip(j - s0, 0, nblk - 1))
    else:
        def imap(c, j, s, i):
            live = (j >= s0) & (j < s0 + nblk)
            return (jnp.where(live, s, 0), jnp.clip(j - s0, 0, nblk - 1))
    return pl.BlockSpec((rows, bn), imap)


@functools.partial(
    jax.jit,
    static_argnames=("widths", "quantize", "bm", "bn", "bk", "interpret",
                     "out_dtype", "nbits", "m_chunk"))
def lut_matmul_fused_multi(
    x: jax.Array,            # (M, K) RAW activations shared by all projections
    inv_stack: jax.Array,    # (P, K) f32 — per-projection Eq. 11 multipliers
    cb_stack: jax.Array,     # (P, KC) f32 — per-projection padded codebooks
    *packed_list: jax.Array, # P × (K*nbits_p//8, widths[p]) uint8
    widths: tuple,           # per-projection output width (multiple of bn)
    quantize: tuple,         # per-projection Eq. 11 quantize flag
    bm: int = None,
    bn: int = None,
    bk: int = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
    nbits: tuple = (4,),     # per-projection packing width
    m_chunk: int = None,     # rows sharing one decoded tile; None -> bm
) -> jax.Array:
    """Y = concat_p(transform_p(x) @ codebook_p[codes_p]) in ONE kernel.

    The activation tile is read (and smoothed/quantized) once per K-step and
    reused by whichever projection owns the current N segment — one kernel
    launch and one activation stream replace P of each, which is the entire
    win for decode QKV / gate+up (DESIGN.md §15). The grid is
    `lut_matmul_fused`'s: each live tile is decoded once per m_chunk rows.
    Returns (M, Σ widths); the ops.py wrapper splits segments and applies
    per-projection act_scale.
    """
    m, k = x.shape
    n = sum(widths)
    if bm is None or bn is None or bk is None:
        tb = autotune.pick_blocks(m, k, n, nbits=max(nbits),
                                  variant="lut_fused_multi",
                                  interpret=interpret, n_ops=len(widths))
        bm, bn, bk = bm or tb[0], bn or tb[1], bk or tb[2]
    _check_multi(x, inv_stack, cb_stack, packed_list, widths, quantize,
                 nbits, bm, bn, bk, "lut_matmul_fused_multi")
    nsteps = k // bk
    bounds = _multi_segments(widths, bn)
    kernel = functools.partial(
        _fused_multi_kernel, gemm=True, bm=bm, bk=bk, bn=bn, nsteps=nsteps,
        quantize=quantize, nbits=nbits, bounds=bounds)
    grid, x_spec, out_spec, scratch = _gemm_layout(m, n, k, bm, bn, bk,
                                                   m_chunk or bm)
    in_specs = [
        x_spec,
        pl.BlockSpec((len(widths), bk), lambda c, j, s, i: (0, s)),
        pl.BlockSpec((len(widths), KC), lambda c, j, s, i: (0, 0)),
    ] + [
        _packed_multi_spec(s0, nblk, bk * nbits[p] // 8, bn, gemv=False)
        for p, (s0, nblk) in enumerate(bounds)
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, inv_stack, cb_stack, *packed_list)


@functools.partial(
    jax.jit,
    static_argnames=("widths", "quantize", "bm", "bn", "bk", "interpret",
                     "out_dtype", "nbits"))
def lut_matmul_fused_multi_gemv(
    x: jax.Array,            # (M, K), M = bm < 128 (decode micro-batch)
    inv_stack: jax.Array,    # (P, K) f32
    cb_stack: jax.Array,     # (P, KC) f32
    *packed_list: jax.Array, # P × (K*nbits_p//8, widths[p]) uint8
    widths: tuple,
    quantize: tuple,
    bm: int = None,
    bn: int = None,
    bk: int = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
    nbits: tuple = (4,),
) -> jax.Array:
    """Decode specialization of the fused multi kernel: one resident M block,
    N-major grid (ΣN/bn, K/bk) walking every projection's packed stream
    back-to-back — the decode step's QKV (or gate+up) is ONE kernel launch
    whose only HBM-bound operand is the concatenated sub-byte code stream.
    """
    m, k = x.shape
    n = sum(widths)
    if bm is None:
        bm = m
    if bn is None or bk is None:
        tb = autotune.pick_blocks(m, k, n, nbits=max(nbits),
                                  variant="lut_fused_multi_gemv",
                                  interpret=interpret, n_ops=len(widths))
        bn, bk = bn or tb[1], bk or tb[2]
    if m != bm or bm > 128:
        raise ValueError(
            f"lut_matmul_fused_multi_gemv: M ({m}) must equal bm ({bm}) "
            f"<= 128")
    _check_multi(x, inv_stack, cb_stack, packed_list, widths, quantize,
                 nbits, bm, bn, bk, "lut_matmul_fused_multi_gemv")
    nsteps = k // bk
    bounds = _multi_segments(widths, bn)
    grid = (n // bn, nsteps)
    kernel = functools.partial(
        _fused_multi_kernel, gemm=False, bm=bm, bk=bk, bn=bn, nsteps=nsteps,
        quantize=quantize, nbits=nbits, bounds=bounds)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda j, s: (0, s)),
        pl.BlockSpec((len(widths), bk), lambda j, s: (0, s)),
        pl.BlockSpec((len(widths), KC), lambda j, s: (0, 0)),
    ] + [
        _packed_multi_spec(s0, nblk, bk * nbits[p] // 8, bn, gemv=True)
        for p, (s0, nblk) in enumerate(bounds)
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda j, s: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, inv_stack, cb_stack, *packed_list)
