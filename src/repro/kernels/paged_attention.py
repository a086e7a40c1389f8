"""Pallas TPU kernel: fused dequantizing paged attention (DESIGN.md §9).

The int8 paged KV cache (launch/engine.py, `EngineConfig.kv_dtype="int8"`)
stores each block as int8 codes plus per-(block-slot, kv-head) scales and a
per-(layer, kv-head, channel) smoothing vector calibrated through
core/smoothing.py. Reading it through XLA would dequantize the gathered cache
into a full-precision HBM tensor first — paying back the bytes the
quantization saved. This kernel keeps the trade honest: the int8 tiles and
their scales stream HBM→VMEM, the dequantize

    k = codes · scale[token, head] · smooth[head, :]

happens in VMEM registers, and only the (S, T, H, D) attention output ever
returns to HBM. The dequantized cache never exists as an HBM tensor.

Grid: one program per (slot, kv-head). Each program reads its slot's whole
logical KV view (the engine's block-table gather happens outside, in int8 —
that gather IS the cache's HBM traffic, at 1/4 the f32 bytes), dequantizes,
and runs a masked softmax over the q rows of every query head in the GQA
group. Per-slot raggedness (`lengths`, `n_new`) and the per-layer sliding
window arrive as data, so the engine's bounded-trace contract is untouched.

Correctness is asserted against the pure-jnp oracle
`kernels/ref.py paged_dequant_attention_ref` in interpret mode on CPU
(tests/test_paged_kv.py). The engine serves through the pool-direct
`paged_pool_attention` below; this gathered-view kernel is not on that path.

`paged_attention_mode(...)` mirrors `kernels/ops.py lut_serving`: it forces
how `models/layers.py paged_attn_block` consumes an int8 cache —
"kernel" (compiled, TPU), "interpret" (same kernel through the Pallas
interpreter), "ref" (the jnp gather-dequant fallback), None = auto.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import kernel_mesh, logical_to_spec
from repro.kernels import autotune
from repro.utils import round_up

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Serving dispatch (mirrors kernels/ops.py lut_serving)
# ---------------------------------------------------------------------------

_FORCED_MODE: Optional[str] = None  # None | "kernel" | "interpret" | "ref"


@contextlib.contextmanager
def paged_attention_mode(mode: Optional[str]):
    """Force how an int8 paged cache is consumed inside the context:

      "kernel"    — compiled fused dequantize-attention kernel (TPU)
      "interpret" — same kernel through the Pallas interpreter (CPU tests)
      "ref"       — jnp gather-dequant fallback in models/layers.py
      None        — auto: kernel on TPU backends, ref elsewhere
    """
    global _FORCED_MODE
    prev, _FORCED_MODE = _FORCED_MODE, mode
    try:
        yield
    finally:
        _FORCED_MODE = prev


def resolved_paged_attention_mode() -> str:
    if _FORCED_MODE is not None:
        return _FORCED_MODE
    return "kernel" if jax.default_backend() == "tpu" else "ref"


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _paged_dequant_kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, ksm_ref,
                          vsm_ref, len_ref, nnew_ref, win_ref, o_ref, *,
                          t: int, l: int, d: int, gt: int, scale: float,
                          softcap: float):
    """One (slot, kv-head) program: dequantize the slot's int8 KV view in
    VMEM and attend every query head of the GQA group over it.

    Only full-block reads (`ref[...]`): no dynamic in-kernel indexing, so the
    body lowers on TPU and runs under this build's Pallas interpreter."""
    q = q_ref[...].reshape(gt, d).astype(jnp.float32) * scale
    # dequantize in VMEM: codes * per-token-per-head scale * smoothing vector
    k = (kq_ref[...].reshape(l, d).astype(jnp.float32)
         * ks_ref[...].reshape(l, 1) * ksm_ref[...].reshape(1, d))
    v = (vq_ref[...].reshape(l, d).astype(jnp.float32)
         * vs_ref[...].reshape(l, 1) * vsm_ref[...].reshape(1, d))

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)       # (gt, l)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    length = len_ref[...].reshape(1, 1).astype(jnp.int32)
    n_new = nnew_ref[...].reshape(1, 1).astype(jnp.int32)
    window = win_ref[...].reshape(1, 1).astype(jnp.int32)
    weff = jnp.where(window > 0, window, 1 << 30)

    rows = jax.lax.broadcasted_iota(jnp.int32, (gt, l), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (gt, l), 1)
    # q rows are (group, T) flattened: row r belongs to window position r % T
    q_pos = length + rows % t
    mask = (q_pos >= cols) & ((q_pos - cols) < weff) & (cols < length + n_new)
    s = jnp.where(mask, s, NEG_INF)

    m = jnp.maximum(jnp.max(s, axis=1, keepdims=True), NEG_INF)
    p = jnp.exp(s - m)
    p = p * mask.astype(jnp.float32)        # fully-masked rows -> all-zero
    denom = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
    out = jnp.dot(p / denom, v, preferred_element_type=jnp.float32)
    o_ref[...] = out.reshape(1, 1, gt, d).astype(o_ref.dtype)


def paged_dequant_attention(
    q: jax.Array,          # (S, T, H, D) float — post-rope queries
    kq: jax.Array,         # (S, L, KV, D) int8 — gathered logical K view
    k_scale: jax.Array,    # (S, L, KV) f32 — per-(token, kv-head) scales
    vq: jax.Array,         # (S, L, KV, D) int8
    v_scale: jax.Array,    # (S, L, KV) f32
    k_smooth: jax.Array,   # (KV, D) f32 — calibrated smoothing vector
    v_smooth: jax.Array,   # (KV, D) f32
    lengths: jax.Array,    # (S,) int32 — cached tokens per slot
    n_new: jax.Array,      # (S,) int32 — valid tokens in this window
    window: jax.Array,     # scalar int32 — sliding window (0 = global)
    *,
    softcap: float = 0.0,
    interpret: bool = False,
    l_pad: Optional[int] = None,   # lane multiple for L; None -> cache/heuristic
) -> jax.Array:
    """Fused dequantize + masked attention over a slot's gathered int8 KV.

    Returns (S, T, H, D) in q's dtype. The gather through the block tables
    stays int8 (the caller does it); this call is the only consumer of the
    quantized view, so no dequantized cache tensor ever lands in HBM."""
    s_slots, t, h, d = q.shape
    l, kv = kq.shape[1], kq.shape[2]
    g = h // kv
    gt = g * t
    if l_pad is None:
        l_pad = autotune.pick_paged_pad(gt, l, d, interpret=interpret)

    # (S, T, H, D) -> (S, KV, g, T, D) -> (S, KV, g*T, D): row r = gi*T + t
    qt = q.reshape(s_slots, t, kv, g, d).transpose(0, 2, 3, 1, 4)
    qt = qt.reshape(s_slots, kv, gt, d)
    kqt = kq.transpose(0, 2, 1, 3)                        # (S, KV, L, D)
    vqt = vq.transpose(0, 2, 1, 3)
    kst = k_scale.transpose(0, 2, 1)                      # (S, KV, L)
    vst = v_scale.transpose(0, 2, 1)

    # sublane-align the q rows and lane-align the KV length (the multiple is
    # the autotuned `l_pad` — DESIGN.md §11); padded keys are masked by
    # `cols < length + n_new` (lengths never exceed the real L)
    gt_p = round_up(gt, 8)
    l_p = round_up(l, l_pad)
    if gt_p != gt:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, gt_p - gt), (0, 0)))
    if l_p != l:
        kqt = jnp.pad(kqt, ((0, 0), (0, 0), (0, l_p - l), (0, 0)))
        vqt = jnp.pad(vqt, ((0, 0), (0, 0), (0, l_p - l), (0, 0)))
        kst = jnp.pad(kst, ((0, 0), (0, 0), (0, l_p - l)))
        vst = jnp.pad(vst, ((0, 0), (0, 0), (0, l_p - l)))

    kernel = functools.partial(
        _paged_dequant_kernel, t=t, l=l_p, d=d, gt=gt_p,
        scale=1.0 / np.sqrt(d), softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid=(s_slots, kv),
        in_specs=[
            pl.BlockSpec((1, 1, gt_p, d), lambda s, hh: (s, hh, 0, 0)),
            pl.BlockSpec((1, 1, l_p, d), lambda s, hh: (s, hh, 0, 0)),
            pl.BlockSpec((1, 1, l_p), lambda s, hh: (s, hh, 0)),
            pl.BlockSpec((1, 1, l_p, d), lambda s, hh: (s, hh, 0, 0)),
            pl.BlockSpec((1, 1, l_p), lambda s, hh: (s, hh, 0)),
            pl.BlockSpec((1, d), lambda s, hh: (hh, 0)),
            pl.BlockSpec((1, d), lambda s, hh: (hh, 0)),
            pl.BlockSpec((1, 1), lambda s, hh: (s, 0)),
            pl.BlockSpec((1, 1), lambda s, hh: (s, 0)),
            pl.BlockSpec((1, 1), lambda s, hh: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gt_p, d), lambda s, hh: (s, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s_slots, kv, gt_p, d), q.dtype),
        interpret=interpret,
    )(qt, kqt, kst, vqt, vst,
      k_smooth.astype(jnp.float32), v_smooth.astype(jnp.float32),
      lengths.astype(jnp.int32).reshape(s_slots, 1),
      n_new.astype(jnp.int32).reshape(s_slots, 1),
      jnp.asarray(window, jnp.int32).reshape(1, 1))

    out = out[:, :, :gt].reshape(s_slots, kv, g, t, d)
    return out.transpose(0, 3, 1, 2, 4).reshape(s_slots, t, h, d)


# ---------------------------------------------------------------------------
# Pool-direct scalar-prefetch paged attention (DESIGN.md §15)
# ---------------------------------------------------------------------------
#
# `paged_dequant_attention` above reads a GATHERED logical view: the caller
# materializes kc[block_tables] through XLA first — a full HBM copy of every
# slot's visible cache, padded to the block-table width, every layer, every
# step. The kernel below removes that copy entirely: the block tables and
# per-slot lengths ride in as SCALAR-PREFETCH operands
# (pltpu.PrefetchScalarGridSpec), so each grid step's index map computes
# which physical pool block to DMA — the kernel reads the paged pools
# IN PLACE, one whole block (every kv head) per grid step. Dead iterations
# (past a slot's live block count) clamp their index to the last live block,
# which Pallas recognizes as "same block, no re-DMA", and a
# `pl.when(b < live)` guard skips their compute; the softmax is the standard
# online (flash) accumulation across a slot's blocks.

def _pool_kernel(bt_ref, len_ref, nnew_ref, win_ref, q_ref, *rest,
                 t: int, bs: int, kv: int, d: int, gt: int, nb_grid: int,
                 scale: float, softcap: float, int8_kv: bool):
    """One (slot, block) program: attend every kv head's query rows over ONE
    physical cache block, accumulating online-softmax partials in VMEM
    scratch; the output DMAs once, at the slot's last block iteration.

    The block arrives whole — (bs, KV, D), every kv head — because a
    per-head block (bs, 1, D) would put a 1 against KV in the tiled
    second-minor dimension, which Mosaic cannot lay out. The heads are
    a static loop over that VMEM tile."""
    if int8_kv:
        (kq_ref, ks_ref, vq_ref, vs_ref, ksm_ref, vsm_ref,
         o_ref, acc_ref, m_ref, l_ref) = rest
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = rest
    s_i = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[s_i]
    total = length + nnew_ref[s_i]
    live = jnp.maximum(jax.lax.div(total + bs - 1, bs), 1)

    @pl.when(b < live)
    def _block():
        window = win_ref[0]
        weff = jnp.where(window > 0, window, 1 << 30)
        rows = jax.lax.broadcasted_iota(jnp.int32, (gt, bs), 0)
        cols = b * bs + jax.lax.broadcasted_iota(jnp.int32, (gt, bs), 1)
        q_pos = length + rows % t      # q rows are (group, T) flattened
        mask = (q_pos >= cols) & ((q_pos - cols) < weff) & (cols < total)
        if int8_kv:
            ks = ks_ref[0].astype(jnp.float32)             # (bs, KV)
            vs = vs_ref[0].astype(jnp.float32)
            ksm = ksm_ref[...].astype(jnp.float32)         # (KV, D)
            vsm = vsm_ref[...].astype(jnp.float32)
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32) * scale   # (gt, D)
            if int8_kv:
                k = (kq_ref[0, :, h, :].astype(jnp.float32)
                     * ks[:, h:h + 1] * ksm[h:h + 1, :])
                v = (vq_ref[0, :, h, :].astype(jnp.float32)
                     * vs[:, h:h + 1] * vsm[h:h + 1, :])
            else:
                k = k_ref[0, :, h, :].astype(jnp.float32)  # (bs, D)
                v = v_ref[0, :, h, :].astype(jnp.float32)
            s_blk = jax.lax.dot_general(                   # (gt, bs) = q k^T
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if softcap > 0:
                s_blk = softcap * jnp.tanh(s_blk / softcap)
            s_blk = jnp.where(mask, s_blk, NEG_INF)

            m_prev = m_ref[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s_blk - m_new) * mask.astype(jnp.float32)
            l_new = l_ref[h][:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = (acc_ref[h] * alpha
                          + jnp.dot(p, v, preferred_element_type=jnp.float32))
            m_ref[h] = jnp.broadcast_to(m_new, (gt, m_ref.shape[-1]))
            l_ref[h] = jnp.broadcast_to(l_new, (gt, l_ref.shape[-1]))

    @pl.when(b == nb_grid - 1)
    def _done():
        denom = jnp.maximum(l_ref[:, :, :1], 1e-30)  # all-masked rows -> 0 out
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _live_block(s, b, bt_ref, len_ref, nnew_ref, nb: int, bs: int):
    """The physical block grid step (s, b) reads: the slot's b-th table entry,
    clamped past the slot's live count to its last live block — an index
    identical to the previous step's, so Pallas skips the DMA."""
    total = len_ref[s] + nnew_ref[s]
    live = jnp.maximum(jax.lax.div(total + bs - 1, bs), 1)
    return jnp.clip(bt_ref[s, jnp.minimum(b, live - 1)], 0, nb - 1)


def _pool_block_map(nb: int, bs: int):
    """Index map for the (nb, bs, KV, D) K/V pools."""
    def imap(s, b, bt_ref, len_ref, nnew_ref, win_ref):
        return (_live_block(s, b, bt_ref, len_ref, nnew_ref, nb, bs), 0, 0, 0)
    return imap


def _pool_scale_map(nb: int, bs: int):
    """Index map for the (nb, bs, KV) int8 scale pools."""
    def imap(s, b, bt_ref, len_ref, nnew_ref, win_ref):
        return (_live_block(s, b, bt_ref, len_ref, nnew_ref, nb, bs), 0, 0)
    return imap


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_pool_attention(
    q: jax.Array,            # (S, T, H, D) float — post-rope queries
    k_pool: jax.Array,       # (nb, bs, KV, D) float or int8 — the paged pool
    v_pool: jax.Array,       # (nb, bs, KV, D)
    block_tables: jax.Array, # (S, NB) int32 logical->physical
    lengths: jax.Array,      # (S,) int32 — cached tokens per slot
    n_new: jax.Array,        # (S,) int32 — valid tokens in this window
    window: jax.Array,       # scalar int32 — sliding window (0 = global)
    *,
    k_scale: Optional[jax.Array] = None,   # (nb, bs, KV) f32 — int8 pools
    v_scale: Optional[jax.Array] = None,
    k_smooth: Optional[jax.Array] = None,  # (KV, D) f32 — int8 pools
    v_smooth: Optional[jax.Array] = None,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """Paged attention reading the block pools IN PLACE (no gather).

    Grid (S, NB) with the block tables, lengths and n_new as
    scalar-prefetch operands: each (slot, block) step DMAs exactly one
    live physical block (all kv heads) out of HBM — per decode step the cache traffic is
    each slot's true length, not the table-width-padded gathered copy the
    `paged_dequant_attention` path pays before it even starts. Works on
    float and int8 pools (int8 dequantizes per-block in VMEM; pass the scale
    pools + smoothing vectors). Returns (S, T, H, D) in q's dtype.

    Numerics: online softmax over a slot's blocks — equal to the
    materialized softmax up to f32 rounding (the oracle tests use
    tolerances; the engine's bit-parity contracts compare this path only
    against itself)."""
    s_slots, t, h, d = q.shape
    nb, bs, kv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    nb_grid = block_tables.shape[1]
    g = h // kv
    gt = g * t
    gt_p = round_up(gt, 8)
    int8_kv = k_pool.dtype == jnp.int8

    # (S, T, H, D) -> (S, KV, g*T, D): row r = gi*T + t (as the dequant kernel)
    qt = q.reshape(s_slots, t, kv, g, d).transpose(0, 2, 3, 1, 4)
    qt = qt.reshape(s_slots, kv, gt, d)
    if gt_p != gt:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, gt_p - gt), (0, 0)))

    grid = (s_slots, nb_grid)
    in_specs = [
        pl.BlockSpec((1, kv, gt_p, d), lambda s, b, bt, ln, nn, w:
                     (s, 0, 0, 0)),
        pl.BlockSpec((1, bs, kv, d), _pool_block_map(nb, bs)),
    ]
    operands = [qt, k_pool]
    if int8_kv:
        in_specs += [pl.BlockSpec((1, bs, kv), _pool_scale_map(nb, bs))]
        operands += [k_scale]
    in_specs += [pl.BlockSpec((1, bs, kv, d), _pool_block_map(nb, bs))]
    operands += [v_pool]
    if int8_kv:
        in_specs += [
            pl.BlockSpec((1, bs, kv), _pool_scale_map(nb, bs)),
            pl.BlockSpec((kv, d), lambda s, b, bt, ln, nn, w: (0, 0)),
            pl.BlockSpec((kv, d), lambda s, b, bt, ln, nn, w: (0, 0)),
        ]
        operands += [v_scale, k_smooth.astype(jnp.float32),
                     v_smooth.astype(jnp.float32)]

    kernel = functools.partial(
        _pool_kernel, t=t, bs=bs, kv=kv, d=d, gt=gt_p, nb_grid=nb_grid,
        scale=1.0 / np.sqrt(d), softcap=softcap, int8_kv=int8_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, gt_p, d),
                               lambda s, b, bt, ln, nn, w: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, gt_p, d), jnp.float32),
            pltpu.VMEM((kv, gt_p, 128), jnp.float32),
            pltpu.VMEM((kv, gt_p, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, kv, gt_p, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32),
      lengths.astype(jnp.int32),
      n_new.astype(jnp.int32),
      jnp.asarray(window, jnp.int32).reshape(1),
      *operands)

    out = out[:, :, :gt].reshape(s_slots, kv, g, t, d)
    return out.transpose(0, 3, 1, 2, 4).reshape(s_slots, t, h, d)


def mesh_paged_pool_attention(q, k_pool, v_pool, block_tables, lengths,
                              n_new, window, *, k_scale=None, v_scale=None,
                              k_smooth=None, v_smooth=None,
                              softcap: float = 0.0, interpret: bool = False):
    """`paged_pool_attention` on the installed serving mesh (DESIGN.md §14).

    One device: the kernel itself. On a multi-device mesh the kernel runs
    per device under shard_map, because GSPMD cannot partition a Pallas
    call. Each device attends only its own request slots (q, block tables,
    lengths split over the data axes as the rules place slots); every
    device holds every block. When the model axis divides the kv heads,
    each device attends its kv-head shard of q and of the pools (the layout
    `cache_shardings` gives them); otherwise every device attends all
    heads over pools replicated on the model axis."""
    kw = dict(softcap=softcap, interpret=interpret)
    mesh = kernel_mesh()
    if mesh is None:
        return paged_pool_attention(
            q, k_pool, v_pool, block_tables, lengths, n_new, window,
            k_scale=k_scale, v_scale=v_scale, k_smooth=k_smooth,
            v_smooth=v_smooth, **kw)
    mp = mesh.shape.get("model", 1)
    hs = "model" if mp > 1 and k_pool.shape[2] % mp == 0 else None
    sl = logical_to_spec(q.shape[:1], ("slots",))[0]
    pool, q_spec = P(None, None, hs, None), P(sl, None, hs, None)
    args = [q, k_pool, v_pool, block_tables, lengths, n_new,
            jnp.asarray(window, jnp.int32)]
    specs = [q_spec, pool, pool, P(sl), P(sl), P(sl), P()]
    if k_pool.dtype == jnp.int8:
        args += [k_scale, v_scale, k_smooth, v_smooth]
        specs += [P(None, None, hs), P(None, None, hs), P(hs, None),
                  P(hs, None)]

    def body(q, kp, vp, bt, ln, nn, w, *int8):
        ks, vs, ksm, vsm = int8 or (None,) * 4
        return paged_pool_attention(q, kp, vp, bt, ln, nn, w, k_scale=ks,
                                    v_scale=vs, k_smooth=ksm, v_smooth=vsm,
                                    **kw)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                         out_specs=q_spec, check_vma=False)(*args)
