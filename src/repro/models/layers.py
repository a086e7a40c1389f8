"""Pure-JAX layer library shared by the architecture zoo.

Everything is functional: `fn(params_subtree, inputs, cfg, ...) -> outputs`.
Attention is q-chunked (scan over query blocks) so the S×S score matrix never
fully materializes — with heads sharded over the model axis this bounds the
per-chip attention working set to  B/dp × H/tp × chunk × S  floats, which is
what lets train_4k/prefill_32k fit v5e HBM without a custom flash kernel
(EXPERIMENTS.md §Perf iterates on this).

GQA is computed by broadcasting the (replicated or kv-sharded) K/V heads up to
the query heads *inside* the einsum operands; the broadcast never hits HBM.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import is_clustered, _unpack_codes
from repro.distributed.sharding import maybe_shard
from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# Linear / norms
# ---------------------------------------------------------------------------

def resolve_weight(w, dtype) -> jax.Array:
    """Dense view of a (possibly clustered, possibly stacked-expert) weight.

    For ClusteredTensors the int4 codes are what lives in HBM; the dequantized
    tile is a transient (one expert batch-matmul at a time under scan) — the
    same trade the Pallas serving kernel makes explicit on TPU."""
    if not is_clustered(w):
        return w.astype(dtype)
    d_in = w.smooth.shape[-1]
    codes = _unpack_codes(w.codes, d_in, w.nbits)         # (..., d_in, d_out)
    if w.codebook.ndim == 1:
        dense = w.codebook[codes]
    else:                                                  # stacked experts (E, K)
        dense = jax.vmap(lambda cb, cd: cb[cd])(w.codebook, codes)
    return (dense / w.smooth[..., :, None]).astype(dtype)


def linear(x: jax.Array, w, b: Optional[jax.Array] = None,
           split: Optional[str] = None) -> jax.Array:
    """Dense projection. `w` may be a plain array or an LCD ClusteredTensor —
    the paper's technique is first-class: any projection can serve clustered.

    Clustered weights dispatch through kernels.ops.clustered_linear: the fused
    smooth+quant+LUT Pallas GEMM on TPU (or under lut_serving("interpret")),
    the trainable gather contraction elsewhere — so this one entry point
    covers training, CPU CI, and the serving engine (DESIGN.md §2).
    `split` ("out" column-parallel, "in" row-parallel) tells the kernel
    which weight dim a serving mesh's model axis shards (DESIGN.md §14)."""
    if is_clustered(w):
        from repro.kernels.ops import clustered_linear
        y = clustered_linear(x, w, split=split)
    else:
        y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def linear_group(x: jax.Array, ws, bs, cfg: ModelConfig) -> Tuple[jax.Array, ...]:
    """Projections sharing one input (QKV; gate+up), fused when possible.

    When `cfg.fused_projections` is on and every weight is an LCD
    ClusteredTensor, the group dispatches through
    kernels.ops.clustered_linear_multi: the activation row is smoothed and
    quantized ONCE and all projections decode inside a single LUT GEMV launch
    (DESIGN.md §15). The fused kernel is bit-equal to per-projection calls
    (tests/test_fused_multi.py), so this changes kernel count and HBM
    traffic, never numerics. Any dense weight in the group — or a
    non-fusable block-shape mix — falls back to independent `linear` calls.
    Every group member is column-parallel on a serving mesh (DESIGN.md §14)."""
    if (cfg.fused_projections and len(ws) > 1
            and all(is_clustered(w) for w in ws)):
        from repro.kernels.ops import clustered_linear_multi
        ys = clustered_linear_multi(x, tuple(ws))
    else:
        ys = tuple(linear(x, w, split="out") for w in ws)
    return tuple(y if b is None else y + b.astype(y.dtype)
                 for y, b in zip(ys, bs))


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (nrm * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def norm(x: jax.Array, p: Dict[str, jax.Array], kind: str) -> jax.Array:
    if kind == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); pos: broadcastable to (..., S). Rotates pairs (d, d+D/2)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs            # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                            # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    # §Perf 'bf16-rope': rotate in the activation dtype (angles/cos/sin stay
    # f32); halves the f32 copy traffic the rope concats generated per layer.
    c2, s2 = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate(
        [x1 * c2 - x2 * s2, x2 * c2 + x1 * s2], axis=-1)


# ---------------------------------------------------------------------------
# Attention (q-chunked, GQA, optional window + softcap)
# ---------------------------------------------------------------------------

def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(scores / cap) if cap > 0 else scores


def _attn_chunk(q, k, v, q_pos, k_pos, *, causal, window, softcap, scale,
                k_len=None):
    """q: (B, Cq, H, D); k/v: (B, Sk, KV, D) with KV | H. Returns (B, Cq, H, D).

    Memory-diet softmax (§Perf iteration 'bf16-scores'): the S×S score/prob
    tensors are materialized in bf16 with f32 reductions (row max + row sum),
    halving the dominant HBM-traffic term of train/prefill attention without a
    custom kernel. exp(x - max) <= 1, so bf16's 8-bit mantissa costs ~1e-2
    relative prob error — below the quantization noise LCD itself introduces
    (validated by tests/test_models.py decode-vs-forward at 2e-3 on f32
    configs; bf16 archs see <1e-2 logits drift).

    Ragged batches (the paged serving engine, DESIGN.md §5): `q_pos` may be
    (B, Cq) — per-row absolute positions — and `k_len` a (B,) count of valid
    keys per row; keys at or beyond `k_len` are masked out, which is how padded
    slots and freed cache blocks are excluded without a second code path.
    """
    b, cq, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    cdt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
    qf = q.reshape(b, cq, kv, g, d)
    scores = (jnp.einsum("bqkgd,bskd->bkgqs", qf, k,
                         preferred_element_type=jnp.float32) * scale)
    scores = _softcap(scores, softcap).astype(cdt)  # fused convert: S x S
    # tensors below live in bf16 on bf16 models
    # `window` may be a traced per-layer value (gemma2 alternates local/global
    # inside one scanned body): apply it branch-free, 0 -> effectively infinite.
    weff = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window), 1 << 30)
    q_pos = jnp.asarray(q_pos)
    if q_pos.ndim == 1:                 # shared positions: mask is (Cq, Sk)
        qp, kp = q_pos[:, None], k_pos[None, :]
    else:                               # per-slot positions: mask is (B, Cq, Sk)
        qp, kp = q_pos[:, :, None], k_pos[None, None, :]
    mask = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        mask &= qp >= kp
    mask &= (qp - kp) < weff
    if k_len is not None:
        kl = jnp.asarray(k_len)[:, None, None]
        mask = (mask if mask.ndim == 3 else mask[None]) & (kp < kl)
    mexp = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
    scores = jnp.where(mexp, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)                     # f32 rows
    m = jnp.maximum(m, -1e30)  # fully-masked rows (window+causal): avoid nan
    e = jnp.exp(scores - m).astype(cdt)                             # bf16 store
    ssum = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)
    probs = (e / jnp.maximum(ssum, 1e-30).astype(cdt))
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(cdt),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, cq, h, d).astype(q.dtype)


def attention(
    q: jax.Array,            # (B, Sq, H, D)
    k: jax.Array,            # (B, Sk, KV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: jax.Array | int = 0,   # absolute position of q[0] (decode)
    chunk: int = 1024,
) -> jax.Array:
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    q_pos0 = jnp.asarray(q_offset)
    k_pos = jnp.arange(sk)

    if sq <= chunk:
        q_pos = q_pos0 + jnp.arange(sq)
        return _attn_chunk(q, k, v, q_pos, k_pos, causal=causal, window=window,
                           softcap=softcap, scale=scale)

    if sq % chunk:
        # non-power-of-two sequences (whisper's 1500 frames, VLM prefix+text
        # lengths): use the largest divisor of sq not exceeding the target
        chunk = next(c for c in range(chunk, 0, -1) if sq % c == 0)
    nch = sq // chunk
    qc = q.reshape(b, nch, chunk, h, d).swapaxes(0, 1)     # (nch, B, Cq, H, D)

    def body(_, qi_i):
        qi, i = qi_i
        q_pos = q_pos0 + i * chunk + jnp.arange(chunk)
        o = _attn_chunk(qi, k, v, q_pos, k_pos, causal=causal, window=window,
                        softcap=softcap, scale=scale)
        return None, o

    # §Perf 'rematerialize-attn-chunks': without this, the backward of the
    # chunk scan stacks every chunk's S x chunk probs tensor in HBM (the
    # gemma2/starcoder train breakdown showed ~1.5 TB/device of stacked
    # saves); recomputing the chunk forward during its backward trades ~15%
    # extra attention flops for eliminating that entire traffic class.
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    _, out = jax.lax.scan(body, None, (qc, jnp.arange(nch)))
    return out.swapaxes(0, 1).reshape(b, sq, h, d)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + cache handling)
# ---------------------------------------------------------------------------

def attn_block(
    p: Dict[str, Any],
    x: jax.Array,                 # (B, S, d_model)
    cfg: ModelConfig,
    *,
    layer_window: int = 0,        # 0 = global
    cache: Optional[Dict[str, jax.Array]] = None,  # {"k","v","pos"} decode cache
    pos_offset: jax.Array | int = 0,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,  # enc-dec cross attn
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    b, s, _ = x.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads_eff, cfg.n_kv_heads

    base = cache["pos"] if cache is not None else pos_offset
    if cross_kv is None:
        q, k, v = linear_group(
            x, (p["wq"], p["wk"], p["wv"]),
            (p.get("bq"), p.get("bk"), p.get("bv")), cfg)
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        q = rope(q, base + jnp.arange(s), cfg.rope_theta)
        k = rope(k, base + jnp.arange(s), cfg.rope_theta)
        causal = True
    else:
        q = linear(x, p["wq"], p.get("bq"), split="out").reshape(b, s, nh, hd)
        k, v = cross_kv          # precomputed encoder K/V: (B, S_enc, KV, D)
        causal = False

    if cache is not None:
        # decode: the KV cache is SEQ-sharded on the model axis (flash-decode);
        # q must stay replicated there — head-sharding q would force GSPMD to
        # all-to-all the whole cache into a head-sharded layout every step
        # (observed: 3.2 GB/step on zamba2 decode_32k).
        q = maybe_shard(q, "batch", None, None, None)
        k = maybe_shard(k, "batch", "seq_kv", "kv", None)
        v = maybe_shard(v, "batch", "seq_kv", "kv", None)
    else:
        q = maybe_shard(q, "batch", None, "heads", None)
        k = maybe_shard(k, "batch", None, "kv", None)
        v = maybe_shard(v, "batch", None, "kv", None)

    new_cache = None
    if cache is not None:
        # decode: write this step's K/V at position `pos`, attend over the prefix
        kc, vc, pos = cache["k"], cache["v"], cache["pos"]
        if kc.dtype == jnp.int8:
            # int8 KV cache (beyond-paper): per-(token, head) absmax scales
            # stored alongside (1/64 the cache bytes); new entries quantized
            # on write, the cache dequantized on read — on TPU the dequant
            # fuses into the attention dots, so the HBM stream is the int8
            # tensor (half the bf16 bytes).
            def q8(t):
                amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=3,
                               keepdims=True)                    # (B,s,KV,1)
                scale = jnp.maximum(amax, 1e-6) / 127.0
                tq = jnp.clip(jnp.round(t.astype(jnp.float32) / scale),
                              -127, 127).astype(jnp.int8)
                return tq, scale[..., 0]                          # (B,s,KV)
            kq, ks_new = q8(k)
            vq, vs_new = q8(v)
            kc = jax.lax.dynamic_update_slice_in_dim(kc, kq, pos, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, vq, pos, axis=1)
            ks_s = jax.lax.dynamic_update_slice_in_dim(
                cache["k_scale"], ks_new.astype(jnp.float32), pos, axis=1)
            vs_s = jax.lax.dynamic_update_slice_in_dim(
                cache["v_scale"], vs_new.astype(jnp.float32), pos, axis=1)
            k = kc.astype(x.dtype) * ks_s[..., None].astype(x.dtype)
            v = vc.astype(x.dtype) * vs_s[..., None].astype(x.dtype)
            new_cache = {"k": kc, "v": vc, "pos": pos + s,
                         "k_scale": ks_s, "v_scale": vs_s}
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos, axis=1)
            k, v = kc, vc
            new_cache = {"k": kc, "v": vc, "pos": pos + s}
        # mask out cache slots beyond pos via the causal mask (q_offset = pos)
        q_off = pos
    else:
        q_off = pos_offset

    o = attention(q, k, v, causal=causal, window=layer_window,
                  softcap=cfg.attn_softcap, q_offset=q_off)
    o = o.reshape(b, s, nh * hd)
    return linear(o, p["wo"], split="in"), new_cache


# ---------------------------------------------------------------------------
# Paged attention block (continuous-batching serving engine, DESIGN.md §5)
# ---------------------------------------------------------------------------

def quantize_kv(t: jax.Array, smooth: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Smoothed symmetric int8 quantization of one step's K or V
    (DESIGN.md §9): divide channel outliers away with the calibrated
    per-(kv-head, channel) smoothing vector (Eq. 11's transform applied to
    the cache instead of a GEMM input), then absmax-quantize per (token,
    kv-head).

    t: (..., KV, D); smooth: (KV, D). Returns (codes int8 (..., KV, D),
    scale f32 (..., KV)); dequant is `codes * scale * smooth`."""
    ts = t.astype(jnp.float32) / smooth.astype(jnp.float32)
    amax = jnp.max(jnp.abs(ts), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    codes = jnp.clip(jnp.round(ts / scale), -127, 127).astype(jnp.int8)
    return codes, scale[..., 0]


def paged_attn_block(
    p: Dict[str, Any],
    x: jax.Array,                 # (S_slots, T, d_model) — T new tokens/slot
    cfg: ModelConfig,
    *,
    layer_window: jax.Array | int,
    kc: jax.Array,                # (num_blocks, block_size, KV, D) paged K
    vc: jax.Array,                # (num_blocks, block_size, KV, D) paged V
    block_tables: jax.Array,      # (S_slots, max_blocks) int32 logical->physical
    lengths: jax.Array,           # (S_slots,) tokens already in the cache
    n_new: jax.Array,             # (S_slots,) valid tokens among the T fed
    kc_scale: Optional[jax.Array] = None,   # (num_blocks, block_size, KV) f32
    vc_scale: Optional[jax.Array] = None,   # int8 cache only (DESIGN.md §9)
    k_smooth: Optional[jax.Array] = None,   # (KV, D) f32 smoothing vectors
    v_smooth: Optional[jax.Array] = None,
) -> Tuple[jax.Array, ...]:
    """One attention block over the paged KV cache (DESIGN.md §5).

    Every slot advances by up to T tokens in the same traced computation —
    prefilling slots feed a prompt chunk (n_new up to T), decoding slots feed
    one token (n_new = 1), idle slots feed nothing (n_new = 0). The three
    ragged quantities (per-slot position, per-slot length, per-slot activity)
    are all masks; the trace shape depends only on (S_slots, T).

    Writes go through each slot's block table: token `lengths[s] + t` lands in
    physical block `block_tables[s, (lengths[s]+t) // block_size]`. Padded
    tokens are redirected to an out-of-range block id and dropped by the
    scatter. Reads gather the slot's blocks back into logical order, so the
    attention math is identical to a contiguous cache of the same length —
    which is what makes engine output bit-equal to single-request decoding
    (tests/test_serving_engine.py).

    int8 cache (kc.dtype == int8, DESIGN.md §9): appended K/V are smoothed
    and absmax-quantized per (token, kv-head) (`quantize_kv`), scales scatter
    into their own pools through the same block table, and reads dequantize —
    on TPU through the fused Pallas kernel
    (kernels/paged_attention.py, dequant in VMEM, no dequantized HBM tensor),
    elsewhere through the jnp gather fallback. Both widths quantize each
    token identically, so the width-independence the engine's parity
    contracts rely on is preserved within a kv dtype. Returns
    (out, kc, vc[, kc_scale, vc_scale])."""
    b, t, _ = x.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads_eff, cfg.n_kv_heads
    nb, bs = kc.shape[0], kc.shape[1]
    int8_kv = kc.dtype == jnp.int8

    q, k, v = linear_group(
        x, (p["wq"], p["wk"], p["wv"]),
        (p.get("bq"), p.get("bk"), p.get("bv")), cfg)
    q = q.reshape(b, t, nh, hd)
    k = k.reshape(b, t, nkv, hd)
    v = v.reshape(b, t, nkv, hd)
    pos = lengths[:, None] + jnp.arange(t, dtype=lengths.dtype)[None, :]  # (S, T)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)

    # scatter this step's K/V into the slots' blocks; padded tokens get an
    # out-of-range block id, which mode="drop" discards
    valid = jnp.arange(t)[None, :] < n_new[:, None]
    blk = jnp.take_along_axis(block_tables, jnp.minimum(
        pos // bs, block_tables.shape[1] - 1), axis=1)          # (S, T)
    blk = jnp.where(valid, blk, nb)
    off = pos % bs
    if int8_kv:
        kq8, ks8 = quantize_kv(k, k_smooth)
        vq8, vs8 = quantize_kv(v, v_smooth)
        kc = kc.at[blk, off].set(kq8, mode="drop")
        vc = vc.at[blk, off].set(vq8, mode="drop")
        kc_scale = kc_scale.at[blk, off].set(ks8, mode="drop")
        vc_scale = vc_scale.at[blk, off].set(vs8, mode="drop")
    else:
        kc = kc.at[blk, off].set(k.astype(kc.dtype), mode="drop")
        vc = vc.at[blk, off].set(v.astype(vc.dtype), mode="drop")

    q = maybe_shard(q, "slots", None, None, None)
    from repro.kernels.paged_attention import (
        mesh_paged_pool_attention, resolved_paged_attention_mode)
    mode = resolved_paged_attention_mode()
    if mode in ("kernel", "interpret"):
        # pool-direct kernel (float AND int8 pools): the block tables ride
        # as scalar-prefetch operands and each (slot, block) grid step
        # DMAs exactly one live physical block — the table-wide
        # `kc[block_tables]` gather below (a full logical-view HBM copy per
        # layer per step) never happens on this path.
        o = mesh_paged_pool_attention(
            q, kc, vc, block_tables, lengths, n_new,
            jnp.asarray(layer_window, jnp.int32),
            k_scale=kc_scale, v_scale=vc_scale,
            k_smooth=k_smooth, v_smooth=v_smooth,
            softcap=cfg.attn_softcap, interpret=(mode == "interpret"))
        o = o.reshape(b, t, nh * hd)
        if int8_kv:
            return linear(o, p["wo"], split="in"), kc, vc, kc_scale, vc_scale
        return linear(o, p["wo"], split="in"), kc, vc

    if int8_kv:
        # gather each slot's logical view IN INT8 — the cache's HBM read
        # traffic stays at the quantized byte count on every path
        kv_kq = kc[block_tables].reshape(b, -1, nkv, hd)
        kv_vq = vc[block_tables].reshape(b, -1, nkv, hd)
        kv_ks = kc_scale[block_tables].reshape(b, -1, nkv)
        kv_vs = vc_scale[block_tables].reshape(b, -1, nkv)
        kv_kq = maybe_shard(kv_kq, "slots", None, "kv", None)
        kv_vq = maybe_shard(kv_vq, "slots", None, "kv", None)
        kv_ks = maybe_shard(kv_ks, "slots", None, "kv")
        kv_vs = maybe_shard(kv_vs, "slots", None, "kv")
        # jnp fallback (CPU CI / non-TPU): same math, XLA materializes
        # the dequantized view
        kv_k = (kv_kq.astype(jnp.float32) * kv_ks[..., None]
                * k_smooth[None, None]).astype(x.dtype)
        kv_v = (kv_vq.astype(jnp.float32) * kv_vs[..., None]
                * v_smooth[None, None]).astype(x.dtype)
        k_pos = jnp.arange(kv_k.shape[1])
        o = _attn_chunk(q, kv_k, kv_v, pos, k_pos, causal=True,
                        window=layer_window, softcap=cfg.attn_softcap,
                        scale=1.0 / np.sqrt(hd), k_len=lengths + n_new)
        o = o.reshape(b, t, nh * hd)
        return linear(o, p["wo"], split="in"), kc, vc, kc_scale, vc_scale

    # gather each slot's logical view: (S, max_blocks*block_size, KV, D)
    kv_k = kc[block_tables].reshape(b, -1, nkv, hd).astype(x.dtype)
    kv_v = vc[block_tables].reshape(b, -1, nkv, hd).astype(x.dtype)
    kv_k = maybe_shard(kv_k, "slots", None, "kv", None)
    kv_v = maybe_shard(kv_v, "slots", None, "kv", None)

    k_pos = jnp.arange(kv_k.shape[1])
    o = _attn_chunk(q, kv_k, kv_v, pos, k_pos, causal=True,
                    window=layer_window, softcap=cfg.attn_softcap,
                    scale=1.0 / np.sqrt(hd), k_len=lengths + n_new)
    o = o.reshape(b, t, nh * hd)
    return linear(o, p["wo"], split="in"), kc, vc


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_block(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.mlp == "swiglu":
        gate, up = linear_group(x, (p["w_gate"], p["w_up"]),
                                (None, None), cfg)
        h = maybe_shard(jax.nn.silu(gate) * up, "batch", None, "ff")
        return linear(h, p["w_down"], split="in")
    h = jax.nn.gelu(linear(x, p["w_up"], p.get("b_up"), split="out"))
    h = maybe_shard(h, "batch", None, "ff")
    return linear(h, p["w_down"], p.get("b_down"), split="in")


# ---------------------------------------------------------------------------
# MoE (grouped, capacity-based, EP-shardable)
# ---------------------------------------------------------------------------

def moe_block(
    p: Dict[str, Any],
    x: jax.Array,                # (B, S, d)
    cfg: ModelConfig,
    *,
    capacity_factor: float = 1.25,
    group_size: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k MoE with per-group expert capacity (Mesh-TF style dense dispatch).

    Returns (out, aux_loss). Experts shard over the model axis ("experts");
    the (G,Sg,E,C) dispatch tensors bound per-chip memory to
    T * Sg * topk * cf floats regardless of E.
    """
    b, s, d = x.shape
    e, topk = cfg.n_experts, cfg.moe_topk
    t = b * s
    sg = min(group_size, t)
    while t % sg:
        sg //= 2
    g = t // sg
    cap = int(np.ceil(sg * topk * capacity_factor / e / 4.0) * 4)
    cap = min(cap, sg)

    xt = x.reshape(g, sg, d)
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))   # (G,Sg,E)
    probs = jax.nn.softmax(logits, axis=-1)

    # aux load-balancing loss (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(me * ce)

    # top-k dispatch with per-slot cumulative positions
    gates_rem = probs
    dispatch = jnp.zeros((g, sg, e, cap), jnp.float32)
    combine = jnp.zeros((g, sg, e, cap), jnp.float32)
    prev_count = jnp.zeros((g, 1, e), jnp.float32)
    for slot in range(topk):
        idx = jnp.argmax(gates_rem, axis=-1)                      # (G,Sg)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)        # (G,Sg,E)
        gate = jnp.sum(probs * onehot, axis=-1, keepdims=True)    # (G,Sg,1)
        pos = jnp.cumsum(onehot, axis=1) - onehot + prev_count    # (G,Sg,E)
        keep = (pos < cap) * onehot
        posc = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)  # (G,Sg,E,C)
        disp = keep[..., None] * posc
        dispatch = dispatch + disp
        combine = combine + disp * gate[..., None]
        prev_count = prev_count + jnp.sum(onehot, axis=1, keepdims=True)
        gates_rem = gates_rem * (1.0 - onehot)

    xe = jnp.einsum("gsec,gsd->egcd", dispatch, xt.astype(jnp.float32))   # (E,G,C,d)
    xe = maybe_shard(xe, "experts", None, None, None).astype(x.dtype)

    # per-expert SwiGLU: weights (E, d, f) / (E, f, d), possibly clustered
    w_gate = resolve_weight(p["w_gate"], x.dtype)
    w_up = resolve_weight(p["w_up"], x.dtype)
    w_down = resolve_weight(p["w_down"], x.dtype)
    gate_h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe, w_gate))
    up_h = jnp.einsum("egcd,edf->egcf", xe, w_up)
    ye = jnp.einsum("egcf,efd->egcd", gate_h * up_h, w_down)
    ye = maybe_shard(ye, "experts", None, None, None)

    out = jnp.einsum("gsec,egcd->gsd", combine.astype(x.dtype), ye)
    return out.reshape(b, s, d), aux
