"""Pallas TPU kernel: flash attention (online-softmax, VMEM-tiled).

§Perf motivation: every train/prefill roofline in EXPERIMENTS.md is dominated
by attention's S×S score/prob HBM traffic — XLA materializes them (it cannot
keep tiles on-chip across the softmax reductions). This kernel implements the
standard flash algorithm: for each (batch*head, q-block) the KV sequence is
streamed block-by-block through VMEM, maintaining running row-max m and row-sum
l, so NOTHING of size S×S ever touches HBM. On v5e that converts the
attention term from memory-bound (e.g. gemma2 prefill: ~9.7 TB/device of
score traffic) to compute-bound (the two matmuls).

The dry-run cannot compile Pallas for TPU on this CPU-only host, so the
roofline tables quantify the kernel's effect analytically (subtract the S×S
traffic — see EXPERIMENTS.md §Perf 'flash-kernel model'); correctness is
asserted against ref.py in interpret mode across shapes/windows/softcaps
(tests/test_kernels.py::TestFlashAttention).

Grid: (B*H, Sq/bq); the kernel loops over KV blocks with lax.fori_loop.
Supports causal masking, sliding windows (gemma2), logit softcap, and a
static `k_len` bound that masks keys past the live length of a padded cache
(the decode-time analogue of the serving engine's length masking).

Paged serving (DESIGN.md §5): the continuous-batching engine needs PER-SLOT
ragged lengths — each batch row attends over a different number of keys —
which this kernel's static masks cannot express. Float block pools run
through the jnp fallback in models/layers.py (`_attn_chunk` with 2-D q_pos +
per-row k_len); int8 pools run through the fused dequantizing paged kernel
(`kernels/paged_attention.py`, DESIGN.md §9), which takes per-slot lengths
as data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import autotune

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, sk: int,
                  scale: float, causal: bool, window: int, softcap: float,
                  q_offset: int, k_len: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale              # (bq, d)
    q_pos = q_offset + qi * bq + jax.lax.iota(jnp.int32, bq)

    nkv = sk // bk

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                       # (bq, bk)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * bk + jax.lax.iota(jnp.int32, bk)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window > 0:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        if k_len > 0:   # padded-cache decode: keys past the live length
            mask &= k_pos[None, :] < k_len
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))        # (bq,)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, l_new

    d = q_ref.shape[-1]
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nkv, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "bq", "bk", "causal", "window", "softcap", "q_offset", "k_len",
    "interpret"))
def flash_attention(
    q: jax.Array,          # (BH, Sq, D) — batch*heads flattened
    k: jax.Array,          # (BH, Sk, D)
    v: jax.Array,          # (BH, Sk, D)
    *,
    bq: int = None,        # None -> autotune cache/heuristic (DESIGN.md §11)
    bk: int = None,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    k_len: int = 0,        # >0: mask keys at positions >= k_len (padded cache)
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    sk = k.shape[1]
    if bq is None or bk is None:
        tbq, tbk = autotune.pick_flash_blocks(sq, sk, d, interpret=interpret)
        bq, bk = bq or tbq, bk or tbk
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    grid = (bh, sq // bq)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, sk=sk, scale=1.0 / np.sqrt(d),
        causal=causal, window=window, softcap=softcap, q_offset=q_offset,
        k_len=k_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
    )(q, k, v)
