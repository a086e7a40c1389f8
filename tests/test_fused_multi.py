"""Fused multi-projection LUT GEMV tests (DESIGN.md §15).

The contract under test is BIT-equality, not tolerance: a same-input
projection group (QKV; gate+up) served through one `lut_gemm_fused_multi`
launch must produce, per projection, exactly the array its solo
`clustered_linear` launch produces — at every packing width, under GQA
output widths, and under a mixed per-projection width assignment. Plus the
scalar-prefetch pool-attention kernel vs its jnp oracle, the per-layer
launch-count drop, and the engine-level fused-vs-unfused token parity with
the bounded-trace contract intact.
"""
import dataclasses

import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import compress_model, dense_to_clustered
from repro.kernels.lut_matmul import lut_matmul_fused_multi
from repro.kernels.ops import (clustered_linear, clustered_linear_multi,
                               lut_serving, pad_codebook, track_lut_launches)
from repro.kernels.paged_attention import paged_pool_attention
from repro.kernels.ref import (lut_matmul_fused_multi_ref,
                               paged_pool_attention_ref)
from repro.launch.engine import EngineConfig, ServingEngine
from repro.models.config import ModelConfig
from repro.models.registry import get_model

VOCAB = 256


def _ct(rng, d_in, d_out, nbits, *, smooth=True, act_scale=None, seed_cb=0.05):
    """A ClusteredTensor with random codes/codebook at `nbits`, optionally
    smoothed and activation-quantized — the fields the serving kernel reads."""
    codes = rng.integers(0, 1 << nbits, size=(d_in, d_out)).astype(np.uint8)
    cb = np.sort(rng.normal(0, seed_cb, 1 << nbits)).astype(np.float32)
    s = ((0.5 + rng.random(d_in)).astype(np.float32) if smooth else None)
    w = cb[codes] / (s[:, None] if s is not None else 1.0)
    return dense_to_clustered(w, codes, cb, smooth=s, act_scale=act_scale,
                              nbits=nbits)


# the projection groups the model fuses: QKV under GQA (kv heads narrower
# than q), and the swiglu gate+up pair — widths chosen so the heuristic bn
# agrees (DESIGN.md §15: agreement is the fusability precondition)
GROUPS = {
    "qkv_gqa": (128, (128, 64, 64)),
    "gate_up": (128, (256, 256)),
}


class TestFusedMultiBitEquality:
    @pytest.mark.parametrize("group", sorted(GROUPS))
    @pytest.mark.parametrize("nbits", [2, 3, 4])
    @pytest.mark.parametrize("m", [1, 7, 256])
    def test_uniform_width(self, group, nbits, m):
        k, widths = GROUPS[group]
        rng = np.random.default_rng(hash((group, nbits, m)) % 2**31)
        cts = tuple(_ct(rng, k, n, nbits, act_scale=0.03) for n in widths)
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        with lut_serving("interpret"):
            fused = clustered_linear_multi(x, cts)
            solo = tuple(clustered_linear(x, ct) for ct in cts)
        for i, (f, s) in enumerate(zip(fused, solo)):
            assert jnp.array_equal(f, s), (
                f"{group} nbits={nbits} m={m}: projection {i} diverged")

    @pytest.mark.parametrize("widths_bits", [(4, 2, 2), (2, 4)])
    def test_mixed_precision_group(self, widths_bits):
        """One launch carries per-projection packing widths (a Fisher-mixed
        assignment fuses without widening anyone)."""
        k = 128
        ns = (128, 64, 64)[:len(widths_bits)]
        rng = np.random.default_rng(11)
        cts = tuple(_ct(rng, k, n, nb, act_scale=0.05)
                    for n, nb in zip(ns, widths_bits))
        x = jnp.asarray(rng.normal(size=(3, k)).astype(np.float32))
        with lut_serving("interpret"):
            fused = clustered_linear_multi(x, cts)
            solo = tuple(clustered_linear(x, ct) for ct in cts)
        for f, s in zip(fused, solo):
            assert jnp.array_equal(f, s)

    @pytest.mark.parametrize("nbits", [(2, 2, 2), (3, 3, 3), (4, 4, 4),
                                       (4, 2, 3)],
                             ids=["2bit", "3bit", "4bit", "mixed"])
    @pytest.mark.parametrize("m,m_chunk", [(128, 128), (256, 256),
                                           (1024, 1024), (1024, 256)],
                             ids=["m128", "m256", "m1024", "m1024-chunk256"])
    def test_gemm_rows_equal_one_block_launches(self, nbits, m, m_chunk):
        """The multi GEMM decodes the live projection's tile once per M
        chunk; every output row stays bit-equal to a launch of its 128 rows
        alone (one M block, the per-M-block path), at uniform and mixed
        per-projection widths. m_chunk=256 at M=1024 walks four chunks."""
        k, widths = 256, (256, 128, 128)
        rng = np.random.default_rng(m + sum(nbits))
        cts = tuple(_ct(rng, k, n, nb, act_scale=0.03)
                    for n, nb in zip(widths, nbits))
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        inv = jnp.stack([ct.inv_scale for ct in cts])
        cb = jnp.stack([pad_codebook(ct.codebook) for ct in cts])
        kw = dict(widths=widths, quantize=(True, False, True), bm=128,
                  bn=128, bk=128, interpret=True, nbits=nbits)
        packed = [ct.packed for ct in cts]
        y = lut_matmul_fused_multi(x, inv, cb, *packed, m_chunk=m_chunk, **kw)
        rows = [lut_matmul_fused_multi(x[r:r + 128], inv, cb, *packed, **kw)
                for r in range(0, m, 128)]
        assert jnp.array_equal(y, jnp.concatenate(rows))

    def test_float_path_without_act_scale(self):
        """Uncalibrated tensors (act_scale=None) fuse through the float
        variant and stay bit-equal to their solo float launches."""
        rng = np.random.default_rng(3)
        cts = tuple(_ct(rng, 128, n, 4, act_scale=None) for n in (256, 256))
        x = jnp.asarray(rng.normal(size=(2, 128)).astype(np.float32))
        with lut_serving("interpret"):
            fused = clustered_linear_multi(x, cts)
            solo = tuple(clustered_linear(x, ct) for ct in cts)
        for f, s in zip(fused, solo):
            assert jnp.array_equal(f, s)

    def test_matches_gather_oracle(self):
        """The fused-multi kernel agrees with the pure-jnp reference
        contraction (tolerance — the oracle uses a different op order)."""
        rng = np.random.default_rng(5)
        cts = tuple(_ct(rng, 128, n, 4) for n in (128, 64, 64))
        x = jnp.asarray(rng.normal(size=(4, 128)).astype(np.float32))
        with lut_serving("interpret"):
            fused = clustered_linear_multi(x, cts)
        ref = lut_matmul_fused_multi_ref(
            x, [ct.inv_scale for ct in cts], [ct.packed for ct in cts],
            [ct.codebook for ct in cts],
            [jnp.float32(1.0) if ct.act_scale is None else ct.act_scale
             for ct in cts],
            quantize=[ct.act_scale is not None for ct in cts],
            nbits=[ct.nbits for ct in cts])
        for f, r in zip(fused, ref):
            np.testing.assert_allclose(np.asarray(f), np.asarray(r),
                                       rtol=2e-5, atol=2e-5)

    def test_ref_mode_falls_back_per_projection(self):
        """Under the gather-oracle serving mode the multi wrapper must not
        enter the kernel — outputs equal the solo ref path exactly."""
        rng = np.random.default_rng(7)
        cts = tuple(_ct(rng, 128, n, 4) for n in (128, 64, 64))
        x = jnp.asarray(rng.normal(size=(2, 128)).astype(np.float32))
        with lut_serving("ref"), track_lut_launches() as log:
            fused = clustered_linear_multi(x, cts)
            solo = tuple(clustered_linear(x, ct) for ct in cts)
        assert log == []          # ref mode never launches
        for f, s in zip(fused, solo):
            assert jnp.array_equal(f, s)


def _pallas_grids(fn, aval):
    """The grids of the pallas_calls `fn` traces to, in trace order."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(sub, jex.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jex.core.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(aval).jaxpr)
    return grids


class TestLaunchRecord:
    @pytest.mark.parametrize("m,rows", [(16, 16), (2048, 2048), (8192, 2048)],
                             ids=["gemv", "gemm-one-chunk", "gemm-4-chunks"])
    def test_rows_per_decode(self, m, rows):
        """The launch log records the rows each decoded weight tile serves:
        a GEMV's whole M (one decode per launch), a GEMM's M chunk — all
        2048 rows of a wide step, 2048 of 8192 once M passes the VMEM
        budget — and it is the chunk the traced kernels run: a GEMM's grid
        (M/rows, N/bn, K/bk, rows/bm). Traced only, so no kernel runs."""
        rng = np.random.default_rng(m)
        k, widths = 768, (512, 256, 256)
        cts = tuple(_ct(rng, k, n, 4, act_scale=0.03) for n in widths)
        variant = "lut_fused_multi_gemv" if m < 128 else "lut_fused_multi"
        aval = jax.ShapeDtypeStruct((m, k), jnp.float32)
        with lut_serving("interpret"), track_lut_launches() as log:
            grids = (_pallas_grids(lambda x: clustered_linear_multi(x, cts),
                                   aval)
                     + _pallas_grids(lambda x: clustered_linear(x, cts[0]),
                                     aval))
        assert log.rows_per_decode == {
            (variant, m, k, sum(widths)): rows,
            (variant.replace("_multi", ""), m, k, widths[0]): rows}
        assert len(grids) == 2
        for grid in grids:
            if m < 128:
                assert len(grid) == 2       # GEMV: (N/bn, K/bk)
            else:
                bm = log.blocks[(variant, m, k, sum(widths))][0]
                assert (grid[0], grid[-1]) == (m // rows, rows // bm)


class TestPoolAttentionKernel:
    """`paged_pool_attention` (scalar-prefetch grid over the live blocks of
    each slot) vs the jnp oracle, float and int8 pools. Relative tolerance:
    dequantized int8 outputs reach O(100) magnitude, so absolute 1e-5 would
    be meaninglessly strict/loose depending on the pool dtype."""

    def _case(self, S, T, H, KV, D, bs, nb, window, softcap, int8, seed=0):
        rng = np.random.default_rng(seed)
        max_blocks = 6
        lengths = rng.integers(0, bs * max_blocks - T, size=S).astype(np.int32)
        n_new = np.full(S, T, np.int32)
        bt = rng.permutation(nb)[:S * max_blocks].reshape(
            S, max_blocks).astype(np.int32)
        q = rng.standard_normal((S, T, H, D)).astype(np.float32)
        kw = dict(softcap=softcap)
        if int8:
            kp = rng.integers(-127, 128, (nb, bs, KV, D)).astype(np.int8)
            vp = rng.integers(-127, 128, (nb, bs, KV, D)).astype(np.int8)
            kw.update(
                k_scale=(0.01 + rng.random((nb, bs, KV))).astype(np.float32),
                v_scale=(0.01 + rng.random((nb, bs, KV))).astype(np.float32),
                k_smooth=(0.5 + rng.random((KV, D))).astype(np.float32),
                v_smooth=(0.5 + rng.random((KV, D))).astype(np.float32))
        else:
            kp = rng.standard_normal((nb, bs, KV, D)).astype(np.float32)
            vp = rng.standard_normal((nb, bs, KV, D)).astype(np.float32)
        out = paged_pool_attention(q, kp, vp, bt, lengths, n_new,
                                   jnp.int32(window), interpret=True, **kw)
        ref = paged_pool_attention_ref(q, kp, vp, bt, lengths, n_new,
                                       jnp.int32(window), **kw)
        scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1.0
        err = float(jnp.max(jnp.abs(
            out.astype(jnp.float32) - ref.astype(jnp.float32)))) / scale
        assert err < 2e-5, f"relative error {err:.2e}"

    def test_float_decode(self):
        self._case(3, 1, 8, 8, 64, 16, 24, 0, 0.0, False)

    def test_float_gqa(self):
        self._case(3, 1, 8, 2, 64, 16, 24, 0, 0.0, False)

    def test_float_chunked_prefill(self):
        self._case(2, 8, 4, 4, 32, 16, 16, 0, 0.0, False)

    def test_float_window_and_softcap(self):
        self._case(2, 1, 4, 4, 64, 16, 16, 20, 0.0, False)
        self._case(2, 1, 4, 4, 64, 16, 16, 0, 30.0, False)

    def test_int8_decode(self):
        self._case(3, 1, 8, 8, 64, 16, 24, 0, 0.0, True)

    def test_int8_gqa_window_softcap_chunk(self):
        self._case(3, 1, 8, 2, 64, 16, 24, 0, 0.0, True)
        self._case(2, 8, 4, 4, 32, 16, 16, 24, 15.0, True)


@pytest.fixture(scope="module")
def tiny_lcd():
    cfg = ModelConfig(arch_id="tiny-fused", family="dense", n_layers=2,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab=VOCAB, head_dim=32, dtype="float32")
    params = get_model(cfg).init(jax.random.key(0))
    cparams, _ = compress_model(params, target_centroids=8, nbits=4)
    return cfg, cparams


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


class TestFusedServing:
    def _run(self, cfg, cparams, fused: bool):
        model = get_model(
            dataclasses.replace(cfg, fused_projections=fused))
        ecfg = EngineConfig(num_slots=2, block_size=4, num_blocks=16,
                            max_blocks_per_slot=4, prefill_chunk=8)
        with lut_serving("interpret"):
            eng = ServingEngine(model, cparams, ecfg)
            a = eng.submit(_prompt(21, 6), 4)
            eng.step()
            b = eng.submit(_prompt(22, 4), 4)
            eng.run()
        return eng, (list(a.out_tokens), list(b.out_tokens))

    def test_fused_engine_tokens_equal_unfused_and_traces_bounded(
            self, tiny_lcd):
        """The §15 adoption contract end-to-end: the fused engine emits the
        unfused engine's tokens bit-for-bit, and fusing does not add traced
        step widths (assert_bounded_traces: ≤2 compiled widths)."""
        cfg, cparams = tiny_lcd
        eng_f, toks_f = self._run(cfg, cparams, fused=True)
        eng_u, toks_u = self._run(cfg, cparams, fused=False)
        assert toks_f == toks_u
        eng_f.assert_bounded_traces()
        eng_u.assert_bounded_traces()

    def test_launch_count_drops_per_layer(self, tiny_lcd):
        """Trace one decode step per dispatch mode under the launch tracker
        (the layer stack is a scan, so the log IS the per-layer sequence):
        fused must launch strictly fewer LUT kernels — 4 vs 7 here (QKV and
        gate+up collapse; wo / w_down consume different inputs and stay
        solo)."""
        cfg, cparams = tiny_lcd
        counts = {}
        for fused in (True, False):
            model = get_model(
                dataclasses.replace(cfg, fused_projections=fused))
            cache = model.init_cache(1, 8)

            def step(p, c):
                return model.decode(
                    p, c, {"tokens": jnp.zeros((1, 1), jnp.int32),
                           "pos": c["pos"]})

            with lut_serving("interpret"), track_lut_launches() as log:
                jax.eval_shape(step, cparams, cache)
            counts[fused] = list(log)
        assert len(counts[True]) == 4, counts[True]
        assert len(counts[False]) == 7, counts[False]
        assert counts[True] == ["fused_multi[3]", "fused",
                                "fused_multi[2]", "fused"]
