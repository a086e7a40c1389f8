"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes (assignment requirement)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lut import lut_matmul_dequant_ref, pack4
from repro.kernels import ref
from repro.kernels.lut_matmul import (lut_matmul_f32, lut_matmul_fused,
                                      lut_matmul_fused_gemv, lut_matmul_int8)
from repro.kernels.ops import (_pick_blocks, lut_gemm, lut_gemm_fused,
                               lut_gemm_int8, pad_codebook)
from repro.kernels.smooth_quant import smooth_quant


def make_case(m, k, n, n_cents, seed, act_dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(m, k)).astype(np.float32)
    codes = rng.integers(0, n_cents, size=(k, n)).astype(np.uint8)
    cb = np.zeros(16, np.float32)
    cb[:n_cents] = np.sort(rng.normal(0, 0.05, n_cents))
    return (jnp.asarray(x, act_dtype), jnp.asarray(pack4(codes)), jnp.asarray(cb))


SHAPES = [
    (128, 256, 128),    # minimal aligned
    (64, 512, 256),     # bm < 128
    (128, 1024, 384),   # deep K, odd-N multiple
    (256, 256, 512),    # wide N
]


class TestLutMatmulF32:
    @pytest.mark.parametrize("m,k,n", SHAPES)
    @pytest.mark.parametrize("n_cents", [3, 9, 16])
    def test_matches_oracle(self, m, k, n, n_cents):
        x, packed, cb = make_case(m, k, n, n_cents, seed=m + n_cents)
        bm = min(64, m)
        y = lut_matmul_f32(x, packed, cb, bm=bm, bn=128, bk=256, interpret=True)
        y_ref = ref.lut_matmul_f32_ref(x, packed, cb)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x, packed, cb = make_case(128, 256, 128, 8, seed=5, act_dtype=dtype)
        y = lut_matmul_f32(x, packed, cb, bm=64, bn=128, bk=256, interpret=True)
        y_ref = ref.lut_matmul_f32_ref(x, packed, cb)
        tol = 1e-4 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_block_shape_invariance(self):
        x, packed, cb = make_case(256, 1024, 256, 11, seed=7)
        outs = []
        for bm, bn, bk in [(64, 128, 256), (128, 256, 512), (256, 128, 1024)]:
            outs.append(np.asarray(lut_matmul_f32(
                x, packed, cb, bm=bm, bn=bn, bk=bk, interpret=True)))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(outs[0], outs[2], rtol=1e-5, atol=1e-4)


class TestLutMatmulInt8:
    @pytest.mark.parametrize("m,k,n", SHAPES[:3])
    def test_matches_oracle(self, m, k, n):
        rng = np.random.default_rng(m + n)
        q = jnp.asarray(rng.integers(-128, 128, size=(m, k)).astype(np.int8))
        codes = rng.integers(0, 13, size=(k, n)).astype(np.uint8)
        cb = np.zeros(16, np.float32)
        cb[:13] = np.sort(rng.normal(0, 0.05, 13))
        packed = jnp.asarray(pack4(codes))
        s = jnp.float32(0.017)
        y = lut_matmul_int8(q, packed, jnp.asarray(cb), s,
                            bm=min(64, m), bn=128, bk=256, interpret=True)
        y_ref = ref.lut_matmul_int8_ref(q, packed, jnp.asarray(cb), s)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)

    def test_equals_bucket_table_semantics(self):
        """Paper §4.2: the kernel == signed bucket-table lookup+accumulate."""
        from repro.core.lut import lut_matmul_ref as bucket_ref
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.integers(-127, 128, size=(32, 64)).astype(np.int8))
        codes = rng.integers(0, 8, size=(64, 48)).astype(np.uint8)
        cb = np.sort(rng.normal(0, 0.05, 8)).astype(np.float32)
        s = jnp.float32(0.02)
        y_bucket = bucket_ref(q, jnp.asarray(codes.astype(np.int32)),
                              jnp.asarray(cb), s)
        y_kernel = lut_gemm_int8(q, jnp.asarray(pack4(codes)),
                                 jnp.asarray(cb), s)
        np.testing.assert_allclose(np.asarray(y_bucket), np.asarray(y_kernel),
                                   rtol=1e-5, atol=1e-4)


class TestLutMatmulFused:
    """Single-pass smooth+quant+LUT serving GEMM vs the gather-dequant oracle
    (lut_matmul_dequant_ref), across ragged decode shapes: M ∈ {1, 3, 8} and
    K/N NOT multiples of the kernel block sizes."""

    def _mk(self, m, k, n, n_cents=11, seed=0):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(0, 2, size=(m, k)).astype(np.float32))
        k_even = k + (k % 2)
        codes = rng.integers(0, n_cents, size=(k_even, n)).astype(np.uint8)
        codes[k:] = 0
        cb = np.sort(rng.normal(0, 0.05, n_cents)).astype(np.float32)
        s = (np.abs(rng.normal(1, 0.2, k)) + 0.5).astype(np.float32)
        sq = float(np.abs(x).max() / 127.0)
        inv = jnp.asarray((1.0 / (s * sq)).astype(np.float32))
        return x, codes, jnp.asarray(cb), jnp.asarray(s), inv, jnp.float32(sq)

    def _oracle(self, x, codes, cb, inv, sq, k):
        """Eq. 11 transform (symmetric clip) + gather-dequant contraction."""
        xp = jnp.pad(x, ((0, 0), (0, codes.shape[0] - k)))
        invp = jnp.pad(inv, (0, codes.shape[0] - k))
        q = jnp.clip(jnp.round(xp * invp), -127, 127).astype(jnp.int8)
        return lut_matmul_dequant_ref(q, jnp.asarray(codes.astype(np.int32)),
                                      cb, sq)

    @pytest.mark.parametrize("m", [1, 3, 8])
    @pytest.mark.parametrize("k,n", [(300, 190), (130, 17), (257, 100)])
    def test_quantized_matches_dequant_oracle(self, m, k, n):
        x, codes, cb, s, inv, sq = self._mk(m, k, n, seed=m * k + n)
        y = lut_gemm_fused(x, inv, jnp.asarray(pack4(codes)), cb, sq,
                           quantize=True, interpret=True)
        y_ref = self._oracle(x, codes, cb, inv, sq, k)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("m,k,n", [(128, 300, 190), (200, 512, 384)])
    def test_gemm_variant_matches_oracle(self, m, k, n):
        """M ≥ 128 dispatches the 3-D-grid kernel; same numerics."""
        x, codes, cb, s, inv, sq = self._mk(m, k, n, seed=m + n)
        y = lut_gemm_fused(x, inv, jnp.asarray(pack4(codes)), cb, sq,
                           quantize=True, interpret=True)
        y_ref = self._oracle(x, codes, cb, inv, sq, k)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("m", [1, 3, 8])
    def test_float_variant_smooth_only(self, m):
        """quantize=False: the smoothing divide alone is fused (uncalibrated
        tensors) — equals (x/s) @ codebook[codes]."""
        k, n = 300, 190
        x, codes, cb, s, inv, sq = self._mk(m, k, n, seed=m)
        y = lut_gemm_fused(x, 1.0 / s, jnp.asarray(pack4(codes)), cb,
                           jnp.float32(1.0), quantize=False, interpret=True)
        w = np.asarray(cb)[codes[:k]]
        y_ref = (np.asarray(x) / np.asarray(s)) @ w
        np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-5, atol=1e-4)

    def test_gemv_equals_gemm_kernel(self):
        """The N-major GEMV and the 3-D-grid kernel agree on the same blocks."""
        rng = np.random.default_rng(0)
        m, k, n = 8, 512, 256
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        inv = jnp.asarray((np.abs(rng.normal(1, 0.1, k)) + 1).astype(np.float32))
        codes = rng.integers(0, 16, size=(k, n)).astype(np.uint8)
        cb = jnp.asarray(np.sort(rng.normal(0, 0.05, 16)).astype(np.float32))
        packed = jnp.asarray(pack4(codes))
        a = lut_matmul_fused_gemv(x, inv, packed, cb, quantize=True,
                                  bm=8, bn=128, bk=256, interpret=True)
        b = lut_matmul_fused(x, inv, packed, cb, quantize=True,
                             bm=8, bn=128, bk=256, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)

    def test_pick_blocks_gemv_aware(self):
        """Regression for the dead first assignment in _pick_blocks: decode
        shapes get a sublane-aligned (multiple of 8) single M block."""
        for m in (1, 3, 8, 70, 127):
            bm, bn, bk = _pick_blocks(m, 4096, 4096)
            assert bm % 8 == 0 and bm >= m and bm <= 128, (m, bm)
        assert _pick_blocks(128, 4096, 4096)[0] == 128
        assert _pick_blocks(1000, 4096, 4096)[0] == 128


class TestBitWidths:
    """Kernel-vs-oracle parity per packing width (DESIGN.md §10): the 2/3-bit
    unpack tiles must reproduce the pure-jnp oracle exactly as the int4 tile
    does, on both the N-major GEMV and the 3-D-grid GEMM variants."""

    def _mk(self, nbits, m, k, n, seed=0):
        from repro.core.lut import pack_codes
        rng = np.random.default_rng(seed)
        ncents = 1 << nbits
        x = jnp.asarray(rng.normal(0, 2, size=(m, k)).astype(np.float32))
        codes = rng.integers(0, ncents, size=(k, n)).astype(np.uint8)
        cb = np.zeros(16, np.float32)
        cb[:ncents] = np.sort(rng.normal(0, 0.05, ncents))
        s = (np.abs(rng.normal(1, 0.2, k)) + 0.5).astype(np.float32)
        sq = float(np.abs(np.asarray(x)).max() / 127.0)
        inv = jnp.asarray((1.0 / (s * sq)).astype(np.float32))
        return (x, jnp.asarray(pack_codes(codes, nbits)), jnp.asarray(cb),
                inv, jnp.float32(sq))

    @pytest.mark.parametrize("nbits", [2, 3, 4])
    @pytest.mark.parametrize("m,k,n", [(128, 256, 128), (64, 512, 256)])
    def test_f32_kernel_matches_oracle(self, nbits, m, k, n):
        x, packed, cb, _, _ = self._mk(nbits, m, k, n, seed=m + nbits)
        y = lut_matmul_f32(x, packed, cb, bm=min(64, m), bn=128, bk=256,
                           interpret=True, nbits=nbits)
        y_ref = ref.lut_matmul_f32_ref(x, packed, cb, nbits=nbits)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("nbits", [2, 3])
    @pytest.mark.parametrize("m", [1, 3, 8])
    def test_fused_gemv_matches_oracle(self, nbits, m):
        """Ragged decode shape through the public wrapper: group padding +
        block padding + the GEMV dispatch, vs the fused oracle."""
        from repro.core.lut import padded_d_in
        k, n = 300, 190
        x, packed, cb, inv, sq = self._mk(nbits, m, k, n, seed=m * nbits)
        y = lut_gemm_fused(x, inv, packed, cb, sq, quantize=True,
                           interpret=True, nbits=nbits)
        kc = padded_d_in(k, nbits)
        xp = jnp.pad(x, ((0, 0), (0, kc - k)))
        invp = jnp.pad(inv, (0, kc - k))
        y_ref = ref.lut_matmul_fused_ref(xp, invp, packed, cb, sq,
                                         nbits=nbits)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("nbits", [2, 3])
    def test_fused_gemm_matches_oracle(self, nbits):
        """M ≥ 128 dispatches the 3-D-grid kernel; same per-width numerics."""
        m, k, n = 128, 512, 256
        x, packed, cb, inv, sq = self._mk(nbits, m, k, n, seed=nbits)
        y = lut_gemm_fused(x, inv, packed, cb, sq, quantize=True,
                           interpret=True, nbits=nbits)
        y_ref = ref.lut_matmul_fused_ref(x, inv, packed, cb, sq, nbits=nbits)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("nbits", [2, 3])
    def test_int8_kernel_matches_oracle(self, nbits):
        from repro.core.lut import pack_codes
        rng = np.random.default_rng(nbits)
        m, k, n = 64, 256, 128
        q = jnp.asarray(rng.integers(-127, 128, size=(m, k)).astype(np.int8))
        codes = rng.integers(0, 1 << nbits, size=(k, n)).astype(np.uint8)
        cb = np.zeros(16, np.float32)
        cb[:1 << nbits] = np.sort(rng.normal(0, 0.05, 1 << nbits))
        packed = jnp.asarray(pack_codes(codes, nbits))
        s = jnp.float32(0.021)
        y = lut_matmul_int8(q, packed, jnp.asarray(cb), s, bm=64, bn=128,
                            bk=256, interpret=True, nbits=nbits)
        y_ref = ref.lut_matmul_int8_ref(q, packed, jnp.asarray(cb), s,
                                        nbits=nbits)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("nbits", [2, 3, 4])
    @pytest.mark.parametrize("m,m_chunk", [(128, 128), (256, 256),
                                           (1024, 1024), (1024, 256)],
                             ids=["m128", "m256", "m1024", "m1024-chunk256"])
    def test_gemm_rows_equal_one_block_launches(self, nbits, m, m_chunk):
        """The GEMM decodes each weight tile once per M chunk and shares it
        across the chunk's 128-row M blocks; every output row must stay
        bit-equal to a launch of its 128 rows alone (one M block: one decode
        per block, the per-M-block path). m_chunk=256 at M=1024 walks four
        chunks on the outer M axis."""
        k, n = 256, 256            # two K steps, two N blocks at bk=bn=128
        x, packed, cb, inv, _ = self._mk(nbits, m, k, n, seed=m + nbits)
        kw = dict(quantize=True, bm=128, bn=128, bk=128, interpret=True,
                  nbits=nbits)
        y = lut_matmul_fused(x, inv, packed, cb, m_chunk=m_chunk, **kw)
        rows = [lut_matmul_fused(x[r:r + 128], inv, packed, cb, **kw)
                for r in range(0, m, 128)]
        assert jnp.array_equal(y, jnp.concatenate(rows))

    def test_two_bit_packed_tile_is_half_the_bytes(self):
        """The §10 stream contract at the kernel boundary: the packed operand
        a 2-bit call streams is exactly half the int4 one's bytes."""
        _, packed2, *_ = self._mk(2, 8, 512, 256)
        _, packed4, *_ = self._mk(4, 8, 512, 256)
        assert packed2.size * 2 == packed4.size


class TestOpsWrappers:
    @pytest.mark.parametrize("m,k,n", [(70, 300, 190), (1, 2048, 100),
                                       (13, 130, 17)])
    def test_padding_path(self, m, k, n):
        rng = np.random.default_rng(n)
        x = rng.normal(size=(m, k)).astype(np.float32)
        k_even = k + (k % 2)
        codes = rng.integers(0, 7, size=(k_even, n)).astype(np.uint8)
        codes[k:] = 0
        cb = np.sort(rng.normal(0, 0.05, 7)).astype(np.float32)
        packed = pack4(codes)
        xp = np.pad(x, ((0, 0), (0, k_even - k)))
        y = lut_gemm(jnp.asarray(xp), jnp.asarray(packed), jnp.asarray(cb))
        y_ref = xp @ cb[codes]
        np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-5, atol=1e-4)

    def test_pad_codebook_rejects_overflow(self):
        """ValueError (python -O-proof, like the packing checks) naming both
        the offending K and the KC capacity."""
        with pytest.raises(ValueError, match=r"K=17 .*K<=KC=16"):
            pad_codebook(jnp.zeros(17))


class TestSmoothQuant:
    @pytest.mark.parametrize("m,c", [(256, 512), (128, 256), (512, 1024)])
    def test_matches_oracle(self, m, c):
        rng = np.random.default_rng(m)
        x = rng.normal(0, 3, size=(m, c)).astype(np.float32)
        inv = (127.0 / np.abs(x).max(0).clip(1e-6)).astype(np.float32)
        q = smooth_quant(jnp.asarray(x), jnp.asarray(inv),
                         bm=128, bc=256, interpret=True)
        q_ref = ref.smooth_quant_ref(jnp.asarray(x), jnp.asarray(inv))
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))

    def test_int4_mode(self):
        rng = np.random.default_rng(9)
        x = rng.normal(0, 3, size=(128, 256)).astype(np.float32)
        inv = (7.0 / np.abs(x).max(0).clip(1e-6)).astype(np.float32)
        q = smooth_quant(jnp.asarray(x), jnp.asarray(inv), bits=4,
                         bm=128, bc=256, interpret=True)
        assert int(np.asarray(q).max()) <= 7 and int(np.asarray(q).min()) >= -8


class TestFlashAttention:
    """Flash kernel (online softmax, VMEM-tiled) vs materialized oracle,
    swept over shapes / masks / windows / softcap / dtypes."""

    def _mk(self, bh, sq, sk, d, dtype=jnp.float32, seed=0):
        rng = np.random.default_rng(seed)

        def mk(s):
            return jnp.asarray(rng.normal(size=s).astype(np.float32), dtype)

        return mk((bh, sq, d)), mk((bh, sk, d)), mk((bh, sk, d))

    @pytest.mark.parametrize("bh,sq,sk,d", [(4, 256, 256, 64), (2, 512, 512, 128),
                                            (1, 128, 512, 64), (8, 256, 256, 32)])
    def test_causal(self, bh, sq, sk, d):
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.ref import flash_attention_ref
        q, k, v = self._mk(bh, sq, sk, d, seed=sq + d)
        o = flash_attention(q, k, v, bq=128, bk=128, interpret=True)
        r = flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kw", [dict(causal=False), dict(window=64),
                                    dict(softcap=50.0),
                                    dict(window=128, softcap=30.0)])
    def test_variants(self, kw):
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.ref import flash_attention_ref
        q, k, v = self._mk(2, 256, 256, 64, seed=11)
        o = flash_attention(q, k, v, bq=128, bk=128, interpret=True, **kw)
        r = flash_attention_ref(q, k, v, **{k_: v_ for k_, v_ in kw.items()})
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.ref import flash_attention_ref
        q, k, v = self._mk(2, 256, 256, 64, dtype=jnp.bfloat16, seed=3)
        o = flash_attention(q, k, v, bq=128, bk=128, interpret=True)
        r = flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_q_offset_decode_window(self):
        """Decode-style call: q is a suffix of the sequence."""
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.ref import flash_attention_ref
        q, k, v = self._mk(2, 128, 512, 64, seed=7)
        o = flash_attention(q, k, v, bq=128, bk=128, q_offset=384, interpret=True)
        r = flash_attention_ref(q, k, v, q_offset=384)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)
