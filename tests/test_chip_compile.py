"""Compile-only checks of the served path's Pallas kernels for a TPU v5e.

The TPU compiler is installed on CPU-only hosts too, and compiles for a
described (not attached) chip: that catches what interpret mode cannot —
tiling, VMEM and lowering refusals — at qwen2-1.5b's published widths
(d_model 1536, 16 served heads x 128 = 2048, 2 kv heads = 256, d_ff 8960)
and the engine's decode geometry. Nothing runs. The topology is described
inside a module fixture, never at import: only one process may load the TPU
library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lut import packed_rows
from repro.kernels.ops import (lut_gemm_fused, lut_gemm_fused_multi,
                               track_lut_launches)
from repro.kernels.paged_attention import paged_pool_attention

D, Q, KV_FLAT, FF = 1536, 2048, 256, 8960
SLOTS = 8                                   # decode GEMV rows (sublane-padded)
BLOCK, NUM_BLOCKS, TABLE, KV, HD, HEADS = 16, 128, 32, 2, 128, 16
# the wide (prefill_chunk) step's rows in both benchmark cells: 16 slots x
# 128 (qwen2-1.5b, 4-bit) and 32 slots x 64 (starcoder2-15b at its
# published widths, 2-bit: d 6144, 48 heads x 128, 4 kv heads, d_ff 24576)
WIDE = 2048
SC_D, SC_KV_FLAT, SC_FF = 6144, 512, 24576


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    """HLO text of `fn` compiled for the described chip."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(dev, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


@pytest.mark.parametrize("nbits", [2, 3, 4])
@pytest.mark.parametrize("k,n", [(Q, D), (FF, D)], ids=["wo", "w_down"])
def test_lut_gemv_compiles(one_chip, nbits, k, n):
    hlo = _compile(
        lambda x, inv, pk, cb, act: lut_gemm_fused(
            x, inv, pk, cb, act, quantize=True, interpret=False,
            nbits=nbits),
        _spec(one_chip, (SLOTS, k), jnp.bfloat16),
        _spec(one_chip, (k,), jnp.float32),
        _spec(one_chip, (packed_rows(k, nbits), n), jnp.uint8),
        _spec(one_chip, (16,), jnp.float32),
        _spec(one_chip, (), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("nbits", [2, 3, 4])
@pytest.mark.parametrize("widths", [(Q, KV_FLAT, KV_FLAT), (FF, FF)],
                         ids=["qkv", "gate_up"])
def test_lut_multi_gemv_compiles(one_chip, nbits, widths):
    p = len(widths)
    hlo = _compile(
        lambda x, inv, cb, act, *pk: lut_gemm_fused_multi(
            x, inv, cb, act, *pk, quantize=(False,) * p, interpret=False,
            nbits=(nbits,) * p),
        _spec(one_chip, (SLOTS, D), jnp.bfloat16),
        _spec(one_chip, (p, D), jnp.float32),
        _spec(one_chip, (p, 16), jnp.float32),
        _spec(one_chip, (p,), jnp.float32),
        *[_spec(one_chip, (packed_rows(D, nbits), w), jnp.uint8)
          for w in widths])
    assert "tpu_custom_call" in hlo


def _gemm_compiles(dev, nbits, k, widths):
    """Compile one wide fused LUT launch (multi when several widths) and
    return the launch log's rows per decoded tile."""
    p = len(widths)
    with track_lut_launches() as log:
        if p == 1:
            hlo = _compile(
                lambda x, inv, pk, cb, act: lut_gemm_fused(
                    x, inv, pk, cb, act, quantize=True, interpret=False,
                    nbits=nbits),
                _spec(dev, (WIDE, k), jnp.bfloat16),
                _spec(dev, (k,), jnp.float32),
                _spec(dev, (packed_rows(k, nbits), widths[0]), jnp.uint8),
                _spec(dev, (16,), jnp.float32),
                _spec(dev, (), jnp.float32))
        else:
            hlo = _compile(
                lambda x, inv, cb, act, *pk: lut_gemm_fused_multi(
                    x, inv, cb, act, *pk, quantize=(True,) * p,
                    interpret=False, nbits=(nbits,) * p),
                _spec(dev, (WIDE, k), jnp.bfloat16),
                _spec(dev, (p, k), jnp.float32),
                _spec(dev, (p, 16), jnp.float32),
                _spec(dev, (p,), jnp.float32),
                *[_spec(dev, (packed_rows(k, nbits), w), jnp.uint8)
                  for w in widths])
    assert "tpu_custom_call" in hlo
    return log.rows_per_decode


@pytest.mark.parametrize("nbits,k,widths", [
    (4, D, (Q, KV_FLAT, KV_FLAT)), (4, D, (FF, FF)), (4, Q, (D,)),
    (4, FF, (D,)),
    (2, SC_D, (SC_D, SC_KV_FLAT, SC_KV_FLAT)), (2, SC_D, (SC_D,)),
    (2, SC_D, (SC_FF,)), (2, SC_FF, (SC_D,))],
    ids=["qwen2.qkv", "qwen2.gate_up", "qwen2.wo", "qwen2.w_down",
         "starcoder2.qkv", "starcoder2.wo", "starcoder2.w_up",
         "starcoder2.w_down"])
def test_lut_wide_gemm_compiles_and_decodes_once(one_chip, nbits, k, widths):
    """The wide step's LUT GEMMs fit the chip's scoped VMEM with all 2048
    rows sharing each decoded tile, as the launch log records."""
    rows = _gemm_compiles(one_chip, nbits, k, widths)
    variant = "lut_fused" if len(widths) == 1 else "lut_fused_multi"
    assert rows == {(variant, WIDE, k, sum(widths)): WIDE}


@pytest.mark.parametrize("pool", ["float", "int8"])
@pytest.mark.parametrize("t", [1, 128], ids=["decode", "prefill_chunk"])
def test_paged_pool_attention_compiles(one_chip, pool, t):
    dt = jnp.int8 if pool == "int8" else jnp.bfloat16
    shapes = [_spec(one_chip, (4, t, HEADS, HD), jnp.bfloat16),
              _spec(one_chip, (NUM_BLOCKS, BLOCK, KV, HD), dt),
              _spec(one_chip, (NUM_BLOCKS, BLOCK, KV, HD), dt),
              _spec(one_chip, (4, TABLE), jnp.int32),
              _spec(one_chip, (4,), jnp.int32),
              _spec(one_chip, (4,), jnp.int32),
              _spec(one_chip, (), jnp.int32)]
    if pool == "int8":
        shapes += [_spec(one_chip, (NUM_BLOCKS, BLOCK, KV), jnp.float32)] * 2
        shapes += [_spec(one_chip, (KV, HD), jnp.float32)] * 2

    def fn(q, kp, vp, bt, ln, nn, w, *int8):
        ks, vs, ksm, vsm = int8 or (None,) * 4
        return paged_pool_attention(q, kp, vp, bt, ln, nn, w, k_scale=ks,
                                    v_scale=vs, k_smooth=ksm, v_smooth=vsm)

    assert "tpu_custom_call" in _compile(fn, *shapes)
