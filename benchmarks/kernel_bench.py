"""Kernel-level benchmark: lut_matmul vs dense GEMM, per bench lane.

    PYTHONPATH=src python -m benchmarks.run --only kernel [--backend compiled]

Two lanes (benchmarks/run.py --backend, DESIGN.md §11):

  interpret — the Pallas kernels through the interpreter (correctness-path
              telemetry, NOT a perf claim) at the autotuner's block shapes,
              which under the interpreter are exactly the `_pick_blocks`
              heuristic;
  compiled  — real wall-clock of compiled code on whatever the host offers:
              the compiled Pallas kernels on TPU, the XLA-compiled gather
              contraction elsewhere (the actual CPU serving dispatch). On TPU
              this lane is the autotuner's measuring caller: before timing a
              shape it times the candidate grid (the heuristic is in it, so
              the winner can only match or beat it) outside any trace and
              stores the winner in the autotune cache, which the serving
              wrappers then read while they trace (DESIGN.md §11).

Every row also carries the v5e roofline byte model (weight-stream bytes per
GEMV for bf16 vs packed sub-byte codes — the quantity the decode speedup
rides on) so `benchmarks/roofline.py` can print measured-vs-roofline
fractions from the BENCH_trajectory.json record this run appends.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit_p50
from repro.core.lut import pack4
from repro.kernels import autotune
from repro.kernels.lut_matmul import lut_matmul_f32
from repro.kernels.ops import lut_gemm, pad_codebook, pad_for_kernel
from repro.kernels.ref import lut_matmul_f32_ref

HBM_BW = 819e9

SHAPES = ((1, 4096, 4096), (8, 4096, 11008), (128, 2048, 2048))


def _measure_fn(x, packed, cb):
    """measure(bm, bn, bk) -> seconds of the compiled `lut_gemm` kernel at
    those blocks, timed eagerly (never inside a trace)."""
    def measure(bm: int, bn: int, bk: int) -> float:
        xp, cp, _ = pad_for_kernel(x, packed, bm, bk, bn, 4)
        us, _ = timeit_p50(functools.partial(
            lut_matmul_f32, xp, cp, pad_codebook(cb), bm=bm, bn=bn, bk=bk,
            interpret=False, nbits=4))
        return us * 1e-6
    return measure


def run(backend: str = "interpret") -> dict:
    on_tpu = jax.default_backend() == "tpu"
    # the LUT kernel itself: interpreter in the interpret lane and on CPU
    # hosts (Pallas TPU kernels cannot compile elsewhere); compiled on TPU
    interpret = backend == "interpret" or not on_tpu
    rng = np.random.default_rng(0)
    rows = []
    for (m, k, n) in SHAPES:
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        codes = rng.integers(0, 16, size=(k, n)).astype(np.uint8)
        cb = jnp.asarray(np.sort(rng.normal(0, 0.05, 16)).astype(np.float32))
        packed = jnp.asarray(pack4(codes))
        w_dense = jnp.asarray((np.asarray(cb)[codes]).astype(np.float32))

        heur = autotune.heuristic_blocks(m, k, n)
        us_dense, _ = timeit_p50(
            jax.jit(lambda a, b: a @ b), x, w_dense)
        fallback_reason = None
        if backend == "compiled" and not on_tpu:
            # the compiled lane off-TPU times the XLA gather contraction —
            # the dispatch clustered_linear actually serves on this host
            us_lut, _ = timeit_p50(
                jax.jit(lambda a, p, c: lut_matmul_f32_ref(a, p, c)),
                x, packed, cb)
            kernel, tuned = "xla-ref", list(heur)
            fallback_reason = (
                f"no TPU on this host (jax backend "
                f"{jax.default_backend()!r}): Pallas TPU kernels cannot "
                f"compile, timing the XLA gather contraction instead")
        else:
            # the blocks lut_gemm resolves while it traces: the cached
            # winner, which this lane measures first on TPU, else (and
            # always under the interpreter) the heuristic
            measure = None if interpret else _measure_fn(x, packed, cb)
            tuned = list(autotune.pick_blocks(
                m, k, n, nbits=4, variant="lut_f32", interpret=interpret,
                measure=measure))
            us_lut, _ = timeit_p50(
                functools.partial(lut_gemm, x, packed, cb,
                                  interpret=interpret))
            kernel = "pallas-interpret" if interpret else "pallas"

        bytes_bf16 = k * n * 2
        bytes_int4 = k * n // 2 + 16 * 4
        t_bf16 = bytes_bf16 / HBM_BW * 1e6
        t_int4 = bytes_int4 / HBM_BW * 1e6
        row = {
            "name": f"lut_gemm_{m}x{k}x{n}", "m": m, "k": k, "n": n,
            "kernel": kernel, "us": round(us_lut, 2),
            "dense_us": round(us_dense, 2),
            "blocks": tuned, "heuristic_blocks": list(heur),
            "roofline_us": round(t_int4, 2),
            "roofline_bf16_us": round(t_bf16, 2),
        }
        if fallback_reason is not None:
            # scripts/perf_gate.py keys timing comparisons by `kernel`, so
            # an xla-ref row never gates against a pallas row; the reason
            # makes the variant switch auditable in the trajectory
            row["fallback_reason"] = fallback_reason
        rows.append(row)
        emit(f"kernel/lut_gemm_{m}x{k}x{n}", us_lut,
             f"dense_us={us_dense:.1f};kernel={kernel};"
             f"blocks={'x'.join(map(str, tuned))};"
             f"v5e_weight_stream_bf16_us={t_bf16:.1f};v5e_int4_us={t_int4:.1f};"
             f"roofline_speedup={t_bf16/t_int4:.2f}x")
    return {"backend": backend, "shapes": rows}


if __name__ == "__main__":
    run()
