"""Shared benchmark infrastructure: trained tiny teacher models (cached per
process) + CSV emission in the harness's `name,us_per_call,derived` format."""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import DataConfig, SyntheticLM, calibration_batches
from repro.models.config import ModelConfig
from repro.models.registry import get_model, lm_loss
from repro.optim.optimizer import OptConfig, adam_update, init_adam

ROWS = []


def emit(name: str, us_per_call: float, derived: str) -> None:
    row = f"{name},{us_per_call:.2f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


# proxies for the paper's three subjects, same wiring, reduced width
PROXIES = {
    # (family-of-paper-subject, n_layers, d_model, heads, kv, d_ff, vocab)
    "bert-large-proxy": dict(family="dense", n_layers=3, d_model=96, n_heads=4,
                             n_kv_heads=4, d_ff=192, vocab=384, head_dim=24,
                             norm="layernorm", mlp="gelu"),
    "gpt2-xl-proxy": dict(family="dense", n_layers=4, d_model=128, n_heads=4,
                          n_kv_heads=4, d_ff=256, vocab=512, head_dim=32,
                          norm="layernorm", mlp="gelu"),
    "llama2-7b-proxy": dict(family="dense", n_layers=4, d_model=128, n_heads=4,
                            n_kv_heads=2, d_ff=256, vocab=512, head_dim=32),
}


@functools.lru_cache(maxsize=None)
def trained_proxy(name: str, steps: int = 200, seed: int = 0):
    """Train a tiny proxy model; returns (cfg, model, params, eval_ce_fn,
    calib_batches, data_cfg)."""
    kw = dict(PROXIES[name])
    cfg = ModelConfig(arch_id=name, dtype="float32", **kw)
    model = get_model(cfg)
    params = model.init(jax.random.key(seed))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=128, batch_size=16, seed=seed)
    data = SyntheticLM(dcfg)
    opt_cfg = OptConfig(lr=3e-3, warmup_steps=20, total_steps=steps)
    opt = init_adam(params)

    def loss_fn(p, batch):
        logits, _ = model.apply(p, batch)
        return lm_loss(logits, batch["targets"], batch["loss_mask"], cfg.vocab)

    @jax.jit
    def step_fn(params, opt, batch):
        loss, g = jax.value_and_grad(lambda p: loss_fn(p, batch))(params)
        params, opt, _ = adam_update(opt_cfg, params, g, opt)
        return params, opt, loss

    for s in range(steps):
        b = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
        params, opt, _ = step_fn(params, opt, b)

    def eval_ce(p, n=4):
        ev = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=128,
                                    batch_size=16, seed=7777))
        return float(np.mean([
            loss_fn(p, {k: jnp.asarray(v) for k, v in ev.batch(i).items()})
            for i in range(n)]))

    calib = [{k: jnp.asarray(v) for k, v in b.items()}
             for b in calibration_batches(dcfg, n=2)]
    return cfg, model, params, eval_ce, loss_fn, calib


def timeit_p50(fn, *args, warmup=1, repeats=5):
    """Single timing discipline for every bench lane (and for the kernel
    autotuner's measurements, which `benchmarks/kernel_bench.py` makes):
    `warmup` discarded calls to absorb compilation/tracing, then the p50 of
    `repeats` wall-clock measurements, each fenced by `jax.block_until_ready`
    so async dispatch cannot hide device time. Returns (us_per_call, out).

    Interpret and compiled lanes time identically through this helper; only
    what `fn` dispatches differs (benchmarks/run.py --backend)."""
    out = None
    for _ in range(max(warmup, 0)):
        out = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.percentile(ts, 50)) * 1e6, out


def timed(fn, *args, reps=3):
    """Back-compat shim over timeit_p50 (old callers pass `reps`)."""
    return timeit_p50(fn, *args, warmup=1, repeats=reps)


def serving_mode(backend: str):
    """lut_serving mode for a bench lane (benchmarks/run.py --backend):

      "interpret" — the Pallas kernels through the interpreter off-TPU
                    (correctness telemetry, the CI smoke lane; on TPU the
                    compiled kernels, as before the lane existed);
      "compiled"  — auto dispatch (None): compiled Pallas kernels on TPU, the
                    XLA-compiled gather fallback elsewhere — real wall-clock
                    of compiled code on whatever device the host offers.
    """
    if backend == "interpret":
        return None if jax.default_backend() == "tpu" else "interpret"
    if backend == "compiled":
        return None
    raise ValueError(f"unknown bench backend {backend!r}; "
                     f"choose 'interpret' or 'compiled'")
