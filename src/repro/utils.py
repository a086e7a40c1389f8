"""Shared small utilities: padding, tree helpers, deterministic RNG, logging."""
from __future__ import annotations

import dataclasses
import json
import logging
import os
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("repro")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[repro %(levelname)s] %(message)s"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)


# the checkout root (src/repro/utils.py -> ../..): caches the program writes
# live at fixed, git-ignored paths under it, never under $HOME or a temp name
REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    `$JAX_COMPILATION_CACHE_DIR`, when set, is read by JAX itself and wins;
    otherwise the cache lives at `<checkout>/.jax_cache`. The path must not
    move between runs: it is part of what a later run looks up. Call before
    the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    return jax.config.jax_compilation_cache_dir


def round_up(x: int, multiple: int) -> int:
    """Smallest multiple of `multiple` that is >= x."""
    return ((x + multiple - 1) // multiple) * multiple


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pad_axis(x: jnp.ndarray, axis: int, multiple: int, value=0) -> jnp.ndarray:
    """Pad `axis` of x up to a multiple of `multiple` with `value`."""
    size = x.shape[axis]
    target = round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def tree_size_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def tree_num_params(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} EiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def asdict_shallow(cfg) -> dict:
    if dataclasses.is_dataclass(cfg):
        return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return dict(cfg)


def stable_hash(obj: Any) -> int:
    """Deterministic hash of a JSON-serializable object (python hash() is salted)."""
    s = json.dumps(obj, sort_keys=True, default=str)
    h = 1469598103934665603
    for ch in s.encode():
        h = ((h ^ ch) * 1099511628211) & ((1 << 64) - 1)
    return h


def split_key_like_tree(key: jax.Array, tree) -> Any:
    """One PRNG key per leaf of `tree`, deterministic in tree structure."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(keys))
