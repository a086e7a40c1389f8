"""Sharding rules: logical axis names -> mesh axes, with divisibility safety.

The framework names activation/parameter dimensions logically ("batch",
"vocab", "heads", "kv", "ff", "embed", "experts", ...) and this module maps
them onto physical mesh axes:

    batch   -> ("pod", "data")     # DP (+ pod axis composes additively)
    vocab/ff/heads/kv/experts/q_dim -> "model"   # TP / EP
    embed   -> ("pod", "data")     # FSDP/ZeRO-3-style parameter sharding of the
                                   # d_model dim of weight matrices: XLA inserts
                                   # the FSDP all-gather at use.
    seq     -> ("pod", "data")     # SP for long-context decode KV/state

Every mapping is *divisibility-checked* against the live mesh: if a dimension
does not divide the axis product, that dimension falls back to replicated
(e.g. qwen2's 12 q-heads on a 16-way model axis -> attention replicated on the
model axis while its MLP/vocab still shard; DESIGN.md §4).

`logical_to_spec(shape, names, mesh)` is the single entry; `auto_shard`
decorates whole pytrees given per-leaf logical names. Activation constraints
inside model code go through `maybe_shard`, a no-op unless a rule context is
installed (so the same model code runs on 1 CPU device and on the 512-way
dry-run mesh unchanged).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# logical name -> mesh axes (in priority order)
DEFAULT_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": None,                 # activations keep seq unsharded by default
    # KV-cache sequence dim: takes whatever axes the batch dim left unused —
    # decode_32k (batch over pod+data) -> seq over model (SP flash-decode);
    # long_500k (batch=1, unshardable) -> seq over ALL 512 chips.
    "seq_kv": ("pod", "data", "model"),
    "embed": ("pod", "data"),    # FSDP dim of params
    "embed_nofsdp": None,
    # continuous-batching serving engine (DESIGN.md §5): request slots shard
    # like a batch dim; the physical block pool stays replicated-per-shard on
    # the model axis (each chip holds its kv-head shard of EVERY block, so a
    # slot's block table is valid on all chips without any re-mapping).
    "slots": ("pod", "data"),
    "blocks": None,
    "vocab": "model",
    "heads": "model",
    "kv": "model",
    "kv_flat": "model",
    "q_dim": "model",
    "ff": "model",
    "experts": "model",
    "ssm_heads": "model",
    "ssm_inner": "model",
    "ssm_in": None,              # mamba in-proj fused out dim: replicated (1.2B model)
    "rwkv_heads": "model",
    "layers": None,
    "conv": None,
    "state": None,
    # capability-typed serving caches (DESIGN.md §13): hybrid paged pools
    # carry a leading shared-attention-site dim (few sites — replicated);
    # encoder-decoder slot state carries the encoder frame dim of the
    # cross-KV. Both stay unsharded: "slots" already takes the data axes and
    # "kv" the model axis, so these dims have no axes left to claim.
    "sites": None,
    "enc_seq": None,
}


_rules_ctx: contextvars.ContextVar = contextvars.ContextVar("sharding_rules", default=None)


@dataclasses.dataclass
class ShardingRules:
    mesh: Mesh
    rules: Dict[str, Axis]
    fsdp: bool = True            # False: drop the "embed" FSDP sharding (serve mode)

    def axis_size(self, axis: Axis) -> int:
        if axis is None:
            return 1
        axes = (axis,) if isinstance(axis, str) else axis
        size = 1
        for a in axes:
            size *= self.mesh.shape.get(a, 1)
        return size


@contextlib.contextmanager
def use_rules(mesh: Mesh, overrides: Optional[Dict[str, Axis]] = None, *, fsdp: bool = True):
    rules = dict(DEFAULT_RULES)
    if not fsdp:
        rules["embed"] = None
    if overrides:
        rules.update(overrides)
    tok = _rules_ctx.set(ShardingRules(mesh, rules, fsdp))
    try:
        yield
    finally:
        _rules_ctx.reset(tok)


def current_rules() -> Optional[ShardingRules]:
    return _rules_ctx.get()


def kernel_mesh() -> Optional[Mesh]:
    """The installed rules' mesh when it spans more than one device, else
    None. A Pallas TPU kernel is one custom call that GSPMD cannot
    partition, so on such a mesh the kernel wrappers run it per device
    under `shard_map` (kernels/ops.py, kernels/paged_attention.py)."""
    sr = current_rules()
    if sr is None or sr.mesh.size == 1:
        return None
    return sr.mesh


def logical_to_spec(shape: Sequence[int], names: Sequence[Optional[str]],
                    sr: Optional[ShardingRules] = None) -> P:
    """Build a PartitionSpec for `shape` from logical dim names, dropping any
    mapping whose axis size does not divide the dimension."""
    sr = sr or current_rules()
    if sr is None:
        return P()
    assert len(shape) == len(names), (shape, names)
    out = []
    used: set = set()
    for dim, name in zip(shape, names):
        axis = sr.rules.get(name) if name else None
        if axis is None:
            out.append(None)
            continue
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        # drop axes already used by an earlier dim (PartitionSpec axes must be unique)
        axes = tuple(a for a in axes if a not in used and a in sr.mesh.shape)
        size = int(np.prod([sr.mesh.shape[a] for a in axes])) if axes else 1
        if size > 1 and dim % size == 0:
            out.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            # try a shrinking suffix (e.g. ("pod","data") -> ("data",)) before
            # giving up — keeps partial sharding when only the pod axis misfits
            placed = False
            for start in range(1, len(axes)):
                sub = axes[start:]
                s = int(np.prod([sr.mesh.shape[a] for a in sub]))
                if s > 1 and dim % s == 0:
                    out.append(sub if len(sub) > 1 else sub[0])
                    used.update(sub)
                    placed = True
                    break
            if not placed:
                out.append(None)
    return P(*out)


def named_sharding(shape: Sequence[int], names: Sequence[Optional[str]],
                   sr: Optional[ShardingRules] = None) -> Optional[NamedSharding]:
    sr = sr or current_rules()
    if sr is None:
        return None
    return NamedSharding(sr.mesh, logical_to_spec(shape, names, sr))


def maybe_shard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Activation sharding constraint; no-op without an installed rule context."""
    sr = current_rules()
    if sr is None:
        return x
    spec = logical_to_spec(x.shape, names, sr)
    return jax.lax.with_sharding_constraint(x, NamedSharding(sr.mesh, spec))


def parse_names(names: str) -> Tuple[Optional[str], ...]:
    """'layers,embed,ff' -> ('layers','embed','ff'); '.' = replicated dim;
    '' = scalar (rank 0)."""
    if names == "":
        return ()
    return tuple(None if n in (".", "") else n for n in names.split(","))


def auto_shard(tree, tree_names, sr: Optional[ShardingRules] = None):
    """NamedShardings for a whole (possibly LCD-compressed) parameter pytree.

    `tree_names` is the DENSE tree's names pytree (plain comma-joined strings,
    models/params.py names_tree) — it does not know about compression. A
    ClusteredTensor leaf (core/api.py) expands into six array children, so the
    two trees stop matching structurally after compress_model; this is the
    single place that bridges them (DESIGN.md §4, §10):

      codes / packed -> the dense weight's names (packed rows are d_in·nbits/8;
                        the divisibility fallback replicates them when the
                        model axis stops dividing);
      smooth / inv_scale -> the names minus the output dim (they are (d_in,)
                        vectors, (L, d_in) when stacked);
      codebook / act_scale -> replicated (tiny).

    Returns a pytree with the same structure as `tree` (None fields stay
    None), ready for `jax.device_put(tree, auto_shard(tree, names))` or for
    attaching to ShapeDtypeStructs when lowering.
    """
    sr = sr or current_rules()
    assert sr is not None, "auto_shard needs a rules context (use_rules) or sr"
    try:
        from repro.core.api import is_clustered
    except ImportError:              # core not importable in stripped builds
        def is_clustered(x):
            return False

    def clustered(ct, nm: Tuple[Optional[str], ...]):
        vec_nm = nm[:-1]             # smoothing vectors live on the d_in dims

        def ns(arr, names):
            if arr is None:
                return None
            return named_sharding(arr.shape, names, sr)

        return type(ct)(
            codes=ns(ct.codes, nm),
            codebook=ns(ct.codebook, (None,) * ct.codebook.ndim),
            smooth=ns(ct.smooth, vec_nm),
            packed=ns(ct.packed, nm),
            inv_scale=ns(ct.inv_scale, vec_nm),
            act_scale=(None if ct.act_scale is None
                       else ns(ct.act_scale, (None,) * ct.act_scale.ndim)),
            nbits=ct.nbits,
        )

    def one(leaf, names: str):
        nm = parse_names(names)
        if is_clustered(leaf):
            return clustered(leaf, nm)
        return named_sharding(leaf.shape, nm, sr)

    return jax.tree_util.tree_map(one, tree, tree_names, is_leaf=is_clustered)


def tree_shardings(tree_shapes, tree_names, sr: Optional[ShardingRules] = None):
    """Map a pytree of ShapeDtypeStructs + a matching pytree of comma-joined
    logical-name strings to NamedShardings (for in_shardings/out_shardings).

    Name leaves are plain strings ("layers,embed,ff") so the names tree has
    exactly the same pytree structure as the params tree.
    """
    sr = sr or current_rules()
    assert sr is not None

    def one(shape_struct, names: str):
        return named_sharding(shape_struct.shape, parse_names(names), sr)

    return jax.tree_util.tree_map(one, tree_shapes, tree_names)
