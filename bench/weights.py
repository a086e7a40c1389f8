"""Seeded serving weights, made on the device in one jitted call.

For a configuration with an `lcd` block the tree has the structure
`compress_model(nbits=n, target_centroids=k)` gives: every leaf it would
cluster is a ClusteredTensor with the same fields, dtypes and `nbits`, and
a k-entry codebook per layer; every other leaf keeps
the dtype of `model.abstract()`. No clustering runs: codes are drawn
uniformly, each codebook is the k-point clustering of a normal weight at
the leaf's fan-in scale with a per-layer scale and per-centroid jitter, the
smoothing vector is drawn near 1, and `packed` is the program's own packing
of the drawn codes. Without an `lcd` block every projection is a dense
normal weight at its fan-in scale, in the model's dtype. The LUT kernels
do the same work whatever the code values are, and the check compares the
served tokens against a reference built from these same arrays.

Published weights are placed in the program's layout here, as a checkpoint
loader would: RMSNorm weights are stored as offsets from 1 (the program
computes x * (1 + scale)); where the configuration serves more query heads
than it publishes, the published heads fill the first slots of each kv
group and the padded heads get zero query columns, query bias and output
rows. `published()` undoes the placement for the reference.
"""
from __future__ import annotations

import numpy as np

NORM_STD = 0.1      # spread of norm weights around their neutral value
CODEBOOK_JITTER = 0.05
BIAS_STD = 0.1
EMBED_STD = 0.02


def leaf_name(path) -> str:
    """'blocks.attn.wq' for the key path of params['blocks']['attn']['wq']."""
    return ".".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def seed_key(seed: int):
    """A jax PRNG key from any non-negative integer seed, also past 2**32."""
    import jax
    word = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]
    return jax.random.key(int(word))


def base_codebook(k: int) -> np.ndarray:
    """k centroids of a unit normal: the midpoints, in probability, of k
    equally likely bins, scaled to unit mean square. Symmetric about 0, as
    the clusters of a zero-mean weight are: a codebook with a mean would give
    every tensor a rank-one part along the all-ones direction, which the
    uniform codes then amplify through the layers."""
    from statistics import NormalDist
    c = np.array([NormalDist().inv_cdf((j + 0.5) / k) for j in range(k)])
    return (c / np.sqrt(np.mean(c * c))).astype(np.float32)


def head_map(spec: dict) -> np.ndarray:
    """Served head slot of each published query head (kv groups are
    contiguous in both layouts, as in grouped-query attention)."""
    h, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    served = spec["served"]["heads"]
    g_pub, g_srv = h // kv, served // kv
    j = np.arange(h)
    return (j // g_pub) * g_srv + j % g_pub


def _padded_mask(spec: dict, width: int) -> np.ndarray:
    """(width,) bool over a served q dimension: True on padded heads."""
    hd = spec["head_dim"]
    real = np.zeros(spec["served"]["heads"], bool)
    real[head_map(spec)] = True
    mask = ~np.repeat(real, hd)
    assert mask.shape == (width,), (mask.shape, width)
    return mask


def build(model, spec: dict, seed: int):
    """The serving parameter tree for `model`, drawn on the device from
    `seed`. `spec` is the configuration file (bench/configs/<name>.json)."""
    return maker(model, spec)(seed_key(seed))


def maker(model, spec: dict):
    """The jitted function key -> serving parameter tree."""
    import jax
    import jax.numpy as jnp
    from repro.core.api import ClusteredTensor, default_predicate
    from repro.core.lut import pack_codes_jax

    lcd = spec.get("lcd")
    if lcd:
        nbits, k = lcd["nbits"], lcd["centroids"]
        assert k <= 1 << nbits, (k, nbits)
    rms = spec["norm"] == "rmsnorm"
    q_out = set(spec["served"].get("q_out", ()))
    q_in = set(spec["served"].get("q_in", ()))
    padded = spec["served"]["heads"] != spec["num_attention_heads"]
    vocab = spec["vocab_size"]
    abstract = model.abstract()
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    leaves = [(leaf_name(p), jax.tree_util.keystr(p), a) for p, a in flat]

    def clustered(key, name, a):
        lead, (d_in, d_out) = a.shape[:-2], a.shape[-2:]
        k1, k2, k3 = jax.random.split(key, 3)
        k1, k4 = jax.random.split(k1)
        jitter = 1.0 + CODEBOOK_JITTER * jax.random.normal(
            k1, lead + (k,), jnp.float32)
        scale = jnp.exp(jax.random.uniform(k4, lead + (1,), jnp.float32,
                                           -0.1, 0.1))
        cb = jnp.asarray(base_codebook(k)) * jitter * scale / np.sqrt(d_in)
        codes = (jax.random.bits(k2, a.shape, jnp.uint8) % k).astype(jnp.int8)
        if padded and (name in q_out or name in q_in):
            # padded heads decode to exactly 0: the centroid nearest 0 is
            # set to 0 (the codebook keeps its balance), and their columns
            # (query) or rows (output) hold its code
            zero = jnp.argmin(jnp.abs(cb), axis=-1)
            cb = jnp.where(jnp.arange(k) == zero[..., None], 0.0, cb)
            zc = zero.astype(jnp.int8)[..., None, None]
            if name in q_out:
                codes = jnp.where(_padded_mask(spec, d_out), zc, codes)
            else:
                codes = jnp.where(_padded_mask(spec, d_in)[:, None], zc, codes)
        smooth = jnp.exp(jax.random.uniform(k3, lead + (d_in,), jnp.float32,
                                            -0.25, 0.25))
        return ClusteredTensor(
            codes=codes, codebook=cb, smooth=smooth,
            packed=pack(codes),
            inv_scale=1.0 / smooth, act_scale=None, nbits=nbits)

    def dense(key, name, keystr, a):
        last = name.rsplit(".", 1)[-1]
        z = jax.random.normal(key, a.shape, jnp.float32)
        if name == "embed":
            v = z * EMBED_STD
            v = jnp.where(jnp.arange(a.shape[0])[:, None] < vocab, v, 0.0)
        elif last == "scale":
            v = z * NORM_STD + (0.0 if rms else 1.0)
        elif last.startswith("b"):
            v = z * BIAS_STD
            if padded and name in q_out:
                v = jnp.where(_padded_mask(spec, a.shape[-1]), 0.0, v)
        elif default_predicate(keystr, a):
            # a projection of a configuration served without LCD codes
            v = z / np.sqrt(a.shape[-2])
            if padded and name in q_out:
                v = jnp.where(_padded_mask(spec, a.shape[-1]), 0.0, v)
            elif padded and name in q_in:
                v = jnp.where(_padded_mask(spec, a.shape[-2])[:, None], 0.0, v)
        else:
            raise ValueError(f"no rule draws leaf {name} {a.shape}")
        return v.astype(a.dtype)

    def pack(codes):
        # codes are drawn on the device in one go; packing them layer by
        # layer keeps the packer's 32-bit words to one layer's worth
        if codes.ndim == 2:
            return pack_codes_jax(codes, nbits)
        return jax.lax.map(lambda c: pack_codes_jax(c, nbits), codes)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, keystr, a) in enumerate(leaves):
            if name == "lm_head":
                continue
            key_i = jax.random.fold_in(key, i)
            out[name] = (clustered(key_i, name, a)
                         if lcd and default_predicate(keystr, a) else
                         dense(key_i, name, keystr, a))
        for i, (name, _, a) in enumerate(leaves):
            if name == "lm_head":
                if spec["tie_word_embeddings"]:
                    head = out["embed"].T
                else:
                    head = jax.random.normal(jax.random.fold_in(key, i),
                                             a.shape, jnp.float32)
                    head = head / np.sqrt(a.shape[0])
                    head = jnp.where(jnp.arange(a.shape[1]) < vocab, head, 0.0)
                out[name] = head.astype(a.dtype)
        return jax.tree_util.tree_unflatten(
            treedef, [out[name] for name, _, _ in leaves])

    return make


def published(params, spec: dict):
    """{name: array} at published shapes and conventions, from the bench's
    own arrays: clustered leaves stay (codes, codebook, smooth) triples with
    the padded heads' columns or rows taken out; RMSNorm offsets become
    weights; embed and lm_head lose their padded vocab rows. No packed codes
    and nothing the program computed is carried over."""
    import jax
    import jax.numpy as jnp
    from repro.core.api import is_clustered

    vocab = spec["vocab_size"]
    served_cols = None
    if spec["served"]["heads"] != spec["num_attention_heads"]:
        hd = spec["head_dim"]
        served_cols = (head_map(spec)[:, None] * hd
                       + np.arange(hd)[None]).reshape(-1)
    q_out = set(spec["served"].get("q_out", ()))
    q_in = set(spec["served"].get("q_in", ()))
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(params, is_leaf=is_clustered)[0]
    for path, leaf in flat:
        name = leaf_name(path)
        if is_clustered(leaf):
            codes, cb, sm = leaf.codes, leaf.codebook, leaf.smooth
            if served_cols is not None and name in q_out:
                codes = codes[..., served_cols]
            elif served_cols is not None and name in q_in:
                codes, sm = codes[..., served_cols, :], sm[..., served_cols]
            out[name] = (codes, cb, sm)
            continue
        if served_cols is not None and name in q_out:
            leaf = leaf[..., served_cols]
        elif served_cols is not None and name in q_in:
            leaf = leaf[..., served_cols, :]
        if name == "embed":
            leaf = leaf[:vocab]
        elif name == "lm_head":
            leaf = leaf[:, :vocab]
        elif name.endswith(".scale") and spec["norm"] == "rmsnorm":
            leaf = 1.0 + leaf.astype(jnp.float32)
        out[name] = leaf
    return out
