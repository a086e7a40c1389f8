"""Plain float32 reference of the served models, written from the published
architecture: token embedding, per layer a pre-norm causal self-attention
with grouped kv heads and rotary positions (rotating dimension pairs
(i, i + head_dim/2), base `rope_theta`) and a pre-norm MLP (SwiGLU, or GELU
with tanh approximation), then a final norm and the vocab head. No kernels,
no cache, no batching; every matmul at `highest` precision; one layer at a
time, its clustered weights expanded to float32 (codebook[codes] / smooth)
only while it runs. It imports nothing of the program.

`weights` is the {name: array} of bench/weights.py `published`: clustered
projections as (codes, codebook, smooth) triples, the rest as arrays.

`precision="fp8"` is the control: every matmul input rounded to float8
(e4m3, one scale per tensor), the step a lower-precision serving path
would take.
"""
from __future__ import annotations

from functools import partial

import numpy as np

PAD = 256                     # shortest padded sequence; longer ones pad to
                              # the next power of two, so few shapes compile


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def _q8(x):
    """x rounded to float8 e4m3 with one scale per tensor, back in f32."""
    _, jnp = _jnp()
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8):
    _, jnp = _jnp()
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b)


def _dense(w):
    """float32 weight of a clustered triple or a plain array."""
    _, jnp = _jnp()
    if isinstance(w, tuple):
        codes, cb, sm = w
        return cb[codes.astype(jnp.int32)] / sm[:, None]
    return w.astype(jnp.float32)


def _norm(x, w, b, kind, eps):
    _, jnp = _jnp()
    if kind == "rmsnorm":
        return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rope(x, theta):
    """x: (S, H, D) at positions 0..S-1."""
    _, jnp = _jnp()
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(arch, fp8, x, p):
    """One decoder layer over the whole padded sequence x: (S, d)."""
    jax, jnp = _jnp()
    h_, kv, hd, kind, eps, theta, mlp = arch
    s = x.shape[0]
    h = _norm(x, p["ln_attn.scale"], p.get("ln_attn.bias"), kind, eps)
    q = _mm(h, _dense(p["attn.wq"]), fp8)
    k = _mm(h, _dense(p["attn.wk"]), fp8)
    v = _mm(h, _dense(p["attn.wv"]), fp8)
    if "attn.bq" in p:
        q, k, v = q + p["attn.bq"], k + p["attn.bk"], v + p["attn.bv"]
    q = _rope(q.reshape(s, h_, hd), theta)
    k = _rope(k.reshape(s, kv, hd), theta)
    v = v.reshape(s, kv, hd)
    rep = h_ // kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    sc = _mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0), fp8) / np.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.where(causal[None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = _mm(pr, v.transpose(1, 0, 2), fp8).transpose(1, 0, 2).reshape(s, -1)
    x = x + _mm(o, _dense(p["attn.wo"]), fp8)
    h = _norm(x, p["ln_mlp.scale"], p.get("ln_mlp.bias"), kind, eps)
    if mlp == "swiglu":
        g = _mm(h, _dense(p["mlp.w_gate"]), fp8)
        u = _mm(h, _dense(p["mlp.w_up"]), fp8)
        a = jax.nn.silu(g) * u
        y = _mm(a, _dense(p["mlp.w_down"]), fp8)
    else:
        u = _mm(h, _dense(p["mlp.w_up"]), fp8) + p["mlp.b_up"]
        a = jax.nn.gelu(u, approximate=True)
        y = _mm(a, _dense(p["mlp.w_down"]), fp8) + p["mlp.b_down"]
    return x + y


class Reference:
    """Logits of a token sequence, one layer at a time."""

    def __init__(self, spec: dict, weights: dict, precision: str = "f32"):
        jax, jnp = _jnp()
        self.spec, self.w = spec, weights
        self.fp8 = precision == "fp8"
        eps = spec.get("rms_norm_eps", spec.get("norm_epsilon"))
        self.arch = (spec["num_attention_heads"], spec["num_key_value_heads"],
                     spec["head_dim"], spec["norm"], float(eps),
                     float(spec["rope_theta"]), spec["mlp"])
        self.layer_fn = jax.jit(partial(_layer, self.arch, self.fp8))
        self.stacked = {n[len("blocks."):]: v for n, v in weights.items()
                        if n.startswith("blocks.")}

    def _layer_weights(self, i):
        def take(v):
            return tuple(a[i] for a in v) if isinstance(v, tuple) else v[i]
        return {n: take(v) for n, v in self.stacked.items()}

    def hidden(self, tokens: np.ndarray):
        """Final-norm hidden states (len(tokens), d) in float32."""
        jax, jnp = _jnp()
        n = len(tokens)
        s = max(PAD, 1 << (n - 1).bit_length())
        ids = np.zeros(s, np.int32)
        ids[:n] = tokens
        with jax.default_matmul_precision("highest"):
            x = self.w["embed"].astype(jnp.float32)[jnp.asarray(ids)]
            for i in range(self.spec["num_hidden_layers"]):
                x = self.layer_fn(x, self._layer_weights(i))
            eps = self.arch[4]
            x = _norm(x, self.w["ln_final.scale"], self.w.get("ln_final.bias"),
                      self.arch[3], eps)
        return x[:n]

    def head(self):
        _, jnp = _jnp()
        if "lm_head" in self.w:
            return self.w["lm_head"].astype(jnp.float32)
        return self.w["embed"].astype(jnp.float32).T

    def logits(self, x, rows: int = 256):
        """Vocab logits of hidden rows x, as float32 numpy, in row blocks."""
        jax, jnp = _jnp()
        head = self.head()
        out = []
        with jax.default_matmul_precision("highest"):
            for i in range(0, x.shape[0], rows):
                out.append(np.asarray(_mm(x[i:i + rows], head, self.fp8)))
        return np.concatenate(out)
