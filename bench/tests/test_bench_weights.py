"""The seeded clustered tree has the structure compress_model gives."""
import jax
import numpy as np
import pytest

from bench import weights
from bench.tests import tiny
from repro.core.api import compress_model, is_clustered
from repro.core.lut import pack_codes
from repro.models.config import get_config, reduced
from repro.models.registry import get_model


def _model(spec):
    cfg = reduced(get_config("qwen2-1.5b"), dtype="float32", n_layers=2,
                  n_heads=spec["num_attention_heads"],
                  n_kv_heads=spec["num_key_value_heads"],
                  head_dim=spec["head_dim"], d_model=spec["hidden_size"],
                  d_ff=spec["intermediate_size"], vocab=spec["vocab_size"])
    return get_model(cfg)


def _describe(tree):
    flat, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_clustered)
    out = []
    for x in flat:
        if is_clustered(x):
            out.append(("ct", x.nbits) + tuple(
                None if a is None else (a.shape, str(a.dtype))
                for a in (x.codes, x.codebook, x.smooth, x.packed,
                          x.inv_scale, x.act_scale)))
        else:
            out.append((x.shape, str(x.dtype)))
    return treedef, out


@pytest.mark.parametrize("nbits,k", [(4, 8), (2, 4)])
def test_seeded_tree_matches_compress_model(nbits, k):
    spec = tiny.spec(nbits, k)
    model = _model(spec)
    ours = weights.build(model, spec, seed=3)
    theirs, _ = compress_model(model.init(jax.random.key(0)), nbits=nbits,
                               target_centroids=k)
    assert _describe(ours) == _describe(theirs)
    for x in jax.tree_util.tree_leaves(ours, is_leaf=is_clustered):
        if is_clustered(x):
            codes = np.asarray(x.codes)
            assert codes.min() >= 0 and codes.max() < k
            assert np.array_equal(np.asarray(x.packed),
                                  pack_codes(codes.astype(np.uint8), nbits))
            np.testing.assert_allclose(np.asarray(x.inv_scale),
                                       1.0 / np.asarray(x.smooth), rtol=1e-6)


def test_seed_fixes_weights_and_padded_heads_are_zero():
    spec = tiny.spec()
    model = _model(spec)
    make = weights.maker(model, spec)
    a, b, c = (make(weights.seed_key(s)) for s in (2**33 + 1, 2**33 + 1,
                                                   2**33 + 2))
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not all(np.array_equal(x, y) for x, y in zip(la, lc))
    wq, wo = a["blocks"]["attn"]["wq"], a["blocks"]["attn"]["wo"]
    pad = weights._padded_mask(spec, wq.codes.shape[-1])

    def dense(ct):
        return np.stack([np.asarray(ct.codebook)[i][np.asarray(ct.codes)[i]]
                         for i in range(ct.codes.shape[0])])
    assert np.all(dense(wq)[..., pad] == 0)
    assert np.mean(dense(wq)[..., ~pad] != 0) > 0.8
    assert np.all(dense(wo)[:, pad, :] == 0)
    assert np.all(np.asarray(a["blocks"]["attn"]["bq"])[..., pad] == 0)
    # codebooks are balanced: no common-mode part in any tensor
    for ct in (wq, a["blocks"]["mlp"]["w_up"]):
        cb = np.asarray(ct.codebook)
        assert np.all(np.abs(cb.mean(-1)) < 0.05 * np.abs(cb).mean(-1))
    pub = weights.published(a, spec)
    assert pub["blocks.attn.wq"][0].shape == (2, 128, 4 * 32)
    assert pub["blocks.attn.wo"][0].shape == (2, 4 * 32, 128)
    assert pub["embed"].shape == (512, 128)
