"""Tensor-parallel serving on a jax mesh (DESIGN.md §14).

The parity contract: the continuous engine's output tokens are identical on
every (data, model) mesh factorization — sharding is a placement decision,
never a numerics decision a user can observe at the token level. The suite
runs engine-vs-solo parity per mesh shape (1x1 anywhere; 2x4 and 8x1 under
the forced-8-device lane — `pytest -m mesh`, conftest.py injects
``--xla_force_host_platform_device_count=8`` before jax initializes), the
bounded-trace contract under sharding constraints, the pinned-ValueError
surface for mesh/config mismatches, and the hlo_cost layout chooser.
"""
import jax
import numpy as np
import pytest

from repro.core.api import compress_model
from repro.kernels.ops import lut_serving
from repro.kernels.paged_attention import paged_attention_mode
from repro.launch.engine import EngineConfig, ServingEngine, build_engine
from repro.launch.mesh import auto_mesh, make_elastic_mesh
from repro.models.config import ModelConfig
from repro.models.registry import get_model


def _mesh(dp: int, mp: int):
    devs = jax.devices()
    if len(devs) < dp * mp:
        pytest.skip(f"needs {dp * mp} devices, have {len(devs)} "
                    f"(run under `pytest -m mesh` / REPRO_MESH_LANE=1)")
    return auto_mesh((dp, mp), ("data", "model"), devices=devs[:dp * mp])


@pytest.fixture(scope="module")
def tiny():
    # every TP-sharded dim divides 8 (q_dim=64, kv_flat=32, ff=128,
    # vocab=256) so the 8x1 and 2x4 lanes genuinely shard the weights;
    # n_kv_heads=2 does NOT divide 4 or 8, so the paged pool's kv dim
    # exercises the divisibility fallback (replicates) at the same time
    cfg = ModelConfig(arch_id="tiny-tp", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab=256, head_dim=16, dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _ecfg(**kw):
    base = dict(num_slots=4, block_size=4, num_blocks=32,
                max_blocks_per_slot=8, prefill_chunk=8)
    base.update(kw)
    return EngineConfig(**base)


def _prompts(n=3, vocab=256):
    rng = np.random.default_rng(7)
    return [rng.integers(0, vocab, int(rng.integers(5, 13))).astype(np.int32)
            for _ in range(n)]


def _engine_tokens(model, params, mesh, prompts, gen=8, ecfg=None):
    """All prompts through ONE engine (continuous batching on `mesh`);
    returns per-request token lists + the engine for trace assertions."""
    eng = ServingEngine(model, params, ecfg or _ecfg(), mesh=mesh)
    reqs = [eng.submit(p, gen) for p in prompts]
    eng.run()
    eng.assert_bounded_traces()
    return [list(r.out_tokens) for r in reqs], eng


def _solo_tokens(model, params, mesh, prompt, gen=8):
    """Single-request reference on the same mesh — a fresh engine per
    prompt, so multi-request batching can't leak across requests."""
    eng = ServingEngine(model, params, _ecfg(), mesh=mesh)
    r = eng.submit(prompt, gen)
    eng.run()
    eng.assert_bounded_traces()
    return list(r.out_tokens)


class TestShardedParity:
    """Engine-vs-solo parity per mesh shape. (1,1) runs on any host; the
    multi-device shapes skip unless the forced-8-device lane granted them."""

    @pytest.mark.parametrize("shape", [(1, 1)], ids=["1x1"])
    def test_parity_single_device(self, tiny, shape):
        self._check_parity(tiny, shape)

    @pytest.mark.mesh
    @pytest.mark.parametrize("shape", [(2, 4), (8, 1)], ids=["2x4", "8x1"])
    def test_parity_forced_mesh(self, tiny, shape):
        self._check_parity(tiny, shape)

    def _check_parity(self, tiny, shape):
        cfg, model, params = tiny
        prompts = _prompts(vocab=cfg.vocab)
        got, eng = _engine_tokens(model, params, _mesh(*shape), prompts)
        for prompt, toks in zip(prompts, got):
            assert toks == _solo_tokens(model, params, _mesh(*shape), prompt)
        # bounded traces UNDER sharding: prefill widths + width-1 decode,
        # same contract as the single-device engine (DESIGN.md §5)
        assert len(eng.traces) <= 1 + len(prompts)

    @pytest.mark.mesh
    @pytest.mark.parametrize("shape", [(2, 4), (8, 1)], ids=["2x4", "8x1"])
    def test_tokens_identical_across_meshes(self, tiny, shape):
        """Cross-mesh invariance: the TP engine emits the same tokens as the
        1-device engine — sharding never shows up in the output."""
        cfg, model, params = tiny
        prompts = _prompts(vocab=cfg.vocab)
        ref, _ = _engine_tokens(model, params, _mesh(1, 1), prompts)
        got, _ = _engine_tokens(model, params, _mesh(*shape), prompts)
        assert got == ref

    @pytest.mark.mesh
    def test_weights_actually_sharded(self, tiny):
        """On a model=8 mesh the engine must hold sharded weights, not 8
        replicas — at least one parameter's spec names the model axis."""
        cfg, model, params = tiny
        eng = ServingEngine(model, params, _ecfg(), mesh=_mesh(1, 8))
        specs = [x.sharding.spec for x in jax.tree_util.tree_leaves(
            eng.params)]
        assert any("model" in str(s) for s in specs), specs

    @pytest.mark.mesh
    def test_int8_kv_parity_on_mesh(self, tiny):
        """The dequantizing paged path under TP: int8 pool tokens on 2x4
        equal the 1x1 int8 pool's (bit-equal within a kv dtype)."""
        cfg, model, params = tiny
        prompts = _prompts(vocab=cfg.vocab)
        ecfg = _ecfg(kv_dtype="int8")
        ref, _ = _engine_tokens(model, params, _mesh(1, 1), prompts,
                                ecfg=ecfg)
        got, eng = _engine_tokens(model, params, _mesh(2, 4), prompts,
                                  ecfg=ecfg)
        assert got == ref
        eng.assert_bounded_traces()


@pytest.fixture(scope="module")
def tiny_lcd(tiny):
    cfg, model, params = tiny
    return cfg, model, compress_model(params, target_centroids=4, nbits=2)[0]


class TestKernelsPerDevice:
    """LCD weights through the Pallas kernels (interpreted) on a mesh: a
    Pallas call is one custom call GSPMD cannot partition, so each kernel
    runs per device under shard_map — column-parallel projections on their
    output columns, row-parallel ones on their K slice plus a psum, paged
    attention replicated when the model axis does not divide the kv heads.
    Tokens must equal the one-device engine's."""

    @pytest.mark.mesh
    @pytest.mark.parametrize("kv_dtype", ["float", "int8"])
    def test_lcd_kernels_on_2x4_match_1x1(self, tiny_lcd, kv_dtype):
        cfg, model, params = tiny_lcd
        prompts = _prompts(vocab=cfg.vocab)
        ecfg = _ecfg(kv_dtype=kv_dtype)
        with lut_serving("interpret"), paged_attention_mode("interpret"):
            ref, _ = _engine_tokens(model, params, _mesh(1, 1), prompts,
                                    ecfg=ecfg)
            got, eng = _engine_tokens(model, params, _mesh(2, 4), prompts,
                                      ecfg=ecfg)
        assert got == ref
        wq = eng.params["blocks"]["attn"]["wq"].packed
        assert "model" in str(wq.sharding.spec)

    @pytest.mark.mesh
    def test_kernel_operands_hold_only_local_slots(self, tiny_lcd,
                                                   monkeypatch):
        """On a 2x4 mesh each device's kernels see half the request slots:
        data parallelism splits the work, it does not replicate it."""
        import repro.kernels.ops as ops
        import repro.kernels.paged_attention as pa
        cfg, model, params = tiny_lcd
        seen = {"lut": set(), "multi": set(), "attn": set()}

        def spy(name, fn, arg):
            def wrapped(*a, **kw):
                seen[name].add(a[arg].shape[0])
                return fn(*a, **kw)
            return wrapped

        monkeypatch.setattr(ops, "lut_gemm_fused",
                            spy("lut", ops.lut_gemm_fused, 0))
        monkeypatch.setattr(ops, "lut_gemm_fused_multi",
                            spy("multi", ops.lut_gemm_fused_multi, 0))
        monkeypatch.setattr(pa, "paged_pool_attention",
                            spy("attn", pa.paged_pool_attention, 0))
        ecfg = _ecfg()
        with lut_serving("interpret"), paged_attention_mode("interpret"):
            _engine_tokens(model, params, _mesh(2, 4), _prompts(vocab=cfg.vocab),
                           gen=2, ecfg=ecfg)
        local = ecfg.num_slots // 2
        assert seen["attn"] == {local}
        assert seen["lut"] | seen["multi"] == {
            local * ecfg.prefill_chunk, local}


class TestMeshKnobSurface:
    """Pinned-ValueError surface (repo convention: eager, python -O-proof,
    messages matched here so they can't silently regress)."""

    def test_engineconfig_rejects_bad_axis_sizes(self):
        with pytest.raises(ValueError, match=r"data_parallel must be a "
                                             r"positive int"):
            EngineConfig(data_parallel=0)
        with pytest.raises(ValueError, match=r"model_parallel must be a "
                                             r"positive int"):
            EngineConfig(model_parallel=-2)

    def test_engineconfig_accepts_valid_axis_sizes(self):
        e = EngineConfig(data_parallel=2, model_parallel=4)
        assert (e.data_parallel, e.model_parallel) == (2, 4)

    def test_engine_rejects_knob_mesh_mismatch(self, tiny):
        cfg, model, params = tiny
        with pytest.raises(ValueError, match=r"model_parallel=4 does not "
                                             r"match the engine mesh's "
                                             r"'model' axis"):
            ServingEngine(model, params, _ecfg(model_parallel=4),
                          mesh=_mesh(1, 1))

    def test_build_engine_rejects_non_factoring_knobs(self):
        with pytest.raises(ValueError, match=r"data_parallel x "
                                             r"model_parallel must factor"):
            build_engine("llama2-7b", use_reduced=True,
                         ecfg=_ecfg(data_parallel=3))

    def test_make_elastic_mesh_message_pinned(self):
        with pytest.raises(ValueError, match=r"n_chips \(5\) must be a "
                                             r"positive multiple of "
                                             r"model_parallel \(2\)"):
            make_elastic_mesh(5, model_parallel=2)


@pytest.mark.mesh
class TestLayoutChooser:
    """`build_engine` layout selection via the hlo_cost roofline
    (distributed/layout.py) on the forced 8-device host."""

    def test_choose_layout_scores_every_factorization(self, tiny):
        from repro.distributed.layout import choose_layout
        cfg, model, params = tiny
        _mesh(1, 8)  # skip guard: needs 8 devices
        mesh, report = choose_layout(model, params, _ecfg())
        assert set(report["candidates"]) == {"1x8", "2x4", "4x2", "8x1"}
        assert report["chosen"] in report["candidates"]
        for row in report["candidates"].values():
            assert row["t_model_s"] > 0
            assert row["flops"] > 0
        assert dict(mesh.shape) == dict(zip(
            ("data", "model"),
            (int(x) for x in report["chosen"].split("x"))))

    def test_build_engine_pins_requested_layout(self):
        _mesh(1, 8)  # skip guard
        engine, _ = build_engine("llama2-7b", use_reduced=True,
                                 ecfg=_ecfg(model_parallel=8))
        assert dict(engine.mesh.shape) == {"data": 1, "model": 8}
        assert engine.layout_report is None  # pinned, not searched
