"""Sharding-rule + HLO cost-model tests."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.hlo_cost import analyze_text
from repro.distributed.sharding import (ShardingRules, DEFAULT_RULES,
                                        logical_to_spec, parse_names, use_rules,
                                        current_rules, maybe_shard)


@pytest.fixture(scope="module")
def mesh():
    # single real CPU device -> mesh (1,1); spec logic is mesh-shape driven,
    # so use a fake 4x2 mesh via axis sizes on the abstract level
    return jax.make_mesh((1, 1), ("data", "model"))


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def rules(shape):
    return ShardingRules(FakeMesh(dict(shape)), dict(DEFAULT_RULES))


class TestLogicalToSpec:
    def test_divisible_dims_shard(self):
        sr = rules({"pod": 2, "data": 16, "model": 16})
        spec = logical_to_spec((4096, 8192), ("embed", "ff"), sr)
        assert spec == P(("pod", "data"), "model")

    def test_indivisible_dim_falls_back(self):
        sr = rules({"pod": 2, "data": 16, "model": 16})
        # 12 heads % 16 -> replicated
        spec = logical_to_spec((64, 1024, 12, 128), ("batch", None, "heads", None), sr)
        assert spec == P(("pod", "data"), None, None, None)

    def test_partial_compound_axis(self):
        sr = rules({"pod": 2, "data": 16, "model": 16})
        # batch 16 divides data(16) but not pod*data(32) -> suffix ("data",)
        spec = logical_to_spec((16, 64), ("batch", None), sr)
        assert spec == P("data", None)
        # batch 8 divides neither -> fully replicated
        spec = logical_to_spec((8, 64), ("batch", None), sr)
        assert spec == P(None, None)

    def test_axis_used_once(self):
        sr = rules({"data": 16, "model": 16})
        # both dims want "model": first wins, second replicated
        spec = logical_to_spec((32, 32), ("vocab", "ff"), sr)
        assert spec == P("model", None)

    def test_parse_names(self):
        assert parse_names("") == ()
        assert parse_names("batch,.,ff") == ("batch", None, "ff")
        assert parse_names("layers,embed") == ("layers", "embed")

    def test_maybe_shard_noop_without_context(self):
        x = jnp.ones((4, 4))
        y = maybe_shard(x, "batch", None)
        assert y is x

    def test_use_rules_context(self, mesh):
        assert current_rules() is None
        with use_rules(mesh):
            assert current_rules() is not None
            assert current_rules().mesh is mesh
        assert current_rules() is None


class TestHloCostModel:
    def test_plain_dot_matches_xla(self):
        c = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((128, 512), jnp.float32),
            jax.ShapeDtypeStruct((512, 256), jnp.float32)).compile()
        mine = analyze_text(c.as_text()).flops
        xla = c.cost_analysis()["flops"]
        assert mine == pytest.approx(xla, rel=1e-6)

    def test_scan_flops_scale_with_trip_count(self):
        def f(L):
            def g(x, ws):
                def body(x, w):
                    return jnp.tanh(x @ w), None
                return jax.lax.scan(body, x, ws)[0]
            return jax.jit(g).lower(
                jax.ShapeDtypeStruct((64, 256), jnp.float32),
                jax.ShapeDtypeStruct((L, 256, 256), jnp.float32)).compile()
        f2 = analyze_text(f(2).as_text()).flops
        f8 = analyze_text(f(8).as_text()).flops
        assert f8 == pytest.approx(4 * f2, rel=1e-3)

    def test_scan_equals_unroll(self):
        def f(unroll):
            def g(x, ws):
                def body(x, w):
                    return jnp.tanh(x @ w), None
                return jax.lax.scan(body, x, ws, unroll=unroll)[0]
            return jax.jit(g).lower(
                jax.ShapeDtypeStruct((64, 256), jnp.float32),
                jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)).compile()
        scan_f = analyze_text(f(1).as_text()).flops
        unroll_f = analyze_text(f(8).as_text()).flops
        assert scan_f == pytest.approx(unroll_f, rel=2e-2)

    def test_xla_undercounts_loops(self):
        """Documents WHY the custom model exists: XLA's cost_analysis counts
        while bodies once (if this ever starts failing, XLA fixed it and the
        custom model can be cross-checked against it again)."""
        def g(x, ws):
            def body(x, w):
                return jnp.tanh(x @ w), None
            return jax.lax.scan(body, x, ws)[0]
        c = jax.jit(g).lower(
            jax.ShapeDtypeStruct((64, 256), jnp.float32),
            jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)).compile()
        assert c.cost_analysis()["flops"] < analyze_text(c.as_text()).flops / 4

    def test_collectives_counted_with_trip_multiplier(self):
        hlo = """
HloModule test, entry_computation_layout={()->f32[8]{0}}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8]{0} get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  %ar = f32[8]{0} all-reduce(%x), to_apply=%sum
  ROOT %t = (s32[], f32[8]{0}) tuple(%i2, %ar)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main () -> f32[8] {
  %zero = s32[] constant(0)
  %x0 = f32[8]{0} broadcast(f32[] constant(1)), dimensions={}
  %init = (s32[], f32[8]{0}) tuple(%zero, %x0)
  %w = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""
        cost = analyze_text(hlo)
        assert cost.coll_bytes.get("all-reduce") == pytest.approx(10 * 32)
        assert cost.coll_counts.get("all-reduce") == 10

    def test_fusion_dynamic_slice_bytes(self):
        """Stacked scan weights must be charged at slice granularity."""
        def g(x, ws):
            def body(x, w):
                return jnp.tanh(x @ w), None
            return jax.lax.scan(body, x, ws)[0]
        c = jax.jit(g).lower(
            jax.ShapeDtypeStruct((64, 256), jnp.float32),
            jax.ShapeDtypeStruct((64, 256, 256), jnp.float32)).compile()
        b = analyze_text(c.as_text()).bytes
        # full-array-per-iteration accounting would give >= 64 * 64*256*256*4
        # = 1.07e9 bytes from the weight operand alone; slice accounting stays
        # near 64 iterations x ~1.1 MB.
        assert b < 3e8, b


class TestSpecProperties:
    """Property tests (seeded sweeps, no hypothesis dependency): the
    divisibility-fallback invariant of `logical_to_spec` and the
    ClusteredTensor expansion of `auto_shard`, over random shapes x mesh
    shapes. A violated invariant here is a crash (non-dividing dim sharded)
    or silent replication (DESIGN.md §14 layout rules) in the engine."""

    MESHES = [{"data": 1, "model": 1}, {"data": 2, "model": 4},
              {"data": 8, "model": 1}, {"data": 1, "model": 8},
              {"pod": 2, "data": 16, "model": 16}, {"data": 3, "model": 5}]
    NAMES = [None, "batch", "embed", "vocab", "ff", "heads", "kv",
             "kv_flat", "q_dim", "slots", "blocks", "experts", "seq_kv"]

    @staticmethod
    def _axes_of(entry):
        if entry is None:
            return ()
        return (entry,) if isinstance(entry, str) else tuple(entry)

    def test_random_shapes_never_crash_and_always_divide(self):
        import numpy as np
        rng = np.random.default_rng(0)
        for _ in range(300):
            shape_dict = self.MESHES[rng.integers(len(self.MESHES))]
            sr = rules(shape_dict)
            rank = int(rng.integers(1, 5))
            shape = tuple(int(rng.integers(1, 65)) for _ in range(rank))
            names = tuple(self.NAMES[rng.integers(len(self.NAMES))]
                          for _ in range(rank))
            spec = logical_to_spec(shape, names, sr)
            assert len(spec) == rank
            used = []
            for dim, entry in zip(shape, spec):
                axes = self._axes_of(entry)
                size = 1
                for a in axes:
                    size *= shape_dict[a]
                # invariant: a sharded dim divides its axis product exactly;
                # a non-dividing mapping must have fallen back to replicated
                assert dim % size == 0, (shape, names, spec)
                used.extend(axes)
            # invariant: each mesh axis appears at most once in the spec
            assert len(used) == len(set(used)), (names, spec)

    def test_specs_round_trip_through_named_sharding(self):
        """Every generated spec must be accepted by NamedSharding on a real
        (abstract) mesh of the same shape and reproduce itself."""
        import numpy as np
        from jax.sharding import AbstractMesh, NamedSharding
        from repro.distributed.sharding import named_sharding
        rng = np.random.default_rng(1)
        for shape_dict in self.MESHES:
            am = AbstractMesh(tuple(shape_dict.values()),
                              tuple(shape_dict.keys()))
            sr = ShardingRules(am, dict(DEFAULT_RULES))
            for _ in range(50):
                rank = int(rng.integers(1, 4))
                shape = tuple(int(rng.integers(1, 33)) for _ in range(rank))
                names = tuple(self.NAMES[rng.integers(len(self.NAMES))]
                              for _ in range(rank))
                ns = named_sharding(shape, names, sr)
                assert isinstance(ns, NamedSharding)
                assert ns.spec == logical_to_spec(shape, names, sr)
                # the spec is realizable: shard shape math must succeed
                assert NamedSharding(am, ns.spec).is_fully_replicated \
                    == all(self._axes_of(e) == () for e in ns.spec)

    def test_auto_shard_expands_clustered_tensor(self):
        """auto_shard maps codes/packed to the dense names, smoothing
        vectors to the d_in dims, and replicates the LUT — on a mesh whose
        model axis does not divide d_out, everything replicates instead of
        crashing."""
        import jax
        import numpy as np
        from repro.core.api import compress_model
        from repro.distributed.sharding import auto_shard
        w = np.random.default_rng(2).normal(size=(32, 48)).astype(np.float32)
        dense = {"w": jax.numpy.asarray(w),
                 "b": jax.numpy.zeros((48,), jax.numpy.float32)}
        compressed, _ = compress_model(dense, target_centroids=4, nbits=2)
        ct = compressed["w"]
        tree = {"w": ct, "b": dense["b"]}
        names = {"w": "embed,ff", "b": "ff"}
        from jax.sharding import AbstractMesh

        def am_rules(shape):
            # NamedSharding construction needs a real(ish) mesh, so the
            # auto_shard sweep uses AbstractMesh instead of FakeMesh
            return ShardingRules(AbstractMesh(tuple(shape.values()),
                                              tuple(shape.keys())),
                                 dict(DEFAULT_RULES))

        sr = am_rules({"data": 2, "model": 4})    # 48 % 4 == 0: ff shards
        sh = auto_shard(tree, names, sr)
        assert sh["w"].codes.spec == logical_to_spec(
            ct.codes.shape, ("embed", "ff"), sr)
        assert "model" in str(sh["w"].codes.spec)
        assert sh["w"].codebook.spec == P(*(None,) * ct.codebook.ndim)
        assert sh["w"].smooth.spec == logical_to_spec(
            ct.smooth.shape, ("embed",), sr)
        assert "model" in str(sh["b"].spec)
        srr = am_rules({"data": 2, "model": 5})   # 48 % 5 != 0: replicate
        shr = auto_shard(tree, names, srr)
        assert "model" not in str(shr["w"].codes.spec)
        assert "model" not in str(shr["b"].spec)
