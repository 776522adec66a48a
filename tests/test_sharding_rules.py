"""Sharding rule engine: path->PartitionSpec mapping and divisibility."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.models import api
from repro.runtime import sharding as shr


def _pspec_map(cfg):
    specs = api.param_specs(cfg)
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): shr.param_pspec(
            path, len(leaf.shape))
        for path, leaf in jax.tree_util.tree_flatten_with_path(specs)[0]
    }


class TestRules:
    def test_dense_attention_rules(self):
        m = _pspec_map(configs.get_config("tinyllama-1.1b"))
        assert m["layers/pos0/attn/wq"] == P(None, "data", "model", None)
        # KV heads over 'model' (dropped by the filter where they do not
        # divide it: TestKVHeadSharding)
        assert m["layers/pos0/attn/wk"] == P(None, "data", "model", None)
        assert m["layers/pos0/attn/wv"] == P(None, "data", "model", None)
        assert m["layers/pos0/attn/wo"] == P(None, "model", None, "data")
        assert m["layers/pos0/mlp/w_in"] == P(None, "data", "model")
        assert m["layers/pos0/mlp/w_out"] == P(None, "model", "data")
        assert m["embed"] == P("model", "data")
        assert m["lm_head"] == P("data", "model")
        assert m["layers/pos0/norm1/scale"] == P(None, None)

    def test_moe_expert_parallel_rules(self):
        m = _pspec_map(configs.get_config("qwen3-moe-235b-a22b"))
        assert m["layers/pos0/moe/w_in"] == P(None, "model", "data", None)
        assert m["layers/pos0/moe/w_out"] == P(None, "model", None, "data")
        assert m["layers/pos0/moe/router"] == P(None, None, None)

    def test_mamba_channel_parallel_rules(self):
        m = _pspec_map(configs.get_config("falcon-mamba-7b"))
        assert m["layers/pos0/mamba/in_proj"] == P(None, "data", "model")
        assert m["layers/pos0/mamba/out_proj"] == P(None, "model", "data")
        assert m["layers/pos0/mamba/A_log"] == P(None, "model", None)

    def test_unknown_leaf_replicates(self):
        assert shr.param_pspec(
            (jax.tree_util.DictKey("mystery"),), 2) == P()


class TestDivisibilityFilter:
    """AbstractMesh carries shapes without needing real devices (built via
    shr.abstract_mesh — the raw constructor wants ((name, size), ...))."""

    def test_minicpm_heads_fall_back_to_replicated(self):
        """36 heads on a 16-wide model axis: dropped, not padded."""
        mesh = shr.abstract_mesh((16, 16), ("data", "model"))
        spec = shr.filter_pspec(P(None, "model", None), mesh, (2304, 32, 64))
        assert spec == P(None, "model", None)  # 32 % 16 == 0
        spec2 = shr.filter_pspec(P(None, "model", None), mesh, (2304, 36, 64))
        assert spec2 == P(None, None, None)  # 36 % 16 != 0 -> replicated

    def test_absent_axis_dropped(self):
        mesh = shr.abstract_mesh((2,), ("data",))
        spec = shr.filter_pspec(P("data", "model"), mesh, (8, 8))
        assert spec == P("data", None)

    def test_vocab_not_divisible(self):
        mesh = shr.abstract_mesh((16, 16), ("data", "model"))
        # minicpm vocab 122753 is prime-ish: both axes dropped
        spec = shr.filter_pspec(P("model", "data"), mesh, (122753, 2304))
        assert spec == P(None, "data")

    def test_dp_axes_divisibility(self):
        mesh = shr.abstract_mesh((16, 16), ("data", "model"))
        assert shr.dp_axes(mesh, 32) == ("data",)
        assert shr.dp_axes(mesh, 7) == ()
        mesh2 = shr.abstract_mesh((2, 16, 16), ("pod", "data", "model"))
        assert shr.dp_axes(mesh2, 256) == ("pod", "data")
        assert shr.dp_axes(mesh2, 2) == ("pod",)
        assert shr.dp_axes(mesh2, 1) == ()

    def test_abstract_mesh_shape(self):
        """Regression: the helper pairs names with sizes (seed bug passed
        bare ints where Mesh expects an iterable spec)."""
        mesh = shr.abstract_mesh((4, 2), ("data", "model"))
        assert dict(mesh.shape) == {"data": 4, "model": 2}


def _mesh_specs(mesh, tree):
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): sh.spec
        for path, sh in jax.tree_util.tree_flatten_with_path(
            shr.tree_shardings(mesh, tree))[0]}


class TestKVHeadSharding:
    """A 1x4 serving mesh over the published granite-3-8b: 8 KV heads and
    a tied 49155-row table, which 4 does not divide."""

    MESH = ((1, 4), ("data", "model"))

    def test_kv_projections_shard_their_heads(self):
        mesh = shr.abstract_mesh(*self.MESH)
        m = _mesh_specs(mesh, api.param_specs(
            configs.get_config("granite-3-8b")))
        assert m["layers/pos0/attn/wk"] == P(None, "data", "model", None)
        assert m["layers/pos0/attn/wv"] == P(None, "data", "model", None)
        assert m["layers/pos0/attn/wq"] == P(None, "data", "model", None)

    def test_kv_heads_that_do_not_divide_replicate(self):
        mesh = shr.abstract_mesh((2, 8), ("data", "model"))
        m = _mesh_specs(mesh, api.param_specs(
            configs.get_config("tinyllama-1.1b")))  # 4 KV heads
        assert m["layers/pos0/attn/wk"] == P(None, "data", None, None)

    def test_tied_table_shards_d_model_where_vocab_does_not_divide(self):
        mesh = shr.abstract_mesh(*self.MESH)
        m = _mesh_specs(mesh, api.param_specs(
            configs.get_config("granite-3-8b")))
        assert "lm_head" not in m
        assert m["embed"] == P("data", "model")
        m = _mesh_specs(mesh, api.param_specs(
            configs.get_config("tinyllama-1.1b")))  # vocab 32000 divides
        assert m["embed"] == P("model", "data")

    def test_a_mesh_without_model_keeps_the_rule(self):
        mesh = shr.abstract_mesh((4,), ("data",))
        m = _mesh_specs(mesh, api.param_specs(
            configs.get_config("granite-3-8b")))
        assert m["embed"] == P(None, "data")

    def test_kv_split(self):
        mesh = shr.abstract_mesh(*self.MESH)
        assert shr.kv_split(mesh, 8) == ("model", None)
        assert shr.kv_split(mesh, 2) == (None, "model")
        assert shr.kv_split(None, 2) == ("model", None)

    @pytest.mark.parametrize("kv_heads, spec", [
        (8, P(None, "data", None, "model", None)),   # KV heads
        (2, P(None, "data", None, None, "model")),   # head_dim
    ])
    def test_arena_shards_kv_heads_where_they_divide(self, kv_heads, spec):
        mesh = shr.abstract_mesh(*self.MESH)
        arena = jax.ShapeDtypeStruct((40, 1793, 16, kv_heads, 128),
                                     jnp.bfloat16)
        sh = shr.pool_shardings(mesh, None, {"pos0": {"k": arena,
                                                      "v": arena}}, 16)
        assert sh["pos0"]["k"].spec == sh["pos0"]["v"].spec == spec


class TestActivationConstraints:
    def test_constrain_noop_without_context(self):
        x = jnp.ones((4, 4))
        y = shr.constrain(x, "dp", "model")
        assert y is x

    def test_constrain_applies_in_context(self):
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((1,), ("model",))
        with shr.activation_context(mesh, ()):
            def f(x):
                return shr.constrain(x, None, "model")
            out = jax.jit(f)(jnp.ones((3, 1)))
        assert out.shape == (3, 1)
