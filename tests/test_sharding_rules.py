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
        assert m["layers/pos0/attn/wk"] == P(None, "data", None, None)
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
