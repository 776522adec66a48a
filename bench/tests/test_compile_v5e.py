"""``bench/compile_v5e.py`` compiles a four-chip cell for a described
TPU v5e ``2x2``: the mesh its configuration names, over the described
chips, with the weights and the page arena sharded across them.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""

import jax
import pytest

from bench import compile_v5e, harness, weights


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_described_mesh_is_the_serving_mesh_over_the_described_chips(topo):
    mesh = compile_v5e.described_mesh("1x4", topo.devices)
    assert dict(mesh.shape) == {"data": 1, "model": 4}
    assert set(mesh.devices.flat) == set(topo.devices)
    # the spec is read for a host of the described chips, not this one
    with pytest.raises(ValueError, match="does not cover"):
        compile_v5e.described_mesh("1x4", topo.devices[:2])


def test_four_chip_tied_cell_compiles_sharded(tiny_cell, topo):
    cell = tiny_cell("tiny-tied.chat")
    assert cell.chips == 4
    report = compile_v5e.compile_cell(cell, topo, 2.0, log=lambda *a: None)
    assert report["mesh"] == {"data": 1, "model": 4}
    assert report["tick"]["argument_size_in_bytes"] > 0
    assert any(k.startswith("prefill_") for k in report)
    cfg = harness.build_cfg(cell)
    whole = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        weights.abstract(harness.arch_sizes(cfg, cell.config))))
    # the weights are spread over the four chips, not copied to each
    assert report["params_bytes"] < whole / 2
