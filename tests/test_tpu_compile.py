"""Compiles for a described TPU v5e chip: what interpret mode cannot check.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached. It refuses what the chip would: a kernel block
that is not tiled to the chip's layout, more VMEM than a kernel may use,
a program that does not fit HBM. Nothing runs here, so these tests say
nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and it keeps it until it exits.
"""
import os

import pytest

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


# zamba2-1.2b decode attention: 32 KV heads of 64, one query per KV head.
# 2048 is the per-chip slice of an 8192-slot cache sequence-sharded over a
# v5e:2x2; 2050 leaves a ragged last block; 100 fits one block.
@pytest.mark.parametrize("cache_len", [2048, 2050, 100])
def test_decode_stats_kernel_compiles(one_chip, cache_len):
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_stats.stats import \
        decode_stats_accumulate_pallas

    B, KV, G, D = 1, 32, 1, 64
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    compiled = decode_stats_accumulate_pallas.lower(
        sds((B, KV, G, cache_len), jnp.float32),
        sds((B, KV, G), jnp.float32),
        sds((B, cache_len, KV, D), jnp.bfloat16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_zamba2_decode_step_compiles(topo):
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.serve import ServeSpec, make_serve_fns

    cfg = configs.get("zamba2-1.2b")
    mesh = make_mesh((1,), ("data",), devices=[topo.devices[0]])
    art = make_serve_fns(cfg, mesh, ServeSpec(batch=4, cache_len=544))
    with jax.set_mesh(mesh):
        compiled = art.decode_fn.lower(
            art.abstract_params, art.abstract_cache,
            jax.ShapeDtypeStruct((4, 1), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert resident < V5E_HBM_BYTES, mem
