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


def _dims(text: str) -> tuple[int, ...]:
    return tuple(int(d) for d in text.split(",") if d)


# transformer.forward carries the stacked decode cache through its layer
# scan and updates it in place. Checked on the chip's compiler (the CPU
# compiler lowers the per-row token scatter through whole-stack layout
# copies): no write of a whole (B, L, KV, D) layer of k or v, every cache
# leaf aliased to the donated input, and no copy of a whole stacked leaf.
# A head narrower than the 128 lanes gets its KV stack laid out with L
# minor on the device; the scan works in the other layout, so the entry
# copies the stack in and out once a step, never inside the scan.
@pytest.mark.parametrize("arch,head_dim", [("zamba2-1.2b", 128),
                                           ("zamba2-1.2b", 32),
                                           ("mamba2-780m", None)])
def test_decode_step_updates_cache_in_place(one_chip, arch, head_dim):
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.models import transformer

    cfg = configs.get_smoke(arch)
    if head_dim:
        cfg = dataclasses.replace(cfg, head_dim=head_dim)
    B, L = 4, 64
    _, reps, _ = transformer.find_period(cfg.layer_plan())
    assert reps >= 2
    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                             sharding=one_chip)
    a_params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: transformer.init_params(k, cfg), jax.random.PRNGKey(0)))
    a_cache = jax.tree.map(on_chip, transformer.cache_specs(
        cfg, B, L, vector_pos=True))
    a_tok = on_chip(jax.ShapeDtypeStruct((B, 1), jnp.int32))

    def decode(params, cache, tokens):
        logits, _, cache = transformer.forward(params, cfg, tokens,
                                               cache=cache)
        return logits, cache

    hlo = jax.jit(decode, donate_argnums=(1,)).lower(
        a_params, a_cache, a_tok).compile().as_text()

    stacked = {s.shape for s in jax.tree.leaves(a_cache["blocks"])}
    kv_layer = {s.shape[1:] for slot in a_cache["blocks"].values()
                for name, s in slot.items() if name in ("k", "v")}
    if arch == "zamba2-1.2b":        # a shared-attention slot in the scan
        assert kv_layer == {(B, L, cfg.n_kv_heads, cfg.head_dim_)}
    e0 = hlo.index("\nENTRY ")
    entry = range(e0, hlo.index("\n}", e0))
    narrow = head_dim is not None and head_dim % 128 != 0
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* copy\(", hlo):
        if _dims(m.group(1)) in stacked:
            assert narrow and m.start() in entry, m.group(0)
    shapes = {m.group(1): _dims(m.group(2)) for m in re.finditer(
        r"(%[\w.\-]+) = \w+\[([\d,]*)\]", hlo)}
    for m in re.finditer(r"dynamic-update-slice\(%[\w.\-]+, (%[\w.\-]+)",
                         hlo):
        upd = shapes[m.group(1)]
        while upd and upd[0] == 1:
            upd = upd[1:]
        assert upd not in kv_layer, m.group(0)

    n_params = len(jax.tree.leaves(a_params))
    n_cache = len(jax.tree.leaves(a_cache))
    header = hlo.splitlines()[0]
    aliased = {int(p) for p in re.findall(r"\{[\d,]*\}: \((\d+), \{",
                                          header)}
    assert set(range(n_params, n_params + n_cache)) <= aliased, header
