"""Compiles for a described TPU v5e chip — nothing runs.

The main serving path's Pallas kernels with ``interpret=False`` and the
full-width SmolLM-360M step programs are compiled ahead of time by the
TPU compiler installed with JAX, against a ``v5e:2x2`` topology that is
described, not attached.  This catches what interpret mode cannot: tile
alignment, fast-memory limits, and programs that do not fit the chip's
16 GB of HBM.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and every test
worker imports this file.  The persistent compilation cache is off
around these compiles; an entry written for a described chip cannot be
read back without one.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.kernels import ops
from repro.launch import serve
from repro.models.model import cache_shape, params_shape
from repro.serving.engine import _raw_fns

# SmolLM-360M attention widths (configs/smollm_360m.py), one KV page
B, H, HK, HD, PAGE = 8, 15, 5, 64, 64
SEQ, CHUNK = 6144, 128
V5E_HBM = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _kernel_args(name, dtype, chip):
    n_pages = B * SEQ // PAGE

    def s(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    rows = s((B,), jnp.int32)
    tables = s((B, SEQ // PAGE), jnp.int32)
    arena = s((n_pages + 1, PAGE, HK, HD))
    if name == "flash_prefill":
        return (s((B, CHUNK, H, HD)), s((B, SEQ, HK, HD)),
                s((B, SEQ, HK, HD)), rows, rows)
    if name == "flash_prefill_paged":
        return (s((B, CHUNK, H, HD)), arena, arena, rows, rows, tables)
    return (s((B, 1, H, HD)), arena, arena, rows, tables)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["flash_prefill", "flash_prefill_paged",
                                  "flash_decode_paged"])
def test_kernel_compiles_for_v5e(one_chip, name, dtype):
    lowered = getattr(ops, name).lower(*_kernel_args(name, dtype, one_chip),
                                       interpret=False)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


def _serving_shapes(layout):
    """The CLI's engine shapes for the published SmolLM-360M at Table-I
    lengths (what ``chip_smoke.py`` serves)."""
    args = serve.build_parser().parse_args(
        ["--arch", "smollm-360m", "--agents", "4", "--token-scale", "1.0",
         "--kv-layout", layout])
    cfg = serve.model_config(args)
    ecfg = serve.engine_config(args, cfg)
    return cfg, ecfg.num_slots, ecfg.max_seq


def _step_memory(chip, layout, step):
    cfg, slots, max_seq = _serving_shapes(layout)
    cache = _on(chip, cache_shape(cfg, slots, max_seq))
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))

    def s(*shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    bt = (s(slots, max_seq // cfg.kv_page_size),) if layout == "paged" else ()
    fns = _raw_fns(cfg, "dense")
    fn = fns[2] if step == "fused" else functools.partial(
        fns[3], num_steps=2)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        _on(chip, params_shape(cfg)), cache, s(slots), s(slots),
        s(slots, dt=jnp.bool_), *bt).compile()
    return compiled.memory_analysis(), cache_bytes


@pytest.mark.parametrize("layout", ["slab", "paged"])
def test_full_width_fused_decode_compiles_in_place(one_chip, layout):
    """The engine's fused decode step at published widths compiles for
    one v5e and updates the donated cache in place: its temporaries
    stay below one cache's size (a second whole cache would mean the
    layer scan rebuilt the cache as a scan output)."""
    ma, cache_bytes = _step_memory(one_chip, layout, "fused")
    assert ma.alias_size_in_bytes >= cache_bytes
    assert ma.temp_size_in_bytes < cache_bytes
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM


def test_full_width_megastep_fits_v5e(one_chip):
    """A two-step decode megastep at published widths fits one v5e's
    HBM (the compile fails with RESOURCE_EXHAUSTED otherwise)."""
    ma, _ = _step_memory(one_chip, "slab", "megastep")
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM


def test_serving_shapes_are_published_widths():
    cfg, slots, max_seq = _serving_shapes("slab")
    assert cfg == get_config("smollm-360m")
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (32, 960, 49152)
    assert max_seq % cfg.kv_page_size == 0 and slots == 6
