"""Compile-only checks of the two paged Pallas kernels, and of the serving
executor's whole jitted step, at real widths for a described (not
attached) TPU v5e chip.

Interpret mode, which every other kernel test runs, never meets Mosaic's
block-shape, layout and VMEM rules, nor the device's memory limit; these
compiles do, at no chip time. Shapes only: nothing is allocated or run.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library, so under
pytest-xdist the worker that runs this file loads it, and a file-level call
would give the other workers different tests to collect.
"""
import math

import jax
import jax.numpy as jnp
import pytest

DEVICE_BYTES = 16 * 10**9      # one v5e chip's HBM

# (H, KV, hd, layers) at published widths: G = 6 and G = 16 query heads
# per KV head, each over a flat bf16 pool of layers x (4,096 + 1 trash)
# pages of 16 tokens — the serving executor's one-chip geometry
WIDTHS = {
    "qwen2-vl-2b": (12, 2, 128, 28),
    "chatglm3-6b": (32, 2, 128, 28),
}
PAGES_PER_LAYER, PAGE = 4097, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described device's compiles cannot be read back from the
    # persistent cache: keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool(sharding, arch):
    H, KV, hd, layers = WIDTHS[arch]
    return _spec(sharding, (layers * PAGES_PER_LAYER, PAGE, KV, hd),
                 jnp.bfloat16)


def _assert_compiled_for_chip(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total < DEVICE_BYTES, m
    return m


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_kernel_compiles_for_v5e(one_chip, arch):
    from repro.kernels.paged_attention import paged_attention
    H, KV, hd, _ = WIDTHS[arch]
    B, width = 16, 256
    pool = _pool(one_chip, arch)
    compiled = jax.jit(
        lambda q, k, v, bt, ln: paged_attention(q, k, v, bt, ln,
                                                interpret=False)
    ).lower(_spec(one_chip, (B, H, hd), jnp.bfloat16), pool, pool,
            _spec(one_chip, (B, width), jnp.int32),
            _spec(one_chip, (B,), jnp.int32)).compile()
    m = _assert_compiled_for_chip(compiled)
    # the pool is read in place: no padded or staged copy of it
    assert m.temp_size_in_bytes < math.prod(pool.shape) * 2 // 100


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_prefill_kernel_compiles_for_v5e(one_chip, arch):
    from repro.kernels.paged_prefill_attention import \
        paged_prefill_attention
    H, KV, hd, _ = WIDTHS[arch]
    B, Sq, width = 4, 512, 256
    pool = _pool(one_chip, arch)
    compiled = jax.jit(
        lambda q, k, v, bt, qs, nl: paged_prefill_attention(
            q, k, v, bt, qs, nl, interpret=False)
    ).lower(_spec(one_chip, (B, Sq, H, hd), jnp.bfloat16), pool, pool,
            _spec(one_chip, (B, width), jnp.int32),
            _spec(one_chip, (B,), jnp.int32),
            _spec(one_chip, (B,), jnp.int32)).compile()
    m = _assert_compiled_for_chip(compiled)
    assert m.temp_size_in_bytes < math.prod(pool.shape) * 2 // 100


@pytest.mark.parametrize("B,C", [(4, 512), (16, 1)])   # prefill, decode
def test_full_serving_step_compiles_for_v5e(one_chip, monkeypatch, B, C):
    """ModelExecutor's whole jitted step at qwen2-vl-2b's published widths:
    28 layers, bf16 weights, the 4,096-page pool donated through the layer
    scan. It must hold the kernels, fit the chip, and update the pool in
    place (aliased, with no pool-sized temporary)."""
    import functools
    from types import SimpleNamespace

    from repro.cache.paged import PagedStackStore
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.models.params import abstract_params
    from repro.serving.executors import ModelExecutor
    # the kernels take their compiled path from the backend they run on,
    # which is the CPU here: steer them to the chip's
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = get_config("qwen2-vl-2b")
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          abstract_params(T.model_decls(cfg), cfg.dtype))
    KV, hd = cfg.num_kv_heads, cfg.hd
    pool_bytes = 0
    stores = []
    for period, reps in cfg.stages():
        shape = (reps * PAGES_PER_LAYER, PAGE, KV, hd)
        pool_bytes += 2 * len(period) * math.prod(shape) * 2
        stores.append({f"b{bi}": PagedStackStore(
            _spec(one_chip, shape, jnp.bfloat16),
            _spec(one_chip, shape, jnp.bfloat16), reps)
            for bi in range(len(period))})
    step = functools.partial(ModelExecutor._prefill_step, SimpleNamespace(
        jnp=jnp, T=T, cfg=cfg, attn_impl="kernel"))
    i32 = functools.partial(_spec, one_chip, dtype=jnp.int32)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, stores, i32((B, C)), i32((B, C)), i32((B, 256)), i32((B,)),
        i32((B,))).compile()
    m = _assert_compiled_for_chip(compiled)
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < pool_bytes // 4
