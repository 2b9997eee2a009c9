"""Compile the fused decode path for a TPU v5e chip that is described, not
attached.

The installed TPU compiler lowers each Pallas kernel through Mosaic at
granite-8b's published widths (B=8 lanes, ``block_size=16``) and the whole
fused decode step at the smoke test's depth. Interpret mode on the CPU
cannot see what this catches: block shapes the tiling refuses and kernels
that overflow the scoped VMEM. Each test asserts the Mosaic kernel is in
the compiled program and that the program fits one chip's 16 GB.

The topology is described only inside the module fixture, never at import,
so every pytest worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config

HBM_BYTES = 16 * 1024 ** 3
B, BLOCK, MAX_LEN, DEPTH = 8, 16, 2048, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        tuple(dims), dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_config("granite-8b"), n_layers=DEPTH)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, used


def test_qkv_rope_paged_compiles(cfg, shape):
    from repro.kernels.fused_decode.kernel import qkv_rope_paged
    D, Hq, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f = lambda x, s, wq, wk, wv, p: qkv_rope_paged(
        x, s, wq, wk, wv, p, theta=cfg.rope_theta, interpret=False)
    _check(jax.jit(f).lower(
        shape((B, D)), shape((D,)), shape((D, Hq, dh)), shape((D, Hkv, dh)),
        shape((D, Hkv, dh)), shape((B,), jnp.int32)).compile())


def test_flash_decode_paged_compiles(cfg, shape):
    from repro.kernels.flash_attention.ops import decode_paged
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rows, maxb = B * MAX_LEN // BLOCK + 1, MAX_LEN // BLOCK
    pool = shape((rows, BLOCK, Hkv, dh))
    f = lambda q, kp, vp, t, n: decode_paged(q, kp, vp, t, n,
                                             interpret=False)
    _check(jax.jit(f).lower(shape((B, Hq, dh)), pool, pool,
                            shape((B, maxb), jnp.int32),
                            shape((B,), jnp.int32)).compile())


def test_oproj_ffn_swiglu_compiles(cfg, shape):
    from repro.kernels.fused_decode.kernel import oproj_ffn_swiglu
    D, HD, F = cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.d_ff
    f = lambda x, a, wo, s, wg, wu, wd: oproj_ffn_swiglu(
        x, a, wo, s, wg, wu, wd, interpret=False)
    _check(jax.jit(f).lower(
        shape((B, D)), shape((B, HD)), shape((HD, D)), shape((D,)),
        shape((D, F)), shape((D, F)), shape((F, D))).compile())


def test_fused_decode_step_compiles(cfg, shape):
    """The whole single-token paged extend of the fused backend, at the
    smoke test's depth, with the KV pool donated as the engine runs it."""
    from repro.models import get_model
    from repro.models.common import abstract_params
    from repro.serving.backends import fused_paged_extend
    rows, maxb = B * MAX_LEN // BLOCK + 1, MAX_LEN // BLOCK
    params = jax.tree.map(lambda s: shape(s.shape, s.dtype),
                          abstract_params(get_model(cfg).param_specs()))
    pool = shape((cfg.n_layers, rows, BLOCK, cfg.n_kv_heads, cfg.head_dim))
    f = lambda p, pk, pv, t, n, a, tk: fused_paged_extend(
        cfg, p, pk, pv, t, n, a, tk, rows - 1, interpret=False)
    _check(jax.jit(f, donate_argnums=(1, 2)).lower(
        params, pool, pool, shape((B, maxb), jnp.int32),
        shape((B,), jnp.int32), shape((B,), jnp.bool_),
        shape((B, 1), jnp.int32)).compile())
