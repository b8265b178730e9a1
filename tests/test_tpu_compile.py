"""Compile the main path's Pallas mixing kernels for a described TPU v5e.

Interpret mode on the CPU checks what the kernels compute; only the TPU
compiler checks that they lower at all (block shapes, memory spaces, VMEM).
Each test compiles one kernel ahead of time for one chip of a described
``v5e:2x2`` topology at smollm-360m's width — K=4 agents, M its parameter
count padded to the tile — (the combination kernel also at chatglm3-6b-d4v4's,
K=2) and finds the native kernel in the result.
Nothing runs, so no chip is needed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist only
the worker that is given this file should.
"""
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import diffusion_mix as dm

TILE = 512
K = 4
D = 3                        # ring neighbor table: self + 2 neighbors


def _padded(n: int, tile: int = TILE) -> int:
    return n + (-n) % tile


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def M():
    return _padded(get_config("smollm-360m").model.total_params())


def _chatglm_M() -> int:
    from benchmarks.chip import model
    cfg = model.model_config(model.load_json(
        Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
        / "configs" / "chatglm3-6b-d4v4.json"))
    return _padded(cfg.total_params())


def _compile_native(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# smollm-360m's stack at K=4 in both dtypes, and chatglm3-6b-d4v4's at K=2
# in bfloat16: the two cells' block widths lower and fit the VMEM they ask for
@pytest.mark.parametrize("k,dtype,chatglm", [
    pytest.param(K, jnp.float32, False, id="float32"),
    pytest.param(K, jnp.bfloat16, False, id="bfloat16"),
    pytest.param(2, jnp.bfloat16, True, id="chatglm-bfloat16"),
])
def test_diffusion_mix_compiles(one_chip, M, k, dtype, chatglm):
    width = _chatglm_M() if chatglm else M
    _compile_native(
        lambda A, a, W: dm.diffusion_mix(A, a, W, tile_m=TILE),
        _sds((k, k), jnp.float32, one_chip), _sds((k,), jnp.float32, one_chip),
        _sds((k, width), dtype, one_chip))


@pytest.mark.parametrize("subtract_identity", [False, True])
def test_diffusion_mix_int8_compiles(one_chip, M, subtract_identity):
    _compile_native(
        lambda A, a, W, s: dm.diffusion_mix_int8(
            A, a, W, s, tile_m=TILE, subtract_identity=subtract_identity),
        _sds((K, K), jnp.float32, one_chip), _sds((K,), jnp.float32, one_chip),
        _sds((K, M), jnp.int8, one_chip),
        _sds((K, M // TILE), jnp.float32, one_chip))


# K=1024, D=5 is the scale path the gather kernels exist for (a bounded-degree
# graph over many agents), at a width that fits one chip
@pytest.mark.parametrize("k,d,width", [(K, D, None), (1024, 5, 64 * TILE)])
def test_gather_mix_compiles(one_chip, M, k, d, width):
    _compile_native(
        lambda i, g, W: dm.gather_mix(i, g, W, tile_m=TILE),
        _sds((k, d), jnp.int32, one_chip), _sds((k, d), jnp.float32, one_chip),
        _sds((k, width or M), jnp.float32, one_chip))


@pytest.mark.parametrize("k,d,width", [(K, D, None), (1024, 5, 64 * TILE)])
def test_gather_robust_mix_compiles(one_chip, M, k, d, width):
    _compile_native(
        lambda i, m, w, a, W: dm.gather_robust_mix(i, m, w, a, W, tile_m=TILE),
        _sds((k, d), jnp.int32, one_chip), _sds((k, d), jnp.float32, one_chip),
        _sds((k, d), jnp.float32, one_chip), _sds((k,), jnp.float32, one_chip),
        _sds((k, width or M), jnp.float32, one_chip))
