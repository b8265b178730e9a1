"""Sharding rules + small-mesh integration of the sharded block step.

These run on 8 forced host devices (subprocess-free: we only check specs
here; the 8-device execution test lives in test_integration via pytest-forked
style env isolation is avoided by using the default 1-device mesh for math
and a spec-only check for the production mesh)."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.models import transformer as tf
from repro.sharding import rules as sh


def _fake_mesh(shape, axes):
    """An abstract mesh over the single real device, repeated — good enough
    for PartitionSpec logic (no execution)."""
    devs = np.array(jax.devices() * int(np.prod(shape)))[:int(np.prod(shape))]
    return Mesh(devs.reshape(shape), axes)


MESH = _fake_mesh((4, 2), ("data", "model"))
ROOT = Path(__file__).resolve().parents[1]


def test_param_specs_divisibility_guard():
    cfg = get_config("smollm_360m").model  # heads=15 not divisible by 2
    specs = tf.param_specs(cfg)
    ps = sh.param_pspecs(specs, MESH)
    flat_specs = jax.tree.leaves(specs)
    flat_ps = jax.tree.leaves(ps, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_specs) == len(flat_ps)
    for s, p in zip(flat_specs, flat_ps):
        # every sharded dim must divide the axis size
        for dim, axis in zip(s.shape, tuple(p)):
            if axis is None:
                continue
            size = MESH.shape[axis] if isinstance(axis, str) else \
                int(np.prod([MESH.shape[a] for a in axis]))
            assert dim % size == 0, (s.shape, tuple(p))


def test_embed_and_head_sharded_over_model():
    cfg = get_config("qwen3_32b").model
    ps = sh.param_pspecs(tf.param_specs(cfg), MESH)
    assert tuple(ps["embed"]) == ("model", None)
    assert tuple(ps["lm_head"]) == (None, "model")


def test_moe_experts_sharded_over_model():
    cfg = get_config("kimi_k2_1t_a32b").model
    ps = sh.param_pspecs(tf.param_specs(cfg), MESH, fsdp=True)
    seg = next(iter(ps["segments"].values()))
    w_gate = seg["moe"]["w_gate"]          # (L, E, D, F)
    assert tuple(w_gate) == (None, "model", "data", None)
    w_down = seg["moe"]["w_down"]          # (L, E, F, D)
    assert tuple(w_down) == (None, "model", None, "data")


def test_agent_axis_prepended():
    cfg = get_config("smollm_360m").model
    ps = sh.param_pspecs(tf.param_specs(cfg), MESH)
    ps2 = sh.add_agent_axis(ps, "data")
    for leaf in jax.tree.leaves(ps2, is_leaf=lambda x: isinstance(x, P)):
        assert tuple(leaf)[0] == "data"


def test_batch_pspec_variants():
    assert tuple(sh.batch_pspec(MESH, agent_axis="data", ndim=4)) == \
        (None, "data", None, None)
    mesh3 = _fake_mesh((2, 2, 2), ("pod", "data", "model"))
    # agents on data => per-agent batch sharded over pod
    assert tuple(sh.batch_pspec(mesh3, agent_axis="data", ndim=4)) == \
        (None, "data", "pod", None)
    # agents on pod => per-agent batch over data
    assert tuple(sh.batch_pspec(mesh3, agent_axis="pod", ndim=4)) == \
        (None, "pod", "data", None)


def test_cache_pspecs_long_context_shards_sequence():
    cfg = get_config("qwen3_32b").model
    cache = tf.cache_specs(cfg, 1, 524_288, window=8192)
    ps = sh.cache_pspecs(cache, MESH, batch=1)
    # batch=1: cannot shard batch; cache length must be sharded over data
    kspec = tuple(jax.tree.leaves(
        ps, is_leaf=lambda x: isinstance(x, P))[0])
    assert "data" in str(kspec)


def test_serve_batch_pspec():
    assert tuple(sh.serve_batch_pspec(MESH, 32, 2))[0] == "data"
    assert tuple(sh.serve_batch_pspec(MESH, 1, 2))[0] is None


def test_agent_mesh_needs_devices_that_divide_K():
    from repro.launch.mesh import make_agent_mesh
    assert make_agent_mesh(4) is None                 # one device here
    two = jax.devices() * 2
    assert make_agent_mesh(4, two).shape == {"data": 2}
    assert make_agent_mesh(3, two) is None


def test_place_agents_shards_the_agent_dimension():
    from repro.launch.mesh import place_agents
    mesh = Mesh(np.array(jax.devices()), ("data",))
    tree = {"w": jnp.ones((4, 3)), "step": jnp.zeros(()),
            "batch": jnp.ones((2, 4, 5))}
    placed = place_agents(tree, mesh, num_agents=4)
    assert placed["w"].sharding.spec == P("data", None)
    assert placed["step"].sharding.spec == P()
    assert placed["batch"].sharding.spec == P()      # dim 0 is not K
    batch = place_agents({"tokens": jnp.ones((2, 4, 5))}, mesh,
                         num_agents=4, agent_dim=1)
    assert batch["tokens"].sharding.spec == P(None, "data", None)


def test_compile_cache_goes_where_the_environment_says(monkeypatch):
    from repro.launch import cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cache.enable_compile_cache() == str(cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cache.CACHE_DIR)
        assert cache.CACHE_DIR.parent == ROOT
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
