"""Agent-axis scaling (the bounded-degree gather path, core/mixing.py +
kernels/diffusion_mix.py + sharding/rules.py).

Coverage: neighbor-table correctness as a property (every realized
contributor appears; padding slots are inert), gather-vs-dense parity for
the linear mix and the neighborhood-robust backends on every built-in
preset under random participation masks, the fused Pallas kernel in
interpret mode, a K=1024 smoke on three bounded-degree topologies, the
loud O(K^2) fallback warning, the support-driven attach/detach in
check_mixer_support, the int8 quantized-wire split, and the agent-axis
sharding rule."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import (DenseMixer, NeighborGatherMixer, make_mixer,
                        make_topology, masked_combination)
from repro.core import graphs as graph_lib
from repro.core import variants
from repro.core.mixing import (FusedNeighborhoodMixer, _NEIGHBORHOOD_WARN_K,
                               make_pipeline, mix_dense)

K = 6

# topology + graph process of every Section-IV preset (the spec surface
# tests live in test_api.py; here we only need the realized matrices)
PRESET_SPECS = {
    "fedavg_full": lambda: variants.fedavg_full(K, T=3, mu=0.02),
    "fedavg_partial_uniform":
        lambda: variants.fedavg_partial_uniform(K, T=2, mu=0.05, q=0.6),
    "vanilla_diffusion": lambda: variants.vanilla_diffusion(K, mu=0.05),
    "asynchronous_diffusion":
        lambda: variants.asynchronous_diffusion(K, mu=0.03, q=0.6),
    "decentralized_fedavg":
        lambda: variants.decentralized_fedavg(K, T=4, mu=0.02),
    "cyclic_fedavg":
        lambda: variants.cyclic_fedavg(K, T=2, mu=0.02, num_groups=3),
    "markov_asynchronous_diffusion":
        lambda: variants.markov_asynchronous_diffusion(K, mu=0.02, q=0.6,
                                                       corr=0.5),
    "link_dropout_diffusion":
        lambda: variants.link_dropout_diffusion(K, mu=0.02, drop=0.3,
                                                corr=0.5, q=0.8),
    "compressed_diffusion":
        lambda: variants.compressed_diffusion(K, mu=0.02, T=2, q=0.8,
                                              compress="topk", ratio=0.5),
    "compressed_fedavg":
        lambda: variants.compressed_fedavg(K, T=2, mu=0.02, q=0.8),
    "byzantine_robust_diffusion":
        lambda: variants.byzantine_robust_diffusion(K, mu=0.02, q=0.9,
                                                    num_byzantine=2,
                                                    scale=3.0),
}


def _preset_graph(name):
    spec = PRESET_SPECS[name]()
    topo = make_topology(spec.topology.kind, K, **dict(spec.topology.kwargs))
    proc = graph_lib.make_graph_process(spec.graph.kind, topo, num_agents=K,
                                        **dict(spec.graph_kwargs()))
    return topo, proc


def _realized(proc, key):
    A_t, _ = proc.sample(proc.init_state(key), key)
    return A_t


def _tree(key, n_agents):
    ks = jax.random.split(key, 2)
    return {"w": jax.random.normal(ks[0], (n_agents, 5, 3)),
            "b": jax.random.normal(ks[1], (n_agents, 4))}


# ---------------------------------------------------------------------------
# neighbor-table correctness (the property behind every gather path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,n,kwargs", [
    ("ring", 8, {}), ("ring", 12, {"hops": 2}), ("grid", 12, {}),
    ("full", 6, {}), ("fedavg", 8, {}), ("erdos", 24, {"p": 0.1, "seed": 2}),
])
def test_neighbor_table_property(kind, n, kwargs):
    """Every contributor that any within_base_support realization can have
    appears exactly once in the target's row; padding slots are inert."""
    topo = make_topology(kind, n, **kwargs)
    idx, valid = topo.neighbor_table()
    assert idx.shape == valid.shape == (n, topo.max_degree + 1)
    assert idx.dtype == np.int32
    np.testing.assert_array_equal(idx[:, 0], np.arange(n))   # slot 0: self
    assert valid[:, 0].all()
    off = topo.adjacency & ~np.eye(n, dtype=bool)
    for k in range(n):
        listed = set(idx[k][valid[k]].tolist())
        assert listed == {k} | set(np.flatnonzero(off[:, k]).tolist())
        # padding gathers the self row, and its realized weight is 0
        np.testing.assert_array_equal(idx[k][~valid[k]], k)
    # realized link-dropout draws never leave the table (inert padding)
    proc = graph_lib.LinkDropout(topo, drop=0.5)
    m = jnp.ones((n,))
    for i in range(20):
        A_t = _realized(proc, jax.random.fold_in(jax.random.PRNGKey(3), i))
        A_eff = np.asarray(masked_combination(A_t, m))
        gw = A_eff[idx, np.arange(n)[:, None]] * valid
        # the gathered weights account for the WHOLE column mass
        np.testing.assert_allclose(gw.sum(axis=1), A_eff.sum(axis=0),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# gather == dense on every preset, random participation masks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PRESET_SPECS))
def test_gather_parity_per_preset(name):
    topo, proc = _preset_graph(name)
    assert proc.within_base_support   # no Section-IV preset leaves it
    dense = make_mixer("dense", topo)
    gather = make_mixer("gather", topo)
    assert isinstance(gather, NeighborGatherMixer)
    W = _tree(jax.random.PRNGKey(1), K)
    for i in range(4):
        kk = jax.random.fold_in(jax.random.PRNGKey(7), i)
        m = (jax.random.uniform(kk, (K,)) < 0.7).astype(jnp.float32)
        A_t = _realized(proc, kk)
        out_d, out_g = dense(W, m, A_t), gather(W, m, A_t)
        for a, b in zip(jax.tree.leaves(out_d), jax.tree.leaves(out_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, err_msg=name)


@pytest.mark.parametrize("name", sorted(PRESET_SPECS))
@pytest.mark.parametrize("robust", ["trimmed_mean", "median"])
def test_robust_gather_parity_per_preset(name, robust):
    """Neighborhood scope: the dmax gather-table sort == the all-slots
    masked sort (same finite multiset per target/coordinate)."""
    topo, proc = _preset_graph(name)
    table = make_mixer(robust, topo, trim=1, scope="neighborhood",
                       gather="table")
    allsl = make_mixer(robust, topo, trim=1, scope="neighborhood",
                       gather="off")
    assert table._table is not None and allsl._table is None
    W = _tree(jax.random.PRNGKey(2), K)
    for i in range(4):
        kk = jax.random.fold_in(jax.random.PRNGKey(11), i)
        m = (jax.random.uniform(kk, (K,)) < 0.7).astype(jnp.float32)
        A_t = _realized(proc, kk)
        out_t, out_a = table(W, m, A_t), allsl(W, m, A_t)
        for a, b in zip(jax.tree.leaves(out_t), jax.tree.leaves(out_a)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, err_msg=f"{name}/{robust}")


@pytest.mark.parametrize("robust", ["trimmed_mean", "median"])
def test_fused_kernel_parity(robust):
    """The Pallas gather+sort kernel (interpret mode off-TPU) == the
    all-slots reference, including frozen inactive agents."""
    topo = make_topology("ring", 8, hops=2)
    fused = make_mixer(robust, topo, trim=1, scope="neighborhood",
                       gather="fused", interpret=True)
    assert isinstance(fused, FusedNeighborhoodMixer)
    fused.use_kernel = True           # force the kernel path off-TPU
    ref = make_mixer(robust, topo, trim=1, scope="neighborhood",
                     gather="off")
    W = _tree(jax.random.PRNGKey(3), 8)
    proc = graph_lib.LinkDropout(topo, drop=0.4)
    for i in range(3):
        kk = jax.random.fold_in(jax.random.PRNGKey(13), i)
        m = (jax.random.uniform(kk, (8,)) < 0.6).astype(jnp.float32)
        A_t = _realized(proc, kk)
        out_f, out_r = fused(W, m, A_t), ref(W, m, A_t)
        for a, b, w in zip(jax.tree.leaves(out_f), jax.tree.leaves(out_r),
                           jax.tree.leaves(W)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
            # inactive agents keep their params bit-exactly
            dead = np.asarray(m) == 0
            np.testing.assert_array_equal(np.asarray(a)[dead],
                                          np.asarray(w)[dead])


def test_gather_linear_pallas_kernel_parity():
    """NeighborGatherMixer's fused flatten+gather kernel == mix_dense."""
    topo = make_topology("ring", 16, hops=2)
    gather = NeighborGatherMixer(topo, tile_m=128, interpret=True,
                                 fused=True)
    W = _tree(jax.random.PRNGKey(4), 16)
    A = jnp.asarray(topo.A, jnp.float32)
    m = (jax.random.uniform(jax.random.PRNGKey(5), (16,)) < 0.7)
    m = m.astype(jnp.float32)
    out = gather(W, m, A)
    ref = mix_dense(masked_combination(A, m), W)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# K=1024 smoke: the whole point of the bounded-degree path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,kwargs", [
    ("ring", {}), ("grid", {}), ("ring", {"hops": 2}),
])
def test_k1024_smoke(kind, kwargs):
    n = 1024
    topo = make_topology(kind, n, **kwargs)
    assert topo.max_degree + 1 <= 8   # bounded degree at any K
    A = jnp.asarray(topo.A, jnp.float32)
    key = jax.random.PRNGKey(6)
    W = {"w": jax.random.normal(key, (n, 32))}
    m = (jax.random.uniform(jax.random.fold_in(key, 1), (n,)) < 0.8)
    m = m.astype(jnp.float32)

    gather = make_mixer("gather", topo)
    out = gather(W, jnp.ones((n,)), A)["w"]
    # full participation + doubly stochastic A: the network mean is fixed
    np.testing.assert_allclose(np.asarray(out.mean(0)),
                               np.asarray(W["w"].mean(0)), atol=1e-5)

    robust = make_mixer("trimmed_mean", topo, trim=1, scope="neighborhood",
                        gather="table")
    out_r = robust(W, m, A)["w"]
    assert np.isfinite(np.asarray(out_r)).all()
    dead = np.asarray(m) == 0
    np.testing.assert_array_equal(np.asarray(out_r)[dead],
                                  np.asarray(W["w"])[dead])
    # the auto policy must pick the bounded-degree path at this K
    auto = make_mixer("auto", topo)
    assert isinstance(auto, NeighborGatherMixer) or auto.name in ("sparse",
                                                                  "pallas")


# ---------------------------------------------------------------------------
# loud fallback + support-driven attach/detach
# ---------------------------------------------------------------------------

def test_allslots_warns_above_threshold():
    n = _NEIGHBORHOOD_WARN_K + 88
    mixer = make_mixer("trimmed_mean", None, num_agents=n, trim=1,
                       scope="neighborhood", gather="off")
    W = {"w": jnp.ones((n, 2))}
    m = jnp.ones((n,))
    A = jnp.eye(n)
    with pytest.warns(UserWarning, match="attach_neighbor_table"):
        mixer(W, m, A)
    # one-time: a second call stays quiet
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mixer(W, m, A)


def test_check_mixer_support_attach_detach():
    topo = make_topology("ring", 8)
    mixer = make_mixer("trimmed_mean", None, num_agents=8, trim=1,
                       scope="neighborhood")
    assert mixer._table is None
    # on-support graph with a known base: auto attaches the table
    graph_lib.check_mixer_support(mixer, graph_lib.LinkDropout(topo,
                                                               drop=0.3))
    assert mixer._table is not None
    # off-support graph: auto detaches it again (correct, just O(K^2))
    graph_lib.check_mixer_support(
        mixer, graph_lib.TimeVaryingErdos(8, p=0.3, topology=topo))
    assert mixer._table is None
    # an EXPLICIT table choice off-support is an error, not a silent detach
    explicit = make_mixer("trimmed_mean", topo, trim=1,
                          scope="neighborhood", gather="table")
    with pytest.raises(ValueError, match="gather"):
        graph_lib.check_mixer_support(
            explicit, graph_lib.TimeVaryingErdos(8, p=0.3, topology=topo))
    # the linear gather mixer hard-errors off-support too
    with pytest.raises(ValueError, match="support"):
        graph_lib.check_mixer_support(
            make_mixer("gather", topo),
            graph_lib.TimeVaryingErdos(8, p=0.3, topology=topo))
    # the fused wrapper degrades gracefully unless the kernel was forced
    fused = make_mixer("trimmed_mean", topo, trim=1, scope="neighborhood",
                       gather="fused")
    graph_lib.check_mixer_support(
        fused, graph_lib.TimeVaryingErdos(8, p=0.3, topology=topo))
    assert fused.use_kernel is False and fused.inner._table is None
    graph_lib.check_mixer_support(fused, graph_lib.StaticGraph(topo))
    assert fused.use_kernel is None and fused.inner._table is not None


# ---------------------------------------------------------------------------
# int8 on the wire (generic GSPMD path)
# ---------------------------------------------------------------------------

def test_int8_quantized_split_matches_encode():
    from repro.core.compression import Int8Stochastic
    comp = Int8Stochastic()
    W = _tree(jax.random.PRNGKey(8), 4)
    key = jax.random.PRNGKey(9)
    q, scales = comp.encode_quantized(W, key)
    for l in jax.tree.leaves(q):
        assert l.dtype == jnp.int8
    msgs, _ = comp.encode(W, None, key)
    rebuilt = comp.dequantize(q, scales, W)
    for a, b in zip(jax.tree.leaves(msgs), jax.tree.leaves(rebuilt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int8_pipeline_mesh_bit_identical_and_s8_on_wire():
    topo = make_topology("ring", 4)
    A = jnp.asarray(topo.A, jnp.float32)
    W = _tree(jax.random.PRNGKey(10), 4)
    m = jnp.ones((4,))
    key = jax.random.PRNGKey(12)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    outs = {}
    for label, mesh_arg in (("plain", None), ("mesh", mesh)):
        pipe = make_pipeline("dense", topo, compress="int8",
                             mesh=mesh_arg)
        out, _ = pipe(W, m, A, None, key)
        outs[label] = out
    for a, b in zip(jax.tree.leaves(outs["plain"]),
                    jax.tree.leaves(outs["mesh"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the quantized buffer is pinned with sharding constraints, so the
    # program carries int8 (not f32) tensors through a sharding constraint
    # — what becomes the s8 all-gather under a real multi-device run.  Read
    # from the jaxpr, which does not depend on the partitioner's lowering
    pipe = make_pipeline("dense", topo, compress="int8", mesh=mesh)
    jaxpr = jax.make_jaxpr(
        lambda W_, m_, A_, k_: pipe(W_, m_, A_, None, k_)[0])(W, m, A, key)
    pinned = [v.aval.dtype for e in jaxpr.eqns
              if e.primitive.name == "sharding_constraint"
              for v in e.invars]
    assert jnp.int8 in pinned


# ---------------------------------------------------------------------------
# agent-axis sharding rule
# ---------------------------------------------------------------------------

def _fake_mesh(shape, axes):
    devs = np.array(jax.devices() * int(np.prod(shape)))[:int(np.prod(shape))]
    return Mesh(devs.reshape(shape), axes)


def test_agent_stack_pspec():
    from repro.sharding.rules import agent_stack_pspec
    mesh = _fake_mesh((4, 2), ("data", "model"))
    assert tuple(agent_stack_pspec(mesh, "data", num_agents=1024)) == \
        ("data", None)
    assert tuple(agent_stack_pspec(mesh, "data", num_agents=1024,
                                   ndim=3)) == ("data", None, None)
    # indivisible K falls back to replicated, as does an unknown axis name
    assert tuple(agent_stack_pspec(mesh, "data", num_agents=6)) == \
        (None, None)
    assert tuple(agent_stack_pspec(mesh, "pod", num_agents=1024)) == \
        (None, None)
    assert tuple(agent_stack_pspec(mesh, None, num_agents=1024)) == \
        (None, None)


def test_shard_agent_axis_single_device_noop_math():
    """shard_agent_axis on a 1-device mesh keeps the math identical (the
    constraint is a layout pin, not a semantic change)."""
    topo = make_topology("ring", 8)
    A = jnp.asarray(topo.A, jnp.float32)
    W = _tree(jax.random.PRNGKey(14), 8)
    m = jnp.ones((8,))
    plain = make_mixer("gather", topo)
    sharded = make_mixer("gather", topo)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    sharded.shard_agent_axis(mesh, "data")
    assert sharded._mesh is mesh and sharded._agent_axis == "data"
    for a, b in zip(jax.tree.leaves(plain(W, m, A)),
                    jax.tree.leaves(sharded(W, m, A))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
