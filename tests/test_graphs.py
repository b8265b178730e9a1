"""GraphProcess layer (core/graphs.py): every realized A_t satisfies the
Assumption-1 invariants (symmetric, doubly stochastic, inside the base
support), StaticGraph is bit-identical to the pre-redesign baked-A path for
every preset, graph_state checkpoints and restores, the adaptive consensus
gamma derives from the spectral gap and anneals from the observed
contraction, and third-party graph kinds register end-to-end."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import GRAPHS, GraphSpec, build
from repro.core import (CommPipeline, DiffusionConfig, DiffusionEngine,
                        GossipMatching, LinkDropout, StaticGraph,
                        TimeVaryingErdos, choco_gamma, make_graph_process,
                        make_mixer, make_pipeline, make_topology, mix_dense)
from repro.core import participation as part
from repro.core import topology as topo_lib
from repro.core import variants
from repro.data.synthetic import make_block_sampler, make_regression_problem

KEY = jax.random.PRNGKey(0)
K = 6


# ---------------------------------------------------------------------------
# property gates: every realized A_t is a valid combination matrix
# ---------------------------------------------------------------------------

def _processes(topo):
    return [
        StaticGraph(topo),
        LinkDropout(topo, drop=0.0),
        LinkDropout(topo, drop=0.3),
        LinkDropout(topo, drop=0.7, corr=0.6),
        GossipMatching(topo),
        TimeVaryingErdos(topo.num_agents, p=0.3),
    ]


@pytest.mark.parametrize("kind,n", [("ring", 8), ("grid", 12),
                                    ("erdos", 10)])
def test_realized_matrices_symmetric_doubly_stochastic(kind, n):
    """Acceptance gate: every A_t from every process is symmetric, doubly
    stochastic, nonnegative — the eq.-20 invariants survive any draw."""
    topo = make_topology(kind, n)
    for proc in _processes(topo):
        state = proc.init_state(jax.random.fold_in(KEY, 7))
        for i in range(12):
            A_t, state = proc.sample(state, jax.random.fold_in(KEY, i))
            A = np.asarray(A_t, np.float64)
            assert topo_lib.is_symmetric(A, tol=1e-5), proc
            assert topo_lib.is_doubly_stochastic(A, tol=1e-5), proc
            assert (A >= -1e-6).all(), proc


@pytest.mark.parametrize("kind,n", [("ring", 8), ("grid", 12)])
def test_dynamic_support_stays_on_base_adjacency(kind, n):
    """LinkDropout / GossipMatching never put weight on a non-edge of the
    base graph (the sparse circulant backend relies on this)."""
    topo = make_topology(kind, n)
    non_edge = ~np.asarray(topo.adjacency)
    for proc in (LinkDropout(topo, drop=0.4),
                 LinkDropout(topo, drop=0.4, corr=0.5),
                 GossipMatching(topo)):
        assert proc.within_base_support
        state = proc.init_state(jax.random.fold_in(KEY, 3))
        for i in range(10):
            A_t, state = proc.sample(state, jax.random.fold_in(KEY, 50 + i))
            assert np.abs(np.asarray(A_t)[non_edge]).max() == 0.0, proc


def test_link_dropout_zero_drop_is_static_metropolis():
    """drop = 0 keeps every link: the realized matrix equals the base
    Metropolis weights every block."""
    topo = make_topology("ring", 8)
    proc = LinkDropout(topo, drop=0.0)
    for i in range(4):
        A_t, _ = proc.sample((), jax.random.fold_in(KEY, i))
        np.testing.assert_allclose(np.asarray(A_t),
                                   topo.A.astype(np.float32), atol=1e-6)


def test_link_dropout_stationary_up_frequency():
    """The per-link up-frequency converges to 1 - drop, with and without
    temporal correlation (the Markov chain's stationary law)."""
    topo = make_topology("ring", 8)
    base_off = np.asarray(topo.adjacency & ~np.eye(8, dtype=bool))
    for corr in (0.0, 0.6):
        proc = LinkDropout(topo, drop=0.3, corr=corr)
        state = proc.init_state(jax.random.PRNGKey(1))
        up_counts = np.zeros((8, 8))
        steps = 1500
        for i in range(steps):
            A_t, state = proc.sample(state, jax.random.fold_in(KEY, i))
            up_counts += np.asarray(A_t) > 0
        freq = up_counts[base_off] / steps
        np.testing.assert_allclose(freq, 0.7, atol=0.06,
                                   err_msg=f"corr={corr}")


def test_gossip_matching_is_a_matching():
    """Every realized gossip matrix pairs each agent with at most one
    neighbor (degree <= 1 in the matched off-diagonal support)."""
    topo = make_topology("ring", 9)
    proc = GossipMatching(topo)
    matched_any = False
    for i in range(20):
        A_t, _ = proc.sample((), jax.random.fold_in(KEY, i))
        A = np.asarray(A_t)
        off_deg = (A > 0).sum(axis=1) - 1
        assert off_deg.max() <= 1
        if off_deg.max() == 1:
            matched_any = True
            # matched pairs average 1/2-1/2; unmatched agents hold
            matched = np.where(off_deg == 1)[0]
            np.testing.assert_allclose(np.diag(A)[matched], 0.5, atol=1e-6)
            unmatched = np.where(off_deg == 0)[0]
            np.testing.assert_allclose(np.diag(A)[unmatched], 1.0,
                                       atol=1e-6)
    assert matched_any


def test_tv_erdos_rejects_sparse_mixer_and_auto_falls_back():
    topo = make_topology("ring", 8)
    cfg = DiffusionConfig(num_agents=8, topology="ring", graph="tv_erdos",
                          graph_kwargs=(("p", 0.4),), mix="sparse")
    data = make_regression_problem(K=8, N=20)
    with pytest.raises(ValueError, match="circulant"):
        DiffusionEngine(cfg, data.loss_fn())
    # "auto" resolves away from sparse instead of dying
    eng = DiffusionEngine(dataclasses.replace(cfg, mix="auto"),
                          data.loss_fn())
    assert not isinstance(eng.mixer,
                          __import__("repro.core.mixing",
                                     fromlist=["x"]).SparseCirculantMixer)
    # and the engine actually runs
    sampler = make_block_sampler(data, T=1, batch=1)
    st = eng.init_state(jnp.zeros((8, 2)))
    st, _ = eng.step(st, sampler(KEY), jax.random.PRNGKey(1))
    assert np.isfinite(np.asarray(st.params)).all()


def test_sharded_builder_without_topology_fails_loudly():
    """A forgotten topology must not silently train with A_t = I (zero
    communication); mixers that ignore the matrix (robust / none) still
    build against an inert identity, as before the redesign."""
    from repro.core.sharded import make_block_step
    loss3 = lambda p, b, rng: 0.0
    with pytest.raises(ValueError, match="topology"):
        make_block_step(loss3, DiffusionConfig(num_agents=8))
    s = make_block_step(loss3, DiffusionConfig(num_agents=8,
                                               mix="trimmed_mean"))
    assert s.graph.num_agents == 8
    s = make_block_step(loss3, DiffusionConfig(num_agents=1, mix="none"))
    assert s.graph.num_agents == 1


def test_make_graph_process_factory_and_validation():
    topo = make_topology("ring", 6)
    assert isinstance(make_graph_process("static", topo), StaticGraph)
    proc = make_graph_process("link_dropout", topo, drop=0.2, corr=0.1)
    assert isinstance(proc, LinkDropout) and proc.stateful
    assert not make_graph_process("link_dropout", topo, drop=0.2).stateful
    assert isinstance(make_graph_process("gossip", topo), GossipMatching)
    assert isinstance(make_graph_process("tv_erdos", None, num_agents=6),
                      TimeVaryingErdos)
    assert make_graph_process(proc) is proc          # passthrough
    with pytest.raises(ValueError):
        make_graph_process("nope", topo)
    with pytest.raises(ValueError):
        make_graph_process("gossip", None)
    with pytest.raises(ValueError):
        LinkDropout(topo, drop=1.0)
    with pytest.raises(ValueError):
        TimeVaryingErdos(6, p=0.0)


# ---------------------------------------------------------------------------
# StaticGraph == pre-redesign baked-A path, bit for bit, for every preset
# ---------------------------------------------------------------------------

def _baked_dense_mixer(A):
    """The PRE-REDESIGN DenseMixer: the matrix frozen at construction,
    per-call A_t ignored — the baseline the runtime-topology path must
    reproduce bit-for-bit when the graph is static."""
    from repro.core import mixing

    class BakedDense(mixing.Mixer):
        def __init__(self, A):
            self.A = jnp.asarray(A, jnp.float32)

        def __call__(self, params, active, A_t=None):
            return mix_dense(part.masked_combination(self.A, active),
                             params)

    return BakedDense(A)


@pytest.mark.parametrize("name", sorted([
    "fedavg_full", "fedavg_partial_uniform", "vanilla_diffusion",
    "asynchronous_diffusion", "decentralized_fedavg", "cyclic_fedavg",
    "markov_asynchronous_diffusion", "compressed_diffusion",
    "compressed_fedavg"]))
def test_static_graph_bit_identical_to_baked_A(name):
    """Acceptance gate: GraphSpec(kind="static") runs are bit-identical to
    the pre-redesign baked-A path for every preset — the engine with a
    mixer that froze A at construction (the old contract) produces
    array_equal outputs against the runtime-A_t engine."""
    factories = {
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
            lambda: variants.markov_asynchronous_diffusion(K, mu=0.02,
                                                           q=0.6, corr=0.5),
        "compressed_diffusion":
            lambda: variants.compressed_diffusion(K, mu=0.02, T=2, q=0.8,
                                                  compress="topk",
                                                  ratio=0.5),
        "compressed_fedavg":
            lambda: variants.compressed_fedavg(K, T=2, mu=0.02, q=0.8),
    }
    spec = factories[name]()
    assert spec.graph == GraphSpec(kind="static")
    data = make_regression_problem(K=K, N=40, M=2, rho=0.1, seed=1)
    eng_runtime = build(spec, data.loss_fn())
    assert isinstance(eng_runtime.graph, StaticGraph)
    cfg = spec.to_diffusion_config()
    eng_baked = DiffusionEngine(
        cfg, data.loss_fn(),
        mixer=_baked_dense_mixer(cfg.make_topology().A),
        participation=eng_runtime.process if cfg.graph == "static" else None)

    T = spec.run.local_steps
    sampler = make_block_sampler(data, T=T, batch=1)
    params = jax.random.normal(jax.random.PRNGKey(0), (K, 2))
    key0 = jax.random.fold_in(jax.random.PRNGKey(3), 0x5EED)
    s_rt = eng_runtime.init_state(params, key=key0)
    s_bk = eng_baked.init_state(params, key=key0)
    assert s_rt.graph_state is None          # static graphs carry nothing
    for i in range(4):
        batch = sampler(jax.random.PRNGKey(100 + i))
        k = jax.random.PRNGKey(200 + i)
        s_rt, m_rt = eng_runtime.step(s_rt, batch, k)
        s_bk, m_bk = eng_baked.step(s_bk, batch, k)
        np.testing.assert_array_equal(np.asarray(m_rt["active"]),
                                      np.asarray(m_bk["active"]))
        np.testing.assert_array_equal(np.asarray(s_rt.params),
                                      np.asarray(s_bk.params))


# ---------------------------------------------------------------------------
# engine threading + checkpoint round trip of graph_state
# ---------------------------------------------------------------------------

def test_engine_threads_graph_state_and_converges():
    """End-to-end: link dropout at drop=0.3 on a ring still converges (the
    acceptance regime of bench_graph_process), threading the link mask
    through EngineState.graph_state."""
    n = 8
    data = make_regression_problem(K=n, N=60, M=2, rho=0.1, seed=0)
    spec = variants.link_dropout_diffusion(n, mu=0.02, drop=0.3, corr=0.5,
                                           T=2, q=0.9)
    eng = build(spec, data.loss_fn())
    assert eng.graph.stateful
    w_o = data.problem().w_opt(np.full(n, 0.9))
    sampler = make_block_sampler(data, T=2, batch=1)
    params = jnp.full((n, 2), 3.0)
    _, _, hist = eng.run(params, sampler, 400, seed=0,
                         w_star=jnp.asarray(w_o))
    assert np.mean(hist[-50:]) < 0.05 * hist[0]


def test_sharded_step_threads_graph_state():
    from repro.core.sharded import make_block_step
    n = 6
    data = make_regression_problem(K=n, N=40, M=2, rho=0.1, seed=3)
    cfg = DiffusionConfig(num_agents=n, local_steps=2, step_size=0.02,
                          topology="ring", participation=0.9,
                          graph="link_dropout",
                          graph_kwargs=(("corr", 0.5), ("drop", 0.3)))
    topo = cfg.make_topology()
    loss3 = lambda p, b, rng: data.loss_fn()(p, b)
    block_step = make_block_step(loss3, cfg, topology=topo)
    step = jax.jit(block_step)
    sampler = make_block_sampler(data, T=2, batch=1)
    state = block_step.init_state(jnp.zeros((n, 2)),
                                  key=jax.random.PRNGKey(4))
    assert state.graph_state is not None
    masks = []
    # a ring of 6 links keeps its whole mask through one block with
    # probability ~0.24 at drop=0.3, corr=0.5: over 12 blocks the chance
    # that no link ever flips is ~1e-7 for any key stream
    for i in range(12):
        state, _ = step(state, sampler(jax.random.PRNGKey(10 + i)),
                        jax.random.PRNGKey(i))
        masks.append(np.asarray(state.graph_state))
    assert any(not np.array_equal(a, b)
               for a, b in zip(masks, masks[1:]))   # links actually churn
    # a stateful graph fed graph_state=None fails loudly
    from repro.core import EngineState
    with pytest.raises(ValueError, match="init_state"):
        step(EngineState(jnp.zeros((n, 2))),
             sampler(jax.random.PRNGKey(0)), jax.random.PRNGKey(0))


def test_checkpoint_roundtrip_graph_state(tmp_path):
    """graph_state rides the EngineState checkpoint: restore rebuilds the
    exact engine and continues bit-identically."""
    from repro.checkpoint import load_experiment, load_spec, save_experiment
    n = K
    data = make_regression_problem(K=n, N=40, M=2, rho=0.1, seed=0)
    spec = variants.link_dropout_diffusion(n, mu=0.02, drop=0.4, corr=0.5,
                                           T=2, q=0.8)
    eng = build(spec, data.loss_fn())
    params = jax.random.normal(jax.random.PRNGKey(0), (n, 2))
    state = eng.init_state(params, key=jax.random.PRNGKey(1))
    sampler = make_block_sampler(data, T=2, batch=1)
    for i in range(3):
        state, _ = eng.step(state, sampler(jax.random.PRNGKey(10 + i)),
                            jax.random.PRNGKey(i))
    assert state.graph_state is not None

    path = str(tmp_path / "graph_ckpt.npz")
    save_experiment(path, state, spec=spec, step=3)
    spec2 = load_spec(path)
    assert spec2 == spec and spec2.graph.kind == "link_dropout"
    eng2 = build(spec2, data.loss_fn())
    like = eng2.init_state(jnp.zeros_like(params),
                           key=jax.random.PRNGKey(9))
    restored, meta = load_experiment(path, like)
    np.testing.assert_array_equal(np.asarray(restored.graph_state),
                                  np.asarray(state.graph_state))
    batch = sampler(jax.random.PRNGKey(99))
    k = jax.random.PRNGKey(7)
    s1, _ = eng.step(state, batch, k)
    s2, _ = eng2.step(restored, batch, k)
    np.testing.assert_array_equal(np.asarray(s1.params),
                                  np.asarray(s2.params))
    np.testing.assert_array_equal(np.asarray(s1.graph_state),
                                  np.asarray(s2.graph_state))


# ---------------------------------------------------------------------------
# adaptive consensus gamma (comm_gamma="auto")
# ---------------------------------------------------------------------------

def test_choco_gamma_formula_properties():
    """The CHOCO step grows with the spectral gap and the compressor
    contraction, and stays in (0, 1]."""
    assert 0 < choco_gamma(0.1, 0.1, 2.0) < choco_gamma(0.5, 0.1, 2.0) <= 1
    assert choco_gamma(0.2, 0.1, 1.5) < choco_gamma(0.2, 0.9, 1.5)


def test_adaptive_gamma_floor_from_spectral_gap():
    """gamma="auto" derives its floor from spectral_gap(A) — no hard-coded
    0.5/ratio value — and requires the base matrix."""
    topo = make_topology("ring", 8)
    pipe = make_pipeline("dense", topo, compress="topk", compress_ratio=0.1,
                         gamma="auto")
    assert pipe.adaptive and pipe.gamma == "auto"
    rho = topo_lib.spectral_gap(topo.A)
    beta = 1.0 - np.linalg.eigvalsh(topo.A).min()
    assert pipe.spectral_gap == pytest.approx(rho)
    assert pipe.gamma_floor == pytest.approx(choco_gamma(rho, 0.1, beta))
    state = pipe.init_state({"w": jnp.zeros((8, 4))})
    assert float(state["delta"]) == pytest.approx(0.1)
    # denser graph (larger gap) -> larger floor
    full = make_topology("fedavg", 8)
    pipe_full = make_pipeline("dense", full, compress="topk",
                              compress_ratio=0.1, gamma="auto")
    assert pipe_full.gamma_floor > pipe.gamma_floor
    with pytest.raises(ValueError, match="spectral gap"):
        CommPipeline(make_mixer("dense", topo),
                     __import__("repro.core.compression",
                                fromlist=["x"]).TopK(0.1), gamma="auto")


def test_adaptive_gamma_anneals_from_observed_contraction():
    """On a fixed signal the diff-mode reference tracks psi, the observed
    contraction EMA rises, and the annealed gamma climbs from the CHOCO
    floor toward 1 — while A_t keeps flowing as an operand."""
    topo = make_topology("ring", 8)
    A = jnp.asarray(topo.A, jnp.float32)
    pipe = make_pipeline("dense", topo, compress="topk", compress_ratio=0.25,
                         gamma="auto")
    params = {"w": jax.random.normal(KEY, (8, 16))}
    state = pipe.init_state(params)
    g0 = float(pipe.annealed_gamma(state))
    assert g0 == pytest.approx(pipe.gamma_floor
                               + (1 - pipe.gamma_floor) * 0.5)  # sqrt(0.25)
    gammas = [g0]
    m = jnp.ones((8,))
    for i in range(15):
        _, state = pipe(params, m, A, state, jax.random.fold_in(KEY, i))
        gammas.append(float(pipe.annealed_gamma(state)))
    assert gammas[-1] > gammas[0]            # annealed up, not down
    assert gammas[-1] <= 1.0 + 1e-6
    # top-k on a fixed signal is strongly contractive: gamma ends well
    # above the conservative floor
    assert gammas[-1] > 10 * pipe.gamma_floor


def test_adaptive_gamma_through_sharded_engine():
    """make_block_step wires the base matrix into the pipeline, so
    comm_gamma="auto" works through the sharded path too (the launchers'
    route) and threads the delta EMA through comm_state."""
    from repro.core.sharded import make_block_step
    n = 6
    data = make_regression_problem(K=n, N=40, M=2, rho=0.1, seed=2)
    cfg = DiffusionConfig(num_agents=n, local_steps=2, step_size=0.02,
                          topology="ring", participation=0.9,
                          compress="topk", compress_ratio=0.25,
                          comm_gamma="auto")
    topo = cfg.make_topology()
    loss3 = lambda p, b, rng: data.loss_fn()(p, b)
    block_step = make_block_step(loss3, cfg, topology=topo)
    assert block_step.pipeline.adaptive
    step = jax.jit(block_step)
    sampler = make_block_sampler(data, T=2, batch=1)
    state = block_step.init_state(jnp.zeros((n, 2)))
    d0 = float(state.comm_state["delta"])
    for i in range(5):
        state, _ = step(state, sampler(jax.random.PRNGKey(10 + i)),
                        jax.random.PRNGKey(i))
    assert float(state.comm_state["delta"]) != d0
    assert np.isfinite(np.asarray(state.params)).all()


@pytest.mark.slow
def test_adaptive_gamma_beats_fixed_heuristic_msd():
    """Acceptance gate: comm_gamma="auto" beats the fixed heuristic's
    steady-state MSD on the compressed_diffusion preset."""
    n, M = 8, 20
    blocks = 1500
    data = make_regression_problem(K=n, N=100, M=M, rho=0.1, seed=6)
    prob = data.problem()
    qv = np.full(n, 0.8)
    w_o = prob.w_opt(qv)
    sampler = make_block_sampler(data, T=2, batch=1)
    msds = {}
    for label, gamma in (("fixed", None), ("auto", "auto")):
        spec = variants.compressed_diffusion(n, mu=0.01, T=2, q=0.8,
                                             compress="topk", ratio=0.1,
                                             gamma=gamma)
        eng = build(spec, data.loss_fn())
        _, _, hist = eng.run(jnp.zeros((n, M)), sampler, blocks, seed=0,
                             w_star=jnp.asarray(w_o))
        msds[label] = float(np.mean(hist[-blocks // 4:]))
    assert msds["auto"] < msds["fixed"], msds


# ---------------------------------------------------------------------------
# registry: third-party graph kinds plug in end-to-end
# ---------------------------------------------------------------------------

def test_registered_custom_graph_kind_builds_and_runs():
    """@GRAPHS.register kinds resolve through GraphSpec(kind=...) exactly
    like the built-ins (the examples/custom_graph.py mechanism)."""
    name = "always_full_TEST"
    if name not in GRAPHS:
        @GRAPHS.register(name)
        def _always_full(spec, topology, n):
            full = make_topology("full", n)
            return StaticGraph(full)

    data = make_regression_problem(K=4, N=20)
    spec = variants.vanilla_diffusion(4, mu=0.05).replace(
        graph=GraphSpec(kind=name))
    eng = build(spec, data.loss_fn())
    A = np.asarray(eng.graph.sample(None, KEY)[0])
    np.testing.assert_allclose(A, np.asarray(make_topology("full", 4).A),
                               atol=1e-6)
    sampler = make_block_sampler(data, T=1, batch=1)
    st = eng.init_state(jnp.zeros((4, 2)))
    st, _ = eng.step(st, sampler(KEY), jax.random.PRNGKey(2))
    assert np.isfinite(np.asarray(st.params)).all()
    # the CONFIG-STRING path reaches registered kinds too (dryrun --spec,
    # DiffusionEngine(cfg, loss) rebuilds): make_graph_process falls back
    # to the GRAPHS registry, and graph_kwargs carries every field for
    # non-built-in kinds so nothing is silently dropped
    dcfg = spec.replace(graph=GraphSpec(kind=name,
                                        drop=0.42)).to_diffusion_config()
    assert dict(dcfg.graph_kwargs)["drop"] == 0.42
    eng2 = DiffusionEngine(dcfg, data.loss_fn())
    A2 = np.asarray(eng2.graph.sample(None, KEY)[0])
    np.testing.assert_allclose(A2, A, atol=1e-6)
    # unknown kinds die with the registry's alternatives listed
    bad = spec.replace(graph=GraphSpec(kind="wormhole"))
    with pytest.raises(ValueError, match="registered graph"):
        build(bad, data.loss_fn())
    with pytest.raises(ValueError, match="GRAPHS"):
        make_graph_process("wormhole", make_topology("ring", 4))
