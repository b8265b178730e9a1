"""Mixer backends (core/mixing.py) and participation processes
(core/schedules.py): cross-backend parity under random activation masks,
the Pallas fused path on a real model pytree, the "auto" policy, and the
stationary behavior of the stateful availability processes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CyclicGroups, DenseMixer, DiffusionConfig,
                        DiffusionEngine, IIDBernoulli, MarkovAvailability,
                        NeighborGatherMixer, NullMixer, PallasFusedMixer,
                        SparseCirculantMixer, make_mixer, make_topology,
                        masked_combination, mix_dense, sample_active)
from repro.core import schedules
from repro.data.synthetic import make_block_sampler, make_regression_problem

KEY = jax.random.PRNGKey(0)


def _rand_tree(key, K):
    ks = jax.random.split(key, 3)
    return {"w": jax.random.normal(ks[0], (K, 7, 3)),
            "b": jax.random.normal(ks[1], (K, 5)),
            "s": jax.random.normal(ks[2], (K, 2, 2, 2))}


# ---------------------------------------------------------------------------
# backend parity (dense == sparse == pallas for every mask)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,K", [("ring", 8), ("ring", 12), ("grid", 12)])
def test_backend_parity_random_masks(kind, K):
    topo = make_topology(kind, K)
    A = jnp.asarray(topo.A, jnp.float32)
    mixers = {
        "dense": make_mixer("dense", topo),
        "sparse": make_mixer("sparse", topo),
        "pallas": make_mixer("pallas", topo, tile_m=128, interpret=True),
    }
    for seed in range(6):
        key = jax.random.fold_in(KEY, seed)
        params = _rand_tree(key, K)
        m = jax.random.bernoulli(key, 0.6, (K,)).astype(jnp.float32)
        ref = mixers["dense"](params, m, A)
        for name in ("sparse", "pallas"):
            out = mixers[name](params, m, A)
            for leaf_r, leaf_o in zip(jax.tree.leaves(ref),
                                      jax.tree.leaves(out)):
                np.testing.assert_allclose(
                    np.asarray(leaf_o), np.asarray(leaf_r),
                    atol=1e-5, rtol=1e-5, err_msg=f"{name} vs dense ({kind})")


def test_pallas_mixer_on_transformer_pytree():
    """Acceptance gate: the fused Pallas path matches the dense einsum
    within 1e-5 on a REAL model pytree (transformer smoke config)."""
    from repro.configs import get_config
    from repro.models import transformer as tf

    K = 4
    cfg = get_config("smollm_360m").smoke
    params = jax.vmap(lambda k: tf.init_params(k, cfg))(
        jax.random.split(KEY, K))
    topo = make_topology("ring", K)
    A = jnp.asarray(topo.A, jnp.float32)
    active = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    dense = make_mixer("dense", topo)(params, active, A)
    pallas = make_mixer("pallas", topo, interpret=True)(params, active, A)
    for d, p in zip(jax.tree.leaves(dense), jax.tree.leaves(pallas)):
        np.testing.assert_allclose(np.asarray(p, np.float32),
                                   np.asarray(d, np.float32), atol=1e-5)


def test_pallas_layout_cache_reused():
    topo = make_topology("ring", 4)
    A = jnp.asarray(topo.A, jnp.float32)
    mixer = PallasFusedMixer(tile_m=128, interpret=True)
    params = _rand_tree(KEY, 4)
    m = jnp.ones((4,))
    mixer(params, m, A)
    assert len(mixer._layouts) == 1
    mixer(params, m, A)                   # same structure: cache hit
    assert len(mixer._layouts) == 1
    mixer({"w": params["w"]}, m, A)       # new structure: second entry
    assert len(mixer._layouts) == 2


def test_mixer_preserves_mean_and_inactive_agents():
    """eq. 20 invariants hold through every backend: doubly-stochastic
    mixing preserves the network mean, inactive agents keep their params."""
    K = 8
    topo = make_topology("ring", K)
    A = jnp.asarray(topo.A, jnp.float32)
    params = _rand_tree(KEY, K)
    m = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32)
    for name in ("dense", "sparse", "pallas"):
        out = make_mixer(name, topo, tile_m=128, interpret=True)(params, m, A)
        for leaf_in, leaf_out in zip(jax.tree.leaves(params),
                                     jax.tree.leaves(out)):
            np.testing.assert_allclose(np.asarray(leaf_out.mean(0)),
                                       np.asarray(leaf_in.mean(0)),
                                       atol=1e-5, err_msg=name)
            for k in (1, 4):   # inactive agents frozen
                np.testing.assert_allclose(np.asarray(leaf_out[k]),
                                           np.asarray(leaf_in[k]),
                                           atol=1e-6, err_msg=name)


def test_make_mixer_auto_policy_and_errors():
    ring = make_topology("ring", 8)
    fedavg = make_topology("fedavg", 8)
    # low degree but many distinct circulant offsets: sparse would be slower
    # than dense, auto must not pick it
    erdos = make_topology("erdos", 24, p=0.1, seed=2)
    auto_ring = make_mixer("auto", ring)
    auto_fedavg = make_mixer("auto", fedavg)
    auto_erdos = make_mixer("auto", erdos)
    if jax.default_backend() == "tpu":
        assert isinstance(auto_ring, PallasFusedMixer)
    else:
        assert isinstance(auto_ring, SparseCirculantMixer)
        assert isinstance(auto_fedavg, DenseMixer)
        if len(erdos.neighbor_offsets_ring()) > 8:
            # too many circulant offsets for sparse, but bounded degree:
            # auto now takes the O(K*dmax) gather path instead of dense
            assert isinstance(auto_erdos, NeighborGatherMixer)
    assert isinstance(make_mixer("none", ring), NullMixer)
    assert isinstance(make_mixer("dense", None, A=ring.A), DenseMixer)
    assert isinstance(make_mixer(auto_ring), type(auto_ring))  # passthrough
    with pytest.raises(ValueError):
        # the matrix is a call operand now, but sparse still needs its
        # static structure (the circulant offsets) at construction
        make_mixer("sparse", None)
    with pytest.raises(ValueError):
        make_mixer("trimmed_mean", None)   # robust backends need K
    with pytest.raises(ValueError):
        make_mixer("nope", ring)


def test_engine_pallas_backend_matches_dense():
    """DiffusionEngine with --mix pallas == the dense engine end-to-end."""
    K = 8
    data = make_regression_problem(K=K, N=40, M=2, rho=0.1, seed=0)
    cfg = DiffusionConfig(num_agents=K, local_steps=2, step_size=0.02,
                          topology="ring", participation=0.7)
    sampler = make_block_sampler(data, T=2, batch=2)
    batch = sampler(jax.random.PRNGKey(7))
    params = jax.random.normal(jax.random.PRNGKey(0), (K, 2))
    key = jax.random.PRNGKey(42)
    outs = {}
    for mix in ("dense", "pallas"):
        eng = DiffusionEngine(cfg, data.loss_fn(),
                              mixer=make_mixer(mix, cfg.make_topology(),
                                               tile_m=128, interpret=True))
        s, m = eng.step(eng.init_state(params), batch, key)
        outs[mix] = (np.asarray(s.params), np.asarray(m["active"]))
    np.testing.assert_array_equal(outs["dense"][1], outs["pallas"][1])
    np.testing.assert_allclose(outs["pallas"][0], outs["dense"][0], atol=1e-5)


# ---------------------------------------------------------------------------
# participation processes
# ---------------------------------------------------------------------------

def test_iid_process_matches_sample_active():
    q = jnp.asarray([0.2, 0.8, 0.5, 1.0])
    proc = IIDBernoulli(np.asarray(q))
    key = jax.random.PRNGKey(3)
    active, state = proc.sample(proc.init_state(key), key)
    np.testing.assert_array_equal(np.asarray(active),
                                  np.asarray(sample_active(key, q)))
    assert state == ()
    assert not proc.stateful


def test_markov_empirical_frequency_matches_stationary_q():
    """The Markov chain's long-run activation frequency must converge to
    the stationary vector q regardless of the correlation."""
    K, steps = 8, 6000
    q = np.linspace(0.2, 0.9, K)
    for corr in (0.0, 0.6):
        proc = MarkovAvailability(q, corr, num_agents=K)
        state0 = proc.init_state(jax.random.PRNGKey(0))

        def walk(state, key):
            active, state = proc.sample(state, key)
            return state, active

        _, masks = jax.lax.scan(walk, state0,
                                jax.random.split(jax.random.PRNGKey(1), steps))
        freq = np.asarray(masks).mean(axis=0)
        # scan-of-bernoulli standard error ~ sqrt(q(1-q)/n_eff); correlated
        # chains mix slower, hence the loose 0.05 band
        np.testing.assert_allclose(freq, q, atol=0.05,
                                   err_msg=f"corr={corr}")
    np.testing.assert_allclose(proc.q_vector(), q)


def test_markov_zero_corr_is_iid():
    """corr = 0: next state is independent of the current one."""
    proc = MarkovAvailability(0.7, 0.0, num_agents=4)
    key = jax.random.PRNGKey(5)
    from_active, _ = proc.sample(jnp.ones((4,)), key)
    from_inactive, _ = proc.sample(jnp.zeros((4,)), key)
    np.testing.assert_array_equal(np.asarray(from_active),
                                  np.asarray(from_inactive))


def test_cyclic_groups_round_robin():
    K, G = 8, 4
    proc = CyclicGroups(K, G)
    state = proc.init_state(None)
    seen = []
    for _ in range(2 * G):
        active, state = proc.sample(state, None)
        active = np.asarray(active)
        assert active.sum() == K // G          # exactly one group active
        seen.append(active)
    # every agent active exactly twice over two full cycles
    np.testing.assert_array_equal(np.stack(seen).sum(0), np.full(K, 2.0))
    np.testing.assert_allclose(proc.q_vector(), np.full(K, 1.0 / G))


def test_engine_run_threads_markov_state():
    """Engine-level: run() with a Markov process converges like the i.i.d.
    engine does (same stationary q), exercising the state threading."""
    K = 8
    data = make_regression_problem(K=K, N=60, M=2, rho=0.1, seed=0)
    proc = MarkovAvailability(0.8, 0.5, num_agents=K)
    cfg = DiffusionConfig(num_agents=K, local_steps=2, step_size=0.02,
                          topology="ring", participation=0.8)
    eng = DiffusionEngine(cfg, data.loss_fn(), participation=proc)
    w_o = data.problem().w_opt(proc.q_vector())
    params = jnp.full((K, 2), 3.0)
    sampler = make_block_sampler(data, T=2, batch=1)
    _, _, hist = eng.run(params, sampler, 400, seed=0,
                         w_star=jnp.asarray(w_o))
    assert np.mean(hist[-50:]) < 0.05 * hist[0]


def test_sharded_step_with_cyclic_process():
    """make_block_step with a stateful process threads the state through
    EngineState.part_state."""
    from repro.core.sharded import make_block_step
    K = 6
    data = make_regression_problem(K=K, N=40, M=2, rho=0.1, seed=3)
    cfg = DiffusionConfig(num_agents=K, local_steps=2, step_size=0.02,
                          topology="ring", participation=0.5)
    topo = cfg.make_topology()
    proc = CyclicGroups(K, 3)
    loss3 = lambda p, b, rng: data.loss_fn()(p, b)
    block_step = make_block_step(loss3, cfg, topology=topo, mix="sparse",
                                 participation=proc)
    step = jax.jit(block_step)
    sampler = make_block_sampler(data, T=2, batch=1)
    state = block_step.init_state(jnp.zeros((K, 2)))
    masks = []
    for i in range(3):
        state, metrics = step(state, sampler(jax.random.PRNGKey(10 + i)),
                              jax.random.PRNGKey(i))
        masks.append(np.asarray(metrics["active"]))
    assert int(state.part_state) == 3
    np.testing.assert_array_equal(np.stack(masks).sum(0), np.ones(K))


# ---------------------------------------------------------------------------
# robust aggregation (SLSGD trimmed mean / coordinate median)
# ---------------------------------------------------------------------------

def test_trimmed_mean_outlier_parity_under_partial_participation():
    """SLSGD parity gate: with one Byzantine agent in the ACTIVE set, the
    trimmed mean equals the numpy trimmed mean over the active values (the
    outlier contributes nothing), and inactive agents keep their params."""
    from repro.core import TrimmedMeanMixer
    K = 8
    key = jax.random.PRNGKey(3)
    vals = jax.random.normal(key, (K, 5))
    vals = vals.at[2].set(1e4)                       # Byzantine outlier
    params = {"w": vals}
    active = jnp.asarray([1, 1, 1, 0, 1, 1, 0, 1], jnp.float32)
    out = TrimmedMeanMixer(K, trim=1)(params, active)

    act_idx = np.where(np.asarray(active) > 0)[0]
    v = np.asarray(vals)[act_idx]                    # (S, 5) active values
    srt = np.sort(v, axis=0)
    expected = srt[1:-1].mean(axis=0)                # trim 1 each side
    for k in act_idx:
        np.testing.assert_allclose(np.asarray(out["w"][k]), expected,
                                   rtol=1e-5, atol=1e-5)
    for k in (3, 6):                                 # inactive: frozen
        np.testing.assert_array_equal(np.asarray(out["w"][k]),
                                      np.asarray(vals[k]))
    # the outlier's magnitude is gone from every active agent's iterate
    assert np.abs(np.asarray(out["w"])[act_idx]).max() < 10.0


def test_coordinate_median_matches_numpy():
    from repro.core import CoordinateMedianMixer
    K = 7
    vals = jax.random.normal(jax.random.PRNGKey(5), (K, 4))
    active = jnp.asarray([1, 0, 1, 1, 1, 0, 1], jnp.float32)
    out = CoordinateMedianMixer(K)({"w": vals}, active)
    act_idx = np.where(np.asarray(active) > 0)[0]
    expected = np.median(np.asarray(vals)[act_idx], axis=0)
    for k in act_idx:
        np.testing.assert_allclose(np.asarray(out["w"][k]), expected,
                                   rtol=1e-5, atol=1e-6)


def test_trimmed_mean_degenerate_active_sets():
    """Fewer than 2 trim + 1 active agents: the trim clips down to the
    median rather than dying; zero active agents freeze everyone."""
    from repro.core import TrimmedMeanMixer
    K = 6
    vals = jnp.asarray(np.arange(K, dtype=np.float32)[:, None])
    mixer = TrimmedMeanMixer(K, trim=2)
    out = mixer({"w": vals}, jnp.asarray([1, 1, 0, 0, 0, 0], jnp.float32))
    # S=2 <= 2*trim: clipped to b=0 -> plain mean of {0, 1}
    np.testing.assert_allclose(np.asarray(out["w"][:2, 0]), 0.5, atol=1e-6)
    out = mixer({"w": vals}, jnp.zeros((K,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(vals))


def test_robust_mixer_in_engine_suppresses_outlier():
    """End-to-end: a DiffusionEngine with the trimmed-mean backend keeps
    training sane while one agent broadcasts garbage every block (via its
    poisoned iterate), where the linear fedavg mixer is dragged away."""
    from repro.core import TrimmedMeanMixer, make_mixer
    K = 8
    data = make_regression_problem(K=K, N=60, M=2, rho=0.1, seed=0)
    cfg = DiffusionConfig(num_agents=K, local_steps=1, step_size=0.05,
                          topology="fedavg", participation=0.9)
    sampler = make_block_sampler(data, T=1, batch=2)
    w_o = data.problem().w_opt(np.full(K, 0.9))

    def poisoned_run(mixer):
        eng = DiffusionEngine(cfg, data.loss_fn(), mixer=mixer)
        state = eng.init_state(jnp.zeros((K, 2)))
        key = jax.random.PRNGKey(0)
        for i in range(120):
            key, kb, ks = jax.random.split(key, 3)
            # agent 0 is Byzantine: overwrite its iterate before the step
            poisoned = state.params.at[0].set(100.0)
            state = state.replace(params=poisoned)
            state, _ = eng.step(state, sampler(kb), ks)
        dists = np.linalg.norm(np.asarray(state.params)[1:]
                               - np.asarray(w_o), axis=1)
        return float(np.median(dists))

    d_robust = poisoned_run(TrimmedMeanMixer(K, trim=1))
    d_linear = poisoned_run(make_mixer("dense", cfg.make_topology()))
    assert d_robust < 1.0, d_robust
    assert d_robust < 0.1 * d_linear, (d_robust, d_linear)


def _legacy_global_robust(vals, active, slot_weights):
    """Frozen verbatim copy of the pre-scope robust aggregation (the
    original _SortedRobustMixer.__call__ body) — the scope="global"
    bit-parity reference."""
    K = vals.shape[0]
    S = active.astype(jnp.float32).sum()
    w = slot_weights(S)
    m = active.astype(jnp.float32).reshape((K, 1))
    x = vals.astype(jnp.float32)
    srt = jnp.sort(jnp.where(m > 0, x, jnp.inf), axis=0)
    wb = w.reshape((K, 1))
    agg = jnp.sum(jnp.where(wb > 0, srt, 0.0) * wb, axis=0, keepdims=True)
    return np.asarray(jnp.where(m > 0, agg.astype(vals.dtype), vals))


@pytest.mark.parametrize("preset", ["ring", "grid", "full", "fedavg",
                                    "erdos"])
def test_robust_global_scope_bit_parity_with_legacy(preset):
    """scope="global" (the default) stays bit-identical to the pre-scope
    robust path for every base topology the presets use, with the A_t
    operand present or absent."""
    from repro.core import CoordinateMedianMixer, TrimmedMeanMixer
    K = 12
    topo = make_topology(preset, K)
    A = jnp.asarray(topo.A, jnp.float32)
    for kind in ("trimmed_mean", "median"):
        for seed in range(3):
            key = jax.random.fold_in(KEY, seed)
            vals = jax.random.normal(key, (K, 5))
            active = jax.random.bernoulli(key, 0.7, (K,)).astype(jnp.float32)
            mixer = (TrimmedMeanMixer(K, trim=2) if kind == "trimmed_mean"
                     else CoordinateMedianMixer(K))
            assert mixer.scope == "global" and not mixer.uses_matrix
            ref = _legacy_global_robust(vals, active, mixer._slot_weights)
            for A_t in (A, None):
                out = np.asarray(mixer({"w": vals}, active, A_t)["w"])
                np.testing.assert_array_equal(out, ref,
                                              err_msg=f"{kind}/{preset}")


def test_neighborhood_scope_matches_numpy_reference():
    """Neighborhood trimmed mean/median == a per-row numpy reference over
    the realized neighborhood (support of masked_combination's column
    intersected with the active set, self included), with the per-row trim
    clip for small neighborhoods."""
    from repro.core import CoordinateMedianMixer, TrimmedMeanMixer
    K = 12
    topo = make_topology("ring", K)
    A = jnp.asarray(topo.A, jnp.float32)
    for seed in range(4):
        key = jax.random.fold_in(KEY, 100 + seed)
        vals = jax.random.normal(key, (K, 3))
        active = jax.random.bernoulli(key, 0.7, (K,)).astype(jnp.float32)
        A_eff = np.asarray(masked_combination(A, active))
        for kind, trim in (("trimmed_mean", 1), ("median", None)):
            mixer = (TrimmedMeanMixer(K, trim=trim, scope="neighborhood")
                     if kind == "trimmed_mean"
                     else CoordinateMedianMixer(K, scope="neighborhood"))
            assert mixer.uses_matrix
            out = np.asarray(jax.jit(mixer)({"w": vals}, active, A)["w"])
            act = np.asarray(active)
            v = np.asarray(vals)
            for k in range(K):
                if act[k] == 0:
                    np.testing.assert_array_equal(out[k], v[k])
                    continue
                members = sorted(set(np.where(A_eff[:, k] != 0)[0]) | {k})
                srt = np.sort(v[members], axis=0)
                S = len(members)
                if kind == "median":
                    ref = np.median(v[members], axis=0)
                else:
                    b = min(trim, (S - 1) // 2)
                    ref = srt[b:S - b].mean(axis=0)
                np.testing.assert_allclose(out[k], ref, rtol=1e-5,
                                           atol=1e-5,
                                           err_msg=f"{kind} agent {k}")


def test_neighborhood_tolerates_trim_byzantine_per_neighborhood():
    """The headline property: with at most `trim` Byzantine agents in every
    closed neighborhood, each honest active agent's neighborhood-trimmed
    output lies within the honest member range — while the global scope on
    a ring leaks once the TOTAL adversary count exceeds `trim`."""
    from repro.core import TrimmedMeanMixer
    K = 12
    topo = make_topology("ring", K)
    A = jnp.asarray(topo.A, jnp.float32)
    active = jnp.ones((K,), jnp.float32)
    byz = (0, 4, 8)                      # <= 1 per closed ring neighborhood
    for seed in range(5):
        key = jax.random.fold_in(KEY, 200 + seed)
        honest_vals = jax.random.uniform(key, (K, 4), minval=-1.0,
                                         maxval=1.0)
        sign = jax.random.bernoulli(key, 0.5, (len(byz), 1)) * 2.0 - 1.0
        vals = honest_vals
        for i, b in enumerate(byz):
            vals = vals.at[b].set(1e3 * sign[i])
        out_n = np.asarray(TrimmedMeanMixer(K, trim=1, scope="neighborhood")(
            {"w": vals}, active, A)["w"])
        out_g = np.asarray(TrimmedMeanMixer(K, trim=1, scope="global")(
            {"w": vals}, active, A)["w"])
        honest = [k for k in range(K) if k not in byz]
        # neighborhood: every honest output within the honest value range
        assert np.abs(out_n[honest]).max() <= 1.0 + 1e-6, out_n[honest]
        # global: 3 adversaries > trim=1 — garbage leaks into the aggregate
        assert np.abs(out_g[honest]).max() > 1.0, out_g[honest]


def test_robust_edge_cases_S0_S1_and_bf16():
    """Satellite regression gate: S=0 freezes everyone with finite
    intermediates, S=1 reduces to the lone member's own value, and bf16
    leaves survive the inf-padding without NaN — in BOTH scopes, both
    backends."""
    from repro.core import CoordinateMedianMixer, TrimmedMeanMixer
    K = 6
    topo = make_topology("ring", K)
    A = jnp.asarray(topo.A, jnp.float32)
    vals = jax.random.normal(KEY, (K, 3))
    mixers = [TrimmedMeanMixer(K, trim=2, scope=s) for s in
              ("global", "neighborhood")]
    mixers += [CoordinateMedianMixer(K, scope=s) for s in
               ("global", "neighborhood")]
    for mixer in mixers:
        # S = 0: everyone inactive -> frozen exactly
        out = jax.jit(mixer)({"w": vals}, jnp.zeros((K,)), A)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(vals), err_msg=repr(mixer))
        # S = 1: the lone active agent keeps its own value exactly (its
        # neighborhood / the active set is just itself)
        one = jnp.zeros((K,)).at[2].set(1.0)
        out = jax.jit(mixer)({"w": vals}, one, A)
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(vals),
                                   atol=1e-6, err_msg=repr(mixer))
        # bf16 leaves: finite, and close to the f32 computation
        bf = vals.astype(jnp.bfloat16)
        active = jnp.asarray([1, 1, 0, 1, 1, 1], jnp.float32)
        out_bf = np.asarray(jax.jit(mixer)({"w": bf}, active, A)["w"]
                            .astype(jnp.float32))
        assert np.isfinite(out_bf).all(), repr(mixer)
        out_f32 = np.asarray(jax.jit(mixer)(
            {"w": bf.astype(jnp.float32)}, active, A)["w"])
        np.testing.assert_allclose(out_bf, out_f32, atol=0.05,
                                   err_msg=repr(mixer))


def test_neighborhood_scope_composes_with_dynamic_graphs():
    """The realized A_t of every dynamic GraphProcess flows into the
    neighborhood aggregation: check_mixer_support accepts all of them
    (incl. tv_erdos, which rejects the sparse backend), and an engine run
    under link dropout + neighborhood trimmed mean stays sane."""
    from repro.core import TrimmedMeanMixer, make_graph_process
    from repro.core.graphs import check_mixer_support
    K = 8
    topo = make_topology("ring", K)
    mixer = TrimmedMeanMixer(K, trim=1, scope="neighborhood")
    for kind in ("static", "link_dropout", "gossip", "tv_erdos"):
        graph = make_graph_process(kind, topo, num_agents=K)
        check_mixer_support(mixer, graph)      # must not raise
    data = make_regression_problem(K=K, N=40, M=2, rho=0.1, seed=0)
    cfg = DiffusionConfig(num_agents=K, local_steps=1, step_size=0.05,
                          topology="ring", participation=0.9,
                          graph="link_dropout",
                          graph_kwargs=(("corr", 0.0), ("drop", 0.3)))
    eng = DiffusionEngine(cfg, data.loss_fn(), mixer=mixer)
    sampler = make_block_sampler(data, T=1, batch=2)
    w_o = data.problem().w_opt(np.full(K, 0.9))
    params = jnp.full((K, 2), 3.0)
    _, _, hist = eng.run(params, sampler, 300, seed=0,
                         w_star=jnp.asarray(w_o))
    assert np.mean(hist[-50:]) < 0.1 * hist[0]


def test_sparse_skip_dead_parity_and_live_count():
    """Dead-offset segment mask (graph-aware sparse offsets): the guarded
    sparse path is numerically identical to dense on matrices with all-zero
    coefficient rows, and count_live_offsets reports the realized permute
    count."""
    from repro.core import (DenseMixer, count_live_offsets,
                            make_graph_process)
    from repro.core.graphs import check_mixer_support
    from repro.core.topology import metropolis_weights
    K = 8
    topo = make_topology("ring", K, hops=2)
    offs = topo.neighbor_offsets_ring()
    # kill every +/-2 edge: that offset's coefficient row is all-zero
    adj = topo.adjacency.copy()
    idx = np.arange(K)
    adj[idx, (idx + 2) % K] = False
    adj[(idx + 2) % K, idx] = False
    A_dead = jnp.asarray(metropolis_weights(adj), jnp.float32)
    params = _rand_tree(KEY, K)
    active = jax.random.bernoulli(KEY, 0.8, (K,)).astype(jnp.float32)
    sk = SparseCirculantMixer(offs, skip_dead=True)
    ref = DenseMixer()(params, active, A_dead)
    out = jax.jit(sk)(params, active, A_dead)
    for r, o in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)
    A_eff = masked_combination(A_dead, jnp.ones((K,)))
    assert int(count_live_offsets(A_eff, offs)) == len(offs) - 2
    assert int(sk.live_offsets(jnp.ones((K,)), A_dead)) == len(offs) - 2
    # check_mixer_support auto-tunes: dynamic graph -> skip on, static -> off
    auto = SparseCirculantMixer(offs)
    assert auto.skip_dead is None
    check_mixer_support(auto, make_graph_process("static", topo))
    assert auto.skip_dead is False
    # an auto decision follows EACH build's graph (reused instances do not
    # keep the first build's tuning); explicit settings are never touched
    check_mixer_support(auto, make_graph_process("link_dropout", topo))
    assert auto.skip_dead is True
    explicit = SparseCirculantMixer(offs, skip_dead=False)
    check_mixer_support(explicit, make_graph_process("link_dropout", topo))
    assert explicit.skip_dead is False


def test_robust_mixer_rejects_compressed_pipeline():
    from repro.core import CommPipeline, TrimmedMeanMixer
    from repro.core.compression import make_compressor
    with pytest.raises(ValueError, match="robust"):
        CommPipeline(TrimmedMeanMixer(8, trim=1),
                     make_compressor("topk", ratio=0.5))
    # identity pipeline is fine
    pipe = CommPipeline(TrimmedMeanMixer(8, trim=1))
    assert pipe.mode == "identity" and not pipe.stateful


def test_process_validation():
    with pytest.raises(ValueError):
        MarkovAvailability(0.5, 1.0, num_agents=4)     # corr out of range
    with pytest.raises(ValueError):
        MarkovAvailability(1.5, 0.5, num_agents=4)     # q out of range
    with pytest.raises(ValueError):
        CyclicGroups(4, 5)                             # more groups than K
    with pytest.raises(ValueError):
        schedules.IIDBernoulli(0.5)                    # scalar q needs K
    with pytest.raises(ValueError):
        # engine rejects a process over the wrong number of agents
        data = make_regression_problem(K=4, N=20)
        DiffusionEngine(DiffusionConfig(num_agents=4), data.loss_fn(),
                        participation=IIDBernoulli(0.5, num_agents=6))
    from repro.core.sharded import make_block_step
    loss3 = lambda p, b, rng: 0.0
    with pytest.raises(ValueError):
        # sharded builder applies the same agent-count validation
        make_block_step(loss3, DiffusionConfig(num_agents=4),
                        topology=make_topology("ring", 4),
                        participation=IIDBernoulli(0.5, num_agents=6))
    with pytest.raises(ValueError):
        # ... and the drift-correction q_k > 0 guard
        make_block_step(loss3,
                        DiffusionConfig(num_agents=4, drift_correction=True),
                        topology=make_topology("ring", 4),
                        participation=IIDBernoulli((0.5, 0.0, 0.5, 0.5)))


# ---------------------------------------------------------------------------
# mesh-aware backend choice: no single-device kernel across devices
# ---------------------------------------------------------------------------

def _two_device_mesh():
    """A 2-device ("data",) mesh over the one real device, repeated: enough
    for the policy, which reads only the mesh size."""
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices() * 2), ("data",))


def test_auto_avoids_the_kernel_across_devices(monkeypatch):
    from repro.core import graphs as graph_lib
    from repro.core import mixing
    monkeypatch.setattr(mixing.jax, "default_backend", lambda: "tpu")
    ring = make_topology("ring", 8)
    mesh = _two_device_mesh()
    assert mixing.resolve_auto(ring)[0] == "pallas"
    assert mixing.resolve_auto(ring, mesh=mesh)[0] == "sparse"
    assert isinstance(make_mixer("auto", ring, mesh=mesh),
                      SparseCirculantMixer)
    # a graph that leaves the base support: dense, not the kernel
    erdos = graph_lib.TimeVaryingErdos(8, p=0.3, topology=ring)
    assert graph_lib.resolve_mix_for_graph("auto", erdos) == "pallas"
    assert graph_lib.resolve_mix_for_graph("auto", erdos, mesh) == "dense"


def test_gather_mixer_uses_the_kernel_only_on_one_device(monkeypatch):
    from repro.core import mixing
    from repro.kernels import diffusion_mix as dm
    topo = make_topology("ring", 8, hops=2)
    calls = []
    real = dm.gather_mix
    monkeypatch.setattr(dm, "gather_mix",
                        lambda *a, **kw: calls.append(1) or real(
                            *a, **{**kw, "interpret": True}))
    monkeypatch.setattr(mixing.jax, "default_backend", lambda: "tpu")
    W = _rand_tree(KEY, 8)
    m, A = jnp.ones((8,)), jnp.asarray(topo.A, jnp.float32)
    gather = make_mixer("gather", topo)
    gather(W, m, A)
    assert calls == [1]                           # one device: the kernel
    # the policy reads only the mesh the mixer was sharded over; the fake
    # mesh cannot place arrays, so set it and skip the layout pins
    gather._mesh, gather._agent_axis = _two_device_mesh(), "data"
    monkeypatch.setattr(mixing, "_constrain_agent_stack",
                        lambda tree, mesh, axis: tree)
    gather(W, m, A)
    assert calls == [1]                           # across devices: einsum


def test_pallas_mixer_bf16_buffer_is_bit_identical_to_f32():
    """bfloat16 leaves flatten to a bfloat16 buffer: the kernel upcasts
    exactly and rounds once, so the result equals the float32 buffer's."""
    topo = make_topology("ring", 6)
    A = jnp.asarray(topo.A, jnp.float32)
    m = jnp.array([1, 0, 1, 1, 1, 0], jnp.float32)
    W = jax.tree.map(lambda x: x.astype(jnp.bfloat16), _rand_tree(KEY, 6))
    mixer = PallasFusedMixer(tile_m=128, interpret=True)
    out = mixer(W, m, A)
    ref = mixer(jax.tree.map(lambda x: x.astype(jnp.float32), W), m, A)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint16),
            np.asarray(b.astype(jnp.bfloat16)).view(np.uint16))
