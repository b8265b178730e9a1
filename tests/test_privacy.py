"""The privacy tier: RDP accounting, clip-and-noise, secure-agg wire
masks, epsilon-aware checkpoints, and the spec/CLI/build plumbing.

The load-bearing invariants:

* the jit accountant (:meth:`Privacy.advance`) is the numpy twin
  (:func:`rdp_increment_np`) accumulated at the realized rates, and both
  collapse to the closed-form Gaussian RDP ``alpha / (2 sigma^2)`` at
  full participation;
* the secure-agg masks telescope to zero — the masked combination equals
  the unmasked eq.-20 combination up to float accumulation, on the static
  graph AND under LinkDropout (per-block pairing re-derivation), with
  inactive receivers bit-exact;
* ``privacy_state`` rides the EngineState append-last contract: private
  checkpoints round-trip the accountant, and pre-privacy archives (the
  committed PR-8-era fixture) keep loading and continuing bit-identically.
"""
import argparse
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.build import build
from repro.api.cli import add_spec_args, get_preset, spec_from_args
from repro.api.spec import (AsyncSpec, CompressionSpec, ExperimentSpec,
                            GraphSpec, OptimizerSpec, ParticipationSpec,
                            PrivacySpec, RunSpec)
from repro.checkpoint import load_experiment, save_experiment
from repro.core import privacy as priv
from repro.core.mixing import CommPipeline, make_mixer
from repro.core.msd import (compressor_injected_variance,
                            dp_injected_variance, theoretical_msd)
from repro.core.participation import masked_combination_np
from repro.core.serving import consensus_from_stacked
from repro.core.state import EngineState
from repro.core.topology import make_topology
from repro.data.synthetic import make_block_sampler, make_regression_problem

FIXTURE = Path(__file__).parent / "fixtures" / "pr8_engine_state.npz"


def _private_spec(K=4, *, nm=0.8, secure_agg=False, graph=None, **priv_kw):
    kw = dict(enabled=True, clip=1.0, noise_multiplier=nm,
              secure_agg=secure_agg)
    kw.update(priv_kw)
    return ExperimentSpec(
        graph=graph if graph is not None else GraphSpec(),
        participation=ParticipationSpec(kind="iid", q=0.8),
        privacy=PrivacySpec(**kw),
        run=RunSpec(num_agents=K, local_steps=1, step_size=0.05, blocks=4))


def _run_blocks(eng, data, state, n, *, key0=0):
    sampler = make_block_sampler(data, T=1, batch=1)
    metrics = None
    for i in range(n):
        state, metrics = eng.step(state, sampler(jax.random.PRNGKey(i)),
                                  jax.random.PRNGKey(100 + key0 + i))
    return state, metrics


# ---------------------------------------------------------------------------
# spec round-trip
# ---------------------------------------------------------------------------

def test_privacy_spec_json_roundtrip():
    spec = _private_spec(secure_agg=True, epsilon=4.0, delta=1e-6)
    again = ExperimentSpec.from_json(spec.to_json())
    assert again == spec
    assert json.loads(spec.to_json())["privacy"]["secure_agg"] is True


# ---------------------------------------------------------------------------
# the accountant
# ---------------------------------------------------------------------------

def test_rdp_full_participation_closed_form():
    """q = 1 collapses the sampled-Gaussian bound to the plain Gaussian
    RDP alpha / (2 sigma^2)."""
    sigma = 2.0
    rdp = priv.rdp_increment_np(1.0, sigma)
    want = np.asarray(priv.DEFAULT_ORDERS, np.float64) / (2.0 * sigma ** 2)
    np.testing.assert_allclose(rdp, want, rtol=1e-10)


def test_accountant_jit_matches_numpy_twin():
    K, sigma = 5, 1.3
    p = priv.Privacy(num_agents=K, clip=1.0, noise_multiplier=sigma,
                     delta=1e-5)
    pstate = p.init_state()
    rng = np.random.default_rng(2)
    rdp_np = np.zeros(len(priv.DEFAULT_ORDERS), np.float64)
    for _ in range(7):
        active = (rng.random(K) < 0.7).astype(np.float32)
        pstate = p.advance(pstate, jnp.asarray(active))
        rdp_np += priv.rdp_increment_np(float(active.sum()) / K, sigma)
    np.testing.assert_allclose(np.asarray(pstate["rdp"]), rdp_np,
                               rtol=2e-4, atol=1e-6)
    assert int(pstate["steps"]) == 7
    eps_np = priv.epsilon_from_rdp_np(rdp_np, 1e-5)
    assert abs(float(p.epsilon(pstate)) - eps_np) < max(2e-3 * eps_np, 1e-3)
    assert abs(p.epsilon_np(pstate) - eps_np) < 1e-3


def test_accountant_zero_participation_is_free():
    p = priv.Privacy(num_agents=4, clip=1.0, noise_multiplier=1.0,
                     delta=1e-5)
    pstate = p.advance(p.init_state(), jnp.zeros((4,)))
    np.testing.assert_array_equal(np.asarray(pstate["rdp"]), 0.0)
    # zero accumulated RDP: epsilon sits at the order grid's conversion
    # floor (the Balle bound is not exactly 0 on a finite grid)
    floor = priv.epsilon_from_rdp_np(
        np.zeros(len(priv.DEFAULT_ORDERS)), 1e-5)
    assert float(p.epsilon(pstate)) == pytest.approx(floor, abs=1e-4)
    assert floor < 0.01


def test_calibration_spends_budget_tightly():
    eps, delta, q, steps = 5.0, 1e-5, 0.5, 300

    def spent(sigma):
        return priv.epsilon_from_rdp_np(
            steps * priv.rdp_increment_np(q, sigma), delta)

    nm = priv.calibrate_noise_multiplier(eps, delta, q, steps)
    assert spent(nm) <= eps + 1e-6
    assert spent(nm * 0.97) > eps          # minimal up to bisection width
    with pytest.raises(ValueError, match="must be > 0"):
        priv.calibrate_noise_multiplier(0.0, delta, q, steps)


def test_accountant_scales_with_local_steps():
    """T local steps per block = T mechanism invocations per block: the
    per-block increment is exactly T times the single-invocation bound
    (the review-critical factor — one increment per block would
    understate epsilon for any run with local_steps > 1)."""
    kw = dict(num_agents=4, clip=1.0, noise_multiplier=1.2, delta=1e-5)
    p1 = priv.Privacy(**kw)
    p3 = priv.Privacy(steps_per_block=3, **kw)
    active = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    s1 = p1.advance(p1.init_state(), active)
    s3 = p3.advance(p3.init_state(), active)
    np.testing.assert_allclose(np.asarray(s3["rdp"]),
                               3.0 * np.asarray(s1["rdp"]), rtol=1e-6)
    assert float(p3.epsilon(s3)) > float(p1.epsilon(s1))
    with pytest.raises(ValueError, match="steps_per_block"):
        priv.Privacy(steps_per_block=0, **kw)


def test_compile_privacy_accounts_local_steps():
    """Calibration composes over blocks * local_steps invocations, and
    the compiled tier carries the per-block invocation count."""
    spec = _private_spec(nm=0.0, epsilon=6.0).replace(
        run=RunSpec(num_agents=4, local_steps=2, step_size=0.05, blocks=4))
    p = priv.compile_privacy(spec)
    assert p.steps_per_block == 2
    spent = priv.epsilon_from_rdp_np(
        4 * 2 * priv.rdp_increment_np(0.8, p.noise_multiplier), p.delta)
    assert spent <= 6.0 + 1e-6
    # T=2 needs MORE noise than T=1 for the same budget over the same
    # number of blocks
    p1 = priv.compile_privacy(_private_spec(nm=0.0, epsilon=6.0))
    assert p.noise_multiplier > p1.noise_multiplier


def test_compile_privacy_rejects_heterogeneous_rates():
    """One tracked epsilon at the population rate is only a per-agent
    guarantee under a uniform rate — mixed-rate networks are rejected."""
    spec = _private_spec().replace(
        participation=ParticipationSpec(kind="iid",
                                        q=(1.0, 0.6, 0.8, 0.8)))
    with pytest.raises(ValueError, match="homogeneous participation"):
        priv.compile_privacy(spec)


def test_privacy_ctor_validation():
    with pytest.raises(ValueError, match="clip"):
        priv.Privacy(num_agents=4, clip=0.0, noise_multiplier=1.0,
                     delta=1e-5)
    with pytest.raises(ValueError, match="noise_multiplier"):
        priv.Privacy(num_agents=4, clip=1.0, noise_multiplier=0.0,
                     delta=1e-5)
    with pytest.raises(ValueError, match="delta"):
        priv.Privacy(num_agents=4, clip=1.0, noise_multiplier=1.0,
                     delta=1.0)


def test_compile_privacy_resolution():
    assert priv.compile_privacy(ExperimentSpec()) is None
    p = priv.compile_privacy(_private_spec(nm=1.5))
    assert p.noise_multiplier == 1.5 and p.epsilon_budget is None
    p = priv.compile_privacy(_private_spec(nm=0.0, epsilon=6.0))
    assert p.epsilon_budget == 6.0 and p.noise_multiplier > 0
    # calibrated sigma actually meets the budget over the spec's blocks
    spent = priv.epsilon_from_rdp_np(
        4 * priv.rdp_increment_np(0.8, p.noise_multiplier), p.delta)
    assert spent <= 6.0 + 1e-6
    with pytest.raises(ValueError, match="neither noise_multiplier nor "
                                         "epsilon"):
        priv.compile_privacy(_private_spec(nm=0.0, epsilon=0.0))


# ---------------------------------------------------------------------------
# clip-and-noise
# ---------------------------------------------------------------------------

def test_clip_and_noise_per_agent_global_norm():
    K = 3
    g = {"a": jnp.full((K, 2), 10.0), "b": jnp.full((K, 4), 10.0)}
    out = priv.clip_and_noise(g, jax.random.PRNGKey(0), clip=1.0,
                              noise_multiplier=0.0)
    sq = (np.asarray(out["a"]) ** 2).sum(1) + (np.asarray(out["b"]) ** 2).sum(1)
    np.testing.assert_allclose(np.sqrt(sq), 1.0, rtol=1e-5)
    # direction preserved: every coordinate scaled by the same factor
    np.testing.assert_allclose(np.asarray(out["a"]) / np.asarray(out["b"])[:, :2],
                               1.0, rtol=1e-5)
    # small gradients pass through untouched (scale = min(1, ...))
    small = {"a": jnp.asarray([[0.1, 0.2]])}
    out2 = priv.clip_and_noise(small, jax.random.PRNGKey(0), clip=1.0,
                               noise_multiplier=0.0)
    np.testing.assert_allclose(np.asarray(out2["a"]), [[0.1, 0.2]],
                               rtol=1e-6)
    # noise actually lands when the multiplier is positive
    out3 = priv.clip_and_noise(small, jax.random.PRNGKey(1), clip=1.0,
                               noise_multiplier=2.0)
    assert not np.allclose(np.asarray(out3["a"]), [[0.1, 0.2]], atol=1e-3)


def test_private_gradients_requires_counter_state():
    t = priv.PrivateGradients(1.0, 0.5).as_transform()
    g = jnp.ones((2, 3))
    with pytest.raises(ValueError, match="engine.optimizer.init"):
        t.update(g, None, g)


# ---------------------------------------------------------------------------
# secure-agg wire masks
# ---------------------------------------------------------------------------

def test_secure_agg_masks_cancel_exactly():
    K, M = 6, 5
    topo = make_topology("ring", K)
    A = jnp.asarray(topo.A, jnp.float32)
    stage = priv.make_secure_agg(K, seed=11, mask_scale=3.0)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(K, M)), jnp.float32)
    for active_np in ([1.0] * K, [1, 0, 1, 1, 0, 1], [0.0] * K):
        active = jnp.asarray(active_np, jnp.float32)
        mixed = np.asarray(stage(X, active, A, jnp.uint32(2)))
        want = masked_combination_np(
            np.asarray(A), np.asarray(active)).T @ np.asarray(X)
        np.testing.assert_allclose(mixed, want, atol=5e-5)
        for k, a in enumerate(active_np):
            if not a:   # inactive receiver: unit column, bit-exact keep
                np.testing.assert_array_equal(mixed[k], np.asarray(X)[k])


def test_secure_agg_mask_stream_varies_by_block():
    """Different blocks draw different masks (fold_in on t) yet both
    cancel — the checkpoint/resume property of the mask epoch counter."""
    K = 4
    A = jnp.asarray(make_topology("ring", K).A, jnp.float32)
    stage = priv.make_secure_agg(K, seed=3)
    X = jnp.asarray(np.random.default_rng(1).normal(size=(K, 3)),
                    jnp.float32)
    ones = jnp.ones((K,), jnp.float32)
    want = np.asarray(A).T @ np.asarray(X)
    for t in (0, 1, 17):
        np.testing.assert_allclose(
            np.asarray(stage(X, ones, A, jnp.uint32(t))), want, atol=5e-5)


def test_secure_agg_rejects_single_agent():
    with pytest.raises(ValueError, match="num_agents >= 2"):
        priv.make_secure_agg(1)


@pytest.mark.parametrize("graph", [
    GraphSpec(),
    GraphSpec(kind="link_dropout", drop=0.3),
], ids=["static", "link_dropout"])
def test_secure_agg_engine_parity(graph):
    """Masked and unmasked runs of the SAME private experiment produce the
    same trajectory — the wire masks are invisible to the algorithm."""
    data = make_regression_problem(K=4, N=20)
    params = jnp.zeros((4, 2))
    out = {}
    for sa in (False, True):
        spec = _private_spec(nm=0.7, secure_agg=sa, graph=graph)
        eng = build(spec, data.loss_fn())
        state = eng.init_state(params, eng.optimizer.init(params),
                               key=jax.random.PRNGKey(5))
        state, _ = _run_blocks(eng, data, state, 3)
        out[sa] = np.asarray(state.params)
    np.testing.assert_allclose(out[True], out[False], atol=5e-5)


def test_pipeline_secure_agg_guards():
    from repro.core import compression as comp
    topo = make_topology("ring", 4)
    stage = priv.make_secure_agg(4)
    dense = make_mixer("dense", topo, num_agents=4)
    with pytest.raises(ValueError, match="identity-mode"):
        CommPipeline(dense, comp.Int8Stochastic(), secure_agg=stage)
    with pytest.raises(ValueError, match="no wire to mask"):
        CommPipeline(make_mixer("none", topo, num_agents=4),
                     secure_agg=stage)
    with pytest.raises(ValueError, match="linear"):
        CommPipeline(make_mixer("trimmed_mean", topo, num_agents=4),
                     secure_agg=stage)
    # the happy path carries the mask-epoch counter in comm_state
    pipe = CommPipeline(dense, secure_agg=stage)
    assert pipe.stateful
    assert int(pipe.init_state(jnp.zeros((4, 2)))["t"]) == 0


# ---------------------------------------------------------------------------
# build() composition guards
# ---------------------------------------------------------------------------

def test_build_rejects_privacy_plus_explicit_transform():
    data = make_regression_problem(K=4, N=20)
    from repro.optim.optimizers import sgd
    with pytest.raises(ValueError, match="explicit grad_transform"):
        build(_private_spec(), data.loss_fn(), grad_transform=sgd())


def test_build_gauss_compression_needs_opt_in():
    data = make_regression_problem(K=4, N=20)
    spec = _private_spec().replace(
        compression=CompressionSpec(kind="gauss", ratio=1.0, sigma=0.05))
    with pytest.raises(ValueError, match="double-noises"):
        build(spec, data.loss_fn())
    spec = dataclasses.replace(
        spec, privacy=dataclasses.replace(spec.privacy, allow_gauss=True))
    eng = build(spec, data.loss_fn())   # explicit opt-in builds fine
    assert eng.privacy is not None


def test_build_rejects_async_secure_agg():
    data = make_regression_problem(K=4, N=20)
    spec = _private_spec(secure_agg=True).replace(
        asynchrony=AsyncSpec(enabled=True))
    with pytest.raises(ValueError, match="secure-agg"):
        build(spec, data.loss_fn())


# ---------------------------------------------------------------------------
# engine threading: metrics, state, resume guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("asynchronous", [False, True],
                         ids=["stacked", "async"])
def test_engine_threads_accountant(asynchronous):
    data = make_regression_problem(K=4, N=20)
    spec = _private_spec(nm=1.0)
    if asynchronous:
        spec = spec.replace(asynchrony=AsyncSpec(enabled=True))
    eng = build(spec, data.loss_fn())
    params = jnp.zeros((4, 2))
    state = eng.init_state(params, eng.optimizer.init(params),
                           key=jax.random.PRNGKey(0))
    assert state.privacy_state is not None
    sampler = make_block_sampler(data, T=1, batch=1)
    eps = []
    for i in range(4):
        state, m = eng.step(state, sampler(jax.random.PRNGKey(i)),
                            jax.random.PRNGKey(10 + i))
        eps.append(float(m["epsilon"]))
    assert eps == sorted(eps)              # spent epsilon is monotone
    assert eps[-1] > 0
    assert int(state.privacy_state["steps"]) == 4
    # the metric agrees with the accountant read off the state
    assert abs(eps[-1] - float(eng.privacy.epsilon(state.privacy_state))) \
        < 1e-6


def test_engine_accountant_counts_local_steps():
    """End to end: a local_steps=2 engine accumulates TWICE the realized
    single-invocation RDP per block (PrivateGradients draws fresh noise
    at every local step inside the scan)."""
    data = make_regression_problem(K=4, N=20)
    spec = _private_spec(nm=1.0).replace(
        run=RunSpec(num_agents=4, local_steps=2, step_size=0.05, blocks=4))
    eng = build(spec, data.loss_fn())
    assert eng.privacy.steps_per_block == 2
    params = jnp.zeros((4, 2))
    state = eng.init_state(params, eng.optimizer.init(params),
                           key=jax.random.PRNGKey(0))
    sampler = make_block_sampler(data, T=2, batch=1)
    rdp = np.zeros(len(priv.DEFAULT_ORDERS), np.float64)
    for i in range(3):
        state, m = eng.step(state, sampler(jax.random.PRNGKey(i)),
                            jax.random.PRNGKey(10 + i))
        q = float(np.asarray(m["active"]).sum()) / 4
        rdp += 2 * priv.rdp_increment_np(q, 1.0)
    np.testing.assert_allclose(np.asarray(state.privacy_state["rdp"]),
                               rdp, rtol=2e-4, atol=1e-6)


def test_step_rejects_missing_privacy_state():
    """A checkpoint from a non-private run cannot resume under a
    PrivacySpec without a fresh accountant — the append-last guard."""
    data = make_regression_problem(K=4, N=20)
    eng = build(_private_spec(), data.loss_fn())
    params = jnp.zeros((4, 2))
    state = eng.init_state(params, eng.optimizer.init(params),
                           key=jax.random.PRNGKey(0))
    bad = state.replace(privacy_state=None)
    sampler = make_block_sampler(data, T=1, batch=1)
    with pytest.raises(ValueError, match="fresh accountant"):
        eng.step(bad, sampler(jax.random.PRNGKey(0)),
                 jax.random.PRNGKey(1))


# ---------------------------------------------------------------------------
# CLI flags, guard, preset
# ---------------------------------------------------------------------------

def _parse(argv):
    # a FRESH parser per parse: add_spec_args shares one _explicit set per
    # parser instance, and the launchers parse exactly once
    ap = argparse.ArgumentParser()
    add_spec_args(ap)
    return ap.parse_args(argv)


def test_cli_privacy_flags_map_to_spec():
    spec = spec_from_args(_parse(
        ["--privacy", "--privacy-epsilon", "4.0", "--privacy-clip", "0.5",
         "--privacy-secure-agg"]))
    assert spec.privacy == PrivacySpec(enabled=True, epsilon=4.0,
                                       clip=0.5, secure_agg=True)
    assert spec_from_args(_parse([])).privacy == PrivacySpec()


def test_cli_privacy_subflags_require_privacy():
    with pytest.raises(ValueError, match="privacy is not enabled"):
        spec_from_args(_parse(["--privacy-epsilon", "4.0"]))
    with pytest.raises(ValueError, match="privacy is not enabled"):
        spec_from_args(_parse(["--privacy-secure-agg"]))


def test_cli_private_diffusion_preset():
    spec = spec_from_args(_parse(
        ["--preset", "private_diffusion", "--agents", "4"]))
    assert spec.privacy.enabled and spec.privacy.secure_agg
    assert spec.privacy.epsilon == 8.0
    # sub-flags overlay the preset without needing --privacy (the preset
    # already enables the tier)
    spec = spec_from_args(_parse(
        ["--preset", "private_diffusion", "--agents", "4",
         "--privacy-noise", "2.0"]))
    assert spec.privacy.noise_multiplier == 2.0
    # and the preset's spec actually builds a private engine
    factory = get_preset("private_diffusion")
    data = make_regression_problem(K=4, N=20)
    eng = build(factory(K=4, T=1, mu=0.05, q=0.8, corr=0.0, num_groups=2),
                data.loss_fn())
    assert eng.privacy is not None and eng.privacy.secure_agg


# ---------------------------------------------------------------------------
# epsilon-aware checkpoints
# ---------------------------------------------------------------------------

def test_private_checkpoint_roundtrips_accountant(tmp_path):
    data = make_regression_problem(K=4, N=20)
    spec = _private_spec(nm=1.0, epsilon=50.0)
    eng = build(spec, data.loss_fn())
    params = jnp.zeros((4, 2))
    state = eng.init_state(params, eng.optimizer.init(params),
                           key=jax.random.PRNGKey(0))
    state, _ = _run_blocks(eng, data, state, 3)
    eps = eng.privacy.epsilon_np(state.privacy_state)
    assert eps > 0
    path = str(tmp_path / "private.npz")
    save_experiment(path, state, spec=spec, step=3,
                    metadata={"epsilon_spent": eps,
                              "privacy_delta": spec.privacy.delta})
    like = jax.tree.map(jnp.zeros_like, state)
    loaded, meta = load_experiment(path, like)
    np.testing.assert_array_equal(np.asarray(loaded.privacy_state["rdp"]),
                                  np.asarray(state.privacy_state["rdp"]))
    assert int(loaded.privacy_state["steps"]) == 3
    assert meta["epsilon_spent"] == pytest.approx(eps)
    assert meta["privacy_delta"] == spec.privacy.delta
    # the restored accountant keeps spending from where it left off
    cont, m = _run_blocks(eng, data, loaded, 1, key0=3)
    assert eng.privacy.epsilon_np(cont.privacy_state) > eps


def test_pr8_checkpoint_loads_and_continues_bit_identically(tmp_path):
    """The committed pre-privacy archive (no privacy_state key — None
    leaves are never serialized) loads into today's EngineState, survives
    a re-save unchanged, and continues exactly as its re-save does: the
    append-last field contract, locked against a real artifact.  The
    comparison is fixture against its own re-save, so it holds under any
    PRNG implementation (the fixture's trajectory was drawn under an
    older default key stream)."""
    data = make_regression_problem(K=4, N=20, seed=3)
    spec = ExperimentSpec(
        optimizer=OptimizerSpec(kind="momentum"),
        participation=ParticipationSpec(kind="iid", q=0.9),
        run=RunSpec(num_agents=4, local_steps=1, step_size=0.05, blocks=5))
    eng = build(spec, data.loss_fn())
    params = jnp.zeros((4, 2))
    state = eng.init_state(params, eng.optimizer.init(params),
                           key=jax.random.PRNGKey(7))
    sampler = make_block_sampler(data, T=1, batch=2)
    # the fixture holds exactly the pre-privacy leaf set
    with np.load(FIXTURE) as z:
        assert not any(k.startswith("privacy_state") for k in z.files)
        assert any(k.startswith("params") for k in z.files)
    like = jax.tree.map(jnp.zeros_like, state)
    from_fixture, meta = load_experiment(str(FIXTURE), like)
    assert meta["step"] == 3
    assert not np.array_equal(np.asarray(from_fixture.params),
                              np.asarray(state.params))   # it trained
    fresh = str(tmp_path / "now.npz")
    save_experiment(fresh, from_fixture, spec=spec, step=3)
    from_fresh, _ = load_experiment(fresh, like)
    for a, b in zip(jax.tree.leaves(from_fixture),
                    jax.tree.leaves(from_fresh)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # ... and both continue bit-identically under the rebuilt engine
    conts = []
    for start in (from_fixture, from_fresh):
        s = start
        for i in range(3, 5):
            s, _ = eng.step(s, sampler(jax.random.PRNGKey(i)),
                            jax.random.PRNGKey(50 + i))
        conts.append(np.asarray(s.params))
    np.testing.assert_array_equal(conts[0], conts[1])


# ---------------------------------------------------------------------------
# serving: freshness-weighted consensus
# ---------------------------------------------------------------------------

def test_consensus_freshness_weights():
    K = 4
    stacked = jnp.arange(K * 3, dtype=jnp.float32).reshape(K, 3)
    x = np.asarray(stacked)
    w = np.array([0.0, 1.0, 3.0, 0.0], np.float32)
    out = consensus_from_stacked(stacked, K, weights=w)
    np.testing.assert_allclose(np.asarray(out),
                               (x[1] + 3.0 * x[2]) / 4.0, rtol=1e-6)
    # all-zero weights degrade to the uniform mean, not NaN
    out0 = consensus_from_stacked(stacked, K, weights=np.zeros(K))
    np.testing.assert_allclose(np.asarray(out0), x.mean(0), rtol=1e-6)
    with pytest.raises(ValueError, match="order statistic"):
        consensus_from_stacked(stacked, K, mix="trimmed_mean", weights=w)
    with pytest.raises(ValueError, match="shape"):
        consensus_from_stacked(stacked, K, weights=np.ones(K + 1))


def test_freshness_weights_from_async_discount():
    """The serving path weighs agents by the engine's own age-discount
    law: a fully fresh clock vector reproduces the uniform consensus."""
    data = make_regression_problem(K=4, N=20)
    spec = ExperimentSpec(
        asynchrony=AsyncSpec(enabled=True),
        run=RunSpec(num_agents=4, local_steps=1, step_size=0.05, blocks=2))
    eng = build(spec, data.loss_fn())
    ages = jnp.asarray([0.0, 2.0, 5.0, 0.0])
    w = np.asarray(eng._discount(ages))
    assert w[0] == w[3] == w.max()
    assert w[1] > w[2]                      # staler -> smaller weight
    stacked = jnp.asarray(np.random.default_rng(0).normal(size=(4, 3)),
                          jnp.float32)
    out = consensus_from_stacked(stacked, 4,
                                 weights=eng._discount(jnp.zeros(4)))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(stacked).mean(0), rtol=1e-5)


# ---------------------------------------------------------------------------
# Theorem-5 surrogate: injected variance
# ---------------------------------------------------------------------------

def test_injected_variance_helpers():
    assert dp_injected_variance(2.0, 3.0) == pytest.approx(36.0)
    assert dp_injected_variance(1.0, 0.0) == 0.0
    # randk: omega = 1/r - 1, weighted by participation and signal power
    assert compressor_injected_variance(
        "randk", ratio=0.25, signal_power=2.0, q=0.5) == pytest.approx(3.0)
    v = compressor_injected_variance("gauss", ratio=1.0, sigma=0.1,
                                     signal_power=4.0, q=1.0)
    assert v == pytest.approx(0.04)
    with pytest.raises(ValueError):
        compressor_injected_variance("topk", ratio=0.25, signal_power=1.0)


def test_theoretical_msd_injected_variance_is_linear():
    data = make_regression_problem(K=4, N=50, M=2, seed=0)
    topo = make_topology("ring", 4)
    kw = dict(A=topo.A, q=np.full(4, 0.8), mu=0.01, T=1)
    base = theoretical_msd(data.problem(), **kw)["msd"]
    m1 = theoretical_msd(data.problem(), injected_variance=0.5, **kw)["msd"]
    m2 = theoretical_msd(data.problem(), injected_variance=1.0, **kw)["msd"]
    assert base < m1 < m2
    # the injected term enters S_noise linearly at fixed operators
    np.testing.assert_allclose(m2 - base, 2.0 * (m1 - base), rtol=1e-4)
    # per-agent (K,) vectors are accepted; negatives are not
    mv = theoretical_msd(data.problem(),
                         injected_variance=np.full(4, 0.5), **kw)["msd"]
    assert mv == pytest.approx(m1, rel=1e-6)
    with pytest.raises(ValueError):
        theoretical_msd(data.problem(), injected_variance=-1.0, **kw)
