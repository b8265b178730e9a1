"""Benchmark harness — one function per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows (derived carries the
figure-specific quantity: MSD values, theory/sim ratios, orderings), and
appends every run's rows to ``benchmarks/results/BENCH_<bench>.json`` — a
machine-readable perf trajectory (git rev + timestamp per record) that CI
and humans can diff across commits.

  PYTHONPATH=src python -m benchmarks.run            # full (paper-scale)
  REPRO_BENCH_FAST=1 PYTHONPATH=src python -m benchmarks.run   # CI-scale
  PYTHONPATH=src python -m benchmarks.run bench_mix_backends   # one bench

Set ``REPRO_BENCH_OUT`` to redirect the JSON trajectory (default:
``benchmarks/results/`` next to this file); ``REPRO_BENCH_OUT=""`` disables
writing.
"""
from __future__ import annotations

import gc
import json
import os
import subprocess
import time
from datetime import datetime, timezone

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import paper_regression as paper
from repro.core import schedules
from repro.core.diffusion import DiffusionConfig, DiffusionEngine
from repro.core.msd import theoretical_msd
from repro.data.synthetic import make_block_sampler, make_regression_problem

FAST = bool(int(os.environ.get("REPRO_BENCH_FAST", "0")))

_ROWS: list[dict] = []   # collected per bench by main(), flushed to JSON


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)
    _ROWS.append({"name": name, "us_per_call": round(us, 1),
                  "derived": derived})


def _time_us(fn, *, reps: int, warm: int = 1):
    """Wall-clock a jitted thunk: ``warm`` untimed calls (compile +
    autotune), then ``reps`` timed calls blocking on the output pytree
    each time.  Returns (last output, us per call) — the pattern every
    timed bench used to hand-roll."""
    out = None
    for _ in range(warm):
        out = jax.block_until_ready(fn())
    t0 = time.time()
    for _ in range(reps):
        out = jax.block_until_ready(fn())
    return out, (time.time() - t0) / reps * 1e6


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — bench must run outside git too
        return "unknown"


def _bench_out_dir() -> str | None:
    out = os.environ.get(
        "REPRO_BENCH_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "results"))
    return out or None


def _append_bench_json(bench_name: str, rows: list[dict],
                       git_rev: str) -> None:
    """Append one record to BENCH_<name>.json (a JSON array trajectory)."""
    out_dir = _bench_out_dir()
    if out_dir is None or not rows:
        return
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{bench_name}.json")
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
            if not isinstance(history, list):
                history = []
        except (json.JSONDecodeError, OSError):
            history = []   # corrupt history: restart the trajectory
    history.append({
        "git_rev": git_rev,
        "timestamp": datetime.now(timezone.utc).isoformat(timespec="seconds"),
        "fast": FAST,
        "backend": jax.default_backend(),
        "rows": rows,
    })
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
        f.write("\n")


def _steady_msd(data, cfg, w_star, blocks, tail, reps=3):
    eng = DiffusionEngine(cfg, data.loss_fn())
    sampler = make_block_sampler(data, T=cfg.local_steps, batch=1)
    msds, t0 = [], time.time()
    for rep in range(reps):
        params = jnp.zeros((cfg.num_agents, 2))
        _, _, hist = eng.run(params, sampler, blocks, seed=rep,
                             w_star=jnp.asarray(w_star))
        msds.append(float(np.mean(hist[-tail:])))
    us = (time.time() - t0) / (reps * blocks) * 1e6
    return float(np.mean(msds)), us


def bench_fig5_msd_vs_theory():
    """Fig. 5: Algorithm 1 steady-state MSD matches Theorem 5 (eq. 77)."""
    K = 8 if FAST else paper.K
    blocks = 800 if FAST else 4000
    data = make_regression_problem(K=K, N=paper.N, M=paper.M, rho=paper.RHO,
                                   seed=0)
    rng = np.random.default_rng(1)
    q = rng.uniform(0.2, 0.95, K)        # random participation probabilities
    cfg = DiffusionConfig(num_agents=K, local_steps=paper.T,
                          step_size=paper.MU, topology="erdos",
                          participation=tuple(q))
    topo = cfg.make_topology()
    th = theoretical_msd(data.problem(), A=topo.A, q=q, mu=paper.MU,
                         T=paper.T, num_mask_samples=300)
    sim, us = _steady_msd(data, cfg, th["w_opt"], blocks, tail=blocks // 4)
    _row("fig5_msd_sim", us, f"{sim:.4e}")
    _row("fig5_msd_theory", 0.0, f"{th['msd']:.4e}")
    _row("fig5_sim_over_theory", 0.0, f"{sim / th['msd']:.3f}")


def bench_fig6_participation():
    """Fig. 6: higher activation probability -> faster + better (T = 1)."""
    K = 8 if FAST else paper.K
    blocks = 600 if FAST else 2500
    data = make_regression_problem(K=K, N=paper.N, M=paper.M, rho=paper.RHO,
                                   seed=0)
    prob = data.problem()
    out = {}
    for qv in (0.1, 0.5, 0.9):
        cfg = DiffusionConfig(num_agents=K, local_steps=1,
                              step_size=paper.MU, topology="erdos",
                              participation=qv)
        topo = cfg.make_topology()
        q = np.full(K, qv)
        w_o = prob.w_opt(q)
        sim, us = _steady_msd(data, cfg, w_o, blocks, tail=blocks // 4)
        th = theoretical_msd(prob, A=topo.A, q=q, mu=paper.MU, T=1,
                             num_mask_samples=200)["msd"]
        out[qv] = sim
        _row(f"fig6_q{qv}", us, f"sim={sim:.4e};theory={th:.4e}")
    ordered = out[0.1] > out[0.5] > out[0.9]
    _row("fig6_ordering_ok", 0.0, str(ordered))


def bench_fig7_local_updates():
    """Fig. 7: more local updates -> faster convergence, worse error."""
    K = 8 if FAST else paper.K
    blocks = 600 if FAST else 2500
    data = make_regression_problem(K=K, N=paper.N, M=paper.M, rho=paper.RHO,
                                   seed=0)
    prob = data.problem()
    w_o = prob.w_opt(None)
    out = {}
    for T in (2, 5, 10):
        cfg = DiffusionConfig(num_agents=K, local_steps=T,
                              step_size=paper.MU, topology="erdos",
                              participation=1.0)
        topo = cfg.make_topology()
        sim, us = _steady_msd(data, cfg, w_o, blocks, tail=blocks // 4)
        th = theoretical_msd(prob, A=topo.A, q=np.ones(K), mu=paper.MU, T=T,
                             num_mask_samples=64)["msd"]
        out[T] = sim
        _row(f"fig7_T{T}", us, f"sim={sim:.4e};theory={th:.4e}")
    _row("fig7_ordering_ok", 0.0, str(out[2] < out[10]))


def bench_drift_correction():
    """§III-C/D: drift under heterogeneous q, removed by mu/q_k (eq. 31)."""
    K = 8
    blocks = 800 if FAST else 2500
    # strong heterogeneity so the drifted optimum is well-separated
    data = make_regression_problem(K=K, N=100, M=2, rho=0.1, seed=0,
                                   mean_scale=1.5, noise_low=0.01,
                                   noise_high=0.05, w_star_spread=0.5)
    prob = data.problem()
    q = tuple([0.9, 0.3] * (K // 2))
    w_orig = prob.w_opt(None)
    w_drift = prob.w_opt(np.asarray(q))
    dists = {}
    for corr in (False, True):
        cfg = DiffusionConfig(num_agents=K, local_steps=1, step_size=0.01,
                              topology="ring", participation=q,
                              drift_correction=corr)  # T=1: the paper derives eq. 38 at T=1
        eng = DiffusionEngine(cfg, data.loss_fn())
        sampler = make_block_sampler(data, T=1, batch=8)
        state = eng.init_state(jnp.zeros((K, 2)))
        key = jax.random.PRNGKey(0)
        t0 = time.time()
        acc, n_acc = np.zeros(2), 0
        for i in range(blocks):
            key, kb, ks = jax.random.split(key, 3)
            state, _ = eng.step(state, sampler(kb), ks)
            if i >= blocks // 2:   # time-average the network mean
                acc += np.asarray(state.params).mean(0)
                n_acc += 1
        us = (time.time() - t0) / blocks * 1e6
        w_bar = acc / n_acc
        dists[corr] = (np.linalg.norm(w_bar - w_orig),
                       np.linalg.norm(w_bar - w_drift))
        _row(f"drift_corr={corr}", us,
             f"dist_orig={dists[corr][0]:.4f};dist_drift={dists[corr][1]:.4f}")
    ok = dists[False][1] < dists[False][0] and dists[True][0] < dists[True][1]
    _row("drift_correction_ok", 0.0, str(ok))


def bench_fedavg_msd():
    """The paper's headline theory claim: Theorem 5 gives the FIRST tight
    MSD expression for federated learning with local updates and partial
    participation (§IV + §VI).  Validate it on FedAvg directly: topology
    (1/K)11^T, T=5 local steps, Bernoulli participation."""
    K = 8
    blocks = 800 if FAST else 3000
    data = make_regression_problem(K=K, N=100, M=2, rho=0.1, seed=2)
    prob = data.problem()
    for q in (1.0, 0.6):
        cfg = DiffusionConfig(num_agents=K, local_steps=5, step_size=0.01,
                              topology="fedavg", participation=q)
        topo = cfg.make_topology()
        qv = np.full(K, q)
        th = theoretical_msd(prob, A=topo.A, q=qv, mu=0.01, T=5)
        sim, us = _steady_msd(data, cfg, th["w_opt"], blocks,
                              tail=blocks // 4)
        _row(f"fedavg_msd_q{q}", us,
             f"sim={sim:.4e};theory={th['msd']:.4e};"
             f"ratio={sim / th['msd']:.3f}")


def bench_topology_ablation():
    """Beyond-paper ablation: mixing topology vs steady-state MSD.

    Theorem 5 depends on the network only through E[A (x) A]; denser graphs
    (larger spectral gap) should give (weakly) lower MSD at equal q, T."""
    from repro.core.topology import make_topology, spectral_gap
    K = 8
    blocks = 600 if FAST else 2000
    data = make_regression_problem(K=K, N=100, M=2, rho=0.1, seed=3)
    prob = data.problem()
    qv = np.full(K, 0.7)
    out = {}
    for kind in ("ring", "grid", "fedavg"):
        cfg = DiffusionConfig(num_agents=K, local_steps=3, step_size=0.01,
                              topology=kind, participation=0.7)
        topo = cfg.make_topology()
        th = theoretical_msd(prob, A=topo.A, q=qv, mu=0.01, T=3)["msd"]
        sim, us = _steady_msd(data, cfg, prob.w_opt(qv), blocks,
                              tail=blocks // 4, reps=2)
        gap = spectral_gap(topo.A)
        out[kind] = (gap, sim, th)
        _row(f"topology_{kind}", us,
             f"gap={gap:.3f};sim={sim:.4e};theory={th:.4e}")
    _row("topology_denser_not_worse", 0.0,
         str(out["fedavg"][2] <= out["ring"][2] * 1.05))


def bench_markov_participation():
    """Beyond-paper ablation: the paper assumes i.i.d. Bernoulli activation
    (eq. 18).  Real device availability is bursty.  We drive Algorithm 1
    with a schedules.MarkovAvailability process (same stationary probability
    q, varying correlation) and measure the steady-state MSD against the
    i.i.d. Theorem 5 value.  Expectation: positive temporal correlation
    degrades MSD (longer outages => larger excursions) while leaving the
    limit point unchanged."""
    K = 8
    q = 0.6
    blocks = 800 if FAST else 2500
    data = make_regression_problem(K=K, N=100, M=2, rho=0.1, seed=4)
    prob = data.problem()
    cfg = DiffusionConfig(num_agents=K, local_steps=3, step_size=0.01,
                          topology="ring", participation=q)
    topo = cfg.make_topology()
    qv = np.full(K, q)
    th = theoretical_msd(prob, A=topo.A, q=qv, mu=0.01, T=3)["msd"]
    w_o = jnp.asarray(prob.w_opt(qv))
    sampler = make_block_sampler(data, T=3, batch=1)
    from repro.core.diffusion import network_msd

    for corr in (0.0, 0.5, 0.9):
        process = schedules.MarkovAvailability(q, corr, num_agents=K)
        eng = DiffusionEngine(cfg, data.loss_fn(), participation=process)
        state = eng.init_state(jnp.zeros((K, 2)),
                               key=jax.random.PRNGKey(1))
        # warm the jit cache (fresh engine per corr = fresh static-arg entry)
        # outside the timed region; discard the outputs
        eng.step(state, sampler(jax.random.PRNGKey(8)),
                 jax.random.PRNGKey(9))
        t0 = time.time()
        msds = []
        key = jax.random.PRNGKey(0)
        for i in range(blocks):
            key, kb, ks = jax.random.split(key, 3)
            state, _ = eng.step(state, sampler(kb), ks)
            if i >= blocks * 3 // 4:
                msds.append(float(network_msd(state.params, w_o)))
        us = (time.time() - t0) / blocks * 1e6
        _row(f"markov_corr{corr}", us,
             f"sim={np.mean(msds):.4e};iid_theory={th:.4e};"
             f"ratio={np.mean(msds) / th:.2f}")


def bench_exact_diffusion():
    """Beyond-paper: exact diffusion (the paper's ref. [39]) hosted in the
    same framework.  Under strong data heterogeneity and FULL participation
    (T=1), bias correction should land the network mean closer to the true
    optimum than standard diffusion at equal step size."""
    from repro.core.variants import ExactDiffusionEngine, vanilla_diffusion
    K = 8
    blocks = 800 if FAST else 2500
    data = make_regression_problem(K=K, N=100, M=2, rho=0.1, seed=5,
                                   mean_scale=1.5, noise_low=0.01,
                                   noise_high=0.05, w_star_spread=0.5)
    prob = data.problem()
    w_o = prob.w_opt(None)
    spec = vanilla_diffusion(K, mu=0.01, topology="ring")
    cfg = spec.to_diffusion_config()
    sampler = make_block_sampler(data, T=1, batch=8)

    eng_std = DiffusionEngine(cfg, data.loss_fn())
    state = eng_std.init_state(jnp.zeros((K, 2)))
    key = jax.random.PRNGKey(0)
    import time as _t
    t0 = _t.time()
    acc_s = np.zeros(2); n = 0
    for i in range(blocks):
        key, kb, ks = jax.random.split(key, 3)
        state, _ = eng_std.step(state, sampler(kb), ks)
        if i >= blocks // 2:
            acc_s += np.asarray(state.params).mean(0); n += 1
    us = (_t.time() - t0) / blocks * 1e6
    d_std = np.linalg.norm(acc_s / n - w_o)
    _row("exact_diff_baseline", us, f"dist_to_wopt={d_std:.5f}")

    eng_ed = ExactDiffusionEngine(cfg, data.loss_fn())
    w = jnp.zeros((K, 2))
    psi = w
    key = jax.random.PRNGKey(0)
    t0 = _t.time()
    acc_e = np.zeros(2); n = 0
    for i in range(blocks):
        key, kb = jax.random.split(key)
        batch = jax.tree.map(lambda x: x[0], sampler(kb))
        w, psi = eng_ed._jit_step(w, psi, batch)
        if i >= blocks // 2:
            acc_e += np.asarray(w).mean(0); n += 1
    us = (_t.time() - t0) / blocks * 1e6
    d_ed = np.linalg.norm(acc_e / n - w_o)
    _row("exact_diff_corrected", us, f"dist_to_wopt={d_ed:.5f}")
    _row("exact_diff_improves", 0.0, str(d_ed <= d_std * 1.05))


def bench_transient_curve():
    """Beyond-paper: full learning-curve prediction from the Theorem-5
    operators (transient extension of the steady-state MSD); reports
    theory/sim at several points along the trajectory (Fig. 5's curve,
    not just its floor)."""
    from repro.core.msd import theoretical_curve
    K, T, mu = 8, 5, 0.01
    blocks = 600 if FAST else 1500
    data = make_regression_problem(K=K, N=100, M=2, rho=0.1, seed=0)
    q = np.full(K, 0.6)
    cfg = DiffusionConfig(num_agents=K, local_steps=T, step_size=mu,
                          topology="ring", participation=0.6)
    topo = cfg.make_topology()
    th = theoretical_msd(data.problem(), A=topo.A, q=q, mu=mu, T=T)
    curve = theoretical_curve(th, np.zeros(2), blocks)
    eng = DiffusionEngine(cfg, data.loss_fn())
    sampler = make_block_sampler(data, T=T, batch=1)
    hists = []
    t0 = time.time()
    reps = 4 if FAST else 8
    for rep in range(reps):
        p = jnp.zeros((K, 2))
        _, _, h = eng.run(p, sampler, blocks, seed=rep,
                          w_star=jnp.asarray(th["w_opt"]))
        hists.append(h)
    us = (time.time() - t0) / (reps * blocks) * 1e6
    sim = np.mean(hists, axis=0)
    pts = [1, 20, 100, blocks - 1]
    deriv = ";".join(f"i{i}:sim={sim[i-1] if i else sim[0]:.3e}/th={curve[i]:.3e}"
                     for i in pts)
    _row("transient_curve", us, deriv)


def bench_mix_backends():
    """Mixer-backend head-to-head (EXPERIMENTS.md §Perf): the SAME block
    step — transformer smoke model, T local updates, eq.-20 combination —
    with only the combination backend swapped via core.mixing.make_mixer
    (dense all-gather einsum vs sparse circulant permute vs fused Pallas
    kernel).  Reports per-backend block-step wall-clock and the max
    divergence from the dense baseline."""
    from repro.configs import get_config
    from repro.core.sharded import make_block_step
    from repro.data.synthetic import lm_token_batch
    from repro.models import transformer as tf

    K, T, batch, seq = 4, 1, 2, 32
    cfg = get_config("smollm_360m").smoke
    dcfg = DiffusionConfig(num_agents=K, local_steps=T, step_size=1e-2,
                           topology="ring", participation=0.9)
    topo = dcfg.make_topology()

    def loss_fn(p, b, rng):
        return tf.train_loss(p, cfg, b, remat=False)

    params = jax.vmap(lambda k: tf.init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), K))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    data = lm_token_batch(jax.random.PRNGKey(1), (T, K, batch, seq),
                          cfg.vocab_size)
    key = jax.random.PRNGKey(2)
    reps = 2 if FAST else 5

    flat = {}
    for name in ("dense", "sparse", "pallas"):
        block_step = make_block_step(loss_fn, dcfg, mix=name,
                                     topology=topo, tile_m=2048)
        step = jax.jit(block_step)
        st0 = block_step.init_state(params)
        (st, _), us = _time_us(lambda: step(st0, data, key), reps=reps)
        flat[name] = np.concatenate(
            [np.asarray(l, np.float32).reshape(K, -1)
             for l in jax.tree.leaves(st.params)], axis=1)
        _row(f"mix_backend_{name}", us, f"K={K};params={n_params}")
    err_s = float(np.abs(flat["sparse"] - flat["dense"]).max())
    err_p = float(np.abs(flat["pallas"] - flat["dense"]).max())
    _row("mix_backend_agree", 0.0,
         f"sparse_maxerr={err_s:.2e};pallas_maxerr={err_p:.2e};"
         f"ok={err_s < 1e-5 and err_p < 1e-5}")


def bench_compression():
    """Compressed-communication shoot-out (EXPERIMENTS.md §Compression).

    Three measurements per scheme (dense f32 / int8 / top-k / rand-k):
    (1) bytes-on-wire per combination step on the transformer smoke param
    pytree (payload accounting, see core/compression.py) — int8 must be
    >= 4x and top-k(0.1) >= 10x below dense; (2) block-step wall clock with
    the compressor in the jitted step; (3) steady-state MSD on a 20-dim
    regression problem (int8 runs direct mode with error feedback, the
    sparsifiers the CHOCO-style diff mode), showing the accuracy cost of
    each scheme at its bytes budget stays bounded."""
    from repro.configs import get_config
    from repro.core import compression as comp
    from repro.core.sharded import make_block_step
    from repro.data.synthetic import lm_token_batch
    from repro.models import transformer as tf

    K, T, batch, seq = 4, 1, 2, 32
    cfg = get_config("smollm_360m").smoke
    dcfg = DiffusionConfig(num_agents=K, local_steps=T, step_size=1e-2,
                           topology="ring", participation=0.9)
    topo = dcfg.make_topology()

    def loss_fn(p, b, rng):
        return tf.train_loss(p, cfg, b, remat=False)

    params = jax.vmap(lambda k: tf.init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), K))
    data = lm_token_batch(jax.random.PRNGKey(1), (T, K, batch, seq),
                          cfg.vocab_size)
    key = jax.random.PRNGKey(2)
    reps = 2 if FAST else 5

    schemes = (
        ("dense_f32", "none", 1.0, False),
        ("int8", "int8", 1.0, True),
        ("topk0.1", "topk", 0.1, False),
        ("randk0.1", "randk", 0.1, False),
    )
    dense_bytes = comp.dense_wire_bytes(params)
    ratios = {}
    for label, name, ratio, ef in schemes:
        step = make_block_step(loss_fn, dcfg, mix="dense", topology=topo,
                               compress=name, compress_ratio=ratio,
                               error_feedback=ef)
        wire = step.pipeline.wire_bytes(params)
        ratios[label] = dense_bytes / max(wire, 1)
        jit_step = jax.jit(step)
        st0 = step.init_state(params)
        _, us = _time_us(lambda: jit_step(st0, data, key), reps=reps)
        _row(f"compress_{label}", us,
             f"wire_bytes={wire};reduction={ratios[label]:.2f}x;"
             f"mode={step.pipeline.mode}")
    _row("compress_bytes_ok", 0.0,
         f"int8={ratios['int8']:.2f}x;topk={ratios['topk0.1']:.2f}x;"
         f"ok={ratios['int8'] >= 4.0 and ratios['topk0.1'] >= 10.0}")

    # accuracy at the bytes budget: regression steady-state MSD (20 dims so
    # ratio-0.1 sparsification is meaningful: 2 of 20 coords per exchange)
    Kr, Mr = 8, 20
    blocks = 600 if FAST else 2000
    rdata = make_regression_problem(K=Kr, N=100, M=Mr, rho=0.1, seed=6)
    prob = rdata.problem()
    qv = np.full(Kr, 0.8)
    w_o = prob.w_opt(qv)
    sampler = make_block_sampler(rdata, T=2, batch=1)
    msd_schemes = schemes[:3] + (("randk0.25", "randk", 0.25, False),)
    msds = {}
    for label, name, ratio, ef in msd_schemes:
        rcfg = DiffusionConfig(num_agents=Kr, local_steps=2, step_size=0.01,
                               topology="ring", participation=0.8,
                               compress=name, compress_ratio=ratio,
                               error_feedback=ef)
        eng = DiffusionEngine(rcfg, rdata.loss_fn())
        p0 = jnp.zeros((Kr, Mr))
        t0 = time.time()
        _, _, hist = eng.run(p0, sampler, blocks, seed=0,
                             w_star=jnp.asarray(w_o))
        us = (time.time() - t0) / blocks * 1e6
        msds[label] = float(np.mean(hist[-blocks // 4:]))
        _row(f"compress_msd_{label}", us,
             f"msd={msds[label]:.4e};mode={eng.pipeline.mode};"
             f"gamma={eng.pipeline.gamma}")
    degr = max(msds[l] / msds["dense_f32"] for l in msds)
    _row("compress_msd_bounded", 0.0,
         f"max_degradation={degr:.2f}x;ok={degr < 10.0}")


def bench_graph_process():
    """Time-varying-topology shoot-out (EXPERIMENTS.md §Dynamic topologies).

    (1) The SAME Algorithm-1 regression run with only the GraphProcess
    swapped — static ring / link-dropout 0.3 / link-dropout 0.3 corr 0.6 /
    gossip matching — reporting per-block wall clock and steady-state MSD
    (the dynamic graphs mix less per block, so their MSD floor is higher
    but must stay bounded: the link-dropout acceptance gate).
    (2) Adaptive consensus gamma: the compressed_diffusion preset with the
    fixed heuristic (gamma=None -> 0.5 top-k) vs comm_gamma="auto"
    (spectral-gap floor + observed-contraction anneal) — auto must not be
    worse.
    (3) The vectorized metropolis_weights / is_primitive at K=256 (the
    per-block reweighting cost of every dynamic graph)."""
    from repro.api import build
    from repro.core import variants
    from repro.core.diffusion import network_msd
    from repro.core.topology import (erdos_renyi_adjacency,
                                     is_doubly_stochastic, is_primitive,
                                     metropolis_weights)

    K = 8
    blocks = 600 if FAST else 2000
    data = make_regression_problem(K=K, N=100, M=2, rho=0.1, seed=7)
    prob = data.problem()
    qv = np.full(K, 0.9)
    w_o = jnp.asarray(prob.w_opt(qv))
    sampler = make_block_sampler(data, T=2, batch=1)

    graphs = (
        ("static", "static", ()),
        ("link_drop0.3", "link_dropout", (("corr", 0.0), ("drop", 0.3))),
        ("link_drop0.3c0.6", "link_dropout",
         (("corr", 0.6), ("drop", 0.3))),
        ("gossip", "gossip", ()),
    )
    msds = {}
    for label, kind, kwargs in graphs:
        cfg = DiffusionConfig(num_agents=K, local_steps=2, step_size=0.01,
                              topology="ring", participation=0.9,
                              graph=kind, graph_kwargs=kwargs)
        eng = DiffusionEngine(cfg, data.loss_fn())
        state = eng.init_state(jnp.zeros((K, 2)),
                               key=jax.random.PRNGKey(1))
        # warm the jit cache outside the timed region
        eng.step(state, sampler(jax.random.PRNGKey(8)),
                 jax.random.PRNGKey(9))
        key = jax.random.PRNGKey(0)
        hist = []
        t0 = time.time()
        for i in range(blocks):
            key, kb, ks = jax.random.split(key, 3)
            state, _ = eng.step(state, sampler(kb), ks)
            if i >= blocks * 3 // 4:
                hist.append(float(network_msd(state.params, w_o)))
        us = (time.time() - t0) / blocks * 1e6
        msds[label] = float(np.mean(hist))
        _row(f"graph_{label}", us, f"msd={msds[label]:.4e}")
    # acceptance gate: link dropout at 0.3 on a ring converges with
    # bounded MSD (vs both its own start and the static floor)
    bounded = msds["link_drop0.3"] < 20.0 * msds["static"]
    _row("graph_linkdrop_msd_bounded", 0.0,
         f"degradation={msds['link_drop0.3'] / msds['static']:.2f}x;"
         f"ok={bounded}")

    # dynamic-graph Theorem 5: the closed form evaluated over the LAW of
    # the realized matrix (exact 2^E link-mask enumeration for
    # link_dropout, deduplicated MC atoms for gossip — core/msd.py
    # graph_matrix_law) must predict each simulated steady state.  The
    # static law is off by the full mixing deficit (~25% at drop 0.3), so
    # this is the acceptance gate that the generalization is real.
    from repro.core.graphs import make_graph_process
    from repro.core.topology import make_topology
    topo = make_topology("ring", K)
    # FAST trades exact 2^K activation-mask enumeration for MC masks:
    # 2^8 masks x 2^8 link masks is ~30s per label otherwise
    mask_kw = (dict(exact_threshold=0, num_mask_samples=64)
               if FAST else {})
    for label, kind, kwargs in graphs:
        g = make_graph_process(kind, topo, **dict(kwargs))
        t0 = time.time()
        th = theoretical_msd(prob, q=qv, mu=0.01, T=2, graph=g,
                             seed=0, **mask_kw)
        us = (time.time() - t0) * 1e6
        ratio = msds[label] / th["msd"]
        # corr>0 shares only the stationary marginal (block-to-block
        # independence is an approximation) and the FAST tails are short:
        # the iid labels get the tight band
        lo, hi = (0.5, 2.0) if "c0.6" not in label else (0.3, 3.0)
        _row(f"graph_theory_{label}", us,
             f"msd_theory={th['msd']:.4e};sim/theory={ratio:.3f};"
             f"ok={lo < ratio < hi}")

    # adaptive consensus gamma vs the fixed heuristic (compressed preset);
    # the annealed gamma needs the transient to decay before its
    # steady-state advantage shows, so this one keeps more blocks in FAST
    Kc, Mc = 8, 20
    cblocks = 1500 if FAST else 2500
    cdata = make_regression_problem(K=Kc, N=100, M=Mc, rho=0.1, seed=6)
    w_oc = jnp.asarray(cdata.problem().w_opt(np.full(Kc, 0.8)))
    csampler = make_block_sampler(cdata, T=2, batch=1)
    gmsd = {}
    for label, gamma in (("fixed", None), ("auto", "auto")):
        spec = variants.compressed_diffusion(Kc, mu=0.01, T=2, q=0.8,
                                             compress="topk", ratio=0.1,
                                             gamma=gamma)
        eng = build(spec, cdata.loss_fn())
        state = eng.init_state(jnp.zeros((Kc, Mc)))
        key = jax.random.PRNGKey(0)
        hist = []
        t0 = time.time()
        for i in range(cblocks):
            key, kb, ks = jax.random.split(key, 3)
            state, _ = eng.step(state, csampler(kb), ks)
            if i >= cblocks * 3 // 4:
                hist.append(float(network_msd(state.params, w_oc)))
        us = (time.time() - t0) / cblocks * 1e6
        gmsd[label] = float(np.mean(hist))
        extra = ""
        if gamma == "auto":
            extra = (f";gamma={float(eng.pipeline.annealed_gamma(state.comm_state)):.3f}"
                     f";floor={eng.pipeline.gamma_floor:.4f}")
        _row(f"gamma_{label}", us, f"msd={gmsd[label]:.4e}{extra}")
    _row("gamma_auto_beats_fixed", 0.0,
         f"auto/fixed={gmsd['auto'] / gmsd['fixed']:.3f};"
         f"ok={gmsd['auto'] <= gmsd['fixed'] * 1.02}")

    # graph-aware sparse offsets: on realized dynamic graphs an offset's
    # whole coefficient row can die (every link at that offset failed this
    # block); the skip_dead sparse path guards each roll with a segment
    # mask (lax.cond), so the realized permute count is the LIVE offset
    # count.  Demonstrate the drop under aggressive dropout on a hops-2
    # ring (untimed row: the gate is the live count, not wall clock).
    from repro.core.graphs import LinkDropout
    from repro.core.mixing import count_live_offsets
    from repro.core.participation import masked_combination
    from repro.core.topology import make_topology
    topo2 = make_topology("ring", 8, hops=2)
    proc2 = LinkDropout(topo2, drop=0.85)
    offs = topo2.neighbor_offsets_ring()
    ones8 = jnp.ones((8,), jnp.float32)
    draws = 100 if FAST else 400
    live = []
    for i in range(draws):
        A_t, _ = proc2.sample((), jax.random.fold_in(jax.random.PRNGKey(5),
                                                     i))
        live.append(int(count_live_offsets(
            masked_combination(A_t, ones8), offs)))
    mean_live = float(np.mean(live))
    _row("sparse_dead_offsets", 0.0,
         f"offsets={len(offs)};mean_live={mean_live:.2f};"
         f"permute_drop={1.0 - mean_live / len(offs):.2f};"
         f"ok={mean_live < len(offs)}")

    # vectorized Metropolis reweighting + validation at K=256 (satellite
    # timing assertion: this is the per-block cost of the dynamic graphs)
    adj = erdos_renyi_adjacency(256, 0.05, seed=1)
    metropolis_weights(adj)            # warm numpy/BLAS before timing
    t0 = time.time()
    for _ in range(10):
        A = metropolis_weights(adj)
    t_met = (time.time() - t0) / 10
    ok = is_doubly_stochastic(A)
    t0 = time.time()
    for _ in range(10):
        ok = ok and is_primitive(A)
    t_prim = (time.time() - t0) / 10
    # timing stays out of BOTH the gated us_per_call column and the ok
    # flag: sub-ms numpy work sees multi-ms scheduler spikes right after
    # the jitted runs; the correctness flag here is doubly-stochastic +
    # primitive, and the K=256 wall-clock assertion lives in
    # tests/test_topology.py where it has generous headroom
    _row("metropolis_K256", 0.0,
         f"ok={ok};us={t_met * 1e6:.0f};"
         f"is_primitive_us={t_prim * 1e6:.0f}")

    # same reweighting cost one agent-axis decade up (the bench_scale_K
    # regime); untimed for the same scheduler-noise reason as K=256
    adj = erdos_renyi_adjacency(1024, 0.01, seed=1)
    metropolis_weights(adj)
    t0 = time.time()
    for _ in range(5):
        A = metropolis_weights(adj)
    t_met = (time.time() - t0) / 5
    _row("metropolis_K1024", 0.0,
         f"ok={is_doubly_stochastic(A)};us={t_met * 1e6:.0f}")

    # hub-heavy support: Metropolis on a K=1000 Barabási–Albert graph —
    # the degree spread (hubs at O(sqrt(K) log K), leaves at m) is the
    # worst case for the max(d_l, d_k) reweighting rule; untimed for the
    # same scheduler-noise reason as above
    from repro.core.topology import scale_free_adjacency
    adj = scale_free_adjacency(1000, m=3, seed=0)
    metropolis_weights(adj)
    t0 = time.time()
    for _ in range(5):
        A = metropolis_weights(adj)
    t_met = (time.time() - t0) / 5
    deg = (adj & ~np.eye(1000, dtype=bool)).sum(axis=1)
    ok = is_doubly_stochastic(A) and is_primitive(A)
    _row("metropolis_scalefree_K1000", 0.0,
         f"ok={ok};us={t_met * 1e6:.0f};dmax={int(deg.max())};"
         f"dmin={int(deg.min())}")


def bench_byzantine():
    """Byzantine-gradient attack benchmark (EXPERIMENTS.md §Robust
    aggregation).

    K = 12, heterogeneous regression, 3 sign-flip adversaries evenly
    spaced on a ring (at most one per closed neighborhood).  Measured:
    steady-state MSD of the HONEST agents for

    * the clean network under the neighborhood trimmed mean (reference),
    * the attacked network under the per-neighborhood trimmed mean on the
      ring and on a 3x4 grid (graph-aware adversary placement) — must stay
      within a bounded factor of clean,
    * the attacked network under the GLOBAL trimmed mean on the ring
      (trim = 1 < 3 adversaries: the SLSGD server setting leaks) and under
      the linear fedavg mean — both degrade, by design.

    The acceptance gate row checks nbr/clean bounded AND global >> nbr.
    A run that diverges (non-finite MSD) counts as degraded.
    """
    from repro.api import build
    from repro.api.spec import AttackSpec, MixerSpec, TopologySpec
    from repro.core import variants
    from repro.core.attacks import byzantine_indices

    K = 12
    blocks = 400 if FAST else 1200
    data = make_regression_problem(K=K, N=100, M=2, rho=0.1, seed=8,
                                   mean_scale=1.5, noise_low=0.01,
                                   noise_high=0.05, w_star_spread=0.5)
    w_o = np.asarray(data.problem().w_opt(None))
    sampler = make_block_sampler(data, T=1, batch=2)
    ring_byz = byzantine_indices(K, 3)                    # (0, 4, 8)
    grid_byz = (0, 7, 9)   # 3x4 grid: pairwise distance >= 3 — at most
    #                        one adversary per closed grid neighborhood

    def run(label, spec, byz):
        honest = [k for k in range(K) if k not in byz]
        eng = build(spec, data.loss_fn())
        p0 = jnp.zeros((K, 2))
        state = eng.init_state(p0, eng.optimizer.init(p0))
        key = jax.random.PRNGKey(0)
        hist, diverged, steps = [], False, 0
        t0 = time.time()
        for i in range(blocks):
            key, kb, ks = jax.random.split(key, 3)
            state, _ = eng.step(state, sampler(kb), ks)
            steps = i + 1
            if i % 50 == 0 or i >= blocks * 3 // 4:
                p = np.asarray(state.params, np.float64)
                msd = float(np.mean(np.sum((p[honest] - w_o) ** 2, axis=1)))
                if not np.isfinite(msd) or msd > 1e12:
                    diverged = True
                    break
                if i >= blocks * 3 // 4:
                    hist.append(msd)
        # per-iteration wall clock over the iterations actually executed
        # (a diverged run breaks early; dividing by `blocks` would feed a
        # truncation-dependent number into the --check gate)
        us = (time.time() - t0) / max(steps, 1) * 1e6
        m = float("inf") if diverged or not hist else float(np.mean(hist))
        _row(f"byz_{label}", us,
             f"honest_msd={m:.4e};diverged={diverged}")
        return m

    base = variants.byzantine_robust_diffusion(K, mu=0.05, num_byzantine=3,
                                               scale=3.0)
    clean = run("clean_ring_nbr_trim",
                base.replace(attack=AttackSpec(kind="none")), ring_byz)
    nbr = run("attack_ring_nbr_trim", base, ring_byz)
    grid = run("attack_grid_nbr_trim",
               base.replace(topology=TopologySpec(kind="grid",
                                                  kwargs=(("rows", 3),)),
                            attack=AttackSpec(kind="sign_flip",
                                              scale=3.0,
                                              agents=grid_byz)),
               grid_byz)
    glb = run("attack_ring_global_trim",
              base.replace(mixer=MixerSpec(kind="trimmed_mean", trim=1,
                                           scope="global")), ring_byz)
    fed = run("attack_fedavg_mean",
              base.replace(mixer=MixerSpec(kind="dense"),
                           topology=TopologySpec(kind="fedavg")), ring_byz)

    # acceptance gate: neighborhood scope bounded under attack on BOTH
    # graphs, global-scope-on-ring and the linear mean degraded (>= 10x
    # the neighborhood MSD, or outright divergence)
    bounded = nbr < 25.0 * clean and grid < 25.0 * clean
    degraded = (not glb < 10.0 * nbr) and (not fed < 10.0 * nbr)
    _row("byzantine_gate", 0.0,
         f"nbr/clean={nbr / clean:.2f};grid/clean={grid / clean:.2f};"
         f"global/nbr={glb / nbr:.1f};fedavg/nbr={fed / nbr:.1f};"
         f"ok={bounded and degraded}")


def bench_scale_K():
    """Agent-axis scaling sweep (EXPERIMENTS.md §Scaling the agent axis).

    The same combination step on a bounded-degree ring (dmax=2) at
    K = 64 / 256 / 1024, per backend:

    * linear — dense (K, K) einsum vs sparse circulant permute vs the
      bounded-degree neighbor gather (O(K*dmax*M));
    * neighborhood-robust — the all-slots masked sort (O(K^2 * M log K);
      NOT run at K=1024, where its vmapped (K, K, M) intermediate is the
      memory blowup this PR removes) vs the dmax gather-table path
      (O(K*dmax*M log dmax)).

    Gates: (1) gather parity vs dense at EVERY K (linear allclose; robust
    allclose where the all-slots baseline runs); (2) the scale acceptance —
    robust-gather us/agent at K=1024 within 3x of its K=64 value (per-agent
    cost is a function of dmax, not K)."""
    from repro.core.mixing import make_mixer
    from repro.core.topology import make_topology

    reps = 3 if FAST else 10
    key = jax.random.PRNGKey(0)
    per_agent = {}

    def timed(mixer, W, m, A):
        jf = jax.jit(lambda W_, m_, A_, mx=mixer: mx(W_, m_, A_))
        return _time_us(lambda: jf(W, m, A), reps=reps)

    for K in (64, 256, 1024):
        topo = make_topology("ring", K)
        A = jnp.asarray(topo.A, jnp.float32)
        kw, km = jax.random.split(jax.random.fold_in(key, K))
        W = {"w": jax.random.normal(kw, (K, 1024)),
             "b": jax.random.normal(kw, (K, 64))}
        m = (jax.random.uniform(km, (K,)) < 0.8).astype(jnp.float32)
        D = topo.max_degree + 1

        outs = {}
        for name in ("dense", "sparse", "gather"):
            outs[name], us = timed(make_mixer(name, topo), W, m, A)
            _row(f"scaleK_{name}_K{K}", us,
                 f"K={K};dmax={topo.max_degree};us_per_agent={us / K:.2f}")
        err_g = max(float(jnp.abs(a - b).max())
                    for a, b in zip(jax.tree.leaves(outs["gather"]),
                                    jax.tree.leaves(outs["dense"])))

        robust = {}
        for label, gather in (("allslots", "off"), ("gathertab", "table")):
            if label == "allslots" and K >= 1024:
                # the all-slots sort materializes a vmapped (K, K, M)
                # f32 intermediate (~4.5 GB here) — the O(K^2) wall this
                # sweep exists to demonstrate; row kept untimed so the
                # --check gate never keys on it
                _row(f"scaleK_robust_{label}_K{K}", 0.0,
                     f"K={K};skipped=KxKxM_intermediate")
                continue
            mixer = make_mixer("trimmed_mean", topo, trim=1,
                               scope="neighborhood", gather=gather)
            robust[label], us = timed(mixer, W, m, A)
            per_agent[(label, K)] = us / K
            _row(f"scaleK_robust_{label}_K{K}", us,
                 f"K={K};dmax={topo.max_degree};us_per_agent={us / K:.2f}")
        err_r = (max(float(jnp.abs(a - b).max())
                     for a, b in zip(jax.tree.leaves(robust["gathertab"]),
                                     jax.tree.leaves(robust["allslots"])))
                 if "allslots" in robust else float("nan"))
        _row(f"scaleK_parity_K{K}", 0.0,
             f"gather_maxerr={err_g:.2e};robust_maxerr={err_r:.2e};"
             f"ok={err_g < 1e-5 and not err_r > 1e-5}")

    # acceptance: bounded-degree per-agent cost stays ~flat over the sweep
    ratio = per_agent[("gathertab", 1024)] / per_agent[("gathertab", 64)]
    _row("scaleK_flat_us_per_agent", 0.0,
         f"K64={per_agent[('gathertab', 64)]:.2f};"
         f"K1024={per_agent[('gathertab', 1024)]:.2f};"
         f"ratio={ratio:.2f};ok={ratio < 3.0}")


def bench_serve():
    """Serving-path benchmark (EXPERIMENTS.md §Serving).

    (1) tokens/s and p50/p99 per-token latency for the per-token py loop
    vs the fused lax.scan decode loop at several (batch, prompt, decode)
    shapes on the smollm smoke config — plus the greedy token-parity and
    the >= 3x fused-over-py acceptance gate at batch 4 / decode 64 (the
    py loop pays one dispatch + host sync per token; the fused loop pays
    one per generation).
    (2) f32 vs int8 consensus extraction on a K-stacked transformer:
    wall clock and the consensus MSD the quantized collapse costs.
    (3) Swap-under-load: the continuous ServeLoop with a param swap
    published after every tick (>= 8 swaps mid-decode), every emitted
    token replayed against its recorded checkpoint generation — the
    no-torn-update gate of the double-buffered ParamStore."""
    from repro.configs import get_config
    from repro.core.serving import consensus_from_stacked
    from repro.launch.serving import Request, ServeLoop, replay_completion
    from repro.models import transformer as tf

    cfg = get_config("smollm_360m").smoke
    params = tf.init_params(jax.random.PRNGKey(0), cfg)

    shapes = (((1, 32, 32), (4, 32, 64)) if FAST
              else ((1, 32, 32), (4, 32, 64), (8, 64, 64)))
    speedup = {}
    parity = []
    for B, P, D in shapes:
        prompts = jax.random.randint(jax.random.fold_in(
            jax.random.PRNGKey(1), B * P), (B, P), 0, cfg.vocab_size)
        max_len = P + D
        prefill = jax.jit(
            lambda p, t, ml=max_len: tf.prefill(p, cfg, t, max_len=ml))
        logits, cache = prefill(params, prompts)
        logits = jax.block_until_ready(logits[:, -1])

        # py loop: one dispatch + host sync per token; per-token latency
        # is measured directly (the p50/p99 a caller would see)
        decode1 = jax.jit(lambda p, c, t: tf.decode_step(p, cfg, c, t))

        def py_generate(lg=logits, c=cache):
            toks, lats = [], []
            for _ in range(D):
                t0 = time.time()
                nxt = tf.sample_logits(lg, None, 0.0)
                out, c = decode1(params, c, nxt[:, None])
                lg = jax.block_until_ready(out[:, 0])
                # the device->host token fetch is part of what a caller
                # waits for per token — it belongs inside the timed window
                toks.append(np.asarray(nxt))
                lats.append(time.time() - t0)
            return np.stack(toks, axis=1), lats

        # wall clock on a loaded box is noisy; both loops are measured as
        # the MEDIAN of `runs` full generations so one slow/fast outlier
        # on either side cannot swing the speedup gate
        runs = 3 if FAST else 5
        gc.collect()                                 # no GC pauses mid-timing
        py_generate()                                # compile + warm
        py_runs = sorted((py_generate() for _ in range(runs)),
                         key=lambda r: sum(r[1]))
        py_toks, lats = py_runs[runs // 2]
        t_py = sum(lats)
        p50, p99 = np.percentile(np.asarray(lats) * 1e6, [50, 99])
        _row(f"serve_py_B{B}_P{P}_D{D}", t_py / D * 1e6,
             f"tok_s={B * D / t_py:.1f};p50_us={p50:.0f};p99_us={p99:.0f}")

        # fused loop: the whole generation is one dispatch; every token
        # shares the dispatch, so per-token p50 == p99 == total/D.  The
        # params are CLOSED OVER, not passed as an argument — a serve
        # process holds one checkpoint for its lifetime, and weights that
        # are jit constants let XLA fold/pre-layout them (measured ~1.6x
        # per token on CPU vs argument weights; see EXPERIMENTS.md)
        fused = jax.jit(lambda c, lg, d=D: tf.decode_loop(
            params, cfg, c, lg, None, d, temperature=0.0))
        gc.collect()
        for _ in range(2):                           # compile + settle
            ftoks = np.asarray(fused(cache, logits)[0])
        f_reps = runs + 2
        f_ts = []
        for _ in range(f_reps):
            t0 = time.time()
            np.asarray(fused(cache, logits)[0])
            f_ts.append(time.time() - t0)
        us_total = sorted(f_ts)[f_reps // 2] * 1e6
        us_tok = us_total / D
        _row(f"serve_fused_B{B}_P{P}_D{D}", us_tok,
             f"tok_s={B * D / (us_total / 1e6):.1f};p50_us={us_tok:.0f};"
             f"p99_us={us_tok:.0f}")
        speedup[(B, P, D)] = t_py * 1e6 / us_total
        parity.append(bool(np.array_equal(py_toks, np.asarray(ftoks))))

    # acceptance gates: greedy bit-parity at every shape; fused >= 3x
    # tokens/s over the py loop at batch 4 / decode 64
    _row("serve_loop_parity", 0.0,
         f"shapes={len(parity)};ok={all(parity)}")
    s = speedup[(4, 32, 64)]
    _row("serve_fused_speedup", 0.0,
         f"B4_P32_D64={s:.2f}x;ok={s >= 3.0}")

    # f32 vs int8 consensus extraction: K-stacked smoke transformer
    K = 4 if FAST else 8
    stacked = jax.vmap(lambda k: tf.init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(2), K))
    reps = 2 if FAST else 5
    c_f32, us_f = _time_us(
        lambda: consensus_from_stacked(stacked, K), reps=reps)
    _row("serve_consensus_f32", us_f, f"K={K}")
    c_i8, us_i = _time_us(
        lambda: consensus_from_stacked(stacked, K, quantize="int8"),
        reps=reps)
    sq_err = sq_ref = 0.0
    for a, b in zip(jax.tree.leaves(c_f32), jax.tree.leaves(c_i8)):
        a = np.asarray(a, np.float64)
        sq_err += float(np.sum((a - np.asarray(b, np.float64)) ** 2))
        sq_ref += float(np.sum(a ** 2))
    rel = sq_err / max(sq_ref, 1e-30)
    _row("serve_consensus_int8", us_i,
         f"K={K};msd_vs_f32={sq_err:.3e};rel={rel:.3e};ok={rel < 1e-3}")

    # swap-under-load: publish a new generation after EVERY tick while
    # the slot-batched loop decodes; replay each completion against its
    # recorded generation schedule (untimed correctness row)
    loop = ServeLoop(cfg, params, slots=2, max_len=48, chunk=2)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, max_new_tokens=12,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=(8 + i,)).astype(np.int32))
            for i in range(4)]
    for r in reqs:
        loop.submit(r)
    params_by_gen, done = {0: params}, []
    while loop._queue or loop.active:
        done.extend(loop.step())
        g = loop.store.generation + 1
        newp = jax.tree.map(lambda x, s=g: x * (1.0 + 0.02 * s), params)
        params_by_gen[loop.store.swap(newp)] = newp
    swaps = loop.store.generation
    try:
        spans = [replay_completion(cfg, params_by_gen, c, max_len=48)
                 for c in done]
        torn = False
    except AssertionError:
        spans, torn = [], True
    ok = (not torn and swaps >= 8 and len(done) == len(reqs)
          and max(spans) > 1)
    _row("serve_swap_under_load", 0.0,
         f"swaps={swaps};completions={len(done)};"
         f"max_generations_spanned={max(spans) if spans else 0};"
         f"torn={torn};ok={ok}")


def bench_async():
    """Event-driven asynchrony (EXPERIMENTS.md §Asynchrony).

    Straggler economics on the same K=8 ring regression: the bulk-
    synchronous engine pays the SLOWEST agent's delay every block (the
    barrier), while the AsyncEngine advances event time at the fastest
    agent's cadence — each agent k fires with probability rate_k/max(rate)
    per tick, so every local clock advances ~min(delay) of wall time per
    tick in expectation.  Under lognormal per-agent delays (sigma = 1,
    ~10-30x spread at K = 8) the async run reaches a target MSD in less
    simulated wall-clock despite its per-tick progress penalty (partial
    firing + staleness-discounted mixing).  The acceptance row gates
    (1) the async steady state actually reaches the target band and
    (2) wall-clock-to-target beats the synchronous barrier.
    """
    from repro.api.spec import AsyncSpec
    from repro.core.async_engine import AsyncEngine
    from repro.core.diffusion import network_msd

    K = 8
    blocks = 400 if FAST else 1200
    data = make_regression_problem(K=K, N=100, M=2, rho=0.1, seed=7)
    prob = data.problem()
    qv = np.full(K, 0.9)
    w_o = jnp.asarray(prob.w_opt(qv))
    sampler = make_block_sampler(data, T=2, batch=1)
    cfg = DiffusionConfig(num_agents=K, local_steps=2, step_size=0.01,
                          topology="ring", participation=0.9)
    aspec = AsyncSpec(enabled=True, rate_dist="lognormal", rate_sigma=1.0,
                      rate_seed=0, tau_max=16, discount="exp",
                      discount_rate=0.1)

    def run_hist(eng, want_wall):
        state = eng.init_state(jnp.zeros((K, 2)),
                               key=jax.random.PRNGKey(1))
        step = jax.jit(eng.step)
        state, _ = step(state, sampler(jax.random.PRNGKey(8)),
                        jax.random.PRNGKey(9))   # warm outside the clock
        state = eng.init_state(jnp.zeros((K, 2)),
                               key=jax.random.PRNGKey(1))
        key = jax.random.PRNGKey(0)
        hist, walls = [], []
        t0 = time.time()
        for _ in range(blocks):
            key, kb, ks = jax.random.split(key, 3)
            state, metrics = step(state, sampler(kb), ks)
            hist.append(float(network_msd(state.params, w_o)))
            if want_wall:
                walls.append(float(metrics["t_wall"]))
        us = (time.time() - t0) / blocks * 1e6
        return np.asarray(hist), walls, us

    def first_crossing(hist, target, window=15):
        sm = np.convolve(hist, np.ones(window) / window, mode="valid")
        below = np.nonzero(sm < target)[0]
        return int(below[0]) + window - 1 if below.size else None

    sync_eng = DiffusionEngine(cfg, data.loss_fn())
    sync_hist, _, us_sync = run_hist(sync_eng, want_wall=False)
    sync_steady = float(np.mean(sync_hist[-blocks // 4:]))
    _row("async_sync_block", us_sync, f"msd={sync_steady:.4e}")

    async_eng = AsyncEngine(cfg, data.loss_fn(), async_spec=aspec)
    delays = np.asarray(async_eng.delays, np.float64)
    async_hist, walls, us_async = run_hist(async_eng, want_wall=True)
    async_steady = float(np.mean(async_hist[-blocks // 4:]))
    _row("async_event_block", us_async,
         f"msd={async_steady:.4e};t_wall={walls[-1]:.1f}s;"
         f"delay_spread={delays.max() / delays.min():.1f}x")

    # target: well below the start, above both steady states
    target = 2.0 * max(sync_steady, async_steady)
    i_sync = first_crossing(sync_hist, target)
    i_async = first_crossing(async_hist, target)
    # the synchronous barrier: every block costs the slowest delay
    sync_wall = ((i_sync + 1) * float(delays.max())
                 if i_sync is not None else float("inf"))
    async_wall = walls[i_async] if i_async is not None else float("inf")
    speedup = sync_wall / async_wall if async_wall > 0 else 0.0
    ok = (i_sync is not None and i_async is not None
          and async_steady < target and speedup > 1.0)
    _row("async_beats_sync_under_stragglers", 0.0,
         f"target={target:.3e};sync_wall={sync_wall:.1f}s;"
         f"async_wall={async_wall:.1f}s;speedup={speedup:.2f}x;ok={ok}")


def bench_privacy():
    """Privacy tier (EXPERIMENTS.md §Privacy).

    Two acceptance surfaces next to the compression bench's MSD-vs-bytes
    curve: (1) mask exactness — the secure-agg wire masks must cancel
    over every realized neighborhood, so the masked-wire run matches the
    unmasked run bit-close, on the static ring AND under LinkDropout
    (degraded edges re-pair per block); (2) the MSD-vs-epsilon frontier —
    for each epsilon budget the noise multiplier is calibrated by the RDP
    accountant over the run length, the steady-state MSD is measured, and
    Theorem 5 with the injected-variance law
    (:func:`repro.core.msd.dp_injected_variance`) predicts it.  Gates:
    masked == unmasked within f32 accumulation on both graphs, MSD
    decreasing in epsilon toward the non-private floor, realized
    accountant epsilon at the calibrated target, theory within a loose
    band at the noise-dominated point.
    """
    import dataclasses
    from repro.api.build import build
    from repro.api.spec import (ExperimentSpec, GraphSpec,
                                ParticipationSpec, PrivacySpec, RunSpec,
                                TopologySpec)
    from repro.core.msd import dp_injected_variance
    from repro.core.topology import make_topology

    K, M = 8, 2
    q = 0.8
    data = make_regression_problem(K=K, N=100, M=M, rho=0.1, seed=11)
    prob = data.problem()
    qv = np.full(K, q)
    w_o = prob.w_opt(qv)
    sampler = make_block_sampler(data, T=2, batch=1)
    blocks = 600 if FAST else 2000
    base = ExperimentSpec(
        topology=TopologySpec(kind="ring"),
        participation=ParticipationSpec(kind="iid", q=q),
        run=RunSpec(num_agents=K, local_steps=2, step_size=0.01,
                    blocks=blocks, seed=0))

    # -- (1) mask exactness: masked wire vs unmasked combination ---------
    # same privacy seed on both sides => identical clip+noise stream; the
    # only difference is whether the wire carries masked payloads
    p0 = jax.random.normal(jax.random.PRNGKey(3), (K, M)) * 0.5
    tol = 5e-5
    diffs = {}
    for gname, gspec in (
            ("static", GraphSpec(kind="static")),
            ("link_dropout", GraphSpec(kind="link_dropout", drop=0.3))):
        states, us_masked = [], 0.0
        for secure_agg in (True, False):
            spec = base.replace(graph=gspec, privacy=PrivacySpec(
                enabled=True, noise_multiplier=0.8, clip=1.0,
                secure_agg=secure_agg))
            eng = build(spec, data.loss_fn())
            st = eng.init_state(p0, eng.optimizer.init(p0),
                                key=jax.random.PRNGKey(5))
            jit_step = jax.jit(eng.step)
            batches = [sampler(jax.random.PRNGKey(100 + i))
                       for i in range(6)]
            if secure_agg:
                _, us_masked = _time_us(
                    lambda: jit_step(st, batches[0], jax.random.PRNGKey(0)),
                    reps=2 if FAST else 5)
            for i, bb in enumerate(batches):
                st, _ = jit_step(st, bb, jax.random.PRNGKey(200 + i))
            states.append(st)
        diffs[gname] = float(jnp.abs(states[0].params
                                     - states[1].params).max())
        _row(f"privacy_mask_{gname}", us_masked,
             f"max_abs_diff={diffs[gname]:.2e}")
    ok_mask = all(d < tol for d in diffs.values())
    _row("privacy_mask_exact", 0.0,
         f"tol={tol:g};static={diffs['static']:.2e};"
         f"link_dropout={diffs['link_dropout']:.2e};ok={ok_mask}")

    # -- (2) MSD-vs-epsilon frontier -------------------------------------
    topo = make_topology("ring", K)
    base_theory = theoretical_msd(prob, A=topo.A, q=qv, mu=0.01, T=2)["msd"]

    def steady(spec):
        eng = build(spec, data.loss_fn())
        st = eng.init_state(jnp.zeros((K, M)),
                            eng.optimizer.init(jnp.zeros((K, M))),
                            key=jax.random.PRNGKey(1))
        jit_step = jax.jit(eng.step)
        key = jax.random.PRNGKey(0)
        from repro.core.diffusion import network_msd
        hist, eps_spent, t0 = [], None, time.time()
        for _ in range(blocks):
            key, kb, ks = jax.random.split(key, 3)
            st, metrics = jit_step(st, sampler(kb), ks)
            hist.append(float(network_msd(st.params, jnp.asarray(w_o))))
            if "epsilon" in metrics:
                eps_spent = float(metrics["epsilon"])
        us = (time.time() - t0) / blocks * 1e6
        return float(np.mean(hist[-blocks // 4:])), eps_spent, us, eng

    msd_floor, _, us_floor, _ = steady(base)
    _row("privacy_msd_nonprivate", us_floor, f"msd={msd_floor:.4e}")
    eps_points = (2.0, 8.0, 32.0)
    msds, eps_hit = {}, {}
    for eps in eps_points:
        spec = base.replace(privacy=PrivacySpec(enabled=True, epsilon=eps,
                                                delta=1e-5, clip=1.0))
        msd, spent, us, eng = steady(spec)
        nm = eng.privacy.noise_multiplier
        theory = theoretical_msd(
            prob, A=topo.A, q=qv, mu=0.01, T=2,
            injected_variance=dp_injected_variance(1.0, nm))["msd"]
        msds[eps], eps_hit[eps] = msd, spent
        _row(f"privacy_msd_eps{eps:g}", us,
             f"msd={msd:.4e};noise_multiplier={nm:.3f};"
             f"eps_spent={spent:.2f};theory={theory:.4e};"
             f"ratio={msd / theory:.2f}")
        if eps == max(eps_points):
            # gate the surrogate where the injected noise dominates the
            # gradient noise but clipping is still inactive — at the
            # tightest budget the multiplier is so large the clip
            # saturates, which dp_injected_variance documents as out of
            # scope (the tightest-budget ratio stays visible in its row)
            noisy_ratio = msd / theory
    # the calibration spends the budget over exactly blocks * local_steps
    # mechanism invocations at the stationary rate; realized
    # participation wanders a little around it
    cal_ok = all(0.7 <= eps_hit[e] / e <= 1.3 for e in eps_points)
    mono_ok = (msds[2.0] > msds[8.0] > msds[32.0] > 0.5 * msd_floor)
    theory_ok = 0.25 <= noisy_ratio <= 4.0
    _row("privacy_frontier_ok", 0.0,
         f"msd_eps2={msds[2.0]:.3e};msd_eps8={msds[8.0]:.3e};"
         f"msd_eps32={msds[32.0]:.3e};floor={msd_floor:.3e};"
         f"cal_ok={cal_ok};theory_ratio={noisy_ratio:.2f};"
         f"ok={mono_ok and cal_ok and theory_ok}")


def bench_heterogeneity():
    """Statistical heterogeneity frontier (EXPERIMENTS.md §Heterogeneity).

    (1) Steady-state MSD vs Dirichlet alpha ∈ {100, 1, 0.1} on ring, grid
    and scale-free: the §VII pool (per-origin generative models via
    ``w_star_spread``) is re-dealt by :func:`partition_regression_data`,
    so shrinking alpha concentrates each agent on few origin classes and
    the eq.-17 local updates drift toward genuinely different local
    minimizers — MSD against the pooled w* must be (weakly) monotone in
    the skew on EVERY topology.
    (2) Degree-aware local updates on the hub graph at the hardest skew:
    ``T_k = max(1, round(T d_min / d_k))`` keeps the hubs (which dominate
    the Metropolis mixing) closest to consensus, so it must not lose to
    the uniform-T baseline.
    (3) The indexed block sampler is a pure function of (seed, index) —
    resume-replay must be bit-identical."""
    from repro.core.diffusion import network_msd
    from repro.data.synthetic import (make_indexed_block_sampler,
                                      partition_regression_data)

    K, T = 12, 4
    blocks = 250 if FAST else 1000
    tail = blocks // 4
    # zero additive noise isolates the alpha-dependent term: every datum
    # satisfies d = u^T w*_k exactly, so a pure-class agent has a noiseless
    # local objective with minimizer w*_k (bias), while a mixed agent's
    # "noise" is the class-disagreement residual u^T (w*_k - w_bar) — MSD
    # then tracks the local-update drift the skew creates, not the
    # measurement-noise floor it would otherwise drown in
    base = make_regression_problem(K=16, N=80, M=2, rho=0.01, seed=5,
                                   mean_scale=1.0, noise_low=0.0,
                                   noise_high=0.0, w_star_spread=1.0)
    qv = np.full(K, 0.7)

    def steady(cfg, alpha, reps=3):
        # one partition draw per rep: a single draw's drift bias depends
        # on how the local-minimizer spread aligns with the graph's mixing
        # modes, so only the seed-average is monotone in the skew
        eng = DiffusionEngine(cfg, base.loss_fn())
        msds, t0 = [], time.time()
        for rep in range(reps):
            data = partition_regression_data(base, K, kind="dirichlet",
                                             alpha=alpha, seed=7 + rep)
            # MSD against the partition's OWN network limit point (eq. 27
            # with uniform q): the pooled w* of the generator sits a
            # constant skew-independent offset away and would drown the
            # alpha signal
            w_ref = jnp.asarray(data.problem().w_opt(qv))
            # batch 4 crushes the within-agent sampling variance (the one
            # term NOT monotone in the skew: it peaks at intermediate
            # alpha, where agents hold few-class mixtures) so the
            # monotone drift-bias term dominates the MSD
            sampler = make_indexed_block_sampler(data, T=cfg.local_steps,
                                                 batch=4, seed=100 + rep)
            key = jax.random.PRNGKey(rep)
            state = eng.init_state(jnp.zeros((cfg.num_agents, 2)),
                                   key=jax.random.fold_in(key, 0x5EED))
            hist = []
            for i in range(blocks):
                key, ks = jax.random.split(key)
                state, _ = eng.step(state, sampler(i), ks)
                hist.append(float(network_msd(state.params, w_ref)))
            msds.append(float(np.mean(hist[-tail:])))
        us = (time.time() - t0) / (reps * blocks) * 1e6
        return float(np.mean(msds)), us

    alphas = (100.0, 1.0, 0.1)
    msd = {}
    for kind in ("ring", "grid", "scale_free"):
        for alpha in alphas:
            cfg = DiffusionConfig(num_agents=K, local_steps=T,
                                  step_size=0.02, topology=kind,
                                  participation=0.7)
            m, us = steady(cfg, alpha)
            msd[kind, alpha] = m
            _row(f"msd_{kind}_alpha{alpha:g}", us, f"msd={m:.4e}")
        # 2% slack: the alpha=100/alpha=1 pair can sit within sampling
        # noise of each other on dense mixers; the skewed end must not
        mono = (msd[kind, 0.1] >= msd[kind, 1.0] * 0.98
                and msd[kind, 1.0] >= msd[kind, 100.0] * 0.98)
        _row(f"msd_monotone_in_skew_{kind}", 0.0,
             f"a0.1={msd[kind, 0.1]:.3e};a1={msd[kind, 1.0]:.3e};"
             f"a100={msd[kind, 100.0]:.3e};ok={mono}")

    res = {}
    for mode in ("uniform", "degree"):
        cfg = DiffusionConfig(num_agents=K, local_steps=T, step_size=0.02,
                              topology="scale_free", participation=0.7,
                              local_steps_mode=mode)
        m, us = steady(cfg, 0.1)          # paired: same partition seeds
        res[mode] = m
        _row(f"scale_free_Tk_{mode}", us, f"msd={m:.4e}")
    ok = res["degree"] <= res["uniform"] * 1.02
    _row("degree_aware_Tk_not_worse", 0.0,
         f"degree={res['degree']:.3e};uniform={res['uniform']:.3e};ok={ok}")

    data = partition_regression_data(base, K, kind="dirichlet", alpha=0.1,
                                     seed=7)
    s1 = make_indexed_block_sampler(data, T=T, batch=2, seed=3)
    s2 = make_indexed_block_sampler(data, T=T, batch=2, seed=3)
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for i in (0, 17, 251) for a, b in zip(s1(i), s2(i)))
    _row("block_replay_bit_identical", 0.0, f"ok={same}")


ALL_BENCHES = (
    bench_fig5_msd_vs_theory,
    bench_fig6_participation,
    bench_fig7_local_updates,
    bench_drift_correction,
    bench_fedavg_msd,
    bench_topology_ablation,
    bench_markov_participation,
    bench_exact_diffusion,
    bench_transient_curve,
    bench_mix_backends,
    bench_compression,
    bench_graph_process,
    bench_byzantine,
    bench_scale_K,
    bench_serve,
    bench_async,
    bench_privacy,
    bench_heterogeneity,
)


# ---------------------------------------------------------------------------
# --check: wall-clock regression gate against the committed trajectory
# ---------------------------------------------------------------------------

# fail on > 1.5x slowdown vs the committed record; overridable for fleets
# whose runners are not perf-comparable to the machine that seeded the
# committed baseline (wall-clock gates only make sense against a baseline
# recorded on comparable hardware — reseed BENCH_*.json when runners change)
CHECK_THRESHOLD = float(os.environ.get("REPRO_BENCH_CHECK_THRESHOLD", "1.5"))
CHECK_FLOOR_US = 1000.0   # only gate rows above 1 ms (below is pure noise)


def _committed_baseline(bench_name: str) -> dict | None:
    """Last committed BENCH_<name>.json record, preferring records from the
    same speed tier (fast flag) and backend as this run."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "results", f"BENCH_{bench_name}.json")
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            history = json.load(f)
    except (json.JSONDecodeError, OSError):
        return None
    if not isinstance(history, list) or not history:
        return None
    backend = jax.default_backend()
    for match in (
        lambda r: r.get("fast") == FAST and r.get("backend") == backend,
        lambda r: r.get("backend") == backend,
        lambda r: True,
    ):
        hits = [r for r in history if match(r)]
        if hits:
            return hits[-1]
    return None


def _check_rows(bench_name: str, rows: list[dict]) -> list[str]:
    """Compare this run's us_per_call against the committed baseline.
    Returns human-readable regression descriptions (empty = pass)."""
    baseline = _committed_baseline(bench_name)
    if baseline is None:
        print(f"# check {bench_name}: no committed baseline — skipped")
        return []
    base = {r["name"]: r.get("us_per_call", 0.0)
            for r in baseline.get("rows", [])}
    regressions = []
    for r in rows:
        old = base.get(r["name"], 0.0)
        new = r.get("us_per_call", 0.0)
        if old <= 0.0 or new <= 0.0:
            continue            # untimed/derived rows
        if max(old, new) < CHECK_FLOOR_US:
            continue            # both below the noise floor
        ratio = new / old
        if ratio > CHECK_THRESHOLD:
            regressions.append(
                f"{bench_name}/{r['name']}: {old:.0f}us -> {new:.0f}us "
                f"({ratio:.2f}x > {CHECK_THRESHOLD}x; baseline "
                f"{baseline.get('git_rev')})")
    status = "FAIL" if regressions else "ok"
    print(f"# check {bench_name}: {status} "
          f"(baseline {baseline.get('git_rev')}, "
          f"{len([r for r in rows if r.get('us_per_call', 0) > 0])} timed "
          f"rows, threshold {CHECK_THRESHOLD}x)")
    return regressions


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("benches", nargs="*",
                    help="benchmark names to run (default: all); e.g. "
                         "bench_mix_backends")
    ap.add_argument("--check", action="store_true",
                    help="compare wall-clock against the last committed "
                         "benchmarks/results/BENCH_*.json record and exit "
                         f"nonzero on any > {CHECK_THRESHOLD}x regression "
                         "(the trajectory file is not appended to)")
    args = ap.parse_args(argv)
    by_name = {f.__name__: f for f in ALL_BENCHES}
    if args.benches:
        unknown = [b for b in args.benches if b not in by_name]
        if unknown:
            raise SystemExit(f"unknown benches {unknown}; "
                             f"available: {sorted(by_name)}")
        selected = [by_name[b] for b in args.benches]
    else:
        selected = list(ALL_BENCHES)
    rev = _git_rev()
    print("name,us_per_call,derived")
    regressions: list[str] = []
    for bench in selected:
        _ROWS.clear()
        bench()
        rows = list(_ROWS)
        if args.check:
            # wall-clock is noisy: measure twice, gate on the per-row
            # minimum (a genuine regression slows BOTH runs down)
            _ROWS.clear()
            bench()
            best = {r["name"]: r["us_per_call"] for r in _ROWS}
            for r in rows:
                other = best.get(r["name"], r["us_per_call"])
                if 0 < other < r["us_per_call"]:
                    r["us_per_call"] = other
            regressions += _check_rows(bench.__name__, rows)
            # acceptance gates (parity, speedup, no-torn-update, ...) are
            # reported as ok=... in the derived column; --check fails on
            # any ok=False regardless of the wall-clock baseline
            regressions += [
                f"{bench.__name__}/{r['name']}: acceptance gate failed "
                f"({r['derived']})"
                for r in rows if "ok=False" in r.get("derived", "")]
        else:
            _append_bench_json(bench.__name__, rows, rev)
    _ROWS.clear()
    if regressions:
        raise SystemExit("bench regression gate FAILED:\n  "
                         + "\n  ".join(regressions))


if __name__ == "__main__":
    main()
