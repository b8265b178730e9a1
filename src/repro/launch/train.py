"""End-to-end training driver: diffusion learning (Algorithm 1) over any
assigned architecture on the local device set.

On CPU this runs the reduced (smoke) configs; on TPU the same code path runs
at published widths.  With several devices and K a multiple of their count,
the agent axis is sharded over them (one 1-D ``data`` mesh, whole agents per
device) and the combination step becomes a collective.  The experiment is
described by ONE :class:`repro.api.ExperimentSpec`, built from the shared
CLI front end
(:mod:`repro.api.cli` — the same flag set ``dryrun`` and ``serve`` use):

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --agents 4 --local-steps 2 --blocks 20 --batch 2 --seq 64

  # the same run, declaratively:
  PYTHONPATH=src python -m repro.launch.train --spec experiment.json
  PYTHONPATH=src python -m repro.launch.train --preset compressed_fedavg \
      --agents 8 --step-size 0.01

Every flag maps onto one spec field (EXPERIMENTS.md has the migration
table): the combination backend (``--mix dense|sparse|pallas|auto|
trimmed_mean|median``), the availability model (``--participation-process
iid|markov|cyclic``), the time-varying combination graph (``--graph
static|link_dropout|gossip|tv_erdos`` + ``--link-drop``; EXPERIMENTS.md
§Dynamic topologies), and the wire compressor (``--compress
topk|randk|int8|gauss`` + ``--compress-ratio``/``--error-feedback``; with
``--mix pallas --compress int8`` the fused dequantize+mix kernel runs).
``--checkpoint`` saves the full EngineState with the spec embedded, so
``serve --checkpoint`` rebuilds the exact engine with zero flags.

Each block's host work runs in profiler spans: ``train.data`` (the block's
batch), ``train.step`` (dispatch of the jitted step), ``train.offload``,
``train.log`` (the privacy accountant's host sync and the log line) and, at
the end, ``train.checkpoint``.  ``--trace-dir DIR`` records a profiler trace
of blocks 1 onward (block 0 compiles) into DIR; the spans share the device
trace's clock, so the device's idle gaps can be put down to them.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import build, spec_from_args
from repro.api.cli import add_spec_args
from repro.checkpoint import save_experiment
from repro.core.privacy import epsilon_from_rdp_np, rdp_increment_np
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_agent_mesh, place_agents
from repro.models import transformer as tf

_span = jax.profiler.TraceAnnotation


def main():
    ap = argparse.ArgumentParser()
    add_spec_args(ap)
    ap.add_argument("--checkpoint", default=None,
                    help="save the final EngineState (+ embedded spec) here")
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--trace-dir", default=None,
                    help="record a profiler trace of blocks 1 onward here")
    args = ap.parse_args()

    spec = spec_from_args(args)
    enable_compile_cache()
    run = spec.run
    K, T = run.num_agents, run.local_steps
    sharded = args.engine == "sharded" or (args.engine == "auto"
                                           and not spec.asynchrony.enabled)
    mesh = make_agent_mesh(K) if sharded else None
    # transformer model -> sharded engine
    eng = build(spec, engine=args.engine, mesh=mesh)
    cfg = eng.model.cfg
    pipeline = getattr(eng, "pipeline", None)   # async: no CommPipeline
    is_async = spec.asynchrony.enabled

    key = jax.random.PRNGKey(run.seed)
    kp, key = jax.random.split(key)
    params = eng.init_params(kp)
    if spec.graph.kind != "static":
        g = eng.graph if hasattr(eng, "graph") else None
        print(f"graph: {spec.graph.kind} — the combination matrix is "
              f"resampled every block ({g!r}); "
              f"stateful={bool(g is not None and g.stateful)}")
    privacy = getattr(eng, "privacy", None)
    budget = (spec.privacy.epsilon
              if privacy is not None and spec.privacy.epsilon > 0 else 0.0)
    if privacy is not None:
        agg = ("secure-agg wire masks on"
               if spec.privacy.secure_agg else "wire unmasked")
        btxt = (f"budget epsilon={budget:g}" if budget
                else "no epsilon budget")
        print(f"privacy: clip={privacy.clip:g} "
              f"noise_multiplier={privacy.noise_multiplier:.4g} "
              f"delta={privacy.delta:g}  {btxt}  {agg}  "
              "(RDP accountant advances at the realized participation "
              f"rate x {privacy.steps_per_block} local steps/block; the "
              "run halts before a block projected to overshoot the "
              "budget — the checkpointed epsilon_spent is the binding "
              "guarantee)")
    if is_async:
        # straggler simulation: per-agent event delays fixed for the run
        d = eng.delays
        a = spec.asynchrony
        print(f"async: {a.rate_dist} rates "
              f"(sigma={a.rate_sigma}, seed={a.rate_seed}) — per-event "
              f"delays min={d.min():.3f}s median={float(jnp.median(jnp.asarray(d))):.3f}s "
              f"max={d.max():.3f}s; tau_max={a.tau_max} "
              f"discount={a.discount}({a.discount_rate}); a synchronous "
              f"block would pay the max every time")
    # state leaves mirror the stacked (K, ...) layout; step counter is shared
    opt_state = eng.optimizer.init(params)
    state = eng.init_state(params, opt_state,
                           key=jax.random.fold_in(key, 0x5EED))
    if mesh is not None:
        print(f"mesh: {K} agents over {mesh.size} devices "
              f"({K // mesh.size} per device); mixer {eng.pipeline.mixer.name}")
        state = place_agents(state, mesh, num_agents=K)
    if spec.compression.kind != "none":
        from repro.core.compression import dense_wire_bytes
        wire = pipeline.wire_bytes(params)
        if wire == 0:
            # K = 1 forces mix="none": no combination step, nothing moves
            print("comm: single agent — mixing disabled, compression inert")
        else:
            dense_wire = dense_wire_bytes(params)
            # pipeline.compressor reflects what actually runs (diff mode
            # unwraps the EF wrapper: the reference IS the feedback there)
            print(f"comm: {pipeline.compressor.name} "
                  f"ratio={spec.compression.ratio} "
                  f"mode={pipeline.mode} gamma={pipeline.gamma}  "
                  f"{wire / 1e6:.2f} MB/combination on the wire "
                  f"({dense_wire / wire:.1f}x below dense f32)")

    # the old state is dead once the step returns: donating it keeps one
    # copy of the K parameter stacks on the device, not two
    jit_step = jax.jit(eng.step, donate_argnums=0)

    # the data half of the loop is compiled from spec.data by build():
    # provider(block_index, key) — kind="iid" reproduces the legacy
    # key-only stream bit-for-bit, the partitioned kinds (dirichlet/
    # shards) replay any block from its index alone
    sample_block = eng.data
    if spec.data.kind != "iid":
        sizes = [len(p) for p in sample_block.partitions]
        print(f"data: {spec.data.kind} partition over {K} agents "
              f"(alpha={spec.data.alpha:g}, seed={spec.data.seed}) — "
              f"windows/agent min={min(sizes)} max={max(sizes)}; blocks "
              "are index-replayable (resume re-derives every batch)")
    if spec.run.local_steps_mode != "uniform":
        mask = eng.step_mask
        if mask is None:
            print(f"local steps: mode={spec.run.local_steps_mode} on a "
                  f"regular graph — every agent runs the full T={T}")
        else:
            t_k = np.asarray(mask.sum(axis=0), np.int64)
            print(f"local steps: degree-aware T_k in [{t_k.min()}, "
                  f"{t_k.max()}] (uniform T={T}; hubs run fewer eq.-17 "
                  "steps, freezing early inside the shared scan)")
    offload = getattr(eng, "offload", lambda s: s)
    fetch = getattr(eng, "fetch", lambda s: s)
    if getattr(eng, "ef_host_offload", False):
        from repro.core.sharded import ef_host_sharding
        host = ef_host_sharding()
        print("comm: EF residual parks in host memory between blocks"
              if host is not None else
              "comm: --ef-host-offload requested but this backend exposes "
              "no pinned_host memory space — offload is a documented no-op")

    eval_loss = jax.jit(jax.vmap(lambda p, b: tf.train_loss(p, cfg, b,
                                                            remat=False)))

    if budget:
        # one stationary-rate block of RDP: projecting the NEXT block's
        # spend from the host-side mirror of the accountant lets the halt
        # fire BEFORE the crossing block, so the checkpointed
        # epsilon_spent stays at or under the budget (realized
        # participation wanders around the stationary rate, so the
        # post-step check below still backstops an early crossing)
        q_bar = float(np.mean(spec.q_vector()))
        inc_bar = privacy.steps_per_block * rdp_increment_np(
            q_bar, privacy.noise_multiplier, privacy.orders)

    t0 = time.time()
    eps_spent = None
    host_rdp = None
    if budget:
        host_rdp = np.zeros(len(privacy.orders), np.float64)
        eps_spent = epsilon_from_rdp_np(host_rdp, privacy.delta,
                                        privacy.orders)
    blocks_done = 0
    tracing = False
    try:
        for i in range(run.blocks):
            if args.trace_dir and i == 1:
                jax.profiler.start_trace(args.trace_dir)
                tracing = True
            if budget:
                projected = epsilon_from_rdp_np(host_rdp + inc_bar,
                                                privacy.delta, privacy.orders)
                if projected > budget:
                    print(f"privacy budget: epsilon={eps_spent:.3f} spent, "
                          f"next block projects to {projected:.3f} > "
                          f"{budget:g} — halting after {blocks_done} blocks")
                    break
            with _span("train.data"):
                key, kb, ks = jax.random.split(key, 3)
                batch = sample_block(i, kb)
                if mesh is not None:
                    batch = place_agents(batch, mesh, num_agents=K,
                                         agent_dim=1)
            with _span("train.step"):
                state, metrics = jit_step(fetch(state), batch, ks)
            with _span("train.offload"):
                state = offload(state)
            blocks_done = i + 1
            log_block = i % args.log_every == 0
            with _span("train.log"):
                if privacy is not None and (budget or log_block):
                    # host sync only when the value is consumed: every
                    # block for budgeted runs (the halt reads it), log
                    # blocks otherwise
                    host_rdp = np.asarray(state.privacy_state["rdp"],
                                          np.float64)
                    eps_spent = epsilon_from_rdp_np(host_rdp, privacy.delta,
                                                    privacy.orders)
                if log_block:
                    active = metrics["active"]
                    losses = eval_loss(state.params,
                                       jax.tree.map(lambda x: x[0], batch))
                    wall = (f"  sim_wall={float(metrics['t_wall']):.1f}s"
                            if is_async else "")
                    eps = (f"  epsilon={eps_spent:.3f}"
                           if eps_spent is not None else "")
                    print(f"block {i:4d}  active={int(active.sum())}/{K}  "
                          f"mean_loss={float(losses.mean()):.4f}  "
                          f"spread={float(losses.max() - losses.min()):.4f}"
                          f"  t={time.time() - t0:.1f}s{wall}{eps}")
            if budget and eps_spent >= budget:
                print(f"privacy budget spent: epsilon={eps_spent:.3f} >= "
                      f"{budget:g} after {blocks_done} blocks — halting")
                break

        if args.checkpoint:
            metadata = {"arch": spec.model.arch}
            if privacy is not None:
                # the guarantee the saved iterate carries — serve
                # --checkpoint reports it next to the model
                metadata["epsilon_spent"] = privacy.epsilon_np(
                    state.privacy_state)
                metadata["privacy_delta"] = spec.privacy.delta
            with _span("train.checkpoint"):
                save_experiment(args.checkpoint, state, spec=spec,
                                step=blocks_done, metadata=metadata)
            print("saved", args.checkpoint)
    finally:
        if tracing:
            jax.profiler.stop_trace()
            print("trace of blocks 1 onward in", args.trace_dir)


if __name__ == "__main__":
    main()
