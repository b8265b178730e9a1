#!/usr/bin/env python3
"""Chip smoke test: the system's main path, once, on a TPU.

Trains smollm-360m at its published widths by diffusion learning
(Algorithm 1: K=4 agents on a ring, i.i.d. participation q=0.9, T=2 local
SGD steps per block, the combination step on the mixer ``"auto"`` picks)
for three blocks through the entry points ``repro.launch.train`` uses,
checks what comes out, collapses the trained agents to their consensus as
``repro.launch.serve`` does, and serves four greedy requests of two prompt
lengths through the continuous-batching ``ServeLoop``.  Weights are random,
made from a seed.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four agents, one per chip, against
                                      # the same run on one chip

Checks: finite losses; the agents active in a block changed and the
inactive ones are bit-identical; the combination step ran the native Pallas
kernel (``tpu_custom_call`` in the compiled step); that kernel agrees with
``mix_dense`` on the full-width stack; every request got its tokens, all in
the vocabulary.  With ``--chips 4`` the mixer is a collective and the
parameters after two blocks are compared with the one-chip run.

Exits nonzero, with no result line, when JAX finds no TPU or any check
fails.  Lines before the last carry information (compile seconds, seconds
per block, peak device memory, tokens served), not metrics.  On success the
last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import TOPOLOGIES, build  # noqa: E402
from repro.api.spec import (ExperimentSpec, MixerSpec, ModelSpec,  # noqa: E402
                            OptimizerSpec, ParticipationSpec, RunSpec,
                            TopologySpec)
from repro.core.mixing import PallasFusedMixer, mix_dense  # noqa: E402
from repro.core.participation import masked_combination  # noqa: E402
from repro.core.serving import consensus_from_stacked  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_agent_mesh, place_agents  # noqa: E402
from repro.launch.serving import Request, ServeLoop  # noqa: E402
from repro.models import transformer as tf  # noqa: E402

# the native mixer must match the float32 reference this closely (relative
# L2 over the whole stack)
MIXER_RTOL = 1e-5
# one-chip vs four-chip parameters after two blocks: the two runs mix with
# different backends (Pallas kernel vs collective permute), so bfloat16
# parameters may differ by a few units in the last place of the largest
# entry of a leaf
MESH_RTOL = 2.0 ** -5


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def smoke_spec(*, smoke: bool = False, num_agents: int = 4, blocks: int = 3,
               seq: int = 512, seed: int = 0) -> ExperimentSpec:
    """The smoke run's experiment.  ``smoke=True`` swaps in the 2-layer
    architecture of the same family (the CPU tests use it); the default
    ``seed`` draws a participation pattern in which an agent sits out a
    block, so the frozen-agent check is not vacuous."""
    return ExperimentSpec(
        topology=TopologySpec(kind="ring"),
        participation=ParticipationSpec(kind="iid", q=0.9),
        mixer=MixerSpec(kind="auto"),
        optimizer=OptimizerSpec(kind="sgd"),
        model=ModelSpec(kind="transformer", arch="smollm-360m", smoke=smoke),
        run=RunSpec(num_agents=num_agents, local_steps=2, blocks=blocks,
                    batch=1, seq=seq, seed=seed))


@dataclasses.dataclass
class Trained:
    engine: object
    state: object              # the EngineState after the last block
    losses: list               # per block: (K,) losses of the new params
    actives: list              # per block: (K,) realized activation mask
    frozen: int                # inactive (agent, block) pairs checked
    hlo: str                   # the compiled block step
    compile_s: float
    block_s: list


def _agent_equal(a, b, k: int) -> bool:
    """Agent k's parameters in ``a`` and ``b`` are bit-identical."""
    def bits(x):
        return x[k].view(np.dtype(f"u{x.dtype.itemsize}"))
    return all(np.array_equal(bits(x), bits(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def train(spec: ExperimentSpec, *, mesh=None) -> Trained:
    """Run ``spec.run.blocks`` blocks the way ``repro.launch.train`` does
    (``build`` -> ``init_params`` -> ``init_state`` -> ``eng.data`` ->
    jitted ``eng.step`` with the state donated), checking every block."""
    run = spec.run
    K = run.num_agents
    eng = build(spec, mesh=mesh)
    key = jax.random.PRNGKey(run.seed)
    kp, key = jax.random.split(key)
    params = eng.init_params(kp)
    state = eng.init_state(params, eng.optimizer.init(params),
                           key=jax.random.fold_in(key, 0x5EED))
    del params
    if mesh is not None:
        state = place_agents(state, mesh, num_agents=K)
    blocks = []
    for i in range(run.blocks):
        key, kb, ks = jax.random.split(key, 3)
        batch = eng.data(i, kb)
        if mesh is not None:
            batch = place_agents(batch, mesh, num_agents=K, agent_dim=1)
        blocks.append((batch, ks))

    t0 = time.perf_counter()
    step = jax.jit(eng.step, donate_argnums=0).lower(
        state, *blocks[0]).compile()
    compile_s = time.perf_counter() - t0
    cfg = eng.model.cfg
    eval_loss = jax.jit(jax.vmap(
        lambda p, b: tf.train_loss(p, cfg, b, remat=False)))

    losses, actives, block_s, frozen = [], [], [], 0
    before = jax.device_get(state.params)
    for i, (batch, ks) in enumerate(blocks):
        t0 = time.perf_counter()
        state, metrics = step(state, batch, ks)
        jax.block_until_ready(state)
        block_s.append(time.perf_counter() - t0)
        loss = np.asarray(eval_loss(state.params,
                                    jax.tree.map(lambda x: x[0], batch)))
        active = np.asarray(metrics["active"])
        after = jax.device_get(state.params)
        check(bool(np.isfinite(loss).all()), f"block {i}: losses {loss}")
        for k in range(K):
            same = _agent_equal(before, after, k)
            if active[k]:
                check(not same, f"block {i}: active agent {k} did not move")
            else:
                check(same, f"block {i}: inactive agent {k} moved")
                frozen += 1
        losses.append(loss)
        actives.append(active)
        before = after
    return Trained(eng, state, losses, actives, frozen, step.as_text(),
                   compile_s, block_s)


def mixer_check(stack, active, A, *, tile_m: int = 512) -> float:
    """Relative L2 gap between one native :class:`PallasFusedMixer` call on
    the full agent stack and :func:`mix_dense` on the same ``A`` and mask,
    both in float32.  ``stack`` is the stack flattened to one (K, M) float32
    leaf, M a multiple of ``tile_m``, so the mixer's flatten is free and the
    reference runs in column chunks: at full width two float32 copies of the
    stack already fill most of a chip."""
    K, M = stack.shape
    chunk = next(c for c in (1 << 20, 1 << 16, tile_m) if M % c == 0)
    mixer = PallasFusedMixer(tile_m=tile_m)

    @jax.jit
    def gap(stack, active, A):
        out = mixer({"stack": stack}, active, A)["stack"]
        A_eff = masked_combination(A, active)

        def one(i):
            x = jax.lax.dynamic_slice_in_dim(stack, i * chunk, chunk, 1)
            y = jax.lax.dynamic_slice_in_dim(out, i * chunk, chunk, 1)
            with jax.default_matmul_precision("highest"):
                ref = mix_dense(A_eff, x)
            return jnp.sum(jnp.square(y - ref)), jnp.sum(jnp.square(ref))

        num, den = jax.lax.map(one, jnp.arange(M // chunk))
        return jnp.sqrt(num.sum() / den.sum())

    return float(gap(stack, active, A))


def flatten_f32(params, *, multiple: int = 1 << 20):
    """The (K, ...) stack as one (K, M) float32 array, zero-padded to a
    multiple of ``multiple`` columns."""
    leaves = jax.tree.leaves(params)
    K = leaves[0].shape[0]
    M = sum(l.size // K for l in leaves)
    pad = (-M) % multiple

    @jax.jit
    def flat(leaves):
        x = jnp.concatenate([l.reshape(K, -1).astype(jnp.float32)
                             for l in leaves], axis=1)
        return jnp.pad(x, ((0, 0), (0, pad)))

    return flat(leaves)


def consensus(spec: ExperimentSpec, stacked):
    """Collapse the trained stack over the topology it trained on, as
    ``repro.launch.serve.load_params`` does for a spec checkpoint."""
    K = spec.run.num_agents
    topo = TOPOLOGIES.get(spec.topology.kind)(spec.topology, K)
    return consensus_from_stacked(stacked, K, spec.mixer.kind,
                                  trim=spec.mixer.trim,
                                  scope=spec.mixer.scope, topology=topo)


def serve(cfg, params, *, prompt_lens=(128, 256), new_tokens: int = 32,
          slots: int = 4, seed: int = 0):
    """Serve ``slots`` greedy requests, prompt lengths taken in turn from
    ``prompt_lens``, through one :class:`ServeLoop`; returns the
    completions, checked."""
    loop = ServeLoop(cfg, params, slots=slots,
                     max_len=max(prompt_lens) + new_tokens,
                     temperature=0.0, chunk=8, seed=seed)
    rng = np.random.default_rng(seed)
    for uid in range(slots):
        prompt = rng.integers(0, cfg.vocab_size,
                              prompt_lens[uid % len(prompt_lens)],
                              dtype=np.int32)
        loop.submit(Request(uid=uid, prompt=prompt,
                            max_new_tokens=new_tokens))
    done = loop.run()
    check(sorted(c.uid for c in done) == list(range(slots)),
          f"served {sorted(c.uid for c in done)} of {slots} requests")
    for c in done:
        toks = np.asarray(c.tokens)
        check(toks.shape == (new_tokens,),
              f"request {c.uid}: {toks.shape[0]} tokens, not {new_tokens}")
        check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
              f"request {c.uid}: token outside the vocabulary")
    return done


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def _report_training(tag: str, res: Trained) -> None:
    print(f"{tag}: compile {res.compile_s:.1f} s; seconds per block "
          f"{[round(s, 3) for s in res.block_s]}; mixer "
          f"{res.engine.pipeline.mixer.name}")
    for i, (loss, active) in enumerate(zip(res.losses, res.actives)):
        print(f"{tag}: block {i} active {active.astype(int).tolist()} "
              f"losses {np.round(loss, 4).tolist()}")
    print(f"{tag}: {res.frozen} inactive agent-blocks stayed bit-identical")


def one_chip(spec: ExperimentSpec) -> None:
    K = spec.run.num_agents
    res = train(spec)
    _report_training("train", res)
    check(res.frozen > 0, "no agent sat out a block; frozen check vacuous")
    check(res.engine.pipeline.mixer.name == "pallas",
          f"'auto' picked {res.engine.pipeline.mixer.name!r} on one chip")
    check("tpu_custom_call" in res.hlo,
          "the compiled block step holds no native Pallas kernel")
    print(f"train: peak device memory {peak_bytes(jax.devices()[0])} bytes")

    params = consensus(spec, res.state.params)
    stack = flatten_f32(res.state.params)
    A = jnp.asarray(res.engine.graph.base_matrix(), jnp.float32)
    # the mask of the last block in which an agent sat out
    active = jnp.asarray(next(a for a in reversed(res.actives)
                              if not a.all()), jnp.float32)
    cfg = res.engine.model.cfg
    del res
    gap = mixer_check(stack, active, A)
    print(f"mixer: native Pallas vs mix_dense on the ({K}, "
          f"{stack.shape[1]}) float32 stack: relative gap {gap:.3e}")
    check(gap <= MIXER_RTOL, f"mixer gap {gap:.3e} > {MIXER_RTOL}")
    del stack

    t0 = time.perf_counter()
    done = serve(cfg, params)
    serve_s = time.perf_counter() - t0
    print(f"serve: {sum(len(c.tokens) for c in done)} tokens served to "
          f"{len(done)} requests in {serve_s:.1f} s, compilation included")
    print(f"peak device memory {peak_bytes(jax.devices()[0])} bytes")


def max_rel_diff(a, b) -> float:
    """Largest per-leaf max|a - b| / max|b|."""
    out = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        out = max(out, float(np.abs(x - y).max()
                             / max(float(np.abs(y).max()), 1e-30)))
    return out


def four_chips(spec: ExperimentSpec, devices) -> None:
    K = spec.run.num_agents
    mesh = make_agent_mesh(K, devices)
    check(mesh is not None, f"no agent mesh for K={K} over {len(devices)}")
    res = train(spec, mesh=mesh)
    _report_training("mesh", res)
    check(res.engine.pipeline.mixer.name != "pallas",
          "'auto' picked the single-device kernel on a 4-device mesh")
    leaf = jax.tree.leaves(res.state.params)[0]
    check(len(leaf.sharding.device_set) == len(devices),
          f"parameter stack on {len(leaf.sharding.device_set)} devices")
    shards = [s for l in jax.tree.leaves(res.state.params)
              for s in l.addressable_shards]
    for d in devices:
        held = sum(s.data.nbytes for s in shards if s.device == d)
        print(f"mesh: device {d.id} peak {peak_bytes(d)} bytes; holds "
              f"{held} parameter bytes")
    sharded = jax.device_get(res.state.params)
    del res
    ref = train(spec)
    _report_training("one chip", ref)
    diff = max_rel_diff(sharded, jax.device_get(ref.state.params))
    print(f"mesh vs one chip after {spec.run.blocks} blocks: max relative "
          f"difference {diff:.3e}")
    check(diff <= MESH_RTOL, f"max relative difference {diff:.3e} > "
          f"{MESH_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Train and serve smollm-360m once on the TPU.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: four agents, one per chip, against the same "
                         "run on one chip (and nothing else)")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}; jax {jax.__version__}; "
          f"compile cache {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(smoke_spec(blocks=2), devices[:4])
    else:
        one_chip(smoke_spec())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
