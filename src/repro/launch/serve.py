"""Batched serving driver: prefill a batch of prompts, decode N tokens.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --batch 4 --prompt-len 64 --decode 32

Decode runs as ONE fused dispatch by default (sampling inside the jitted
``lax.scan`` step — :func:`repro.models.transformer.decode_loop`);
``--decode-loop py`` keeps the legacy per-token host loop as an escape
hatch, token-parity-gated against the fused path at temperature 0
(``tests/test_serving.py``, ``bench_serve``).  Greedy decoding
(``--temperature 0``) is key-free in both loops.

Serving a diffusion-trained model: ``--checkpoint ckpt.npz`` alone is
enough for checkpoints written by ``repro.launch.train`` — they embed the
:class:`repro.api.ExperimentSpec`, so the exact engine (agent count,
architecture, combination backend) is rebuilt with ZERO flags and the
consensus model (the network average, one application of the FedAvg matrix)
is extracted through the trained mixer backend.  Spec-less (legacy / plain)
checkpoints fall back to the flag path: ``--agents K`` marks an
agent-stacked archive, ``--mix`` selects the consensus-extraction backend.
The spec flags are the same shared set ``train`` and ``dryrun`` use
(:mod:`repro.api.cli`).  ``--consensus-quantize int8`` collapses the agent
stack from int8-quantized leaves (4x smaller resident stack at large K);
``--watch DIR`` switches to the continuous-batching
:class:`repro.launch.serving.ServeLoop` and re-extracts consensus as
training checkpoints stream into DIR (double-buffered swap — in-flight
decodes never see a torn update).
"""
from __future__ import annotations

import argparse
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import EngineState, TOPOLOGIES, build, spec_from_args
from repro.api.cli import add_spec_args
from repro.checkpoint import load_checkpoint, load_experiment, load_spec
from repro.configs import get_config
# consensus_from_stacked moved to repro.core.serving; re-exported here for
# the existing import surface (tests, notebooks)
from repro.core.serving import CONSENSUS_QUANTIZE, consensus_from_stacked
from repro.launch.cache import enable_compile_cache
from repro.launch.serving import Request, ServeLoop
from repro.models import transformer as tf

__all__ = ["consensus_from_stacked", "load_params", "main"]


def load_params(args, key):
    """Resolve (params, cfg) from the checkpoint spec, the legacy stacked
    path, or fresh initialization."""
    quantize = getattr(args, "consensus_quantize", None)
    spec = load_spec(args.checkpoint) if args.checkpoint else None
    if spec is not None and spec.model.kind == "external":
        # the spec describes an externally supplied loss (regression /
        # theory workloads) — nothing servable; fall back to the flag path
        print(f"checkpoint spec has model kind 'external' (nothing to "
              f"serve); falling back to --arch/--agents/--mix flags")
        spec = None
    if spec is not None:
        if getattr(args, "spec", None) or getattr(args, "preset", None):
            warnings.warn(
                "the checkpoint embeds its own ExperimentSpec, which "
                "takes precedence — the --spec/--preset flags are "
                "ignored for serving", stacklevel=2)
        # self-describing checkpoint: rebuild the exact engine, zero flags
        eng = build(spec)
        K = spec.run.num_agents
        # eval_shape: the template only provides structure/shapes — no
        # reason to materialize K full randomly initialized models
        like = EngineState(jax.eval_shape(eng.init_params,
                                          jax.random.PRNGKey(0)))
        weights = None
        if spec.asynchrony.enabled:
            # restore the per-agent clocks too: the consensus weights the
            # stack by iterate freshness (the engine's age-discount law)
            like = EngineState(
                like.params,
                async_state={"t_local": jax.ShapeDtypeStruct(
                    (K,), jnp.float32)})
        state, meta = load_experiment(args.checkpoint, like)
        if spec.asynchrony.enabled:
            t_local = jnp.asarray(state.async_state["t_local"])
            weights = eng._discount(t_local.max() - t_local)
            print(f"async checkpoint: freshness-weighted consensus "
                  f"(discount={spec.asynchrony.discount}"
                  f"({spec.asynchrony.discount_rate}); agent clock ages "
                  f"max={float((t_local.max() - t_local).max()):.1f})")
        if meta.get("epsilon_spent") is not None:
            # the guarantee the served iterate carries, written by
            # launch/train from the RDP accountant's final state
            print(f"privacy: checkpoint trained under "
                  f"(epsilon={float(meta['epsilon_spent']):.3f}, "
                  f"delta={meta.get('privacy_delta', spec.privacy.delta):g})"
                  "-DP (RDP accountant at the realized participation rate)")
        # the consensus must come from the topology the agents TRAINED on
        # (spec checkpoints used to hard-code FedAvg here); non-static
        # graphs are approximated by their base topology
        topo = (TOPOLOGIES.get(spec.topology.kind)(spec.topology, K)
                if K > 1 else None)
        if spec.graph.kind != "static":
            warnings.warn(
                f"checkpoint was trained on a time-varying graph "
                f"({spec.graph.kind!r}); consensus extraction uses the "
                f"base {spec.topology.kind!r} topology, not a realized "
                "draw", stacklevel=2)
        print(f"loaded spec checkpoint (K={K}, arch={spec.model.arch}, "
              f"step={meta.get('step')}); extracting consensus via "
              f"mix={spec.mixer.kind} over topology={spec.topology.kind}")
        params = consensus_from_stacked(state.params, K, spec.mixer.kind,
                                        trim=spec.mixer.trim,
                                        scope=spec.mixer.scope,
                                        topology=topo, quantize=quantize,
                                        weights=weights)
        return params, eng.model.cfg

    bundle = get_config(args.arch)
    cfg = bundle.smoke if args.smoke else bundle.model
    params = tf.init_params(key, cfg)
    if not args.checkpoint:
        return params, cfg
    if args.agents > 1:
        like = jax.tree.map(
            lambda x: jnp.zeros((args.agents,) + x.shape, x.dtype), params)
        stacked, meta = load_checkpoint(args.checkpoint, like)
        print(f"loaded stacked checkpoint (K={args.agents}, "
              f"step={meta.get('step')}); extracting consensus via "
              f"--mix {args.mix}")
        return (consensus_from_stacked(stacked, args.agents, args.mix,
                                       trim=args.trim,
                                       scope=args.robust_scope,
                                       quantize=quantize), cfg)
    params, meta = load_checkpoint(args.checkpoint, params)
    print(f"loaded checkpoint (step={meta.get('step')})")
    return params, cfg


def _check_preset_shim(ap: argparse.ArgumentParser, args) -> None:
    """serve defaults --agents to 1 (deprecation shim: a spec-less
    checkpoint is a plain single model), but a --preset factory is
    parameterized by K=args.agents — so the shim default used to silently
    build a 1-agent variant of a preset that train/dryrun build with the
    shared default of 4.  Explicit-flag tracking makes the collision
    detectable: --preset on serve now requires an explicit --agents."""
    if args.preset and "agents" not in getattr(args, "_explicit", set()):
        ap.error(
            "--preset on serve needs an explicit --agents K: serve's "
            "spec-less shim defaults --agents to 1 (a plain checkpoint "
            "is a single model), which would silently override the "
            "preset's agent count")


def main(argv=None):
    ap = argparse.ArgumentParser()
    add_spec_args(ap)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--checkpoint", default=None,
                    help="npz checkpoint (spec-embedding, agent-stacked, or "
                         "plain)")
    ap.add_argument("--decode-loop", choices=["fused", "py"],
                    default="fused",
                    help="fused: sampling inside the jitted lax.scan step, "
                         "one dispatch per generation (default); py: "
                         "legacy per-token host loop (token-parity with "
                         "fused at temperature 0)")
    ap.add_argument("--consensus-quantize", choices=list(CONSENSUS_QUANTIZE),
                    default="none",
                    help="collapse the (K, M) agent stack from "
                         "int8-quantized leaves (Int8Stochastic — the "
                         "training-side wire quantizer) instead of f32")
    ap.add_argument("--watch", default=None, metavar="DIR",
                    help="continuous mode: serve through the slot-batched "
                         "ServeLoop while re-extracting consensus from "
                         "*.npz checkpoints streaming into DIR "
                         "(double-buffered param swap)")
    ap.add_argument("--watch-poll", type=float, default=2.0,
                    help="watch-mode poll interval, seconds")
    ap.add_argument("--watch-ticks", type=int, default=None,
                    help="stop watch mode after N ticks (default: forever)")
    # deprecation shim: a spec-less checkpoint is a plain single model
    # unless --agents says otherwise (spec checkpoints carry K themselves)
    ap.set_defaults(agents=1)
    args = ap.parse_args(argv)
    _check_preset_shim(ap, args)
    spec_from_args(args)      # validate the shared flags map onto a spec
    enable_compile_cache()

    key = jax.random.PRNGKey(args.seed)
    kp, kt, key = jax.random.split(key, 3)
    params, cfg = load_params(args, kp)

    shape = (args.batch, args.prompt_len)
    if cfg.num_codebooks:
        shape = shape + (cfg.num_codebooks,)
    prompts = jax.random.randint(kt, shape, 0, cfg.vocab_size)
    img = None
    if cfg.img_tokens:
        img = jax.random.normal(key, (args.batch, cfg.img_tokens,
                                      tf.VISION_DIM), jnp.float32) * 0.02

    max_len = args.prompt_len + args.decode

    if args.watch:
        loop = ServeLoop(cfg, params, slots=args.batch, max_len=max_len,
                         decode_loop=args.decode_loop,
                         temperature=args.temperature,
                         chunk=max(1, min(8, args.decode)), seed=args.seed)
        for i in range(args.batch):
            loop.submit(Request(uid=i, prompt=np.asarray(prompts[i]),
                                max_new_tokens=args.decode))
        done = loop.watch(args.watch, poll_s=args.watch_poll,
                          max_ticks=args.watch_ticks,
                          quantize=args.consensus_quantize)
        for c in sorted(done, key=lambda c: c.uid):
            print(f"request {c.uid}: {len(c.tokens)} tokens across "
                  f"{len(set(c.generations))} checkpoint generation(s)")
        return

    prefill_fn = jax.jit(lambda p, t, i: tf.prefill(p, cfg, t, img_embeds=i,
                                                    max_len=max_len))

    t0 = time.time()
    logits, cache = prefill_fn(params, prompts, img)
    logits = jax.block_until_ready(logits[:, -1])
    t_prefill = time.time() - t0

    greedy = args.temperature <= 0
    if args.decode_loop == "fused":
        # params are arguments, as in ServeLoop: closed over, every weight
        # is serialized into the program as a constant (a 134 MB matrix
        # made a 268 MB module that took 3.9 s instead of 0.06 s to lower
        # and compile on the CPU), which at published widths is minutes
        fused = jax.jit(lambda p, c, lg, k: tf.decode_loop(
            p, cfg, c, lg, k, args.decode, temperature=args.temperature))
        t0 = time.time()
        gen, logits, cache = fused(params, cache, logits,
                                   None if greedy else key)
        gen = jax.block_until_ready(gen)
        t_decode = time.time() - t0
    else:
        decode_fn = jax.jit(lambda p, c, t: tf.decode_step(p, cfg, c, t))
        out_tokens = []
        t0 = time.time()
        for _ in range(args.decode):
            ks = None
            if not greedy:          # greedy is key-free in BOTH loops
                key, ks = jax.random.split(key)
            nxt = tf.sample_logits(logits, ks, args.temperature)
            out_tokens.append(nxt)
            tok = (nxt[:, None, :] if cfg.num_codebooks else nxt[:, None])
            lg, cache = decode_fn(params, cache, tok)
            logits = lg[:, 0]
        gen = jax.block_until_ready(jnp.stack(out_tokens, axis=1))
        t_decode = time.time() - t0

    print(f"prefill: {args.batch}x{args.prompt_len} in {t_prefill:.2f}s")
    print(f"decode:  {args.decode} steps ({args.decode_loop} loop) in "
          f"{t_decode:.2f}s "
          f"({args.decode * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    print("sample tokens[0,:16]:", gen[0, :16].tolist())


if __name__ == "__main__":
    main()
