"""Continuous-batching consensus server: request queue, slot-based
batching, and watch-mode checkpoint swaps.

:class:`ServeLoop` turns the one-shot ``launch/serve.py`` demo into a
serving loop: requests of different prompt lengths share one per-slot
decode cache (``tf.init_cache(..., per_slot=True)`` — each batch row is an
independent request at its own position), a new request is admitted the
moment a slot frees (batch-1 prefill written into the slot, no global
barrier), and decode runs in fused ``chunk``-token ticks through
:func:`repro.models.transformer.decode_loop` (one dispatch per chunk) or
the per-token py loop (``decode_loop="py"`` escape hatch, token-parity
with fused at temperature 0).

Watch mode (:meth:`ServeLoop.watch`) re-extracts consensus as training
checkpoints stream into a directory and publishes each through the
double-buffered :class:`repro.core.serving.ParamStore` — in-flight
decodes never see a torn update, and every emitted token is tagged with
the exact checkpoint generation that produced its logits
(:class:`Completion.generations`).  :func:`replay_completion` replays a
greedy completion against the recorded generation schedule and fails
loudly on any token that did not come from exactly one generation — the
torn-update gate ``tests/test_serving.py`` and ``bench_serve`` both run.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.serving import ParamStore, consensus_from_stacked
from repro.models import transformer as tf

__all__ = ["Request", "Completion", "ServeLoop", "load_consensus",
           "replay_completion"]


@dataclass(frozen=True)
class Request:
    uid: int
    prompt: np.ndarray            # (P,) int32, or (P, nq) multi-codebook
    max_new_tokens: int


@dataclass(frozen=True)
class Completion:
    uid: int
    prompt: np.ndarray
    tokens: list                  # per-token int, or per-token [nq] list
    generations: list = field(default_factory=list)
    # generations[i] = ParamStore generation of the params that produced
    # the logits tokens[i] was sampled from (exactly one per token — the
    # double-buffer contract replay_completion verifies)


def _write_slot(big: tf.Cache, small: tf.Cache, slot: int) -> tf.Cache:
    """Write a batch-1 prefill cache into row ``slot`` of a per-slot cache.

    Every segment leaf is ``(n_layers, B, ...)`` (batch at axis 1 — KV
    rings and SSM states alike), so one tree_map covers the zoo; ``pos`` /
    ``slot_pos`` move from the whole-batch layout (scalar / ``(C,)``) into
    the per-slot rows.
    """
    segs = jax.tree.map(lambda b, s: b.at[:, slot].set(s[:, 0]),
                        big.segments, small.segments)
    return tf.Cache(segments=segs,
                    pos=big.pos.at[slot].set(small.pos),
                    slot_pos=big.slot_pos.at[slot].set(small.slot_pos))


class ServeLoop:
    """Slot-batched continuous decode over a double-buffered param store.

    One tick (:meth:`step`) = snapshot params -> admit queued requests
    into free slots (batch-1 prefill each) -> decode ``chunk`` tokens for
    the whole batch in one fused dispatch -> emit tokens (tagged with
    their generation) and retire finished slots.  Free slots decode junk
    that is discarded — admission overwrites the slot wholesale, so a
    retired slot needs no reset pass.

    ``decode_loop="py"`` swaps the fused chunk for the legacy per-token
    host loop (same tick structure, same tagging) — the escape hatch the
    parity tests and ``bench_serve`` measure against.  Greedy decoding
    (``temperature <= 0``) is key-free in both modes.
    """

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 128,
                 decode_loop: str = "fused", temperature: float = 0.0,
                 chunk: int = 4, seed: int = 0):
        if decode_loop not in ("fused", "py"):
            raise ValueError(f"decode_loop={decode_loop!r} not in "
                             "('fused', 'py')")
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.decode_loop = decode_loop
        self.temperature = temperature
        self.chunk = chunk
        self.store = ParamStore(params)
        self._greedy = temperature <= 0
        self._key = None if self._greedy else jax.random.PRNGKey(seed)
        self._queue: deque[Request] = deque()
        self._requests: list[Request | None] = [None] * slots
        self._emitted: list[list] = [[] for _ in range(slots)]
        self._gens: list[list] = [[] for _ in range(slots)]
        self._lg_gen = [0] * slots
        self._ticks = 0
        self._cache = tf.init_cache(cfg, slots, max_len, per_slot=True)
        lg_shape = ((slots, cfg.num_codebooks, cfg.vocab_size)
                    if cfg.num_codebooks else (slots, cfg.vocab_size))
        self._logits = jnp.zeros(lg_shape, jnp.float32)
        # one jit object per loop; prefill re-specializes per prompt
        # length (cached per shape), decode shapes are fixed.  Params are
        # ARGUMENTS: the watch loop hot-swaps checkpoints through the
        # ParamStore, and argument weights swap with zero recompiles (see
        # EXPERIMENTS.md section Serving)
        self._prefill = jax.jit(
            lambda p, t: tf.prefill(p, cfg, t, max_len=max_len))
        self._fused = jax.jit(
            lambda p, c, lg, k: tf.decode_loop(p, cfg, c, lg, k, chunk,
                                               temperature=temperature))
        self._step1 = jax.jit(lambda p, c, t: tf.decode_step(p, cfg, c, t))

    @property
    def active(self) -> int:
        return sum(r is not None for r in self._requests)

    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds the "
                f"serve cache budget max_len={self.max_len}")
        self._queue.append(req)

    def ingest_checkpoint(self, path, *, quantize: str | None = None) -> int:
        """Extract consensus from a training checkpoint and publish it as
        the next param generation.  Returns the new generation."""
        params, _cfg, _meta = load_consensus(path, quantize=quantize)
        return self.store.swap(params)

    def _admit(self, params, gen: int) -> None:
        for s in range(self.slots):
            if self._requests[s] is not None or not self._queue:
                continue
            req = self._queue.popleft()
            lg, small = self._prefill(params, jnp.asarray(req.prompt)[None])
            self._cache = _write_slot(self._cache, small, s)
            self._logits = self._logits.at[s].set(
                lg[0, -1].astype(jnp.float32))
            self._requests[s] = req
            self._emitted[s] = []
            self._gens[s] = []
            self._lg_gen[s] = gen

    def step(self) -> list[Completion]:
        """One serving tick; returns the completions retired this tick."""
        params, gen = self.store.snapshot()
        self._admit(params, gen)
        if self.active == 0:
            return []
        if self.decode_loop == "fused":
            key = None
            if not self._greedy:
                self._key, key = jax.random.split(self._key)
            toks, lg, cache = self._fused(params, self._cache, self._logits,
                                          key)
        else:
            toks, lg, cache = self._py_chunk(params, self._cache,
                                             self._logits)
        self._cache, self._logits = cache, lg
        toks = np.asarray(toks)           # (slots, chunk[, nq])
        done = []
        for s, req in enumerate(self._requests):
            if req is None:
                continue
            # first token of the tick was sampled from logits carried in
            # from the PREVIOUS tick's params (or the admission prefill);
            # the rest were produced under this tick's snapshot
            gens = [self._lg_gen[s]] + [gen] * (self.chunk - 1)
            take = min(self.chunk, req.max_new_tokens - len(self._emitted[s]))
            self._emitted[s].extend(toks[s, :take].tolist())
            self._gens[s].extend(gens[:take])
            self._lg_gen[s] = gen
            if len(self._emitted[s]) >= req.max_new_tokens:
                done.append(Completion(req.uid, req.prompt, self._emitted[s],
                                       self._gens[s]))
                self._requests[s] = None
        self._ticks += 1
        return done

    def run(self, *, max_ticks: int = 100_000) -> list[Completion]:
        """Drain the queue: tick until every request has completed."""
        out = []
        for _ in range(max_ticks):
            if not self._queue and self.active == 0:
                return out
            out.extend(self.step())
        raise RuntimeError(f"serve loop did not drain in {max_ticks} ticks")

    def watch(self, ckpt_dir, *, poll_s: float = 0.5,
              max_ticks: int | None = None,
              quantize: str | None = None) -> list[Completion]:
        """Serve while re-extracting consensus from checkpoints streaming
        into ``ckpt_dir``.

        Each poll picks up ``*.npz`` files that are new or rewritten
        (name + mtime) and publishes their consensus via
        :meth:`ingest_checkpoint`; decode ticks run between polls.
        Writers should write-then-rename so a poll never reads a
        half-written archive.  Runs until ``max_ticks`` ticks (forever
        when ``None`` — the CLI mode); returns completions retired while
        watching.
        """
        seen: dict[str, int] = {}
        out = []
        ticks = 0
        while max_ticks is None or ticks < max_ticks:
            for p in sorted(Path(ckpt_dir).glob("*.npz")):
                stamp = p.stat().st_mtime_ns
                if seen.get(p.name) != stamp:
                    seen[p.name] = stamp
                    gen = self.ingest_checkpoint(p, quantize=quantize)
                    print(f"[watch] {p.name} -> generation {gen}")
            if self._queue or self.active:
                out.extend(self.step())
            else:
                time.sleep(poll_s)
            ticks += 1
        return out

    def _py_chunk(self, params, cache, logits):
        """Per-token host loop over one chunk — the ``--decode-loop py``
        escape hatch.  Same params snapshot for the whole tick, so the
        generation tagging in :meth:`step` holds for both modes."""
        toks = []
        for _ in range(self.chunk):
            key = None
            if not self._greedy:
                self._key, key = jax.random.split(self._key)
            nxt = tf.sample_logits(logits, key, self.temperature)
            tok = (nxt[:, None, :] if self.cfg.num_codebooks
                   else nxt[:, None])
            lg, cache = self._step1(params, cache, tok)
            logits = lg[:, 0].astype(jnp.float32)
            toks.append(nxt)
        return jnp.stack(toks, axis=1), logits, cache


def load_consensus(path, *, quantize: str | None = None):
    """(consensus params, model cfg, meta) from a spec-embedding training
    checkpoint — the watch-mode ingest path.

    The checkpoint's own :class:`~repro.api.ExperimentSpec` decides the
    agent count, architecture, mixer backend, and topology; ``quantize``
    selects the extraction precision (``"int8"`` collapses from
    int8-quantized leaves — see
    :func:`repro.core.serving.consensus_from_stacked`).
    """
    # local imports: keep repro.launch.serving importable without pulling
    # the full api/engine surface until a checkpoint is actually ingested
    from repro.api import EngineState, TOPOLOGIES, build
    from repro.checkpoint import load_experiment, load_spec

    path = str(path)              # the checkpoint store speaks str paths
    spec = load_spec(path)
    if spec is None:
        raise ValueError(
            f"{path}: not a spec-embedding checkpoint; watch-mode ingest "
            "needs checkpoints written by repro.launch.train (use "
            "launch/serve.py --agents/--mix for legacy stacked archives)")
    if spec.model.kind == "external":
        raise ValueError(f"{path}: checkpoint spec has model kind "
                         "'external' — nothing servable")
    eng = build(spec)
    K = spec.run.num_agents
    like = EngineState(jax.eval_shape(eng.init_params, jax.random.PRNGKey(0)))
    weights = None
    if spec.asynchrony.enabled:
        # async checkpoints carry per-agent clocks next to the iterate:
        # restore t_local too and weight the collapse by freshness via the
        # engine's own age-discount law (sum_k w_k x_k, w = discount(age))
        like = EngineState(
            like.params,
            async_state={"t_local": jax.ShapeDtypeStruct((K,),
                                                         jnp.float32)})
    state, meta = load_experiment(path, like)
    if spec.asynchrony.enabled:
        t_local = jnp.asarray(state.async_state["t_local"])
        weights = eng._discount(t_local.max() - t_local)
    topo = (TOPOLOGIES.get(spec.topology.kind)(spec.topology, K)
            if K > 1 else None)
    params = consensus_from_stacked(state.params, K, spec.mixer.kind,
                                    trim=spec.mixer.trim,
                                    scope=spec.mixer.scope, topology=topo,
                                    quantize=quantize, weights=weights)
    return params, eng.model.cfg, meta


def replay_completion(cfg, params_by_gen, completion: Completion, *,
                      max_len: int) -> int:
    """Replay a greedy completion against its recorded generation schedule.

    Re-runs prefill + per-token greedy decode, switching to
    ``params_by_gen[g]`` exactly where ``completion.generations`` says a
    new checkpoint generation took over, and asserts every token matches
    the single-generation replay bit-for-bit.  A torn param update (a
    token computed from a mix of two checkpoints) cannot match any
    single-generation schedule, so this is the no-torn-update gate.
    Returns the number of distinct generations the completion spanned.
    """
    gens, toks = completion.generations, completion.tokens
    assert len(gens) == len(toks) > 0
    prompt = jnp.asarray(completion.prompt)[None]
    lg, cache = tf.prefill(params_by_gen[gens[0]], cfg, prompt,
                           max_len=max_len)
    logits = lg[:, -1]
    for i, (t, g) in enumerate(zip(toks, gens)):
        want = np.asarray(tf.sample_logits(logits, None, 0.0))[0]
        assert np.array_equal(want, np.asarray(t, want.dtype)), (
            f"uid {completion.uid} token {i}: emitted {t} but generation "
            f"{g} params produce {want.tolist()} — torn or mis-tagged "
            "param update")
        if i + 1 == len(toks):
            break
        tok = (jnp.asarray(t, jnp.int32)[None, None, :]
               if cfg.num_codebooks else jnp.full((1, 1), t, jnp.int32))
        # the logits for token i+1 were produced under generation gens[i+1]
        lg, cache = tf.decode_step(params_by_gen[gens[i + 1]], cfg, cache,
                                   tok)
        logits = lg[:, 0]
    return len(set(gens))
