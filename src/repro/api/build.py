"""build(spec) — the single entry point from :class:`ExperimentSpec` to a
running engine.

Every spec ``kind`` resolves through a string-keyed :class:`Registry`
(:mod:`repro.api.spec`), pre-populated with the repo's built-in backends;
``@REGISTRY.register("name")`` adds new ones without touching the engines,
the CLIs, or the checkpoint format.

Both engines come back with the SAME surface:

    engine = build(spec[, loss_fn])
    state  = engine.init_state(params, opt_state, key=...)
    state, metrics = engine.step(state, block_batch, key)   # jit this

``engine="stacked"`` returns the exact-paper
:class:`repro.core.diffusion.DiffusionEngine` (2-arg loss, no per-step rng);
``engine="sharded"`` the GSPMD :class:`repro.core.sharded.ShardedEngine`
(3-arg loss with per-agent rng); ``engine="async"`` the event-driven
:class:`repro.core.async_engine.AsyncEngine` (2-arg loss, per-agent
clocks + staleness buffer).  ``engine="auto"`` picks async when
``spec.asynchrony.enabled``, else sharded when the model spec is
self-contained (kind="transformer") and stacked for external losses —
the combinations every driver and test in the repo uses.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax

import jax.numpy as jnp
import numpy as np

from repro.api.spec import (AttackSpec, CompressionSpec, DataSpec,
                            ExperimentSpec, GraphSpec, MixerSpec, ModelSpec,
                            OptimizerSpec, ParticipationSpec, Registry,
                            TopologySpec)
from repro.core import attacks as attack_lib
from repro.core import compression as comp_lib
from repro.core import graphs as graph_lib
from repro.core import mixing
from repro.core import privacy as privacy_lib
from repro.core import schedules
from repro.core import topology as topo_lib
from repro.core.async_engine import AsyncEngine
from repro.core.diffusion import DiffusionEngine
from repro.core.sharded import ShardedEngine
from repro.optim import adam, momentum, sgd

PyTree = Any

__all__ = [
    "build",
    "ModelBundle",
    "train_block_struct",
    "make_block_provider",
    "TOPOLOGIES",
    "GRAPHS",
    "PARTICIPATION",
    "MIXERS",
    "COMPRESSORS",
    "ATTACKS",
    "OPTIMIZERS",
    "MODELS",
    "DATASETS",
]

TOPOLOGIES = Registry("topology")        # (TopologySpec, K) -> Topology
GRAPHS = Registry("graph")               # (GraphSpec, topology, K) -> process
PARTICIPATION = Registry("participation")  # (ParticipationSpec, K) -> process
MIXERS = Registry("mixer")               # (MixerSpec, topology, K) -> Mixer
COMPRESSORS = Registry("compressor")     # (CompressionSpec,) -> Compressor
ATTACKS = Registry("attack")             # (AttackSpec, K, inner) -> transform
OPTIMIZERS = Registry("optimizer")       # (OptimizerSpec,) -> GradTransform
MODELS = Registry("model")               # (ModelSpec,) -> ModelBundle | None
DATASETS = Registry("dataset")           # (DataSpec, spec, cfg) -> provider


# -- topologies (delegate to core/topology.make_topology) -------------------

def _register_topologies():
    for kind in topo_lib.TOPOLOGY_KINDS:
        @TOPOLOGIES.register(kind)
        def _build(spec: TopologySpec, K: int, _kind=kind):
            return topo_lib.make_topology(_kind, K, **dict(spec.kwargs))


_register_topologies()


# -- graph processes (time-varying topology, core/graphs.py) ----------------

@GRAPHS.register("static")
def _static_graph(spec: GraphSpec, topology, K: int):
    return graph_lib.StaticGraph(topology)


@GRAPHS.register("link_dropout")
def _link_dropout(spec: GraphSpec, topology, K: int):
    return graph_lib.LinkDropout(topology, drop=spec.drop, corr=spec.corr)


@GRAPHS.register("gossip")
def _gossip(spec: GraphSpec, topology, K: int):
    return graph_lib.GossipMatching(topology)


@GRAPHS.register("tv_erdos")
def _tv_erdos(spec: GraphSpec, topology, K: int):
    return graph_lib.TimeVaryingErdos(K, p=spec.p, topology=topology)


# -- participation processes ------------------------------------------------

@PARTICIPATION.register("iid")
def _iid(spec: ParticipationSpec, K: int):
    return schedules.IIDBernoulli(spec.q, num_agents=K)


@PARTICIPATION.register("markov")
def _markov(spec: ParticipationSpec, K: int):
    return schedules.MarkovAvailability(spec.q, spec.corr, num_agents=K)


@PARTICIPATION.register("cyclic")
def _cyclic(spec: ParticipationSpec, K: int):
    return schedules.CyclicGroups(K, spec.num_groups)


# -- mixers (delegate to core/mixing.make_mixer) ----------------------------

def _register_mixers():
    for kind in ("dense", "sparse", "pallas", "gather", "auto", "none",
                 "trimmed_mean", "median", "adaptive_trim"):
        @MIXERS.register(kind)
        def _build(spec: MixerSpec, topology, K: int, _kind=kind):
            return mixing.make_mixer(_kind, topology, num_agents=K,
                                     tile_m=spec.tile_m,
                                     interpret=spec.interpret,
                                     trim=spec.trim, scope=spec.scope,
                                     gather=spec.gather)


_register_mixers()


# -- compressors ------------------------------------------------------------

def _register_compressors():
    for kind in ("none", "topk", "randk", "int8", "gauss"):
        @COMPRESSORS.register(kind)
        def _build(spec: CompressionSpec, _kind=kind):
            return comp_lib.make_compressor(
                _kind, ratio=spec.ratio, error_feedback=spec.error_feedback,
                sigma=spec.sigma)


_register_compressors()


# -- byzantine gradient attacks (core/attacks.py) ---------------------------

def _register_attacks():
    for kind in attack_lib.ATTACK_KINDS:
        @ATTACKS.register(kind)
        def _build(spec: AttackSpec, K: int, inner, _kind=kind):
            return attack_lib.make_attack(
                _kind, K, num_byzantine=spec.num_byzantine,
                scale=spec.scale, agents=spec.agents, seed=spec.seed,
                inner=inner)


_register_attacks()


# -- optimizers -------------------------------------------------------------

for _kind, _factory in (("sgd", sgd), ("momentum", momentum), ("adam", adam)):
    OPTIMIZERS.register(_kind)(
        lambda spec, _f=_factory: _f(**dict(spec.kwargs)))


# -- models -----------------------------------------------------------------

class ModelBundle(NamedTuple):
    """Self-contained model half of an experiment: configuration, the two
    loss conventions (stacked engines vmap 2-arg losses, the sharded engine
    3-arg losses with a per-agent rng), and single-agent initialization."""

    cfg: Any
    loss: Callable[[PyTree, Any], jax.Array]
    loss_rng: Callable[[PyTree, Any, jax.Array], jax.Array]
    init_params: Callable[[jax.Array], PyTree]


@MODELS.register("external")
def _external(spec: ModelSpec):
    return None        # loss supplied by the build() caller


@MODELS.register("transformer")
def _transformer(spec: ModelSpec):
    from repro.configs import get_config          # lazy: keep api import light
    from repro.models import transformer as tf
    bundle = get_config(spec.arch)
    cfg = bundle.smoke if spec.smoke else bundle.model
    # published widths checkpoint every block as their ParallelConfig says
    # (without it K full-width agents' activations overflow one chip); the
    # 2-layer smoke configs keep every activation
    remat = bundle.parallel.remat and not spec.smoke

    def loss(p, b):
        return tf.train_loss(p, cfg, b, remat=remat)

    def loss_rng(p, b, rng):
        return tf.train_loss(p, cfg, b, rng, remat=remat)

    return ModelBundle(cfg=cfg, loss=loss, loss_rng=loss_rng,
                       init_params=lambda k: tf.init_params(k, cfg))


# -- datasets (per-agent block providers) -----------------------------------

def train_block_struct(cfg, *, T: int, K: int, batch: int, seq: int,
                       img_dtype=jnp.float32) -> dict:
    """ShapeDtypeStructs of one (T, K, B, S[, C]) training block — the ONE
    place the engines' block-batch layout is written down.  Every provider
    below and the dryrun compile driver derive their shapes from it, so the
    data path and the roofline path cannot drift."""
    from repro.models import transformer as tf   # lazy: keep api import light
    tok_shape = (T, K, batch, seq)
    if cfg.num_codebooks:
        tok_shape = tok_shape + (cfg.num_codebooks,)
    out = {"tokens": jax.ShapeDtypeStruct(tok_shape, jnp.int32),
           "labels": jax.ShapeDtypeStruct(tok_shape, jnp.int32)}
    if cfg.img_tokens:
        out["img_embeds"] = jax.ShapeDtypeStruct(
            (T, K, batch, cfg.img_tokens, tf.VISION_DIM), img_dtype)
    return out


def _img_embeds(key, struct):
    return jax.random.normal(key, struct["img_embeds"].shape,
                             jnp.float32) * 0.02


@DATASETS.register("iid")
def _iid_provider(dspec: DataSpec, spec: ExperimentSpec, cfg):
    """The legacy synthetic stream: fresh uniform tokens every block, keyed
    ONLY by the block key (the index is ignored).  Key discipline is
    bit-identical to the pre-DataSpec inline ``sample_block``:
    ``k_tok, k_img = split(key)`` — parity-gated by tests/test_api.py."""
    from repro.data.synthetic import lm_token_batch
    run = spec.run
    struct = train_block_struct(cfg, T=run.local_steps, K=run.num_agents,
                                batch=run.batch, seq=run.seq)
    tok_shape = struct["tokens"].shape

    def provider(index: int, key: jax.Array) -> dict:
        k_tok, k_img = jax.random.split(key)
        batch = lm_token_batch(k_tok, tok_shape, cfg.vocab_size)
        if "img_embeds" in struct:
            batch["img_embeds"] = _img_embeds(k_img, struct)
        return batch

    return provider


def _corpus_provider(dspec: DataSpec, spec: ExperimentSpec, cfg,
                     partition_fn):
    """Shared body of the partitioned-corpus kinds: a seeded Zipf
    :class:`~repro.data.pipeline.TokenDataset`, per-agent window partitions
    from ``partition_fn``, and an index-replayable
    :class:`~repro.data.pipeline.BlockIterator` (any block is a pure
    function of ``(data.seed, index, agent)`` — resume needs no data-state
    files)."""
    from repro.data import pipeline as pipe
    run = spec.run
    if cfg.num_codebooks:
        raise ValueError(
            f"data kind {dspec.kind!r} partitions a flat token corpus, "
            "which has no codebook axis — multi-codebook archs take the "
            'synthetic stream (data kind "iid")')
    struct = train_block_struct(cfg, T=run.local_steps, K=run.num_agents,
                                batch=run.batch, seq=run.seq)
    ds = pipe.TokenDataset.synthetic(cfg.vocab_size, dspec.corpus_tokens,
                                     run.seq, seed=dspec.seed)
    parts = partition_fn(pipe, ds.num_windows, run.num_agents)
    it = pipe.BlockIterator(ds, parts, local_steps=run.local_steps,
                            per_agent_batch=run.batch, seed=dspec.seed)

    def provider(index: int, key: jax.Array) -> dict:
        batch = it.block(index)
        if "img_embeds" in struct:
            # same key discipline as "iid": the img stream rides the
            # second split half, the token half is owned by the iterator
            _, k_img = jax.random.split(key)
            batch["img_embeds"] = _img_embeds(k_img, struct)
        return batch

    provider.iterator = it
    provider.partitions = parts
    return provider


@DATASETS.register("dirichlet")
def _dirichlet_provider(dspec: DataSpec, spec: ExperimentSpec, cfg):
    """Label-Dirichlet skew over ``dspec.clusters`` latent classes: corpus
    windows are labeled by contiguous cluster (document locality), then
    dealt to agents by per-class Dirichlet(alpha) draws."""
    def partition(pipe, n_windows, K):
        if n_windows < K:
            raise ValueError(
                f"corpus of {n_windows} windows cannot cover {K} agents — "
                "raise DataSpec.corpus_tokens or shrink RunSpec.seq")
        C = max(1, dspec.clusters)
        labels = (np.arange(n_windows) * C) // n_windows
        return pipe.dirichlet_partition(labels, K, dspec.alpha,
                                        seed=dspec.seed)

    return _corpus_provider(dspec, spec, cfg, partition)


@DATASETS.register("shards")
def _shards_provider(dspec: DataSpec, spec: ExperimentSpec, cfg):
    """Contiguous disjoint shards (document-locality non-IIDness): the
    corpus splits into K x shards_per_agent equal shards, dealt
    ``shards_per_agent`` per agent in a seeded order."""
    def partition(pipe, n_windows, K):
        S = max(1, dspec.shards_per_agent)
        if n_windows < K * S:
            raise ValueError(
                f"corpus of {n_windows} windows cannot cover {K} agents x "
                f"{S} shards — raise DataSpec.corpus_tokens or shrink "
                "RunSpec.seq/DataSpec.shards_per_agent")
        shards = pipe.contiguous_partition(n_windows, K * S)
        deal = np.random.default_rng(dspec.seed).permutation(K * S)
        return [np.concatenate([shards[j] for j in deal[k * S:(k + 1) * S]])
                for k in range(K)]

    return _corpus_provider(dspec, spec, cfg, partition)


def make_block_provider(spec: ExperimentSpec, cfg):
    """Compile ``spec.data`` into ``provider(block_index, key) -> batch``.

    The provider is the data half of the driver loop: TRAIN drivers call it
    with the running block index and the per-block key, so ``kind="iid"``
    reproduces the legacy key-only stream bit-for-bit while the partitioned
    kinds replay any block from its index alone (checkpoint-resume without
    data-state files)."""
    return DATASETS.get(spec.data.kind)(spec.data, spec, cfg)


# -- the entry point --------------------------------------------------------

def build(spec: ExperimentSpec, loss_fn=None, *, engine: str = "auto",
          grad_transform=None, mesh=None):
    """Materialize an engine from a declarative spec.

    Args:
      spec: the experiment description.
      loss_fn: required when ``spec.model.kind == "external"`` — the
        per-agent loss in the convention of the selected engine (2-arg for
        stacked, 3-arg with rng for sharded).  Overrides the model bundle's
        loss when both exist.
      engine: "stacked" | "sharded" | "async" | "auto" (async iff
        ``spec.asynchrony.enabled``, else sharded iff the model spec is
        self-contained).
      grad_transform: explicit gradient-transform override; defaults to the
        optimizer spec ("sgd" means None — exact Algorithm 1).
      mesh: a device mesh with a ``"data"`` axis to shard the agent axis
        over (sharded engine only; see :func:`repro.launch.mesh.
        make_agent_mesh`).  It also informs the "auto" mixer: across
        several devices the combination step is a collective, never the
        single-device Pallas kernel.

    Returns:
      A :class:`~repro.core.diffusion.DiffusionEngine` or
      :class:`~repro.core.sharded.ShardedEngine`, decorated with ``.spec``,
      ``.optimizer`` (the GradTransform), ``.model`` (the
      :class:`ModelBundle` or None) and — when the model is self-contained —
      ``.init_params(key)`` returning the stacked (K, ...) parameter pytree.
    """
    K = spec.run.num_agents
    cfg = spec.to_diffusion_config()
    topology = (TOPOLOGIES.get(spec.topology.kind)(spec.topology, K)
                if K > 1 else None)
    process = PARTICIPATION.get(spec.participation.kind)(spec.participation, K)
    graph = (GRAPHS.get(spec.graph.kind)(spec.graph, topology, K)
             if topology is not None else None)
    # "auto" must not pick the sparse path for graphs that realize edges
    # outside the base support; resolve before the registry lookup
    mix_kind = graph_lib.resolve_mix_for_graph(spec.mixer.kind, graph, mesh)
    if mix_kind == "auto":
        # resolved here, where the mesh is known: the registry builders
        # see only the spec, the topology and K
        mix_kind, _ = mixing.resolve_auto(topology, mesh=mesh)
    mixer = MIXERS.get(mix_kind)(spec.mixer, topology, K)
    graph_lib.check_mixer_support(mixer, graph)
    compressor = COMPRESSORS.get(spec.compression.kind)(spec.compression)
    optimizer = OPTIMIZERS.get(spec.optimizer.kind)(spec.optimizer)
    privacy = privacy_lib.compile_privacy(spec)
    if privacy is not None:
        if grad_transform is not None:
            # same ambiguity class as the attack guard below: silently
            # dropping the clip+noise stage would report a non-private run
            # as private (and misreport the accountant's epsilon)
            raise ValueError(
                "spec.privacy and an explicit grad_transform were both "
                "supplied — compose them yourself via "
                "repro.core.privacy.PrivateGradients(..., inner=...) and "
                "pass its .update as grad_transform, or drop one")
        if (spec.compression.kind == "gauss"
                and not spec.privacy.allow_gauss):
            raise ValueError(
                "spec.privacy with GaussianMask compression double-noises "
                "the exchange: the compressor's sigma is NOT counted by "
                "the accountant, so it is silent utility loss with no "
                "epsilon credit — set PrivacySpec.allow_gauss=True to opt "
                "in deliberately, or drop one of the noise sources")
        # composition order (defined HERE, once): raw grads -> attack
        # corrupts -> privacy clips + noises -> optimizer.  The privacy
        # stage wraps the optimizer first so the attack wrapper below
        # lands outermost — the DP mechanism bounds the influence of
        # whatever gradient an agent computes, Byzantine or honest.
        optimizer = privacy.wrap(optimizer)
    if spec.attack.kind != "none":
        if grad_transform is not None:
            # silently dropping the attack would report an honest network
            # as attacked (and for "noise" leave optimizer.init allocating
            # state the caller's transform cannot consume)
            raise ValueError(
                "spec.attack and an explicit grad_transform were both "
                "supplied — compose them yourself via "
                "repro.core.attacks.make_attack(..., inner=...) and pass "
                "its .update as grad_transform, or drop one")
        # the attack corrupts Byzantine gradients BEFORE the optimizer
        # sees them; the composed transform replaces the optimizer surface
        # (``engine.optimizer.init`` allocates the composed state)
        optimizer = ATTACKS.get(spec.attack.kind)(spec.attack, K, optimizer)
    model = MODELS.get(spec.model.kind)(spec.model)

    if engine == "auto":
        # an enabled AsyncSpec opts the whole experiment into the
        # event-driven engine; otherwise sharded iff self-contained model
        if spec.asynchrony.enabled:
            engine = "async"
        else:
            engine = "sharded" if model is not None else "stacked"
    if engine not in ("stacked", "sharded", "async"):
        raise ValueError(f"unknown engine {engine!r} "
                         "(expected stacked|sharded|async|auto)")
    if mesh is not None and engine != "sharded":
        raise ValueError(f"a device mesh shards the sharded engine's agent "
                         f"axis; engine={engine!r} has none")
    if spec.compression.ef_host_offload and engine != "sharded":
        # the stacked/async engines have no between-block comm memory to
        # park on the host; silently ignoring the flag would report a
        # memory optimization that never ran
        raise ValueError(
            "CompressionSpec.ef_host_offload parks the sharded engine's "
            f"between-block pipeline memory in host RAM; engine={engine!r} "
            "has no such residency to move — use engine='sharded' or drop "
            "the flag")
    if engine != "async" and spec.asynchrony.enabled:
        # silently running a spec that asks for event-driven execution on
        # a bulk-synchronous engine would misreport the experiment
        raise ValueError(
            f"spec.asynchrony.enabled is set but engine={engine!r} was "
            "requested — use engine='async'/'auto', or disable the "
            "asynchrony sub-spec")
    if grad_transform is None and (spec.optimizer.kind != "sgd"
                                   or spec.attack.kind != "none"
                                   or privacy is not None):
        grad_transform = optimizer.update

    if engine == "async":
        # stacked-style 2-arg loss; the staleness buffer replaces the
        # CommPipeline (the engine rejects compression itself)
        loss = loss_fn if loss_fn is not None else (model.loss if model
                                                    else None)
        if loss is None:
            raise ValueError('model kind "external" needs an explicit '
                             "loss_fn (or select a self-contained model "
                             "spec, e.g. kind='transformer')")
        if privacy is not None and privacy.secure_agg:
            raise ValueError(
                "secure-agg wire masks ride the CommPipeline, which the "
                "async engine's staleness buffer replaces — stale masked "
                "payloads from different blocks cannot cancel; drop "
                "PrivacySpec.secure_agg or use a synchronous engine")
        eng = AsyncEngine(cfg, loss, grad_transform,
                          async_spec=spec.asynchrony,
                          participation=process, graph=graph,
                          privacy=privacy)
    elif engine == "stacked":
        loss = loss_fn if loss_fn is not None else (model.loss if model
                                                    else None)
        if loss is None:
            raise ValueError('model kind "external" needs an explicit '
                             "loss_fn (or select a self-contained model "
                             "spec, e.g. kind='transformer')")
        eng = DiffusionEngine(cfg, loss, grad_transform, mixer=mixer,
                              participation=process, compressor=compressor,
                              graph=graph, privacy=privacy)
    else:
        loss = loss_fn if loss_fn is not None else (model.loss_rng if model
                                                    else None)
        if loss is None:
            raise ValueError('model kind "external" needs an explicit '
                             "3-arg loss_fn for the sharded engine")
        eng = ShardedEngine(loss, cfg, topology=topology, mix=mixer,
                            participation=process, compress=compressor,
                            graph=graph, grad_transform=grad_transform,
                            privacy=privacy,
                            ef_host_offload=spec.compression.ef_host_offload,
                            mesh=mesh,
                            agent_axis="data" if mesh is not None else None)

    eng.spec = spec
    eng.optimizer = optimizer
    eng.model = model
    if model is not None:
        def init_params(key, _init=model.init_params, _K=K):
            return jax.vmap(_init)(jax.random.split(key, _K))
        eng.init_params = init_params
        eng.data = make_block_provider(spec, model.cfg)
    return eng
