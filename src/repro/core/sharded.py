"""Mesh-sharded execution of Algorithm 1 (the production engine).

Layout: every parameter leaf carries a leading *agent* axis of size K that is
sharded over one mesh axis (``data`` for small/mid models, ``pod`` for models
whose K copies only fit one-per-pod).  Within an agent the remaining mesh
axes provide FSDP/TP sharding of the inner dims (see repro/sharding/rules).

The block step is assembled from the same three layers as the stacked
engine (:mod:`repro.core.diffusion`):

* local updates — the shared :func:`repro.core.diffusion.local_update_scan`,
* combination step — a :class:`repro.core.mixing.CommPipeline`: a pluggable
  compression stage (:mod:`repro.core.compression` — top-k / rand-k / int8 /
  Gaussian mask, optional error feedback) feeding a pluggable
  :class:`repro.core.mixing.Mixer` backend ("dense" einsum / "sparse"
  circulant collective-permute / "pallas" fused kernel; see EXPERIMENTS.md
  §Perf and §Compression),
* activation model — a :class:`repro.core.schedules.ParticipationProcess`
  (i.i.d. Bernoulli by default; Markov / cyclic availability plug in the
  same way).

Both engines speak the SAME step contract:

    block_step(state: EngineState, block_batch, key) -> (EngineState, metrics)

with :class:`repro.core.state.EngineState` bundling
``params / opt_state / part_state / comm_state`` (absent components stay
``None``, so one signature covers every process/compressor combination —
the state is data, not call-shape).

All paths are *data-oblivious*: the activation mask enters as arrays, so one
compiled program serves every activation pattern.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as comp_lib
from repro.core import graphs as graph_lib
from repro.core import mixing
from repro.core import participation as part
from repro.core import schedules
from repro.core import topology as topo_lib
from repro.core.diffusion import (DiffusionConfig, local_update_scan,
                                  resolve_step_mask)
from repro.core.mixing import mix_dense, mix_sparse  # noqa: F401 (compat)
from repro.core.state import (EngineState, check_engine_state,
                              init_engine_state)

PyTree = Any

__all__ = ["mix_dense", "mix_sparse", "make_block_step", "ShardedEngine",
           "ef_host_sharding", "offload_comm_state", "fetch_comm_state"]


# ---------------------------------------------------------------------------
# error-feedback residual host offload (ROADMAP carry-over)
# ---------------------------------------------------------------------------

def ef_host_sharding():
    """The pinned-host sharding of the default device, or ``None`` when the
    backend exposes no pinned-host memory space (then the offload is an
    explicit no-op)."""
    dev = jax.devices()[0]
    if "pinned_host" not in {m.kind for m in dev.addressable_memories()}:
        return None
    return jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")


def _move_comm_state(comm_state: PyTree, memory_kind: str) -> PyTree:
    """Each leaf to ``memory_kind``, keeping its sharding (one device or an
    agent mesh)."""
    if ef_host_sharding() is None or comm_state is None or comm_state == ():
        return comm_state
    return jax.tree.map(
        lambda l: jax.device_put(l, l.sharding.with_memory_kind(memory_kind)),
        comm_state)


def offload_comm_state(comm_state: PyTree) -> PyTree:
    """Move the pipeline memory (EF residual / diff reference) to pinned
    host memory between blocks — frees ~1x params of HBM while the
    model's forward/backward owns the device."""
    return _move_comm_state(comm_state, "pinned_host")


def fetch_comm_state(comm_state: PyTree) -> PyTree:
    """Bring an offloaded pipeline memory back to the default device
    memory ahead of the next block's combination step.  The target names
    the ``device`` memory kind explicitly: a sharding without one keeps
    the buffer's own (host) kind."""
    return _move_comm_state(comm_state, "device")


def make_block_step(
    loss_fn: Callable[[PyTree, Any, jax.Array], jax.Array],
    config: DiffusionConfig,
    A: jax.Array | None = None,
    *,
    mix: str | mixing.Mixer | None = None,
    offsets: Sequence[int] = (),
    grad_transform=None,
    topology=None,
    participation: schedules.ParticipationProcess | None = None,
    graph: "str | graph_lib.GraphProcess | None" = None,
    tile_m: int = 512,
    interpret: bool | None = None,
    trim: int = 1,
    robust_scope: str = "global",
    robust_gather: str = "auto",
    compress: str | comp_lib.Compressor | None = None,
    compress_ratio: float | None = None,
    compress_sigma: float | None = None,
    error_feedback: bool | None = None,
    comm_mode: str | None = None,
    comm_gamma: float | None = None,
    mesh=None,
    agent_axis: str | None = None,
    privacy=None,
    ef_host_offload: bool = False,
) -> Callable:
    """Build the pure block-step function for jit/pjit.

    Args:
      loss_fn: ``loss_fn(agent_params, agent_batch, rng) -> scalar`` —
        a single agent's loss (vmapped over the agent axis internally).
      config: Algorithm 1 hyper-parameters; ``config.num_agents`` must equal
        the leading dim of every param leaf.
      A: (K, K) base combination matrix (device array); optional when
        ``topology`` is given or ``mix`` is already a Mixer.
      mix: mixer backend name (any :func:`repro.core.mixing.make_mixer`
        name) or a prebuilt :class:`repro.core.mixing.Mixer`; defaults to
        ``config.mix``.
      offsets: circulant offsets for the sparse path (derived from
        ``topology`` when omitted).
      grad_transform: optional ``(grads, state, params) -> (updates, state)``
        applied per-agent before the step-size mask.
      topology: the :class:`repro.core.topology.Topology` behind A; enables
        the "auto"/"sparse" backends without passing offsets explicitly.
      participation: activation model; defaults to the paper's i.i.d.
        Bernoulli with the config's q vector.
      graph: combination-graph model — a
        :class:`repro.core.graphs.GraphProcess` or kind name; defaults to
        the config's ``graph`` / ``graph_kwargs`` ("static" wraps the base
        topology, bit-identical to the pre-redesign baked-A step).  The
        realized A_t is sampled per block inside the jitted step; stateful
        graphs thread their link mask through ``EngineState.graph_state``.
      tile_m / interpret: Pallas mixer knobs.
      trim / robust_scope / robust_gather: robust-backend knobs (per-side
        trim count; "global" vs "neighborhood" aggregation scope; and the
        bounded-degree gather policy "auto" | "table" | "fused" | "off"
        for the neighborhood scope — see
        :class:`repro.core.mixing.TrimmedMeanMixer` and
        :func:`repro.core.mixing.make_mixer`).
      compress / compress_ratio / compress_sigma / error_feedback:
        communication-compression stage
        (:func:`repro.core.compression.make_compressor`); ``compress`` also
        accepts a prebuilt Compressor.  Each defaults to the config's field
        of the same name; "none" keeps the step bit-identical to the plain
        mixer.
      comm_mode / comm_gamma: exchange scheme and consensus step of the
        :class:`repro.core.mixing.CommPipeline` (defaults: config fields;
        "auto" picks diff mode for sparsifiers, direct for int8).
      mesh / agent_axis: agent-axis sharding — when a mesh is given,
        mixers that materialize the (K, M) stack pin its agent rows to
        ``agent_axis`` (default "data") via
        :func:`repro.sharding.rules.agent_stack_pspec`, the generic int8
        pipeline keeps the quantized bytes on the wire under GSPMD, and
        "auto" picks a collective backend when the mesh spans several
        devices (:func:`repro.core.mixing.resolve_auto`).
      privacy: compiled :class:`repro.core.privacy.Privacy` tier or None —
        advances the RDP accountant in ``EngineState.privacy_state`` at
        the realized participation rate every block (scaled by the T
        local mechanism invocations per block) and routes the
        combination through the secure-agg wire masks when requested (the
        clip+noise transform arrives pre-composed via ``grad_transform``).
      ef_host_offload: park the pipeline memory (EF residual / diff-mode
        reference — ~1x params) in pinned host memory between blocks.
        The driver calls the returned step's ``offload(state)`` after a
        block and ``fetch(state)`` before the next one; where the backend
        has no pinned-host space both are identity (CPU).  Requires a
        stateful pipeline — requesting it on a stateless one is an error
        (the flag would silently do nothing).

    Returns:
      The unified-contract step function
      ``block_step(state: EngineState, block_batch, key) ->
      (EngineState, metrics)`` with ``metrics["active"]`` the realized (K,)
      mask.  Param leaves are (K, ...) and block-batch leaves (T, K, ...).
      The returned function carries ``.pipeline`` (the CommPipeline),
      ``.process`` (the ParticipationProcess), ``.config``, and
      ``.init_state(params, opt_state=None, key=None)`` which bundles the
      initial state (stateful components allocated, absent ones ``None``).
    """
    K = config.num_agents
    process, q_np = schedules.resolve(config, participation)
    q = jnp.asarray(q_np, dtype=jnp.float32)
    mix_name = mix if mix is not None else config.mix
    mixer = mixing.make_mixer(mix_name, topology, A=A,
                              offsets=tuple(offsets) or None,
                              num_agents=K, tile_m=tile_m,
                              interpret=interpret, trim=trim,
                              scope=robust_scope, gather=robust_gather,
                              mesh=mesh)
    A_graph = A
    if topology is None and A is None and not mixer.uses_matrix:
        # mixers that ignore the matrix operand (K = 1 / robust server
        # aggregation) run against an inert identity; matrix-consuming
        # mixers without a topology still fail loudly in the graph build
        A_graph = jnp.eye(K, dtype=jnp.float32)
    graph_proc = graph_lib.make_graph_process(
        graph if graph is not None else config.graph, topology, A=A_graph,
        num_agents=K, **dict(config.graph_kwargs))
    resolved = graph_lib.resolve_mix_for_graph(mix_name, graph_proc, mesh)
    if resolved is not mix_name:
        # "auto" picked the sparse path before the graph was known; the
        # realized edges can leave the base support, so rebuild on the
        # always-correct backend
        mixer = mixing.make_mixer(resolved, topology, A=A, num_agents=K,
                                  tile_m=tile_m, interpret=interpret,
                                  trim=trim, scope=robust_scope,
                                  gather=robust_gather, mesh=mesh)
    graph_lib.check_mixer_support(mixer, graph_proc)
    if mesh is not None:
        mixer.shard_agent_axis(mesh, agent_axis or "data")
    compressor = comp_lib.make_compressor(
        compress if compress is not None else config.compress,
        ratio=(compress_ratio if compress_ratio is not None
               else config.compress_ratio),
        error_feedback=(error_feedback if error_feedback is not None
                        else config.error_feedback),
        sigma=(compress_sigma if compress_sigma is not None
               else config.compress_sigma))
    pipeline = mixing.CommPipeline(
        mixer, compressor,
        mode=comm_mode if comm_mode is not None else config.comm_mode,
        gamma=comm_gamma if comm_gamma is not None else config.comm_gamma,
        base_A=topology.A if topology is not None else A, mesh=mesh,
        secure_agg=(privacy.make_mask_stage() if privacy is not None
                    else None))
    if ef_host_offload and not pipeline.stateful:
        raise ValueError(
            "ef_host_offload requires a stateful pipeline (error feedback "
            "or a diff-mode compressor) — this pipeline carries no "
            "between-block memory to offload")
    mask_topo = topology
    if mask_topo is None and config.local_steps_mode != "uniform":
        if A is None:
            raise ValueError(
                "local_steps_mode='degree' reads per-agent degrees — pass "
                "topology= (or the base matrix A)")
        A_np = np.asarray(A)
        mask_topo = topo_lib.Topology(name="from_A", A=A_np,
                                      adjacency=A_np != 0)
    step_mask = resolve_step_mask(config, mask_topo)
    grad_fn = jax.vmap(jax.grad(loss_fn), in_axes=(0, 0, 0))

    # key_comm / key_graph come from fold_ins (not a wider split) so the
    # activation and loss key streams are unchanged vs the uncompressed /
    # static-topology step
    def block_step(state: EngineState, block_batch, key):
        check_engine_state(process, pipeline, compressor, state,
                           "block_step.init_state", graph=graph_proc,
                           privacy=privacy)
        # the scope names label the step's layers in the compiled
        # program's op metadata, where a profiler trace is attributed
        with jax.named_scope("sample"):
            key_act, key_loss = jax.random.split(key)
            key_comm = jax.random.fold_in(key, 0xC0)
            active, part_state = process.sample(state.part_state, key_act)
            A_t, graph_state = graph_proc.sample(
                state.graph_state, jax.random.fold_in(key, 0x9A))
            mus = part.step_size_matrix(config.step_size, active, q,
                                        config.drift_correction)
        params, opt_state = local_update_scan(
            grad_fn, state.params, state.opt_state, mus, block_batch,
            local_steps=config.local_steps, grad_transform=grad_transform,
            loss_key=key_loss, num_agents=K, step_mask=step_mask)
        with jax.named_scope("combine"):
            params, comm_state = pipeline(params, active, A_t,
                                          state.comm_state, key_comm)
        metrics = {"active": active}
        privacy_state = state.privacy_state
        if privacy is not None:
            privacy_state = privacy.advance(privacy_state, active)
            metrics["epsilon"] = privacy.epsilon(privacy_state)
        new_state = EngineState(params, opt_state, part_state, comm_state,
                                graph_state, privacy_state=privacy_state)
        return new_state, metrics

    def init_state(params, opt_state=None, *, key=None) -> EngineState:
        return init_engine_state(process, pipeline, params, opt_state,
                                 key=key, graph=graph_proc,
                                 privacy=privacy)

    def offload(state: EngineState) -> EngineState:
        if not ef_host_offload:
            return state
        return state.replace(comm_state=offload_comm_state(state.comm_state))

    def fetch(state: EngineState) -> EngineState:
        if not ef_host_offload:
            return state
        return state.replace(comm_state=fetch_comm_state(state.comm_state))

    block_step.pipeline = pipeline
    block_step.process = process
    block_step.graph = graph_proc
    block_step.config = config
    block_step.privacy = privacy
    block_step.init_state = init_state
    block_step.step_mask = step_mask
    block_step.ef_host_offload = ef_host_offload
    block_step.offload = offload
    block_step.fetch = fetch
    return block_step


class ShardedEngine:
    """Engine-shaped wrapper over :func:`make_block_step` so the sharded
    path exposes the exact object surface of
    :class:`repro.core.diffusion.DiffusionEngine`:

        state = engine.init_state(params, opt_state, key=...)
        state, metrics = engine.step(state, block_batch, key)

    All keyword arguments are forwarded to :func:`make_block_step`.
    ``engine.step`` is the pure block-step function itself (jit/pjit it
    directly; shard the EngineState components like their leaves).
    """

    def __init__(self, loss_fn, config: DiffusionConfig, A=None, **kwargs):
        self.config = config
        self.step = make_block_step(loss_fn, config, A, **kwargs)
        self.pipeline = self.step.pipeline
        self.process = self.step.process
        self.graph = self.step.graph
        self.privacy = self.step.privacy
        self.init_state = self.step.init_state
        self.step_mask = self.step.step_mask
        self.ef_host_offload = self.step.ef_host_offload
        self.offload = self.step.offload
        self.fetch = self.step.fetch

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"ShardedEngine(K={self.config.num_agents}, "
                f"pipeline={self.pipeline!r})")
