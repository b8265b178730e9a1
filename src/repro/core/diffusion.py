"""Algorithm 1 — diffusion learning with local updates and partial agent
participation (paper eq. 25) — stacked-agent execution engine.

All K agents live on the leading axis of every parameter leaf.  One *block
step* performs:

  1. sample the activation mask from the participation process (eq. 18 by
     default) and realized step sizes (eq. 18 / eq. 31 with drift
     correction),
  2. ``T`` local stochastic-gradient updates via the shared
     :func:`local_update_scan` (eq. 17 with A_{iT+t} = I for t != T),
  3. one combination step through the engine's
     :class:`repro.core.mixing.CommPipeline` — a pluggable compressor stage
     (:mod:`repro.core.compression`: top-k / rand-k / int8 / Gaussian mask,
     optional error feedback) feeding a pluggable :class:`~repro.core.mixing`
     backend (eq. 20).

Steps 1 and 3 are pluggable: the activation model is any
:class:`repro.core.schedules.ParticipationProcess` and the combination step
any compressor + :class:`repro.core.mixing.Mixer` combination (dense einsum,
sparse circulant, or the fused Pallas kernel; with ``compress="none"`` the
pipeline is bit-identical to the plain mixer).  This engine is exact
Algorithm 1 and is what the paper-reproduction benchmarks and
theory-validation tests run.  The mesh-sharded engine with identical
semantics lives in :mod:`repro.core.sharded`; both consume the same
scan/pipeline/process layers.

State threading: both engines share ONE step contract,

    engine.step(state: EngineState, block_batch, key) -> (EngineState, metrics)

where :class:`repro.core.state.EngineState` bundles
``params / opt_state / part_state / comm_state`` (absent components are
``None``).  Construct the state with :meth:`DiffusionEngine.init_state`;
:meth:`DiffusionEngine.run` does so automatically.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression
from repro.core import graphs as graph_lib
from repro.core import mixing
from repro.core import participation as part
from repro.core import schedules
from repro.core import topology as topo_lib
from repro.core.mixing import mix_dense as mix_stacked  # noqa: F401 (compat)
from repro.core.state import (EngineState, check_engine_state,
                              init_engine_state)

PyTree = Any
LossFn = Callable[[PyTree, Any], jax.Array]   # (agent_params, agent_batch) -> scalar

__all__ = ["DiffusionConfig", "DiffusionEngine", "EngineState",
           "degree_local_steps", "local_steps_mask", "local_update_scan",
           "mix_stacked", "network_msd", "resolve_step_mask"]


@dataclasses.dataclass(frozen=True)
class DiffusionConfig:
    """Hyper-parameters of Algorithm 1."""

    num_agents: int
    local_steps: int = 1                 # T
    step_size: float = 0.01              # mu
    topology: str = "ring"               # ring|grid|full|fedavg|erdos
    topology_kwargs: tuple = ()          # extra kwargs as sorted (k, v) pairs
    graph: str = "static"                # static|link_dropout|gossip|tv_erdos
    graph_kwargs: tuple = ()             # graph-process kwargs, sorted (k, v)
    participation: Any = 1.0             # scalar or length-K sequence of q_k
    drift_correction: bool = False       # eq. (31): mu/q_k for active agents
    mix: str = "dense"                   # dense|sparse|pallas|auto|none
    compress: str = "none"               # none|topk|randk|int8|gauss
    compress_ratio: float = 1.0          # kept fraction (topk/randk/gauss)
    compress_sigma: float = 0.0          # Gaussian-mask noise scale (gauss)
    error_feedback: bool = False         # EF residual memory (direct mode)
    comm_mode: str = "auto"              # auto|identity|direct|diff
    comm_gamma: Any = None               # consensus step (None: auto)
    local_steps_mode: str = "uniform"    # uniform|degree (per-agent T_k)

    def q_vector(self) -> np.ndarray:
        q = np.asarray(self.participation, dtype=np.float64)
        if q.ndim == 0:
            q = np.full((self.num_agents,), float(q))
        if q.shape != (self.num_agents,):
            raise ValueError(f"participation shape {q.shape} != ({self.num_agents},)")
        if ((q < 0) | (q > 1)).any():
            raise ValueError("participation probabilities must lie in [0, 1]")
        if self.drift_correction and (q <= 0).any():
            raise ValueError("drift correction requires q_k > 0")
        return q

    def make_topology(self) -> topo_lib.Topology:
        return topo_lib.make_topology(
            self.topology, self.num_agents, **dict(self.topology_kwargs))

    def make_graph(self, topology: topo_lib.Topology | None = None):
        """The :class:`repro.core.graphs.GraphProcess` this config denotes
        (the static wrapper of the base topology by default)."""
        topo = topology if topology is not None else self.make_topology()
        return graph_lib.make_graph_process(
            self.graph, topo, num_agents=self.num_agents,
            **dict(self.graph_kwargs))


def _bshape(v: jax.Array, leaf: jax.Array) -> jax.Array:
    """Reshape a (K,) vector for broadcasting against a (K, ...) leaf."""
    return v.reshape((v.shape[0],) + (1,) * (leaf.ndim - 1))


def degree_local_steps(topology, local_steps: int) -> np.ndarray:
    """Per-agent local-update counts for ``local_steps_mode="degree"``.

    ``T_k = max(1, round(T * d_min / d_k))`` — compute scales inversely
    with degree, so hubs (which communicate the most) drift the least
    toward their local optimum while leaves keep the full T.  On a regular
    graph every ``d_k = d_min`` and the law collapses to the uniform T
    (bit-identical to ``local_steps_mode="uniform"``).
    """
    K = topology.num_agents
    off = np.asarray(topology.adjacency, dtype=bool) & ~np.eye(K, dtype=bool)
    deg = np.maximum(off.sum(axis=1), 1)
    return np.maximum(
        1, np.round(local_steps * deg.min() / deg)).astype(np.int32)


def local_steps_mask(t_k: np.ndarray, local_steps: int) -> jax.Array:
    """(T, K) step mask: row t is 1 for agents still updating at local
    step t (``t < T_k``), 0 once frozen — eq. 17 with early identity
    updates, keeping the scan length uniform."""
    t_k = np.asarray(t_k)
    mask = np.arange(local_steps)[:, None] < t_k[None, :]
    return jnp.asarray(mask.astype(np.float32))


def local_update_scan(grad_fn, params: PyTree, opt_state: PyTree,
                      mus: jax.Array, block_batch: PyTree, *,
                      local_steps: int, grad_transform=None,
                      loss_key: jax.Array | None = None,
                      num_agents: int | None = None,
                      step_mask: jax.Array | None = None):
    """The T local stochastic-gradient updates of Algorithm 1 (eq. 17).

    The single scan body shared by ALL execution engines (stacked,
    mesh-sharded, async) — any change to the local-update semantics lands
    here once.

    Args:
      grad_fn: vmapped per-agent gradient.  Two calling conventions:
        ``grad_fn(params, batch_t)`` when ``loss_key`` is None, or
        ``grad_fn(params, batch_t, rngs)`` with per-agent rng keys folded
        from ``loss_key`` at each local step (stochastic losses: dropout,
        remat policies, ...).
      params / opt_state: stacked (K, ...) pytrees.
      mus: (K,) realized per-agent step sizes (already activation-masked).
      block_batch: pytree with leaves (T, K, ...).
      local_steps: T.
      grad_transform: optional ``(grads, state, params) -> (updates, state)``.
      loss_key: enables the 3-arg grad_fn convention.
      num_agents: K, required when ``loss_key`` is given.
      step_mask: optional (T, K) per-step freeze mask (see
        :func:`local_steps_mask`): at local step t, agent k updates only
        while ``step_mask[t, k] != 0`` — afterwards both its parameters
        AND its optimizer state take the identity update (eq. 17's
        A_{iT+t} = I applied early), so a frozen agent is bit-identical
        to one whose scan ended at T_k.  ``None`` (the default) is the
        uniform-T path, unchanged from before this knob existed.
    Returns:
      (params, opt_state) after T updates.
    """
    def local_step(carry, xs):
        p, s = carry
        if step_mask is not None:
            xs, mask_t = xs
        if loss_key is None:
            batch_t = xs
            grads = grad_fn(p, batch_t)
        else:
            batch_t, t = xs
            rngs = jax.random.split(jax.random.fold_in(loss_key, t),
                                    num_agents)
            grads = grad_fn(p, batch_t, rngs)
        with jax.named_scope("apply"):
            if grad_transform is not None:
                updates, s_new = grad_transform(grads, s, p)
            else:
                updates, s_new = grads, s
            m = mus if step_mask is None else mus * mask_t.astype(mus.dtype)
            p = jax.tree.map(
                lambda w, g: (w - _bshape(m, w).astype(w.dtype)
                              * g.astype(w.dtype)), p, updates)
            if step_mask is not None and grad_transform is not None:
                # identity update for frozen agents extends to the
                # optimizer state; leaves without the (K, ...) agent axis
                # (global counters, e.g. the privacy mechanism index)
                # advance as usual
                def keep_frozen(n, o):
                    if n.ndim >= 1 and n.shape[0] == mask_t.shape[0]:
                        return jnp.where(_bshape(mask_t, n).astype(bool),
                                         n, o)
                    return n
                s = jax.tree.map(keep_frozen, s_new, s)
            else:
                s = s_new
        return (p, s), None

    if loss_key is None:
        xs = block_batch
    else:
        if num_agents is None:
            raise ValueError("loss_key requires num_agents")
        xs = (block_batch, jnp.arange(local_steps))
    if step_mask is not None:
        xs = (xs, step_mask)
    # named scopes label the layers in the compiled program's op metadata;
    # forward, backward and recompute are marked there by JAX itself
    # (``jvp(``, ``transpose(``, ``rematted_computation``)
    with jax.named_scope("local_update"):
        (params, opt_state), _ = jax.lax.scan(
            local_step, (params, opt_state), xs, length=local_steps)
    return params, opt_state


def resolve_step_mask(config: DiffusionConfig,
                      topology) -> jax.Array | None:
    """The (T, K) freeze mask a config's ``local_steps_mode`` denotes.

    ``None`` for the uniform mode — and also for a degree law that
    collapses to uniform (regular graphs), so the scan runs the exact
    pre-mask code path (bit-parity) whenever the mask would be all-ones.
    """
    mode = config.local_steps_mode
    if mode == "uniform":
        return None
    if mode != "degree":
        raise ValueError(f"unknown local_steps_mode {mode!r} — valid "
                         "modes: ['degree', 'uniform']")
    t_k = degree_local_steps(topology, config.local_steps)
    if (t_k == config.local_steps).all():
        return None
    return local_steps_mask(t_k, config.local_steps)


class DiffusionEngine:
    """Stacked-agent executor for Algorithm 1.

    Args:
      config: diffusion hyper-parameters.
      loss_fn: per-agent scalar loss ``loss_fn(params, batch)`` where
        ``params`` is a single agent's pytree and ``batch`` one agent's
        minibatch.  The engine vmaps it across the agent axis.
      grad_transform: optional per-agent gradient transformation applied
        *before* the step-size mask (e.g. momentum).  Signature
        ``(grads, opt_state, params) -> (updates, opt_state)``; default
        identity (plain SGD, as in the paper).
      mixer: combination-step backend — a mixing.Mixer instance or a name
        for :func:`repro.core.mixing.make_mixer`; defaults to ``config.mix``
        ("dense": exact paper baseline).
      participation: activation model — a schedules.ParticipationProcess;
        defaults to the paper's i.i.d. Bernoulli with the config's q vector.
        Stateful processes carry their state in ``EngineState.part_state``
        (:meth:`init_state` seeds it; ``run`` threads it automatically).
      compressor: communication-compression stage — a
        compression.Compressor; defaults to the config's ``compress`` /
        ``compress_ratio`` / ``error_feedback`` fields ("none": bit-identical
        to the plain mixer).  Stateful pipelines (error feedback, diff mode)
        carry their memory in ``EngineState.comm_state`` the same way.
      graph: combination-graph model — a graphs.GraphProcess or a kind name
        for :func:`repro.core.graphs.make_graph_process`; defaults to the
        config's ``graph`` / ``graph_kwargs`` fields ("static": the base
        topology every block, bit-identical to the pre-redesign baked-A
        path).  The realized per-block matrix A_t flows into the
        combination step as data; stateful graphs (correlated link
        dropout) carry their link mask in ``EngineState.graph_state``.
      privacy: compiled differential-privacy tier — a
        :class:`repro.core.privacy.Privacy` or None (non-private, the
        default).  The engine advances its RDP accountant every block at
        the realized participation rate, scaled by the T local mechanism
        invocations the block composes (``EngineState.privacy_state``)
        and routes the combination step through the secure-agg wire masks
        when the tier requests them; the clip+noise gradient transform
        itself arrives pre-composed via ``grad_transform`` (``build()``
        owns the composition order).
    """

    def __init__(self, config: DiffusionConfig, loss_fn: LossFn,
                 grad_transform=None, *, mixer=None, participation=None,
                 compressor=None, graph=None, privacy=None):
        self.config = config
        self.loss_fn = loss_fn
        self.grad_transform = grad_transform
        self.topology = config.make_topology()
        self.process, q = schedules.resolve(config, participation)
        self._q = jnp.asarray(q, dtype=jnp.float32)
        self.graph = graph_lib.make_graph_process(
            graph if graph is not None else config.graph, self.topology,
            num_agents=config.num_agents, **dict(config.graph_kwargs))
        self.mixer = mixing.make_mixer(
            graph_lib.resolve_mix_for_graph(
                mixer if mixer is not None else config.mix, self.graph),
            self.topology, num_agents=config.num_agents)
        graph_lib.check_mixer_support(self.mixer, self.graph)
        if compressor is None:
            compressor = compression.make_compressor(
                config.compress, ratio=config.compress_ratio,
                error_feedback=config.error_feedback,
                sigma=config.compress_sigma)
        self.privacy = privacy
        self.pipeline = mixing.CommPipeline(
            self.mixer, compressor, mode=config.comm_mode,
            gamma=config.comm_gamma, base_A=self.topology.A,
            secure_agg=(privacy.make_mask_stage() if privacy is not None
                        else None))
        self.compressor = self.pipeline.compressor
        self.step_mask = resolve_step_mask(config, self.topology)
        self._grad_fn = jax.vmap(jax.grad(loss_fn))

    # -- state construction -------------------------------------------------
    def init_state(self, params: PyTree, opt_state: PyTree = None, *,
                   key: jax.Array | None = None) -> EngineState:
        """Bundle the initial :class:`EngineState` for :meth:`step`.

        Fills ``part_state`` (stateful participation processes draw their
        initial state from ``key``), ``comm_state`` (stateful pipelines
        allocate the EF residual / diff-mode reference, shaped like
        ``params``), and ``graph_state`` (stateful graph processes draw
        their initial link mask); components the engine does not carry
        stay ``None``.
        """
        return init_engine_state(self.process, self.pipeline, params,
                                 opt_state, key=key, graph=self.graph,
                                 privacy=self.privacy)

    # -- the single block iteration (jit-compatible) ------------------------
    @partial(jax.jit, static_argnums=0)
    def step(self, state: EngineState, block_batch: PyTree,
             key: jax.Array):
        """One block iteration of Algorithm 1 — the unified step contract.

        Args:
          state: :class:`EngineState` with ``params`` leaves (K, ...) (see
            :meth:`init_state`).
          block_batch: pytree with leaves (T, K, ...) — one minibatch per
            agent per local step.
          key: PRNG key for this block (activation sampling + any
            key-consuming compressor).
        Returns:
          ``(new_state, metrics)`` with ``metrics["active"]`` the realized
          (K,) activation mask.
        """
        cfg = self.config
        check_engine_state(self.process, self.pipeline, self.compressor,
                           state, "engine.init_state", graph=self.graph,
                           privacy=self.privacy)
        key_act, key_comm = jax.random.split(key)
        active, part_state = self.process.sample(state.part_state,
                                                 key_act)       # eq. (18)
        # the graph key is a fold, not a wider split, so the activation /
        # compression key streams are unchanged vs the static-topology step
        A_t, graph_state = self.graph.sample(state.graph_state,
                                             jax.random.fold_in(key, 0x9A))
        mus = part.step_size_matrix(cfg.step_size, active, self._q,
                                    cfg.drift_correction)       # (K,)
        params, opt_state = local_update_scan(
            self._grad_fn, state.params, state.opt_state, mus, block_batch,
            local_steps=cfg.local_steps, grad_transform=self.grad_transform,
            step_mask=self.step_mask)
        params, comm_state = self.pipeline(params, active, A_t,
                                           state.comm_state,
                                           key_comm)            # eq. (20)
        metrics = {"active": active}
        privacy_state = state.privacy_state
        if self.privacy is not None:
            privacy_state = self.privacy.advance(privacy_state, active)
            metrics["epsilon"] = self.privacy.epsilon(privacy_state)
        new_state = EngineState(params, opt_state, part_state, comm_state,
                                graph_state, privacy_state=privacy_state)
        return new_state, metrics

    # -- convenience runner -------------------------------------------------
    def run(self, params: PyTree, sampler: Callable[[jax.Array], PyTree],
            num_blocks: int, seed: int = 0, opt_state: PyTree = None,
            w_star: PyTree | None = None):
        """Run ``num_blocks`` block iterations.

        ``sampler(key)`` must return a block batch with leaves (T, K, ...).
        If ``w_star`` is given, records per-block network MSD
        ``(1/K) sum_k ||w_k - w_star||^2``.
        Returns (params, opt_state, msd_history list).
        """
        key = jax.random.PRNGKey(seed)
        state = self.init_state(params, opt_state,
                                key=jax.random.fold_in(key, 0x5EED))
        history = []
        for _ in range(num_blocks):
            key, k_batch, k_step = jax.random.split(key, 3)
            state, _ = self.step(state, sampler(k_batch), k_step)
            if w_star is not None:
                history.append(float(network_msd(state.params, w_star)))
        return state.params, state.opt_state, history


def network_msd(params: PyTree, w_star: PyTree) -> jax.Array:
    """(1/K) sum_k ||w_k - w*||^2 over all leaves (stacked layout)."""
    sq = 0.0
    K = None
    for p, w in zip(jax.tree.leaves(params), jax.tree.leaves(w_star)):
        K = p.shape[0]
        diff = p - jnp.broadcast_to(w, p.shape)
        sq = sq + jnp.sum(diff.astype(jnp.float32) ** 2)
    return sq / K
