"""Time-varying combination graphs — the topology as a runtime layer.

The paper motivates partial participation with the volatility of edge
devices; the same volatility hits the *links*: radio fades, switches
reboot, gossip rounds pair random neighbors.  This module makes the
combination matrix a per-block operand rather than a constructor constant:
a :class:`GraphProcess` is a jit-compatible state machine mirroring
:class:`repro.core.schedules.ParticipationProcess`,

    state      = graph.init_state(key)              # pytree (or ())
    A_t, state = graph.sample(state, key)           # (K, K) float32

and the engines thread ``graph_state`` through
:class:`repro.core.state.EngineState` exactly like ``part_state``.  The
realized ``A_t`` flows into the combination step as data — the Mixer
contract is ``mixer(params, active, A_t)`` (:mod:`repro.core.mixing`), so
one compiled program serves every realized topology, exactly as it does
every activation mask.

Processes:

* :class:`StaticGraph` — wraps a validated :class:`~repro.core.topology.
  Topology` (or a raw matrix); ``sample`` returns the same device constant
  every block, so the compiled step is identical to the pre-redesign
  baked-``A`` path (bit-for-bit — gated by ``tests/test_graphs.py``).
* :class:`LinkDropout` — i.i.d. (or Markov-correlated) symmetric edge
  failures on the base adjacency with per-draw Metropolis reweighting, so
  every realized ``A_t`` stays symmetric doubly stochastic over the
  surviving links.  ``corr > 0`` gives bursty link outages (the link-level
  analogue of :class:`~repro.core.schedules.MarkovAvailability`) and makes
  the process stateful: the current link up/down mask lives in
  ``EngineState.graph_state`` and checkpoints with everything else.
* :class:`GossipMatching` — one random pairwise matching of the base graph
  per block (mutual-max priorities), the classic randomized-gossip
  exchange: matched pairs average with weight 1/2, everyone else holds.
* :class:`TimeVaryingErdos` — an independent Erdős–Rényi graph each block
  (Metropolis-weighted); connectivity holds over windows rather than per
  draw, the regime of the time-varying-graph literature (asynchronous
  diffusion, arXiv:2402.05529; coordination-free decentralised FL,
  arXiv:2312.04504).

Every realized matrix is symmetric and doubly stochastic by construction
(property-tested), so the eq.-20 invariants — inactive agents frozen,
network mean preserved — survive any graph draw.

``metropolis_weights_jnp`` is the jit-side twin of
:func:`repro.core.topology.metropolis_weights` (vectorized O(K^2) ops, no
Python loops) used for the per-block reweighting.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topology as topo_lib

PyTree = Any

__all__ = [
    "GraphProcess",
    "StaticGraph",
    "LinkDropout",
    "GossipMatching",
    "TimeVaryingErdos",
    "make_graph_process",
    "metropolis_weights_jnp",
    "check_mixer_support",
    "resolve_mix_for_graph",
]


def metropolis_weights_jnp(off_adj: jax.Array) -> jax.Array:
    """Metropolis–Hastings weights from a {0,1} *off-diagonal* adjacency.

    jit-side twin of :func:`repro.core.topology.metropolis_weights`:
    ``a_lk = 1 / (1 + max(deg_l, deg_k))`` on surviving edges, self weight
    completing each column to one.  ``off_adj`` must be symmetric with a
    zero diagonal; the result is symmetric doubly stochastic for ANY such
    mask, which is what lets the dynamic processes reweight per draw.
    """
    off = off_adj.astype(jnp.float32)
    deg = off.sum(axis=1)
    pair = jnp.maximum(deg[:, None], deg[None, :])
    W = off / (1.0 + pair)
    return W + jnp.diag(1.0 - W.sum(axis=0))


def _sym_uniform(key: jax.Array, K: int) -> jax.Array:
    """Symmetric (K, K) uniform draws with a zero diagonal: one value per
    undirected edge, mirrored, so both endpoints of a link see the same
    randomness (links fail as links, not as two directed arcs)."""
    u = jnp.triu(jax.random.uniform(key, (K, K)), k=1)
    return u + u.T


class GraphProcess:
    """Combination-graph model driving the per-block matrix of Algorithm 1.

    ``stateful`` processes carry their state in ``EngineState.graph_state``
    — ``engine.init_state`` draws the initial state and the unified
    ``engine.step`` threads it; stateless ones leave it ``None``.
    ``within_base_support`` declares that every realized ``A_t`` is zero
    outside the base topology's adjacency (required by the sparse
    circulant mixing backend, which only moves bytes along base offsets).
    Every ``sample`` receives a PRNG key (the engines fold one off the
    block key unconditionally); deterministic processes simply ignore it.
    """

    stateful: bool = False
    within_base_support: bool = True
    name = "base"
    topology: topo_lib.Topology | None = None

    @property
    def num_agents(self) -> int:
        raise NotImplementedError

    def base_matrix(self) -> jax.Array:
        """The (K, K) float32 base matrix (spectral-gap / theory anchor)."""
        raise NotImplementedError

    def init_state(self, key: jax.Array) -> PyTree:
        """Initial process state (drawn from the stationary law)."""
        return ()

    def sample(self, state: PyTree, key: jax.Array):
        """Advance one block: returns ((K, K) float32 A_t, new state)."""
        raise NotImplementedError

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"{type(self).__name__}(K={self.num_agents})"


class StaticGraph(GraphProcess):
    """The paper's fixed topology: every block sees the same matrix.

    ``sample`` returns a closed-over device constant, so under jit the
    compiled program is identical to the pre-redesign baked-``self.A``
    mixers — zero overhead, bit-identical outputs.
    """

    name = "static"

    def __init__(self, topology: topo_lib.Topology | None = None, *, A=None):
        if A is None:
            if topology is None:
                raise ValueError("StaticGraph needs a topology or a matrix A")
            A = topology.A
        self.topology = topology
        self._A = jnp.asarray(A, jnp.float32)

    @property
    def num_agents(self) -> int:
        return int(self._A.shape[0])

    def base_matrix(self) -> jax.Array:
        return self._A

    def sample(self, state: PyTree, key: jax.Array):
        return self._A, state


class LinkDropout(GraphProcess):
    """Random link failures on the base graph, Metropolis-reweighted.

    Each undirected base edge is *up* with probability ``1 - drop`` per
    block; the realized adjacency is reweighted by the Metropolis rule so
    ``A_t`` is symmetric doubly stochastic over the surviving links (an
    agent whose links all failed holds its iterate: self weight 1).

    ``corr`` in [0, 1) makes outages bursty via a two-state Markov chain
    per link with the same stationary up-probability (corr = 0 is i.i.d.;
    the link-level analogue of MarkovAvailability's agent chain).  The
    chain's state — the current {0,1} link mask — is ``graph_state``.

    Note the reweighting is the *Metropolis* rule on the surviving
    adjacency, so at ``drop = 0`` the realized matrix equals
    ``metropolis_weights(base adjacency)`` — the base Topology's own A for
    the metropolis-built kinds (ring/grid/full/erdos), not for ``fedavg``
    (whose base is the averaging matrix).
    """

    name = "link_dropout"

    def __init__(self, topology: topo_lib.Topology, drop: float,
                 corr: float = 0.0):
        if not 0.0 <= drop < 1.0:
            raise ValueError(f"drop={drop} must lie in [0, 1)")
        if not 0.0 <= corr < 1.0:
            raise ValueError(f"corr={corr} must lie in [0, 1)")
        self.topology = topology
        self.drop = float(drop)
        self.corr = float(corr)
        self.stateful = corr > 0.0
        K = topology.num_agents
        off = topology.adjacency & ~np.eye(K, dtype=bool)
        self._base_off = jnp.asarray(off, jnp.float32)
        up = 1.0 - self.drop
        # two-state chain per link, stationary up-probability 1 - drop
        self._p_stay_up = up + self.corr * self.drop
        self._p_up_from_down = (1.0 - self.corr) * up

    @property
    def num_agents(self) -> int:
        return int(self._base_off.shape[0])

    def base_matrix(self) -> jax.Array:
        return jnp.asarray(self.topology.A, jnp.float32)

    def init_state(self, key: jax.Array) -> PyTree:
        if not self.stateful:
            return ()
        u = _sym_uniform(key, self.num_agents)
        return (u < 1.0 - self.drop).astype(jnp.float32) * self._base_off

    def sample(self, state: PyTree, key: jax.Array):
        u = _sym_uniform(key, self.num_agents)
        if not self.stateful:
            up = (u < 1.0 - self.drop).astype(jnp.float32)
            new_state = state
        else:
            # both branches go up on a low-u region so corr = 0 would be
            # exactly state-independent (mirrors MarkovAvailability)
            up = jnp.where(state > 0.5,
                           (u < self._p_stay_up).astype(jnp.float32),
                           (u < self._p_up_from_down).astype(jnp.float32))
            new_state = up * self._base_off
        adj = self._base_off * up
        return metropolis_weights_jnp(adj), new_state

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"LinkDropout(K={self.num_agents}, drop={self.drop}, "
                f"corr={self.corr})")


class GossipMatching(GraphProcess):
    """One random pairwise matching of the base graph per block.

    Every base edge draws a symmetric uniform priority; an edge is matched
    iff it is the maximum-priority edge at BOTH endpoints (mutual-max), so
    the matched set is a valid matching almost surely.  Matched pairs
    average with weight 1/2 each; unmatched agents hold (self weight 1) —
    the classic randomized-gossip exchange (Boyd et al.) on the diffusion
    seam.  Stateless; needs a key.
    """

    name = "gossip"

    def __init__(self, topology: topo_lib.Topology):
        self.topology = topology
        K = topology.num_agents
        off = topology.adjacency & ~np.eye(K, dtype=bool)
        self._base_off = jnp.asarray(off, jnp.float32)

    @property
    def num_agents(self) -> int:
        return int(self._base_off.shape[0])

    def base_matrix(self) -> jax.Array:
        return jnp.asarray(self.topology.A, jnp.float32)

    def sample(self, state: PyTree, key: jax.Array):
        K = self.num_agents
        u = _sym_uniform(key, K) * self._base_off     # priorities on edges
        rowmax = u.max(axis=1)
        matched = ((u > 0)
                   & (u >= rowmax[:, None]) & (u >= rowmax[None, :])
                   ).astype(jnp.float32)
        A = (jnp.eye(K, dtype=jnp.float32)
             - 0.5 * jnp.diag(matched.sum(axis=1)) + 0.5 * matched)
        return A, state


class TimeVaryingErdos(GraphProcess):
    """A fresh Erdős–Rényi graph G(K, p) every block, Metropolis-weighted.

    Edges are i.i.d. across pairs and blocks; a single draw need not be
    connected — information still spreads because the union over a window
    of blocks is connected with overwhelming probability (the B-connected
    regime of the time-varying-graph literature).  Realized matrices may
    put weight on ANY pair, so ``within_base_support`` is False and the
    sparse circulant mixing backend is rejected (use dense / pallas).
    """

    name = "tv_erdos"
    within_base_support = False

    def __init__(self, num_agents: int, p: float = 0.3,
                 topology: topo_lib.Topology | None = None):
        if not 0.0 < p <= 1.0:
            raise ValueError(f"p={p} must lie in (0, 1]")
        if num_agents < 1:
            raise ValueError(f"num_agents={num_agents} must be >= 1")
        self._K = int(num_agents)
        self.p = float(p)
        self.topology = topology

    @property
    def num_agents(self) -> int:
        return self._K

    def base_matrix(self) -> jax.Array:
        if self.topology is not None:
            return jnp.asarray(self.topology.A, jnp.float32)
        # the expected graph is dense: anchor theory on the full topology
        return jnp.asarray(topo_lib.make_topology("full", self._K).A,
                           jnp.float32)

    def sample(self, state: PyTree, key: jax.Array):
        u = _sym_uniform(key, self._K)
        adj = ((u > 0) & (u < self.p)).astype(jnp.float32)
        return metropolis_weights_jnp(adj), state

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"TimeVaryingErdos(K={self._K}, p={self.p})"


# ---------------------------------------------------------------------------
# factory + mixer-compatibility guards (shared by both engines)
# ---------------------------------------------------------------------------

def make_graph_process(kind: "str | GraphProcess",
                       topology: topo_lib.Topology | None = None, *,
                       A=None, num_agents: int | None = None,
                       drop: float = 0.3, corr: float = 0.0,
                       p: float = 0.3) -> GraphProcess:
    """Build a graph process.

    Args:
      kind: "static" | "link_dropout" | "gossip" | "tv_erdos", or an
        existing :class:`GraphProcess` (returned unchanged).
      topology: the base :class:`~repro.core.topology.Topology` (required
        by link_dropout / gossip, optional for tv_erdos, either-or with
        ``A`` for static).
      A: explicit base matrix for the static graph (K = 1 / tests).
      num_agents: K for tv_erdos when no topology is given.
      drop / corr: link_dropout knobs.
      p: tv_erdos per-block edge probability.
    """
    if isinstance(kind, GraphProcess):
        return kind
    if kind == "static":
        if topology is None and A is None and num_agents == 1:
            A = np.eye(1)               # K = 1: mixing disabled anyway
        if topology is None and A is None:
            raise ValueError(
                "the static graph needs a base topology or matrix "
                "(pass topology= or A=) — without one, agents would "
                "silently never communicate")
        return StaticGraph(topology, A=A)
    if kind in ("link_dropout", "gossip") and topology is None:
        raise ValueError(f"graph kind {kind!r} needs a base topology")
    if kind == "link_dropout":
        return LinkDropout(topology, drop=drop, corr=corr)
    if kind == "gossip":
        return GossipMatching(topology)
    if kind == "tv_erdos":
        K = (num_agents if num_agents is not None
             else topology.num_agents if topology is not None else None)
        if K is None:
            raise ValueError("tv_erdos needs num_agents or a topology")
        return TimeVaryingErdos(K, p=p, topology=topology)
    # third-party kinds registered against repro.api.build.GRAPHS resolve
    # here too, so the config-string paths (DiffusionConfig.graph, dryrun
    # --spec, engine rebuilds) reach them exactly like build(spec) does
    try:
        from repro.api.build import GRAPHS
        from repro.api.spec import GraphSpec
    except ImportError:          # pragma: no cover - core without api
        GRAPHS = None
    if GRAPHS is not None and kind in GRAPHS:
        K = (num_agents if num_agents is not None
             else topology.num_agents if topology is not None else None)
        if K is None:
            raise ValueError(f"graph kind {kind!r} needs num_agents or a "
                             "topology")
        return GRAPHS.get(kind)(
            GraphSpec(kind=kind, drop=drop, corr=corr, p=p), topology, K)
    raise ValueError(f"unknown graph kind {kind!r} "
                     "(expected static|link_dropout|gossip|tv_erdos, or a "
                     "kind registered against repro.api.build.GRAPHS)")


def resolve_mix_for_graph(mix, graph: GraphProcess | None, mesh=None):
    """The "auto" mixer policy must not pick the sparse circulant path for
    graphs whose realized edges can leave the base support (tv_erdos) —
    fall back to the always-correct backends instead (the Pallas kernel
    only where the agent axis sits on one device)."""
    if (isinstance(mix, str) and mix == "auto" and graph is not None
            and not graph.within_base_support):
        from repro.core.mixing import spans_devices
        return ("pallas" if jax.default_backend() == "tpu"
                and not spans_devices(mesh) else "dense")
    return mix


def check_mixer_support(mixer, graph: GraphProcess | None) -> None:
    """Reject mixer/graph combinations that would silently drop edges: the
    sparse circulant backend only moves bytes along the base topology's
    offsets, so it requires every realized A_t inside that support.

    Also tunes the sparse backend for the graph: dynamic processes can
    realize matrices whose per-offset coefficient row is all-zero (every
    link at that offset failed this block), so ``skip_dead`` is flipped on
    — each roll/collective-permute is guarded by a segment mask and dead
    offsets are skipped (:func:`repro.core.mixing.mix_sparse`).

    The bounded-degree gather paths carry the same support requirement as
    the sparse backend — the neighbor table only reads base-adjacency
    rows — so :class:`~repro.core.mixing.NeighborGatherMixer` is rejected
    off support, and the robust backends' gather machinery follows the
    ``skip_dead`` convention: an "auto" decision is re-derived per call
    (table attached for ``within_base_support`` graphs with a known base
    topology, detached otherwise; the fused kernel enabled/disabled the
    same way), while an explicit ``gather="table"`` / ``use_kernel=True``
    off support is a build-time error.  The robust backends otherwise
    compose with every graph in both scopes: without a table the
    neighborhood scope reads the realized support per call, so nothing is
    rejected for link_dropout / gossip / tv_erdos.
    """
    from repro.core import mixing  # local: mixing does not import graphs
    on_support = graph is None or graph.within_base_support
    if not on_support and isinstance(mixer, mixing.SparseCirculantMixer):
        raise ValueError(
            f"{type(mixer).__name__} moves bytes only along the base "
            f"topology's circulant offsets, but the {graph.name!r} graph "
            "process realizes edges outside that support — use "
            "mix='dense' or 'pallas'")
    if not on_support and isinstance(mixer, mixing.NeighborGatherMixer):
        raise ValueError(
            f"{type(mixer).__name__} gathers only the base topology's "
            f"neighbor rows, but the {graph.name!r} graph process "
            "realizes edges outside that support — use mix='dense' or "
            "'pallas'")
    if (isinstance(mixer, mixing.SparseCirculantMixer)
            and mixer._skip_dead_auto):
        mixer.skip_dead = (graph is not None
                           and not isinstance(graph, StaticGraph))
    if isinstance(mixer, mixing.FusedNeighborhoodMixer):
        if not on_support and mixer.use_kernel is True:
            raise ValueError(
                f"{type(mixer).__name__}(use_kernel=True) gathers only "
                f"the base topology's neighbor rows, but the "
                f"{graph.name!r} graph process realizes edges outside "
                "that support — use gather='off' (all-slots sort)")
        if mixer._use_kernel_auto:
            mixer.use_kernel = None if on_support else False
        _sync_robust_table(mixer.inner, graph, on_support)
        return
    if isinstance(mixer, mixing._SortedRobustMixer):
        _sync_robust_table(mixer, graph, on_support)


def _sync_robust_table(mixer, graph: GraphProcess | None,
                       on_support: bool) -> None:
    """Attach/detach a robust mixer's neighbor table per the graph, the
    way sparse ``skip_dead`` is re-derived per build: explicit choices
    (``gather="table"``/``"off"``) are never touched, "auto" follows the
    graph."""
    if mixer.scope != "neighborhood":
        return
    explicit = getattr(mixer, "_gather_mode", "auto") != "auto"
    if not on_support:
        if mixer._table is not None:
            if explicit:
                raise ValueError(
                    f"{type(mixer).__name__}(gather='table') gathers only "
                    f"the base topology's neighbor rows, but the "
                    f"{graph.name!r} graph process realizes edges outside "
                    "that support — use gather='off' (all-slots sort)")
            mixer.detach_neighbor_table()
        return
    if (mixer._table is None and not explicit and graph is not None
            and graph.topology is not None):
        mixer.attach_neighbor_table(graph.topology)
