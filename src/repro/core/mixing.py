"""Pluggable mixing backends for the combination step (paper eq. 20).

Every backend implements the same contract: given an agent-stacked parameter
pytree with leaves ``(K, ...)``, an activation mask ``(K,)``, and the
*realized* per-block combination matrix ``A_t`` (a device operand sampled
each block by a :class:`repro.core.graphs.GraphProcess` — the topology is a
runtime value, not a constructor constant), apply the per-sample-path
masked combination matrix

    w_k  <-  sum_l  a_lk(mask, A_t)  psi_l .

Backends differ only in *how* the contraction is executed:

* :class:`DenseMixer` — einsum against the realized (K, K) matrix.  GSPMD
  lowers this to an all-gather over the agent axis.  Paper-faithful baseline,
  valid for any topology.
* :class:`SparseCirculantMixer` — decompose the masked matrix into circulant
  offsets and use ``jnp.roll`` along the agent axis (collective-permute under
  GSPMD).  Communication drops from O(K |w|) to O(deg |w|) bytes.
* :class:`PallasFusedMixer` — flatten the pytree to one padded (K, M) buffer
  and run the fused Pallas kernel (:mod:`repro.kernels.diffusion_mix`) that
  rebuilds the eq.-20 mask in VMEM and streams the parameters exactly once.
  The flatten/unflatten layout is computed once per (treedef, shapes) and
  cached across steps.
* :class:`NeighborGatherMixer` — the bounded-degree path for K >= 1024:
  each target row gathers its D = dmax + 1 contributor rows through the
  static neighbor table of the base topology
  (:meth:`repro.core.topology.Topology.neighbor_table`) — O(K dmax M)
  instead of the dense O(K^2 M), with no (K, K) matmul operand.  Valid for
  any graph process that stays ``within_base_support``.  On TPU it runs
  the fused Pallas gather kernel over the cached flatten layout.
* :class:`NullMixer` — identity (K = 1, or mixing disabled).
* :class:`TrimmedMeanMixer` / :class:`CoordinateMedianMixer` — robust
  (Byzantine-tolerant) order-statistic aggregation à la SLSGD
  (arXiv:1903.06996); non-linear, so they pair with ``compress="none"``
  only.  ``scope="global"`` is the SLSGD server setting (one aggregate
  over the whole realized active set, the topology ignored);
  ``scope="neighborhood"`` aggregates per agent over the support of its
  row of the realized ``A_t`` intersected with the active mask — the
  decentralized setting the paper's eq. 20 actually describes, composing
  with every dynamic :class:`repro.core.graphs.GraphProcess`.
* :class:`AdaptiveTrimMixer` — trimmed mean whose per-side trim count is
  *estimated per coordinate* from a MAD outlier fence over the realized
  contributor set (capped at ``trim``); with no attack it reduces to the
  plain mean, so the robustness tax of the fixed trim disappears.

Use :func:`make_mixer` to construct one; ``"auto"`` picks the Pallas kernel
on TPU when the agent axis sits on one device, and otherwise the sparse
path for bounded-degree topologies (a collective-permute across devices).
Benchmarked head-to-head by ``benchmarks.run bench_mix_backends`` (see
EXPERIMENTS.md §Perf).

The combination step itself is a staged :class:`CommPipeline`

    encode (Compressor) --> exchange/combine (Mixer) --> correct

so compressed communication (top-k / rand-k sparsification, int8
stochastic quantization, Gaussian masking — :mod:`repro.core.compression`)
plugs in front of any mixing backend without touching the Mixer contract.
With the identity compressor the pipeline IS the mixer (bit-identical);
with the int8 compressor and the Pallas mixer the encode and combine stages
fuse into :func:`repro.kernels.diffusion_mix.diffusion_mix_int8`, streaming
the quantized ``(K, M)`` buffer once.  See EXPERIMENTS.md §Compression.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as comp_lib
from repro.core import participation as part
from repro.core import topology as topo_lib

PyTree = Any

__all__ = [
    "Mixer",
    "NullMixer",
    "DenseMixer",
    "SparseCirculantMixer",
    "PallasFusedMixer",
    "NeighborGatherMixer",
    "FusedNeighborhoodMixer",
    "TrimmedMeanMixer",
    "CoordinateMedianMixer",
    "AdaptiveTrimMixer",
    "CommPipeline",
    "choco_gamma",
    "make_mixer",
    "make_pipeline",
    "resolve_auto",
    "spans_devices",
    "mix_dense",
    "mix_sparse",
    "mix_gather",
    "count_live_offsets",
]

# sparse cost is one full-parameter roll+multiply PER DISTINCT CIRCULANT
# OFFSET (not per neighbor): beyond this many offsets the decomposition moves
# as many bytes as the dense all-gather, so "auto" falls back — to the
# bounded-degree gather path when the base degree leaves headroom over K,
# else dense
_AUTO_SPARSE_MAX_OFFSETS = 8

# the neighbor-table gather does K * (dmax + 1) row reads vs the dense
# path's K^2; require 2x headroom before "auto" prefers it (below that the
# gather bookkeeping does not pay for itself)
_AUTO_GATHER_HEADROOM = 2

# all-slots neighborhood sort above this K is the O(K^2 M log K) path the
# gather table exists to avoid — warn (once per mixer) when it runs anyway
_NEIGHBORHOOD_WARN_K = 512


# ---------------------------------------------------------------------------
# functional primitives (shared by the Mixer classes and legacy call sites)
# ---------------------------------------------------------------------------

def _tree_sq_norm(tree: PyTree) -> jax.Array:
    """Sum of squares over every leaf (float32 scalar)."""
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
               for l in jax.tree.leaves(tree))


def choco_gamma(spectral_gap: float, delta: float, beta: float) -> float:
    """The CHOCO-Gossip consensus step size (Koloskova et al. 2019, Thm. 2):

        gamma* = rho^2 delta / (16 rho + rho^2 + 4 beta^2
                                + 2 rho beta^2 - 8 rho delta)

    with ``rho`` the spectral gap 1 - |lambda_2(A)|, ``delta`` the
    compressor contraction (E||C(x) - x||^2 <= (1 - delta)||x||^2), and
    ``beta = ||I - A||_2``.  Provably convergent for any topology /
    compressor pair, and famously conservative — the adaptive pipeline
    uses it as the FLOOR and anneals toward 1 from the observed
    contraction (see :class:`CommPipeline`).
    """
    rho = float(spectral_gap)
    delta = float(delta)
    beta = float(beta)
    denom = (16.0 * rho + rho ** 2 + 4.0 * beta ** 2
             + 2.0 * rho * beta ** 2 - 8.0 * rho * delta)
    return float(np.clip(rho ** 2 * delta / max(denom, 1e-12), 1e-4, 1.0))

def mix_dense(A_eff: jax.Array, params: PyTree) -> PyTree:
    """Combination step  w_k <- sum_l a_lk psi_l  over stacked agents.

    In stacked form with leaves (K, ...), this is ``w' = A_eff^T w``.
    """
    def mix_leaf(p: jax.Array) -> jax.Array:
        flat = p.reshape(p.shape[0], -1)
        mixed = jnp.einsum("lk,lm->km", A_eff.astype(flat.dtype), flat)
        return mixed.reshape(p.shape)
    return jax.tree.map(mix_leaf, params)


def mix_sparse(A_eff: jax.Array, params: PyTree,
               offsets: Sequence[int], *, skip_dead: bool = False) -> PyTree:
    """Circulant-offset mixing: w'_k = sum_o c_o[k] * w_{(k+o) mod K}.

    Valid whenever every nonzero off-diagonal of the base topology lies on a
    circulant offset in ``offsets`` (ring, ring-with-hops; grids flattened
    row-major with offsets {±1, ±cols}).  Entries of A_eff that fall outside
    the true neighborhood are zero, so wrap-around reads are annihilated.

    ``jnp.roll`` along the (sharded) agent axis lowers to collective-permute
    under GSPMD, replacing the dense path's all-gather.

    ``skip_dead`` guards every roll with a ``lax.cond`` on its coefficient
    row being all-zero (segment mask): on a realized dynamic graph
    (link dropout / gossip matchings) an offset whose every edge failed
    this block contributes nothing, and the cond skips the permute instead
    of moving bytes that are multiplied by zero.  Numerically identical to
    the unguarded path (a dead offset adds exact zeros).
    """
    K = A_eff.shape[0]
    idx = jnp.arange(K)
    # c_o[k] = A_eff[(k + o) % K, k]
    coeffs = {o: A_eff[(idx + o) % K, idx] for o in (0, *offsets)}
    live = ({o: jnp.any(coeffs[o] != 0) for o in offsets}
            if skip_dead else None)

    def mix_leaf(p: jax.Array) -> jax.Array:
        out = coeffs[0].reshape((K,) + (1,) * (p.ndim - 1)).astype(p.dtype) * p
        for o in offsets:
            c = coeffs[o].reshape((K,) + (1,) * (p.ndim - 1)).astype(p.dtype)
            if skip_dead:
                out = out + jax.lax.cond(
                    live[o],
                    lambda p_, c_, _o=o: c_ * jnp.roll(p_, shift=-_o, axis=0),
                    lambda p_, c_: jnp.zeros_like(p_),
                    p, c)
            else:
                out = out + c * jnp.roll(p, shift=-o, axis=0)
        return out

    return jax.tree.map(mix_leaf, params)


def mix_gather(A_eff: jax.Array, params: PyTree, idx: jax.Array,
               valid: jax.Array) -> PyTree:
    """Bounded-degree combination through a static neighbor table.

    ``idx`` / ``valid`` come from
    :meth:`repro.core.topology.Topology.neighbor_table`: each target row k
    reads only its ``D = max_degree + 1`` possible contributor rows and
    contracts them with the realized weights ``A_eff[idx[k, j], k]`` —
    O(K D M) work and no (K, K) operand in the leaf contraction.  Padding
    slots gather the self row with weight exactly zero, so the result
    matches :func:`mix_dense` (same terms, shorter contraction — equal to
    float tolerance) whenever every nonzero of ``A_eff`` lies on the base
    support (``within_base_support`` graphs).
    """
    K = idx.shape[0]
    gw = (A_eff[idx, jnp.arange(K)[:, None]]
          * valid.astype(A_eff.dtype))                     # (K, D)

    def mix_leaf(p: jax.Array) -> jax.Array:
        flat = p.reshape(K, -1)
        mixed = jnp.einsum("kd,kdm->km", gw.astype(flat.dtype), flat[idx])
        return mixed.reshape(p.shape)

    return jax.tree.map(mix_leaf, params)


def count_live_offsets(A_eff: jax.Array, offsets: Sequence[int]) -> jax.Array:
    """How many circulant offsets carry any nonzero coefficient in this
    realized matrix — the number of rolls/collective-permutes the
    ``skip_dead`` sparse path actually executes (int32 scalar)."""
    K = A_eff.shape[0]
    idx = jnp.arange(K)
    return sum(jnp.any(A_eff[(idx + int(o)) % K, idx] != 0).astype(jnp.int32)
               for o in offsets)


# ---------------------------------------------------------------------------
# Mixer interface
# ---------------------------------------------------------------------------

class Mixer:
    """Combination-step backend: ``mixer(params, active, A_t) -> params``.

    ``params`` has leaves (K, ...); ``active`` is the (K,) activation mask
    in {0, 1}; ``A_t`` is the realized (K, K) combination matrix for this
    block — an operand, not baked state, so time-varying graphs
    (:mod:`repro.core.graphs`) flow through one compiled program exactly
    like activation masks do.  Implementations must be jit-compatible
    (mask and matrix as data).  Linear backends (``linear = True``) are
    semantically equal to ``mix_dense(masked_combination(A_t, active),
    params)``; robust backends (trimmed mean / median) set
    ``linear = False`` and only support the identity pipeline (the
    compressed exchange modes correct through ``mix(c) - c``, which
    presumes linearity).  Their ``scope="global"`` form ignores ``A_t``
    (server-style aggregation over the active set, ``uses_matrix =
    False``); ``scope="neighborhood"`` consumes it (per-agent aggregation
    over the realized neighborhood).
    """

    name = "base"
    linear = True
    uses_matrix = True        # False: A_t is accepted but ignored
    _mesh = None              # set by shard_agent_axis
    _agent_axis = None

    def __call__(self, params: PyTree, active: jax.Array,
                 A_t: jax.Array) -> PyTree:
        raise NotImplementedError

    def shard_agent_axis(self, mesh, axis: str) -> None:
        """Request agent-axis sharding: backends that materialize the
        (K, M) stack pin its leading axis to mesh dimension ``axis``
        through GSPMD sharding constraints
        (:func:`repro.sharding.rules.agent_stack_pspec`), so K >= 1024
        never holds K model copies in one device's HBM.  Backends that
        never materialize the stack ignore the request."""
        self._mesh = mesh
        self._agent_axis = str(axis)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"{type(self).__name__}()"


def spans_devices(mesh) -> bool:
    """Whether ``mesh`` spreads work over more than one device.  A
    ``pallas_call`` over the whole agent stack is not partitionable, so the
    kernel backends are chosen only where this is False."""
    return mesh is not None and mesh.size > 1


def _constrain_agent_stack(tree: PyTree, mesh, axis: str) -> PyTree:
    """Pin every leaf's leading (agent) axis to ``axis`` of ``mesh`` via a
    sharding constraint — a no-op spec when the axis size does not divide
    K (the ``_maybe`` guard in sharding/rules.py)."""
    from jax.sharding import NamedSharding

    from repro.sharding.rules import agent_stack_pspec

    def leaf(l: jax.Array) -> jax.Array:
        spec = agent_stack_pspec(mesh, axis, num_agents=l.shape[0],
                                 ndim=l.ndim)
        return jax.lax.with_sharding_constraint(l, NamedSharding(mesh, spec))

    return jax.tree.map(leaf, tree)


class NullMixer(Mixer):
    """Identity combination step (K = 1 or mixing disabled)."""

    name = "none"
    uses_matrix = False

    def __call__(self, params: PyTree, active: jax.Array,
                 A_t: jax.Array | None = None) -> PyTree:
        return params


class DenseMixer(Mixer):
    """Dense einsum against the realized (K, K) matrix (baseline).

    Stateless: the matrix arrives per call (the graph layer owns it)."""

    name = "dense"

    def __call__(self, params: PyTree, active: jax.Array,
                 A_t: jax.Array) -> PyTree:
        A_eff = part.masked_combination(A_t, active)
        return mix_dense(A_eff, params)


class SparseCirculantMixer(Mixer):
    """Circulant roll/collective-permute path for bounded-degree topologies.

    Only the *offsets* (the static communication structure) are
    constructor state; the realized matrix is a per-call operand.  Valid
    whenever every nonzero off-diagonal of A_t lies on a base circulant
    offset — dynamic graphs that stay within the base support
    (link dropout, gossip matchings) qualify; tv_erdos does not
    (:func:`repro.core.graphs.check_mixer_support` rejects it).
    """

    name = "sparse"

    def __init__(self, offsets: Sequence[int],
                 skip_dead: bool | None = None):
        self.offsets = tuple(int(o) for o in offsets)
        # None = auto: graphs.check_mixer_support flips it on for dynamic
        # graph processes, whose realized coefficient rows can go all-zero
        # (a dead offset's roll is skipped via lax.cond; the static graph
        # keeps the unguarded path — its rows are dead only under extreme
        # participation masks, not worth the conditional in the hot loop).
        # An auto decision is re-derived on every check_mixer_support call,
        # so one instance reused across builds follows each build's graph;
        # an explicit True/False is never touched.
        self.skip_dead = skip_dead
        self._skip_dead_auto = skip_dead is None

    def __call__(self, params: PyTree, active: jax.Array,
                 A_t: jax.Array) -> PyTree:
        A_eff = part.masked_combination(A_t, active)
        return mix_sparse(A_eff, params, self.offsets,
                          skip_dead=bool(self.skip_dead))

    def live_offsets(self, active: jax.Array, A_t: jax.Array) -> jax.Array:
        """Realized permute count for this (mask, matrix) draw."""
        return count_live_offsets(part.masked_combination(A_t, active),
                                  self.offsets)


class _Layout(NamedTuple):
    """Cached flatten/unflatten spec for one (treedef, shapes) combination."""

    sizes: tuple[int, ...]   # per-leaf inner size (leaf.size // K)
    M: int                   # total inner size
    M_padded: int            # M rounded up so tile_m divides it
    tile_m: int              # effective tile (<= requested, lane-aligned)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


class PallasFusedMixer(Mixer):
    """Fused mask+mix Pallas kernel over the flattened parameter pytree.

    The agent-stacked pytree is flattened to one (K, M) buffer padded to a
    multiple of ``tile_m`` — in the leaves' own dtype when they are all
    bfloat16, float32 otherwise; the kernel sizes its grid's blocks from K,
    M and that dtype (MiBs of the buffer per step, within a VMEM budget),
    rebuilds the eq.-20 masked matrix in VMEM per block, accumulates in
    float32, and streams the buffer exactly once.  ``tile_m`` is the padding
    quantum and the width the kernel's contraction chunks are multiples of,
    not the block; it is also the int8 path's scale granularity (one scale
    per agent and tile).
    A bfloat16 buffer gives bit-identical results to a float32 one (the
    upcast is exact and the output is rounded to bfloat16 once either
    way) at half the HBM footprint and traffic; in a full-width block step
    these two buffers are the largest live set.  The layout (leaf sizes,
    padding, effective tile) is computed on first use per pytree structure
    and cached, so repeated block steps pay zero layout overhead.

    ``interpret=None`` resolves per call: native on TPU, interpret elsewhere.

    The kernel always took ``A`` as an operand; only the Python-side layout
    cache is constructor state, so per-block matrices cost nothing extra.
    """

    name = "pallas"

    def __init__(self, *, tile_m: int = 512, interpret: bool | None = None):
        if tile_m % 128:
            raise ValueError(f"tile_m={tile_m} must be a multiple of 128")
        self.tile_m = int(tile_m)
        self.interpret = interpret
        self._layouts: dict = {}

    def _layout(self, leaves, treedef) -> _Layout:
        key = (treedef, tuple(l.shape for l in leaves),
               tuple(str(l.dtype) for l in leaves))
        lay = self._layouts.get(key)
        if lay is None:
            K = leaves[0].shape[0]
            sizes = tuple(int(np.prod(l.shape[1:], dtype=np.int64))
                          for l in leaves)
            M = int(sum(sizes))
            tile = min(self.tile_m, _round_up(max(M, 1), 128))
            lay = _Layout(sizes=sizes, M=M,
                          M_padded=_round_up(max(M, 1), tile), tile_m=tile)
            self._layouts[key] = lay
        return lay

    def __call__(self, params: PyTree, active: jax.Array,
                 A_t: jax.Array) -> PyTree:
        from repro.kernels.diffusion_mix import diffusion_mix

        leaves, treedef = jax.tree_util.tree_flatten(params)
        lay = self._layout(leaves, treedef)
        dtype = (jnp.bfloat16 if all(l.dtype == jnp.bfloat16 for l in leaves)
                 else jnp.float32)
        flat = self._flatten(leaves, lay, dtype)
        interpret = (jax.default_backend() != "tpu"
                     if self.interpret is None else self.interpret)
        mixed = diffusion_mix(A_t.astype(jnp.float32), active, flat,
                              tile_m=lay.tile_m, interpret=interpret)
        return self._unflatten(mixed, leaves, treedef, lay)

    @jax.named_scope("flatten")
    def _flatten(self, leaves, lay, dtype=jnp.float32) -> jax.Array:
        K = leaves[0].shape[0]
        flat = jnp.concatenate(
            [l.reshape(K, -1).astype(dtype) for l in leaves], axis=1)
        if lay.M_padded != lay.M:
            flat = jnp.pad(flat, ((0, 0), (0, lay.M_padded - lay.M)))
        return flat

    @jax.named_scope("unflatten")
    def _unflatten(self, flat, leaves, treedef, lay):
        outs, off = [], 0
        for leaf, n in zip(leaves, lay.sizes):
            outs.append(flat[:, off:off + n].reshape(leaf.shape)
                        .astype(leaf.dtype))
            off += n
        return jax.tree_util.tree_unflatten(treedef, outs)

    def mix_int8(self, params: PyTree, active: jax.Array, A_t: jax.Array,
                 key: jax.Array, *, want_messages: bool = False):
        """Compressed combination: per-tile int8 stochastic quantization of
        the cached flatten layout, then the fused dequantize+mask+mix kernel
        (:func:`repro.kernels.diffusion_mix.diffusion_mix_int8`).

        Returns ``(delta, messages)``: ``delta`` is the pytree of
        combination deltas ``[ (A_eff - I)^T c ]_k`` (so the caller applies
        ``w = psi + delta``), and ``messages`` is the dequantized transmitted
        pytree c (exactly what the kernel dequantizes — needed for the
        error-feedback residual) or None unless ``want_messages``.
        """
        from repro.kernels.diffusion_mix import diffusion_mix_int8

        leaves, treedef = jax.tree_util.tree_flatten(params)
        K = leaves[0].shape[0]
        lay = self._layout(leaves, treedef)
        flat = self._flatten(leaves, lay)
        nm = lay.M_padded // lay.tile_m
        tiles = flat.reshape(K, nm, lay.tile_m)
        q, scale3 = comp_lib.quantize_int8(tiles, key, axis=2)
        scales = scale3[:, :, 0]                              # (K, nm)
        Wq = q.astype(jnp.int8).reshape(K, lay.M_padded)
        interpret = (jax.default_backend() != "tpu"
                     if self.interpret is None else self.interpret)
        delta = diffusion_mix_int8(A_t.astype(jnp.float32), active, Wq,
                                   scales, tile_m=lay.tile_m,
                                   interpret=interpret,
                                   subtract_identity=True)
        delta_tree = self._unflatten(delta, leaves, treedef, lay)
        msgs = None
        if want_messages:
            c = (q.astype(jnp.float32) * scales[:, :, None]
                 ).reshape(K, lay.M_padded)
            msgs = self._unflatten(c, leaves, treedef, lay)
        return delta_tree, msgs


class NeighborGatherMixer(Mixer):
    """Bounded-degree linear combination — the scale path for K >= 1024.

    Holds the static neighbor table of the base topology
    (:meth:`repro.core.topology.Topology.neighbor_table`) and runs
    :func:`mix_gather`: each target row reads only its ``D = dmax + 1``
    possible contributor rows, so per-agent cost is a function of the max
    degree, not K, and no (K, K) matmul operand is materialized.  Valid
    whenever the realized graphs stay ``within_base_support``
    (:func:`repro.core.graphs.check_mixer_support` rejects tv_erdos).

    ``fused=None`` resolves per call: on TPU, with the agent axis on one
    device, the fused Pallas gather kernel
    (:func:`repro.kernels.diffusion_mix.gather_mix`) streams the cached
    (K, M) flatten layout once (the :class:`PallasFusedMixer` tile/layout
    cache is reused); elsewhere the per-leaf gather einsum runs.
    ``fused=True`` forces the kernel (interpret mode off-TPU);
    ``fused=False`` forces the einsum.

    :meth:`shard_agent_axis` pins the (K, ...) stack and the (K, D)
    gather table to a mesh dimension, so the resident state per device is
    K/devices rows.
    """

    name = "gather"

    def __init__(self, topology: topo_lib.Topology, *, tile_m: int = 512,
                 interpret: bool | None = None, fused: bool | None = None):
        if topology is None:
            raise ValueError("NeighborGatherMixer needs the base topology "
                             "(source of the static neighbor table)")
        idx, valid = topology.neighbor_table()
        self.num_agents = topology.num_agents
        self.max_degree = topology.max_degree
        self.idx = jnp.asarray(idx)          # (K, D) int32
        self.valid = jnp.asarray(valid)      # (K, D) bool
        self.fused = fused
        # flatten/unflatten + layout cache shared with the fused kernels
        self._pallas = PallasFusedMixer(tile_m=tile_m, interpret=interpret)

    def shard_agent_axis(self, mesh, axis: str) -> None:
        super().shard_agent_axis(mesh, axis)
        from jax.sharding import NamedSharding

        from repro.sharding.rules import agent_stack_pspec
        spec = agent_stack_pspec(mesh, axis, num_agents=self.num_agents,
                                 ndim=2)
        sh = NamedSharding(mesh, spec)
        self.idx = jax.device_put(self.idx, sh)
        self.valid = jax.device_put(self.valid, sh)

    def _gather_weights(self, A_eff: jax.Array) -> jax.Array:
        """(K, D) realized weight per table slot; padding slots exactly 0."""
        K = self.num_agents
        return (A_eff[self.idx, jnp.arange(K)[:, None]]
                * self.valid.astype(A_eff.dtype))

    def __call__(self, params: PyTree, active: jax.Array,
                 A_t: jax.Array) -> PyTree:
        A_eff = part.masked_combination(A_t.astype(jnp.float32), active)
        if self._mesh is not None:
            params = _constrain_agent_stack(params, self._mesh,
                                            self._agent_axis)
        fused = (jax.default_backend() == "tpu"
                 and not spans_devices(self._mesh)
                 if self.fused is None else bool(self.fused))
        if fused:
            from repro.kernels.diffusion_mix import gather_mix
            pm = self._pallas
            leaves, treedef = jax.tree_util.tree_flatten(params)
            lay = pm._layout(leaves, treedef)
            flat = pm._flatten(leaves, lay)
            interpret = (jax.default_backend() != "tpu"
                         if pm.interpret is None else pm.interpret)
            mixed = gather_mix(self.idx, self._gather_weights(A_eff), flat,
                               tile_m=lay.tile_m, interpret=interpret)
            out = pm._unflatten(mixed, leaves, treedef, lay)
        else:
            out = mix_gather(A_eff, params, self.idx, self.valid)
        if self._mesh is not None:
            out = _constrain_agent_stack(out, self._mesh, self._agent_axis)
        return out

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"NeighborGatherMixer(K={self.num_agents}, "
                f"D={self.max_degree + 1}, fused={self.fused})")


# ---------------------------------------------------------------------------
# robust aggregation (SLSGD, arXiv:1903.06996): Byzantine-tolerant backends
# ---------------------------------------------------------------------------

class _SortedRobustMixer(Mixer):
    """Shared machinery for order-statistic (robust) combination backends.

    Two scopes:

    * ``scope="global"`` — SLSGD's *server* aggregation hosted on the Mixer
      seam: every active agent receives the same coordinate-wise robust
      aggregate of the realized active set (the fedavg / fully-connected
      setting — the topology operand is ignored, ``uses_matrix = False``).
    * ``scope="neighborhood"`` — the decentralized setting: each active
      agent k aggregates over its *realized* neighborhood, the support of
      column k of ``masked_combination(A_t, active)`` (self always
      included) — i.e. the support of its row of ``A_t`` intersected with
      the active mask.  ``uses_matrix = True``: the realized per-block
      matrix of any dynamic :class:`repro.core.graphs.GraphProcess` flows
      straight in, so link dropout / gossip / tv_erdos compose.  When a
      neighborhood has fewer than ``2 trim + 1`` active members the trim
      degrades gracefully (clipped per row, down to the local median /
      the lone member's own value).

    In both scopes inactive agents keep their parameters exactly, so the
    eq.-20 inactive-agent invariant survives.  Robust aggregation is NOT
    linear, so the network mean is deliberately *not* preserved when
    outliers are suppressed — that is the point.  ``linear = False``:
    only the identity pipeline (``compress="none"``) is supported.

    Implementation: per coordinate (and per target row in neighborhood
    scope), sort the K values along the contributor axis with
    non-contributors pushed to +inf, so the S contributors occupy the
    first S slots; subclasses supply data-dependent weights over those
    sorted slots (jit-compatible — S is data, not structure), and every
    contraction keeps ``0 * inf = nan`` out via a where on the weights.

    Scale: with a neighbor table attached
    (:meth:`attach_neighbor_table`), the neighborhood scope gathers only
    the ``D = max_degree + 1`` rows that can ever contribute to each
    target and sorts those — O(K dmax M log dmax) — instead of sorting
    all K slots.  Valid whenever the graph process stays
    ``within_base_support`` (link dropout, gossip matchings, the static
    graph); :func:`repro.core.graphs.check_mixer_support` attaches and
    detaches the table automatically per build.  Without a table the
    all-slots sort runs — O(K^2 M log K) and a (K, K, M) broadcast per
    leaf — and emits a one-time warning above ``_NEIGHBORHOOD_WARN_K``
    agents naming the gather escape hatch.  Both paths sort the same
    finite multiset per (target, coordinate), so they agree to float
    tolerance (gated in tests/test_scale.py).
    """

    linear = False
    uses_matrix = False       # per-instance: True for scope="neighborhood"

    def __init__(self, num_agents: int, scope: str = "global"):
        if num_agents < 1:
            raise ValueError(f"num_agents={num_agents} must be >= 1")
        if scope not in ("global", "neighborhood"):
            raise ValueError(f"scope={scope!r} must be 'global' or "
                             "'neighborhood'")
        self.num_agents = int(num_agents)
        self.scope = scope
        self.uses_matrix = scope == "neighborhood"
        self._table: tuple[jax.Array, jax.Array] | None = None
        # "auto": graphs.check_mixer_support attaches/detaches the table
        # per build (the skip_dead convention); "table"/"off" are explicit
        # user choices it never touches (set by make_mixer)
        self._gather_mode = "auto"
        self._warned_dense = False

    def attach_neighbor_table(self, topology: topo_lib.Topology) -> None:
        """Enable the bounded-degree gather for the neighborhood scope.

        ``topology`` must be the BASE topology of the graph process, and
        every realized matrix must stay within its support (padding slots
        rely on ``A_eff[idx[k, j], k] * valid[k, j]`` being exactly zero
        for non-edges).  :func:`repro.core.graphs.check_mixer_support`
        enforces this at build time.
        """
        if topology.num_agents != self.num_agents:
            raise ValueError(
                f"neighbor table is for K={topology.num_agents} agents; "
                f"this mixer has num_agents={self.num_agents}")
        idx, valid = topology.neighbor_table()
        self._table = (jnp.asarray(idx), jnp.asarray(valid))

    def detach_neighbor_table(self) -> None:
        """Drop the gather table (graph may leave the base support)."""
        self._table = None

    def _slot_weights(self, S: jax.Array,
                      slots: int | None = None) -> jax.Array:
        """(slots,) weights over ascending sorted slots given S
        contributors; ``slots`` defaults to ``num_agents`` (the all-slots
        sort) and is D = dmax + 1 on the gather path.

        Must put zero weight on every slot >= S (those hold +inf), and on
        every slot when S = 0 (nothing to aggregate)."""
        raise NotImplementedError

    def __call__(self, params: PyTree, active: jax.Array,
                 A_t: jax.Array | None = None) -> PyTree:
        if self.scope == "neighborhood":
            if A_t is None:
                raise ValueError(
                    f"{type(self).__name__}(scope='neighborhood') "
                    "aggregates over the realized neighborhood and needs "
                    "the A_t operand")
            return self._neighborhood(params, active, A_t)
        return self._global(params, active)

    # -- scope="global": bit-identical to the pre-scope robust path --------
    def _global(self, params: PyTree, active: jax.Array) -> PyTree:
        K = self.num_agents
        S = active.astype(jnp.float32).sum()
        w = self._slot_weights(S)                          # (K,) float32

        def leaf(p: jax.Array) -> jax.Array:
            m = active.astype(jnp.float32).reshape(
                (K,) + (1,) * (p.ndim - 1))
            x = p.astype(jnp.float32)
            srt = jnp.sort(jnp.where(m > 0, x, jnp.inf), axis=0)
            wb = w.reshape((K,) + (1,) * (p.ndim - 1))
            # wb > 0 only on slots < S, which hold finite values; the where
            # keeps 0 * inf = nan out of the contraction
            agg = jnp.sum(jnp.where(wb > 0, srt, 0.0) * wb, axis=0,
                          keepdims=True)
            return jnp.where(m > 0, agg.astype(p.dtype), p)

        return jax.tree.map(leaf, params)

    # -- scope="neighborhood": per-row masked sort over the realized A_t ---
    def _neighborhood(self, params: PyTree, active: jax.Array,
                      A_t: jax.Array) -> PyTree:
        if self._table is not None:
            return self._neighborhood_gather(params, active, A_t)
        if self.num_agents > _NEIGHBORHOOD_WARN_K and not self._warned_dense:
            self._warned_dense = True
            import warnings
            warnings.warn(
                f"{type(self).__name__}(scope='neighborhood') is running "
                f"the all-slots sort at K={self.num_agents} — O(K^2 M "
                "log K) work per block.  If the graph process stays "
                "within_base_support, attach the bounded-degree gather "
                "table (mixer.attach_neighbor_table(topology), or build "
                "through make_mixer(..., topology)/check_mixer_support) "
                "for O(K dmax M log dmax).", stacklevel=3)
        return self._neighborhood_dense(params, active, A_t)

    def _neighborhood_dense(self, params: PyTree, active: jax.Array,
                            A_t: jax.Array) -> PyTree:
        K = self.num_agents
        m = active.astype(jnp.float32)
        A_eff = part.masked_combination(A_t.astype(jnp.float32), active)
        # l contributes to target k iff A_eff[l, k] != 0 (off-diagonals
        # survive iff both endpoints are active and the realized edge
        # exists); the renormalized self weight can hit exactly 0, so
        # self-membership is forced — every agent hears itself
        member = ((A_eff != 0) | jnp.eye(K, dtype=bool))   # (contrib, target)
        S = member.astype(jnp.float32).sum(axis=0)         # (K,) per target
        W = jax.vmap(self._slot_weights)(S)                # (K, K) per-row
        mem_t = member.T                                   # (target, contrib)

        def leaf(p: jax.Array) -> jax.Array:
            x = p.astype(jnp.float32).reshape(K, -1)       # (K, M)

            def row(mem_k, w_k):
                # +inf padding pushes non-members past the S_k live slots
                vals = jnp.where(mem_k[:, None], x, jnp.inf)
                srt = jnp.sort(vals, axis=0)
                wb = w_k[:, None]
                return jnp.sum(jnp.where(wb > 0, srt, 0.0) * wb, axis=0)

            agg = jax.vmap(row)(mem_t, W)                  # (K, M)
            # inactive agents keep their params EXACTLY (no f32 roundtrip
            # for wider dtypes) — same invariant as the global scope
            out = jnp.where(m[:, None] > 0, agg.astype(p.dtype),
                            p.reshape(K, -1))
            return out.reshape(p.shape)

        return jax.tree.map(leaf, params)

    # -- neighborhood via the bounded-degree gather table ------------------
    def _neighborhood_gather(self, params: PyTree, active: jax.Array,
                             A_t: jax.Array) -> PyTree:
        K = self.num_agents
        idx, valid = self._table
        D = int(idx.shape[1])
        m = active.astype(jnp.float32)
        A_eff = part.masked_combination(A_t.astype(jnp.float32), active)
        # realized weight of slot j for target k — padding slots gather the
        # self row but valid = 0 zeroes them, so they never join the sort
        gw = (A_eff[idx, jnp.arange(K)[:, None]]
              * valid.astype(jnp.float32))                 # (K, D)
        # slot 0 is self: membership forced (the renormalized self weight
        # can hit exactly 0), mirroring the all-slots `| eye` term
        member = (gw != 0).at[:, 0].set(True)              # (K, D)
        S = member.astype(jnp.float32).sum(axis=1)         # (K,)
        W = jax.vmap(lambda s: self._slot_weights(s, D))(S)  # (K, D)

        def leaf(p: jax.Array) -> jax.Array:
            x = p.astype(jnp.float32).reshape(K, -1)       # (K, M)
            vals = jnp.where(member[:, :, None], x[idx], jnp.inf)  # (K, D, M)
            srt = jnp.sort(vals, axis=1)
            wb = W[:, :, None]
            agg = jnp.sum(jnp.where(wb > 0, srt, 0.0) * wb, axis=1)
            out = jnp.where(m[:, None] > 0, agg.astype(p.dtype),
                            p.reshape(K, -1))
            return out.reshape(p.shape)

        return jax.tree.map(leaf, params)


class TrimmedMeanMixer(_SortedRobustMixer):
    """Coordinate-wise trimmed mean (SLSGD eq. 4), global or per
    neighborhood.

    Per coordinate, drop the ``trim`` smallest and ``trim`` largest values
    among the S contributions and average the rest — tolerant to up to
    ``trim`` Byzantine agents per side (per neighborhood in neighborhood
    scope).  When fewer than ``2 trim + 1`` members contribute, the trim
    is clipped to ``floor((S - 1) / 2)`` so at least the coordinate median
    survives.  ``trim = 0`` is the plain mean over the contributors.
    """

    name = "trimmed_mean"

    def __init__(self, num_agents: int, trim: int = 1,
                 scope: str = "global"):
        super().__init__(num_agents, scope=scope)
        if not 0 <= trim < max(num_agents, 1):
            raise ValueError(f"trim={trim} must lie in [0, {num_agents})")
        self.trim = int(trim)

    def _slot_weights(self, S: jax.Array,
                      slots: int | None = None) -> jax.Array:
        n = self.num_agents if slots is None else int(slots)
        idx = jnp.arange(n, dtype=jnp.float32)
        b = jnp.clip(jnp.minimum(float(self.trim),
                                 jnp.floor((S - 1.0) / 2.0)), 0.0)
        keep = ((idx >= b) & (idx < S - b)).astype(jnp.float32)
        return keep / jnp.maximum(keep.sum(), 1.0)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"TrimmedMeanMixer(K={self.num_agents}, trim={self.trim}, "
                f"scope={self.scope!r})")


class CoordinateMedianMixer(_SortedRobustMixer):
    """Coordinate-wise median — the maximally robust order statistic
    (breakdown point 1/2), at the cost of discarding the most averaging;
    SLSGD's b -> (S-1)/2 limit.  Global or per neighborhood."""

    name = "median"

    def _slot_weights(self, S: jax.Array,
                      slots: int | None = None) -> jax.Array:
        n = self.num_agents if slots is None else int(slots)
        idx = jnp.arange(n, dtype=jnp.float32)
        lo = jnp.clip(jnp.floor((S - 1.0) / 2.0), 0.0)
        hi = jnp.clip(jnp.ceil((S - 1.0) / 2.0), 0.0)
        w = 0.5 * ((idx == lo).astype(jnp.float32)
                   + (idx == hi).astype(jnp.float32))
        # S = 0: every slot holds +inf — nothing to aggregate, weights die
        # (the inactive-agent where already freezes the output; the guard
        # keeps the masked-out aggregate finite: no inf in intermediates)
        w = w * (S >= 1.0).astype(jnp.float32)
        return w / jnp.maximum(w.sum(), 1.0)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"CoordinateMedianMixer(K={self.num_agents}, "
                f"scope={self.scope!r})")


class AdaptiveTrimMixer(TrimmedMeanMixer):
    """Trimmed mean with a per-coordinate DATA-DEPENDENT trim count.

    The fixed :class:`TrimmedMeanMixer` always discards ``trim`` values
    per side — paying a robustness tax (less averaging, higher MSD) even
    when nobody is attacking.  This backend *estimates* the outlier count
    per (target, coordinate) from the contributions themselves and trims
    only what it flags, capped at ``trim`` per side:

    * robust location/scale over the S contributors: the coordinate
      median and the MAD (median absolute deviation, normal-consistency
      factor 1.4826);
    * a contribution further than ``mad_thresh`` consistent-MADs from the
      median is flagged as an outlier.  In ascending sorted order the low
      flags occupy the first slots and the high flags the last, so the
      adaptive trim is still an order-statistic slot-weighting —
      ``b_lo = min(#low flags, trim)`` / ``b_hi = min(#high flags,
      trim)`` (each also capped at ``floor((S-1)/2)`` so the median
      always survives);
    * the surviving slots are averaged, exactly like the fixed trim.

    With no attack almost nothing clears a 3-MAD fence (~4.45 sigma for
    Gaussian contributions), so the aggregate is the plain mean over the
    realized neighborhood and the MSD matches the LINEAR mixer — no
    robustness tax (gated in ``tests/test_adaptive_trim.py``).  Under a
    sign-flip attack the corrupted coordinates blow through the fence and
    the backend degrades to the fixed trimmed mean.  Flagging is strict
    (``<`` / ``>``), so an exactly-tied majority (MAD = 0) never flags
    equal values.

    Weights depend on the data per coordinate, so the Pallas fused
    gather kernel (precomputed per-row slot weights) does not apply —
    ``make_mixer`` keeps this backend on the vmapped gather table.
    """

    name = "adaptive_trim"

    def __init__(self, num_agents: int, trim: int = 1,
                 scope: str = "global", mad_thresh: float = 3.0):
        super().__init__(num_agents, trim=trim, scope=scope)
        if mad_thresh <= 0:
            raise ValueError(f"mad_thresh={mad_thresh} must be > 0")
        self.mad_thresh = float(mad_thresh)

    def _adaptive_weights(self, srt: jax.Array, S: jax.Array) -> jax.Array:
        """Per-coordinate keep weights over ascending sorted slots.

        ``srt``: (n, ...) sorted along axis 0, +inf beyond the S live
        slots; ``S``: scalar contributor count.  Returns weights shaped
        like ``srt`` that are zero on dead slots and on the flagged
        outlier tails, renormalized to sum to 1 per coordinate.
        """
        n = srt.shape[0]
        ranks = jnp.arange(n, dtype=jnp.float32).reshape(
            (n,) + (1,) * (srt.ndim - 1))
        live = (ranks < S).astype(jnp.float32)
        lo_i = jnp.clip(jnp.floor((S - 1.0) / 2.0), 0.0).astype(jnp.int32)
        hi_i = jnp.clip(jnp.ceil((S - 1.0) / 2.0), 0.0).astype(jnp.int32)
        med = jnp.where(S >= 1.0,
                        0.5 * (jnp.take(srt, lo_i, axis=0)
                               + jnp.take(srt, hi_i, axis=0)), 0.0)
        # MAD needs a second sort: |x - med| is not monotone in x
        dev = jnp.where(live > 0, jnp.abs(srt - med), jnp.inf)
        dev_srt = jnp.sort(dev, axis=0)
        mad = jnp.where(S >= 1.0,
                        0.5 * (jnp.take(dev_srt, lo_i, axis=0)
                               + jnp.take(dev_srt, hi_i, axis=0)), 0.0)
        thr = self.mad_thresh * 1.4826 * mad
        # strict inequalities: exactly-tied values (MAD = 0) never flag
        lo_out = jnp.sum(live * (srt < med - thr), axis=0)
        hi_out = jnp.sum(live * (srt > med + thr), axis=0)
        cap = jnp.clip(jnp.minimum(float(self.trim),
                                   jnp.floor((S - 1.0) / 2.0)), 0.0)
        b_lo = jnp.minimum(lo_out, cap)
        b_hi = jnp.minimum(hi_out, cap)
        keep = live * (ranks >= b_lo) * (ranks < S - b_hi)
        return keep / jnp.maximum(keep.sum(axis=0, keepdims=True), 1.0)

    # the three aggregation paths mirror the base class, with the
    # per-row scalar slot weights replaced by per-coordinate adaptive
    # weights computed from the sorted values themselves
    def _global(self, params: PyTree, active: jax.Array) -> PyTree:
        K = self.num_agents
        S = active.astype(jnp.float32).sum()

        def leaf(p: jax.Array) -> jax.Array:
            m = active.astype(jnp.float32).reshape(
                (K,) + (1,) * (p.ndim - 1))
            x = p.astype(jnp.float32)
            srt = jnp.sort(jnp.where(m > 0, x, jnp.inf), axis=0)
            w = self._adaptive_weights(srt, S)
            agg = jnp.sum(jnp.where(w > 0, srt, 0.0) * w, axis=0,
                          keepdims=True)
            return jnp.where(m > 0, agg.astype(p.dtype), p)

        return jax.tree.map(leaf, params)

    def _neighborhood_dense(self, params: PyTree, active: jax.Array,
                            A_t: jax.Array) -> PyTree:
        K = self.num_agents
        m = active.astype(jnp.float32)
        A_eff = part.masked_combination(A_t.astype(jnp.float32), active)
        member = ((A_eff != 0) | jnp.eye(K, dtype=bool))   # (contrib, target)
        S = member.astype(jnp.float32).sum(axis=0)
        mem_t = member.T

        def leaf(p: jax.Array) -> jax.Array:
            x = p.astype(jnp.float32).reshape(K, -1)       # (K, M)

            def row(mem_k, S_k):
                vals = jnp.where(mem_k[:, None], x, jnp.inf)
                srt = jnp.sort(vals, axis=0)
                w = self._adaptive_weights(srt, S_k)
                return jnp.sum(jnp.where(w > 0, srt, 0.0) * w, axis=0)

            agg = jax.vmap(row)(mem_t, S)                  # (K, M)
            out = jnp.where(m[:, None] > 0, agg.astype(p.dtype),
                            p.reshape(K, -1))
            return out.reshape(p.shape)

        return jax.tree.map(leaf, params)

    def _neighborhood_gather(self, params: PyTree, active: jax.Array,
                             A_t: jax.Array) -> PyTree:
        K = self.num_agents
        idx, valid = self._table
        m = active.astype(jnp.float32)
        A_eff = part.masked_combination(A_t.astype(jnp.float32), active)
        gw = (A_eff[idx, jnp.arange(K)[:, None]]
              * valid.astype(jnp.float32))                 # (K, D)
        member = (gw != 0).at[:, 0].set(True)
        S = member.astype(jnp.float32).sum(axis=1)

        def leaf(p: jax.Array) -> jax.Array:
            x = p.astype(jnp.float32).reshape(K, -1)       # (K, M)
            vals = jnp.where(member[:, :, None], x[idx], jnp.inf)
            srt = jnp.sort(vals, axis=1)
            w = jax.vmap(self._adaptive_weights)(srt, S)   # (K, D, M)
            agg = jnp.sum(jnp.where(w > 0, srt, 0.0) * w, axis=1)
            out = jnp.where(m[:, None] > 0, agg.astype(p.dtype),
                            p.reshape(K, -1))
            return out.reshape(p.shape)

        return jax.tree.map(leaf, params)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"AdaptiveTrimMixer(K={self.num_agents}, trim={self.trim}, "
                f"mad_thresh={self.mad_thresh}, scope={self.scope!r})")


class FusedNeighborhoodMixer(Mixer):
    """Neighborhood-robust aggregation through the fused Pallas gather
    kernel (:func:`repro.kernels.diffusion_mix.gather_robust_mix`).

    Wraps a neighborhood-scope :class:`_SortedRobustMixer` (trimmed mean
    or median) with a gather table attached and fuses gather + masked
    bitonic sort + slot-weight contraction in VMEM over the cached (K, M)
    flatten layout — the :class:`PallasFusedMixer` tile/layout cache is
    reused, so repeated block steps pay zero layout overhead.  Selected by
    ``make_mixer(..., gather="fused")``, or by the "auto" policy on TPU
    when the graph stays on base support.

    ``use_kernel=None`` mirrors ``SparseCirculantMixer.skip_dead``: an
    auto decision that :func:`repro.core.graphs.check_mixer_support`
    flips off (delegating to the inner mixer's all-slots sort) when the
    graph process leaves the base support; an explicit ``True`` makes
    that a build-time error instead.  The membership mask, contributor
    count, and slot weights are computed outside the kernel — O(K D)
    work on (K, D) operands — so only the O(K D M) gather/sort/contract
    runs fused.
    """

    linear = False
    uses_matrix = True

    def __init__(self, inner: "_SortedRobustMixer",
                 topology: topo_lib.Topology, *, tile_m: int = 512,
                 interpret: bool | None = None,
                 use_kernel: bool | None = None):
        if inner.scope != "neighborhood":
            raise ValueError(
                "FusedNeighborhoodMixer fuses the neighborhood scope; got "
                f"scope={inner.scope!r}")
        if topology is None:
            raise ValueError("FusedNeighborhoodMixer needs the base "
                             "topology (source of the neighbor table)")
        inner.attach_neighbor_table(topology)
        self.inner = inner
        self.name = inner.name
        self.num_agents = inner.num_agents
        self.use_kernel = use_kernel
        self._use_kernel_auto = use_kernel is None
        self._pallas = PallasFusedMixer(tile_m=tile_m, interpret=interpret)

    def __call__(self, params: PyTree, active: jax.Array,
                 A_t: jax.Array) -> PyTree:
        use = (not spans_devices(self._mesh) if self.use_kernel is None
               else bool(self.use_kernel))
        if not use or self.inner._table is None:
            return self.inner(params, active, A_t)
        from repro.kernels.diffusion_mix import gather_robust_mix

        idx, valid = self.inner._table
        K = self.num_agents
        D = int(idx.shape[1])
        A_eff = part.masked_combination(A_t.astype(jnp.float32), active)
        gw = (A_eff[idx, jnp.arange(K)[:, None]]
              * valid.astype(jnp.float32))                 # (K, D)
        member = (gw != 0).at[:, 0].set(True)              # slot 0: self
        S = member.astype(jnp.float32).sum(axis=1)
        wslot = jax.vmap(lambda s: self.inner._slot_weights(s, D))(S)
        pm = self._pallas
        leaves, treedef = jax.tree_util.tree_flatten(params)
        lay = pm._layout(leaves, treedef)
        flat = pm._flatten(leaves, lay)
        interpret = (jax.default_backend() != "tpu"
                     if pm.interpret is None else pm.interpret)
        mixed = gather_robust_mix(idx, member.astype(jnp.float32), wslot,
                                  active.astype(jnp.float32).reshape(K, 1),
                                  flat, tile_m=lay.tile_m,
                                  interpret=interpret)
        # the kernel's inactive branch returns the agent's own f32 row;
        # the f32 roundtrip is exact for the supported leaf dtypes
        # (bf16/f16/f32), so the eq.-20 inactive-keep invariant survives
        return pm._unflatten(mixed, leaves, treedef, lay)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"FusedNeighborhoodMixer({self.inner!r}, "
                f"use_kernel={self.use_kernel})")


# ---------------------------------------------------------------------------
# CommPipeline: encode -> exchange/combine -> correct
# ---------------------------------------------------------------------------

class CommPipeline:
    """Staged combination step with pluggable compression.

    Three exchange modes (``mode="auto"`` picks per compressor):

    * ``"identity"`` — no compression: the pipeline IS the mixer,
      bit-identical to the uncompressed backends (the Mixer contract).
    * ``"direct"`` — transmit the compressed iterate and correct locally
      (DeepSqueeze-style; Tang et al. 2019):

          c   = C(psi [+ e])                     # encode (+ error feedback)
          w_k = psi_k + gamma ([A_eff^T c]_k - c_k)

      Sound when the compression error is small relative to the signal —
      int8 stochastic quantization (error <= max|psi|/127), where it also
      enables the fused dequantize+mask+mix Pallas kernel on the int8
      ``(K, M)`` buffer.  ``error_feedback`` threads the classic EF
      residual e through ``comm_state``.
    * ``"diff"`` — transmit the compressed *difference* from a reference
      copy every agent maintains for every peer (CHOCO-SGD, Koloskova et
      al. 2019; the sparse-differential scheme of Zhang et al. 2020):

          c    = C_contractive(psi - ref)        # no unbiased rescale
          ref' = ref + c                         # receivers update copies
          w_k  = psi_k + gamma ([A_eff^T ref']_k - ref'_k)

      The reference provides *implicit* error feedback — whatever C drops
      stays in ``psi - ref`` and is retransmitted once it matters — and the
      compression error vanishes as training converges, so aggressive
      sparsifiers (top-k / rand-k / Gaussian mask at ratio << 1) keep a
      near-dense error floor.  The consensus step ``gamma`` damps the
      exchange (compressing raw iterates at gamma = 1 is provably unstable
      for aggressive sparsification); ``gamma=None`` auto-selects 1.0 for
      lossless ratios, 0.5 for top-k (magnitude selection concentrates
      energy), and the contraction factor ``ratio`` for rand-k/Gaussian
      (the CHOCO guidance gamma ~ delta).

    In every mode, A_eff's column k is the unit vector e_k for inactive
    agents and A_eff is doubly stochastic, so inactive agents keep their
    parameters exactly and the network mean is preserved — the eq.-20
    invariants survive any compressor.

    ``stateful`` pipelines (diff mode, or direct mode with error feedback)
    carry a per-agent memory pytree in ``EngineState.comm_state``,
    allocated by ``engine.init_state`` and threaded by the unified
    ``engine.step`` of both engines (:mod:`repro.core.diffusion`,
    :mod:`repro.core.sharded`).

    The consensus step ``gamma`` of the compressed modes accepts three
    forms: a float (fixed), ``None`` (the legacy fixed heuristic — 1.0
    lossless/direct, 0.5 top-k, ``ratio`` rand-k/Gaussian; kept so
    existing presets stay bit-identical), or ``"auto"`` (diff mode only):
    the CHOCO-optimal value derived from the base topology's spectral gap
    (:func:`choco_gamma` — Koloskova et al. 2019, Thm. 2) as a floor,
    annealed toward 1 from the *observed* per-block contraction of the
    compression gap ``||psi - ref||`` (an EMA of how much of the gap each
    transmission closes — the effective compressor delta on the actual
    signal, which for top-k is far larger than the worst-case ``ratio``).
    The EMA is a scalar in ``comm_state`` ("delta"), so the annealed gamma
    checkpoints and restores with everything else.

    With ``secure_agg`` (a stage from
    :func:`repro.core.privacy.make_secure_agg`) the identity-mode
    combination runs through pairwise-canceling per-edge wire masks —
    payloads are noise to honest-but-curious receivers, the combination
    stays exact up to float accumulation, and the pipeline carries a
    block counter in ``comm_state`` (the mask epoch, so masked runs
    checkpoint and resume on the same mask stream).  The masks presume a
    linear combination over uncompressed payloads: compressed modes and
    robust (non-linear) mixers are rejected loudly.
    """

    def __init__(self, mixer: Mixer,
                 compressor: comp_lib.Compressor | None = None,
                 *, mode: str = "auto", gamma=None, base_A=None,
                 mesh=None, secure_agg=None):
        # mesh: when set, the generic direct int8 path pins the quantized
        # buffer + per-agent scales with sharding constraints so GSPMD's
        # collective carries s8 bytes, not the dequantized f32 (the 4x on
        # the wire — see launch/dryrun collective_stats).  Bit-identical
        # to mesh=None.
        self.mesh = mesh
        self.mixer = mixer
        self.compressor = (compressor if compressor is not None
                           else comp_lib.Identity())
        base = self._base()
        if mode == "auto":
            if isinstance(base, comp_lib.Identity) and not self._ef():
                mode = "identity"
            elif isinstance(base, (comp_lib.TopK, comp_lib.RandK,
                                   comp_lib.GaussianMask)):
                mode = "diff"
            else:
                mode = "direct"
        if mode not in ("identity", "direct", "diff"):
            raise ValueError(f"unknown pipeline mode {mode!r} "
                             "(expected identity|direct|diff|auto)")
        if mode != "identity" and not mixer.linear:
            raise ValueError(
                f"{type(mixer).__name__} is a robust (non-linear) backend; "
                "the compressed exchange modes correct through mix(c) - c, "
                "which presumes linear mixing — use compress='none'")
        if mode == "identity" and (self._ef() or not isinstance(
                base, comp_lib.Identity)):
            raise ValueError("identity mode requires the Identity "
                             "compressor without error feedback")
        if mode == "diff" and self._ef():
            # the reference provides the feedback in diff mode; keeping the
            # wrapper would silently never run (diff uses encode_contractive)
            self.compressor = base
        self.mode = mode
        self.secure_agg = secure_agg
        if secure_agg is not None:
            # the masks telescope to zero inside each receiver's LINEAR
            # weighted sum over uncompressed payloads — any other pipeline
            # silently breaks the cancellation invariant, so refuse
            if mode != "identity":
                raise ValueError(
                    f"secure-agg wire masks require the uncompressed "
                    f"identity-mode pipeline; this pipeline runs {mode!r} "
                    "mode — use compress='none' (or drop secure_agg)")
            if isinstance(mixer, NullMixer):
                raise ValueError(
                    "secure-agg wire masks need a real combination step "
                    "(K >= 2, mixing enabled) — there is no wire to mask")
            if not mixer.linear:
                raise ValueError(
                    f"{type(mixer).__name__} is a robust (non-linear) "
                    "backend; per-edge masks only cancel inside a linear "
                    "combination — use a linear mixer kind (dense/sparse/"
                    "pallas/gather/auto) or drop secure_agg")
        self.adaptive = (gamma == "auto" and mode == "diff"
                         and not isinstance(mixer, NullMixer))
        if gamma == "auto" and not self.adaptive:
            # the annealed gamma is defined by the diff-mode reference gap
            # ||psi - ref||; other modes have no reference to observe, so
            # "auto" degrades to the fixed defaults — say so, loudly
            import warnings
            warnings.warn(
                f'comm_gamma="auto" anneals the diff-mode consensus step; '
                f"this pipeline runs {mode!r} mode, so the fixed default "
                "gamma is used instead", stacklevel=2)
            gamma = None          # identity/direct: nothing to anneal
        if self.adaptive:
            if base_A is None:
                raise ValueError(
                    'comm_gamma="auto" derives its floor from the base '
                    "topology's spectral gap — pass base_A (or build the "
                    "pipeline through an engine / make_pipeline with a "
                    "topology)")
            A0 = np.asarray(base_A, np.float64)
            rho = topo_lib.spectral_gap(A0)
            beta = float(1.0 - np.linalg.eigvalsh(A0).min())  # ||I - A||_2
            self._delta0 = float(min(max(getattr(base, "ratio", 1.0),
                                         1e-3), 1.0))
            self.gamma_floor = choco_gamma(rho, self._delta0, beta)
            self.spectral_gap = float(rho)
            self.gamma = "auto"
        elif gamma is None:
            ratio = getattr(base, "ratio", 1.0)
            if mode != "diff" or ratio >= 1.0:
                gamma = 1.0
            elif isinstance(base, comp_lib.TopK):
                gamma = 0.5
            else:
                gamma = float(ratio)
            self.gamma = float(gamma)
        else:
            self.gamma = float(gamma)

    def _ef(self) -> bool:
        return isinstance(self.compressor, comp_lib.ErrorFeedback)

    def _base(self) -> comp_lib.Compressor:
        c = self.compressor
        return c.inner if isinstance(c, comp_lib.ErrorFeedback) else c

    @property
    def stateful(self) -> bool:
        if isinstance(self.mixer, NullMixer):
            return False          # __call__ is a no-op: no state to thread
        if self.secure_agg is not None:
            return True           # the block counter (mask epoch)
        if self.mode == "diff":
            return True
        return self.mode == "direct" and self.compressor.stateful

    @property
    def needs_key(self) -> bool:
        return self.compressor.needs_key

    def init_state(self, params: PyTree) -> PyTree:
        if not self.stateful:
            return ()
        if self.secure_agg is not None:
            return {"t": jnp.zeros((), jnp.uint32)}
        if self.mode == "diff":
            state = {"ref": jax.tree.map(jnp.zeros_like, params)}
            if self.adaptive:
                # EMA of the observed compressor contraction, seeded at the
                # worst-case delta (the sparsifier's kept ratio)
                state["delta"] = jnp.asarray(self._delta0, jnp.float32)
            return state
        return self.compressor.init_state(params)

    def annealed_gamma(self, comm_state: PyTree) -> jax.Array:
        """The consensus step an adaptive (gamma="auto") diff-mode pipeline
        uses for a given comm_state: the CHOCO floor annealed toward 1 by
        the observed-contraction EMA.

        The interpolation is sqrt(delta) — halfway (geometrically) between
        the worst-case CHOCO guidance gamma ~ delta and the lossless
        gamma = 1: at delta -> 1 (lossless) it reaches 1, at delta -> 0 it
        collapses to the provably-safe floor, and at the ~0.2 contraction
        top-k typically shows at steady state it lands in the empirically
        MSD-optimal band (see bench_graph_process's fixed-gamma sweep).
        """
        if not self.adaptive:
            raise ValueError("annealed_gamma is defined for the adaptive "
                             '(gamma="auto") diff-mode pipeline only')
        d = jnp.sqrt(jnp.clip(comm_state["delta"], 0.0, 1.0))
        return self.gamma_floor + (1.0 - self.gamma_floor) * d

    def wire_bytes(self, params: PyTree) -> int:
        """Value-payload bytes per combination step (see compression.py)."""
        if isinstance(self.mixer, NullMixer) or self.mode == "identity":
            return (0 if isinstance(self.mixer, NullMixer)
                    else comp_lib.dense_wire_bytes(params))
        return self.compressor.wire_bytes(params)

    def __call__(self, params: PyTree, active: jax.Array, A_t: jax.Array,
                 comm_state: PyTree = (), key: jax.Array | None = None):
        """Apply the pipeline; returns ``(params, comm_state)``.

        ``A_t`` is the realized combination matrix for this block (sampled
        by the engine's :class:`repro.core.graphs.GraphProcess`)."""
        if self.mode == "identity":
            if self.secure_agg is not None:
                # the combination THROUGH per-edge masked payloads — same
                # result as the plain mixer up to float accumulation
                # (gated by bench_privacy's mask-exactness row)
                t = comm_state["t"]
                mixed = self.secure_agg(params, active, A_t, t)
                return mixed, {"t": t + 1}
            # bit-identical to the plain mixer (the Mixer contract)
            return self.mixer(params, active, A_t), comm_state
        if isinstance(self.mixer, NullMixer):
            # K = 1 / mixing disabled: the correction is identically zero
            return params, comm_state
        comp = self.compressor
        base = self._base()
        if comp.needs_key and key is None:
            raise ValueError(f"{comp!r} needs a PRNG key; pass key=")

        def masked(new, old):
            """Per-agent select: active agents take ``new``, inactive keep
            ``old`` — an agent that does not participate transmits nothing,
            so neither the reference copies nor the EF residual may move.
            (The simulation assumes an active agent's message reaches every
            peer's reference copy, i.e. reliable broadcast / re-sync.)"""
            def leaf(n, o):
                m = active.astype(n.dtype).reshape(
                    (n.shape[0],) + (1,) * (n.ndim - 1))
                return m * n + (1 - m) * o
            return jax.tree.map(leaf, new, old)

        if self.mode == "diff":
            ref_prev = comm_state["ref"]
            diff = jax.tree.map(lambda p, r: p - r.astype(p.dtype),
                                params, ref_prev)
            c = base.encode_contractive(diff, key)
            ref = masked(
                jax.tree.map(lambda r, ci: r + ci.astype(r.dtype),
                             ref_prev, c),
                ref_prev)
            mixed = self.mixer(ref, active, A_t)
            if self.adaptive:
                # observed compressor contraction on the actual signal:
                # how much of the gap ||psi - ref|| this transmission
                # closed — over the ACTIVE agents only (inactive agents
                # transmit nothing, their gap never moves, and counting
                # them would bias the EMA toward 0 under partial
                # participation)
                def act(tree):
                    return jax.tree.map(
                        lambda l: l * active.astype(l.dtype).reshape(
                            (l.shape[0],) + (1,) * (l.ndim - 1)), tree)
                pre = _tree_sq_norm(act(diff))
                post = _tree_sq_norm(act(jax.tree.map(
                    lambda p, r: p - r.astype(p.dtype), params, ref)))
                delta_obs = jnp.clip(
                    1.0 - jnp.sqrt(post / jnp.maximum(pre, 1e-30)), 0.0, 1.0)
                # no active transmissions this block: nothing observed,
                # leave the EMA where it is
                delta_obs = jnp.where(pre > 1e-30, delta_obs,
                                      comm_state["delta"])
                delta = 0.9 * comm_state["delta"] + 0.1 * delta_obs
                g = self.annealed_gamma({"delta": delta})
                out = jax.tree.map(
                    lambda p, mx, r: p + (g * (mx - r)).astype(p.dtype),
                    params, mixed, ref)
                return out, {"ref": ref, "delta": delta}
            g = self.gamma
            out = jax.tree.map(lambda p, mx, r: p + g * (mx - r).astype(p.dtype),
                               params, mixed, ref)
            return out, {"ref": ref}
        # direct mode: inactive senders' messages are already annihilated by
        # the eq.-20 mask (off-diagonals need both endpoints active), so only
        # the EF residual needs explicit masking
        g = self.gamma
        ef = self._ef()
        if (isinstance(base, comp_lib.Int8Stochastic)
                and isinstance(self.mixer, PallasFusedMixer)):
            target = (jax.tree.map(lambda p, e: p + e.astype(p.dtype),
                                   params, comm_state) if ef else params)
            delta, msgs = self.mixer.mix_int8(target, active, A_t, key,
                                              want_messages=ef)
            out = jax.tree.map(lambda p, d: p + g * d.astype(p.dtype),
                               params, delta)
            if ef:
                comm_state = masked(
                    jax.tree.map(lambda t, m: t - m.astype(t.dtype),
                                 target, msgs),
                    comm_state)
            return out, comm_state
        if isinstance(base, comp_lib.Int8Stochastic):
            # generic (non-Pallas) int8 path: emit the quantized buffer +
            # per-agent scales through the collective — under GSPMD the
            # replication constraints below sit on the s8/f32-scale
            # operands, so the all-gather moves int8 bytes, not the
            # dequantized float32.  Bit-identical to comp.encode when no
            # mesh is set (same key stream; exact int8 round-trip).
            target = (jax.tree.map(lambda p, e: p + e.astype(p.dtype),
                                   params, comm_state) if ef else params)
            q, scales = base.encode_quantized(target, key)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                from repro.sharding.rules import agent_stack_pspec
                rep = NamedSharding(self.mesh, PartitionSpec())
                axis = getattr(self.mixer, "_agent_axis", None) or "data"

                def pin(l):
                    # two constraints, not one: first pin the quantized
                    # leaf SHARDED on the agent axis, then replicated.
                    # With only the replicated constraint the SPMD
                    # partitioner reshards the convert's f32 *input*
                    # (an f32 all-gather) and converts after; anchoring
                    # the s8 tensor sharded forces the reshard — the
                    # actual all-gather — onto the int8 bytes.
                    spec = agent_stack_pspec(self.mesh, axis,
                                             num_agents=l.shape[0],
                                             ndim=l.ndim)
                    l = jax.lax.with_sharding_constraint(
                        l, NamedSharding(self.mesh, spec))
                    return jax.lax.with_sharding_constraint(l, rep)

                q = jax.tree.map(pin, q)
                scales = jax.tree.map(pin, scales)
            msgs = base.dequantize(q, scales, target)
            new_state = (masked(jax.tree.map(lambda t, m_: t - m_, target,
                                             msgs), comm_state)
                         if ef else comm_state)
            mixed = self.mixer(msgs, active, A_t)
            out = jax.tree.map(lambda p, mx, m_: p + g * (mx - m_), params,
                               mixed, msgs)
            return out, new_state
        msgs, new_state = comp.encode(params, comm_state, key)
        if ef:
            new_state = masked(new_state, comm_state)
        mixed = self.mixer(msgs, active, A_t)
        out = jax.tree.map(lambda p, mx, m: p + g * (mx - m), params,
                           mixed, msgs)
        return out, new_state

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"CommPipeline({self.mixer!r}, {self.compressor!r}, "
                f"mode={self.mode!r}, gamma={self.gamma})")


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------

def resolve_auto(topology: topo_lib.Topology | None,
                 offsets: Sequence[int] | None = None, *, mesh=None):
    """Pick a backend name; returns (name, offsets) so the sparse branch is
    built with exactly the offsets the decision was based on.  The Pallas
    kernel is picked on TPU unless ``mesh`` spreads the agents over several
    devices: there the combination step must be a collective."""
    if jax.default_backend() == "tpu" and not spans_devices(mesh):
        return "pallas", offsets
    if topology is not None and topology.max_degree < topology.num_agents - 1:
        # irregular graphs (e.g. Erdős–Rényi) can have low degree but many
        # distinct offsets, making sparse slower than dense — count offsets
        offsets = topology.neighbor_offsets_ring()
    if offsets and 0 < len(offsets) <= _AUTO_SPARSE_MAX_OFFSETS:
        return "sparse", offsets
    if (topology is not None
            and _AUTO_GATHER_HEADROOM * (topology.max_degree + 1)
            <= topology.num_agents):
        # bounded degree but too many distinct offsets for the circulant
        # path (irregular graphs): the neighbor-table gather still does
        # O(K dmax M) work vs the dense O(K^2 M)
        return "gather", offsets
    return "dense", offsets


def make_mixer(name: str | Mixer, topology: topo_lib.Topology | None = None,
               *, A=None, offsets: Sequence[int] | None = None,
               num_agents: int | None = None, tile_m: int = 512,
               interpret: bool | None = None, trim: int = 1,
               scope: str = "global", gather: str = "auto",
               mesh=None) -> Mixer:
    """Build a mixing backend.

    The matrix is NOT baked into the mixer — it arrives per call as the
    ``A_t`` operand (see :class:`Mixer`).  ``topology`` / ``A`` here only
    inform the *structure*: the "auto" policy, the circulant offsets of
    the sparse path, the neighbor table of the gather paths, and the
    agent count.

    Args:
      name: "dense" | "sparse" | "pallas" | "gather" | "auto" | "none" |
        "trimmed_mean" | "median" | "adaptive_trim", or an existing
        :class:`Mixer` (returned unchanged).
      topology: source of the circulant offsets / neighbor table / auto
        policy / K.
      A: (K, K) base matrix — used only to infer ``num_agents``.
      offsets: circulant offsets override for the sparse path.
      num_agents: disables mixing when 1 (returns :class:`NullMixer`).
      tile_m / interpret: Pallas kernel knobs (see :class:`PallasFusedMixer`).
      trim: per-side trim count for the "trimmed_mean" backend; per-side
        trim CAP for "adaptive_trim" (the realized count is estimated per
        coordinate from a MAD outlier fence).
      scope: robust-aggregation scope — "global" (SLSGD server setting,
        A_t ignored) or "neighborhood" (per-agent over the realized
        neighborhood of A_t).
      gather: bounded-degree policy for the *neighborhood-robust* scope —
        "auto" (attach the neighbor table when a topology is given; on
        TPU additionally fuse via :class:`FusedNeighborhoodMixer`),
        "table" (vmapped gather, topology required), "fused" (the Pallas
        gather kernel, topology required), or "off" (the all-slots sort,
        valid even off base support).  Graph-support validity is enforced
        later by :func:`repro.core.graphs.check_mixer_support`.
      mesh: the device mesh the agent axis will be sharded over, if any —
        informs the "auto" policy (see :func:`resolve_auto`).
    """
    if isinstance(name, Mixer):
        return name
    if num_agents is None:
        if topology is not None:
            num_agents = topology.num_agents
        elif A is not None:
            num_agents = int(np.asarray(A).shape[0])
    if name == "none" or (num_agents is not None and num_agents <= 1):
        return NullMixer()
    if name in ("trimmed_mean", "median", "adaptive_trim"):
        # robust aggregation; needs only K (and A_t per call for the
        # neighborhood scope)
        if num_agents is None:
            raise ValueError(f"{name!r} mixer needs num_agents "
                             "(or a topology / A to infer it from)")
        if gather not in ("auto", "table", "fused", "off"):
            raise ValueError(f"gather={gather!r} must be auto|table|"
                             "fused|off")
        if name == "adaptive_trim" and gather == "fused":
            raise ValueError(
                "adaptive_trim computes data-dependent per-coordinate "
                "weights — the fused kernel precomputes slot weights per "
                "row and cannot apply it; use gather=table|auto|off")
        mixer = (TrimmedMeanMixer(num_agents, trim=trim, scope=scope)
                 if name == "trimmed_mean"
                 else AdaptiveTrimMixer(num_agents, trim=trim, scope=scope)
                 if name == "adaptive_trim"
                 else CoordinateMedianMixer(num_agents, scope=scope))
        if scope != "neighborhood":
            return mixer
        if gather == "off":
            mixer._gather_mode = "off"
            return mixer
        if gather in ("table", "fused") and topology is None:
            raise ValueError(
                f"gather={gather!r} needs the base topology (source of "
                "the neighbor table) — pass topology=")
        if topology is None:
            # auto without structure: all-slots sort for now;
            # check_mixer_support attaches a table from graph.topology
            return mixer
        if (name != "adaptive_trim"
                and (gather == "fused"
                     or (gather == "auto"
                         and jax.default_backend() == "tpu"))):
            # the wrapped inner stays _gather_mode="auto" so an
            # off-support graph degrades to the all-slots sort instead of
            # erroring (only use_kernel=True makes that a hard error)
            return FusedNeighborhoodMixer(mixer, topology, tile_m=tile_m,
                                          interpret=interpret)
        mixer.attach_neighbor_table(topology)
        if gather == "table":
            mixer._gather_mode = "table"
        return mixer
    if name == "auto":
        name, offsets = resolve_auto(topology, offsets, mesh=mesh)
    if name == "dense":
        return DenseMixer()
    if name == "sparse":
        if offsets is None:
            if topology is None:
                raise ValueError("sparse mixer needs circulant offsets "
                                 "(pass offsets= or a topology)")
            offsets = topology.neighbor_offsets_ring()
        return SparseCirculantMixer(offsets)
    if name == "gather":
        if topology is None:
            raise ValueError("gather mixer needs the base topology "
                             "(source of the neighbor table)")
        return NeighborGatherMixer(topology, tile_m=tile_m,
                                   interpret=interpret)
    if name == "pallas":
        return PallasFusedMixer(tile_m=tile_m, interpret=interpret)
    raise ValueError(f"unknown mixer {name!r} (expected dense|sparse|"
                     "pallas|gather|auto|none|trimmed_mean|median|"
                     "adaptive_trim)")


def make_pipeline(mix: str | Mixer, topology: topo_lib.Topology | None = None,
                  *, compress: str | comp_lib.Compressor | None = None,
                  compress_ratio: float = 1.0, error_feedback: bool = False,
                  sigma: float = 0.0, mode: str = "auto",
                  gamma=None, A=None,
                  offsets: Sequence[int] | None = None,
                  num_agents: int | None = None, tile_m: int = 512,
                  interpret: bool | None = None,
                  trim: int = 1, scope: str = "global",
                  gather: str = "auto", mesh=None) -> CommPipeline:
    """Build the full combination pipeline (compressor stage + mixer).

    ``mix`` and the mixer kwargs go to :func:`make_mixer`; ``compress`` /
    ``compress_ratio`` / ``error_feedback`` / ``sigma`` go to
    :func:`repro.core.compression.make_compressor`; ``mode`` / ``gamma``
    select the exchange scheme (see :class:`CommPipeline`; ``gamma="auto"``
    derives its floor from the topology's spectral gap); ``mesh`` lets the
    generic int8 path keep the quantized bytes on the wire under GSPMD.
    ``compress=None`` or ``"none"`` yields the bit-identical identity
    pipeline.
    """
    mixer = make_mixer(mix, topology, A=A, offsets=offsets,
                       num_agents=num_agents, tile_m=tile_m,
                       interpret=interpret, trim=trim, scope=scope,
                       gather=gather)
    compressor = comp_lib.make_compressor(compress, ratio=compress_ratio,
                                          error_feedback=error_feedback,
                                          sigma=sigma)
    if A is None and topology is not None:
        A = topology.A
    return CommPipeline(mixer, compressor, mode=mode, gamma=gamma, base_A=A,
                        mesh=mesh)
