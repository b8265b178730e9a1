"""The comparison that decides a run's ``correct``.

The window drives the program's jitted block step.  Set-up drives the same
compiled step, on the same feed, through the first ``check_blocks`` blocks
of the run and keeps, after each, per leaf and agent: the sum of squares of
the change since the initial weights, and a bit-exact fingerprint.  Once
the window has closed and the program's state is freed, :class:`Reference`
follows those blocks in plain float32 from the same weights, data and
realized participation mask: each active agent takes T SGD steps on
gradients of the plain loss (parameters kept in the configuration's dtype
between steps, as the configuration states), then the eq.-20 masked
combination over the configured graph, computed in float32.

Numbers compared, each against its limit in ``limits/<cell>.json``:

* ``change1_gap`` and ``changeN_gap``: over the leaves and agents, the
  largest gap between the program's and the reference's norm of one
  agent's change of one leaf, after the first and after the last checked
  block, over the reference's norm of that (leaf, agent) or the median of
  the reference's nonzero (leaf, agent) norms, whichever is larger.  Each
  agent is compared on its own, so change moved between agents (a wrong
  row of the combination, agents swapped) shows.  Leaves whose reference
  change is under a thousandth of the median leaf's, after the first block
  in which the reference moved at all, are left out.
* ``changeN_median_gap``: after the last checked block, over the agents,
  the largest median over the kept leaves of the same gap.  Where a leaf
  moves by a few rounding steps of its dtype alone (a norm's scale near the
  floor below), its gap swings from seed to seed and sets the worst leaf;
  the median does not follow it, and so can sit closer to the sound
  program's readings.
* ``inactive_moved``: (agent, block) pairs in which an agent the step drew
  as inactive changed by even one bit.  Limit 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ring_matrix", "masked_combination", "leaf_names", "sq_change",
           "fingerprint", "leaf_gaps", "worst_leaf_gap", "median_leaf_gap",
           "kept_leaves", "broadcast_agents",
           "Reference", "GRAPHS", "LEAF_FLOOR"]

#: a leaf whose reference change is under this share of the median leaf's
#: moves by round-off alone and is left out of the gaps
LEAF_FLOOR = 1e-3


def ring_matrix(K: int) -> np.ndarray:
    """Metropolis weights of the ring: 1 / (1 + max degree) on each edge,
    the rest on the diagonal."""
    adj = np.zeros((K, K), bool)
    for k in range(K):
        adj[k, (k + 1) % K] = adj[(k + 1) % K, k] = True
    np.fill_diagonal(adj, False)
    deg = adj.sum(1)
    A = np.where(adj, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])),
                 0.0)
    A[np.diag_indices(K)] = 1.0 - A.sum(1)
    return A


GRAPHS = {"ring": ring_matrix}


def masked_combination(A: np.ndarray, active: np.ndarray) -> np.ndarray:
    """Eq. 20: an edge survives when both ends are active; an active agent
    puts what its inactive neighbours would have given on itself, an
    inactive one keeps its own weights."""
    m = np.asarray(active, np.float64)
    off = A * (1.0 - np.eye(len(m))) * np.outer(m, m)
    return off + np.diag(m * (1.0 - off.sum(0)) + (1.0 - m))


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def sq_change(W, w0):
    """(leaves, K) float32: per agent, the sum of squares of W - w0;
    ``W`` stacked over agents, ``w0`` one agent's weights."""
    def leaf(x, x0):
        d = x.astype(jnp.float32) - x0.astype(jnp.float32)[None]
        return jnp.sum(jnp.square(d).reshape(d.shape[0], -1), axis=1)
    return jnp.stack(jax.tree.leaves(jax.tree.map(leaf, W, w0)))


def fingerprint(W):
    """(leaves, K) uint32: a position-weighted sum of each agent's bits,
    wrapping; equal fingerprints mean equal bits but for a collision."""
    def leaf(x):
        bits = jax.lax.bitcast_convert_type(
            x, jnp.dtype(f"uint{8 * x.dtype.itemsize}")).astype(jnp.uint32)
        bits = bits.reshape(x.shape[0], -1)
        pos = jnp.arange(bits.shape[1], dtype=jnp.uint32)
        mult = pos * jnp.uint32(2654435761) + jnp.uint32(0x9E3779B9)
        return jnp.sum(bits * (mult | jnp.uint32(1)), axis=1,
                       dtype=jnp.uint32)
    return jnp.stack(jax.tree.leaves(jax.tree.map(leaf, W)))


def leaf_gaps(prog_sq, ref_sq, keep) -> np.ndarray:
    """(leaves, K): the gap of each (leaf, agent) norm of the change, over
    the reference's norm or the median live one, whichever is larger; 0 on
    leaves left out.  ``prog_sq``, ``ref_sq``: (leaves, K) sums of
    squares."""
    n_p = np.sqrt(np.asarray(prog_sq, np.float64))
    n_r = np.sqrt(np.asarray(ref_sq, np.float64))
    live = n_r[keep][n_r[keep] > 0]
    if live.size:
        gap = np.abs(n_p - n_r) / np.maximum(n_r, np.median(live))
    else:   # the reference moved nothing: neither may the program
        gap = np.where(n_p > 0, np.inf, 0.0)
    gap = np.where(keep[:, None], gap, 0.0)
    return np.where(np.isfinite(gap), gap, np.inf)


def worst_leaf_gap(prog_sq, ref_sq, keep) -> tuple[float, tuple]:
    """The largest gap of (leaf, agent) norms of the change and where it
    lies."""
    gap = leaf_gaps(prog_sq, ref_sq, keep)
    i = np.unravel_index(int(np.argmax(gap)), gap.shape)
    return float(gap[i]), (int(i[0]), int(i[1]))


def median_leaf_gap(prog_sq, ref_sq, keep) -> float:
    """Over the agents, the largest median over the kept leaves of the
    (leaf, agent) gap: one small leaf's round-off does not move it."""
    gap = leaf_gaps(prog_sq, ref_sq, keep)[np.asarray(keep, bool)]
    return float(np.max(np.median(gap, axis=0))) if gap.size else 0.0


def kept_leaves(ref_sqs) -> np.ndarray:
    """Leaves compared: those whose reference change is at least
    :data:`LEAF_FLOOR` of the median leaf's, after the first of ``ref_sqs``
    in which the reference moved."""
    for ref_sq in ref_sqs:
        n_r = np.sqrt(np.asarray(ref_sq, np.float64).sum(1))
        if n_r.any():
            return n_r >= LEAF_FLOOR * np.median(n_r)
    return np.ones(len(ref_sqs[0]), bool)


class Reference:
    """Plain float32 diffusion training of one cell, block by block.

    ``loss`` is the configuration's reference loss
    ``(weights, sizes, tokens, labels, precision) -> scalar``; ``sizes``
    the configuration's ``"model"`` dict; ``precision`` ``"highest"`` or
    the control's ``"fp8"``.
    """

    def __init__(self, loss, sizes: dict, traffic: dict,
                 precision: str = "highest"):
        self.dtype = jnp.dtype(sizes.get("dtype", "float32"))
        self.mu = float(traffic["step_size"])
        self.T = int(traffic["local_steps"])
        self.A = GRAPHS[traffic["topology"]](int(traffic["agents"]))

        def agent_step(W, k, tokens, labels):
            w = jax.tree.map(lambda x: x[k].astype(jnp.float32), W)
            g = jax.grad(loss)(w, sizes, tokens, labels, precision)
            new = jax.tree.map(
                lambda x, gx: (x - self.mu * gx).astype(self.dtype), w, g)
            return jax.tree.map(lambda X, n: X.at[k].set(n), W, new)

        def mix(W, A_eff):
            return jax.tree.map(
                lambda X: jnp.einsum(
                    "lk,l...->k...", A_eff, X.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST).astype(self.dtype),
                W)

        self._agent_step = jax.jit(agent_step, donate_argnums=0)
        self._mix = jax.jit(mix, donate_argnums=0)

    def block(self, W, batch, active):
        """One block: T local steps of each active agent, then the
        combination.  ``batch``: tokens/labels (T, K, B, S)."""
        active = np.asarray(active)
        for k in np.flatnonzero(active):
            for t in range(self.T):
                W = self._agent_step(W, int(k), batch["tokens"][t, k],
                                     batch["labels"][t, k])
        A_eff = jnp.asarray(masked_combination(self.A, active), jnp.float32)
        return self._mix(W, A_eff)


def broadcast_agents(w, K: int):
    """Every leaf of one agent's weights repeated over K agents."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (K,) + x.shape), w)
