"""Pallas TPU kernels for the paper's combination step (eq. 20 + mixing).

Fuses the per-sample-path masking of the combination matrix (eq. 20) with
the parameter mix  W'_k = sum_l a_lk W_l , so the masked (K, K) matrix is
(re)built in VMEM registers per tile and never round-trips to HBM, and the
stacked parameter matrix is streamed exactly once.

Layout: the agent-stacked parameter tree is flattened to (K, M); the grid
tiles M.  K is small (<= 64 agents), so the (K, K) mix lives comfortably in
VMEM next to a (K, tile_m) parameter tile; tile_m is a multiple of 128 for
lane alignment.

Four variants:

* :func:`diffusion_mix` — float32 buffer (the PR-1 kernel).  Materializes
  the (K, K) matrix per tile: the right shape when K is small (<= a few
  hundred agents).
* :func:`diffusion_mix_int8` — the compressed-communication path: the
  buffer arrives *quantized* (int8 values + one float32 scale per (agent,
  tile)) and the kernel fuses dequantize + eq.-20 mask + mix, so only a
  quarter of the parameter bytes are streamed from HBM.  With
  ``subtract_identity=True`` it emits the combination *delta*
  (A_eff - I)^T C directly, which is what the
  :class:`~repro.core.mixing.CommPipeline` correction  w = psi + mix(c) - c
  consumes.
* :func:`gather_mix` — the bounded-degree linear path for K >= 1024: each
  target row gathers its D = dmax + 1 contributor rows through a static
  neighbor-index table (:meth:`repro.core.topology.Topology.
  neighbor_table`) and accumulates them with realized weights — O(K D M)
  instead of the O(K^2 M) dense contraction, and no (K, K) operand ever
  materializes in VMEM.
* :func:`gather_robust_mix` — the neighborhood-robust counterpart: gather
  the D contributor rows, push non-members to +inf, sort the D slots with
  a static bitonic compare-exchange network (jnp.sort does not lower on
  TPU), and contract with precomputed per-row order-statistic slot
  weights (trimmed mean / median) — the fused gather + trim + mix of the
  O(K dmax M log dmax) neighborhood path.

The gather kernels take the index table and every per-row operand as
*scalar-prefetch* operands (``pltpu.PrefetchScalarGridSpec``), the
supported TPU pattern for data-dependent row addressing: they land in SMEM,
flattened to 1-D, before the body runs, and the indices feed ``pl.ds``
dynamic slices of the (K, tile_m) parameter block.  The grid is
(num_tiles, K / R) with the row blocks innermost, so the parameter tile
stays resident in VMEM across the whole K sweep; each program writes an
(R, tile_m) output block, R = 8 rows (or all K when 8 does not divide K),
the smallest block the TPU lowering accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# per-tile int8 scales travel in lane-dense (K, 128) blocks: a (K, 1) block
# per tile is refused by the TPU lowering (last block dim must be a multiple
# of 128 or the whole array)
_SCALE_LANES = 128


def _masked_matrix(A: jax.Array, m: jax.Array, K: int,
                   subtract_identity: bool = False) -> jax.Array:
    """Rebuild the eq.-20 masked combination matrix in VMEM registers."""
    row = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    eye = (row == col).astype(jnp.float32)

    off = A * (1.0 - eye) * (m[:, None] * m[None, :])   # both endpoints active
    col_off = off.sum(axis=0)                           # (K,)
    diag = m * (1.0 - col_off) + (1.0 - m)              # eq. (20) self-weights
    A_eff = off + diag[None, :] * eye
    if subtract_identity:
        A_eff = A_eff - eye
    return A_eff


def _contract(A_eff: jax.Array, W: jax.Array) -> jax.Array:
    """A_eff^T @ W in full float32.  HIGHEST pins the f32 contraction on
    the MXU instead of leaving its pass count to the compiler default."""
    return jax.lax.dot_general(A_eff, W, (((0,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _mix_kernel(a_ref, m_ref, w_ref, o_ref, *, K: int):
    A = a_ref[...].astype(jnp.float32)                  # (K, K)
    m = m_ref[...].astype(jnp.float32)[:, 0]            # (K,)
    W = w_ref[...].astype(jnp.float32)                  # (K, TM)
    A_eff = _masked_matrix(A, m, K)

    # W'_k = sum_l A_eff[l, k] W[l]  ==  A_eff^T @ W
    o_ref[...] = _contract(A_eff, W).astype(o_ref.dtype)


def _mix_int8_kernel(a_ref, m_ref, wq_ref, s_ref, o_ref, *, K: int,
                     subtract_identity: bool):
    A = a_ref[...].astype(jnp.float32)                  # (K, K)
    m = m_ref[...].astype(jnp.float32)[:, 0]            # (K,)
    # the scale block holds _SCALE_LANES tiles' scales; select this tile's
    # column (one nonzero term per row, so the sum is exact)
    lane = jax.lax.broadcasted_iota(jnp.int32, (K, _SCALE_LANES), 1)
    col = pl.program_id(0) % _SCALE_LANES
    scale = jnp.sum(jnp.where(lane == col, s_ref[...], 0.0), axis=1,
                    keepdims=True)                      # (K, 1) per-tile
    W = wq_ref[...].astype(jnp.float32) * scale         # dequantize in VMEM
    A_eff = _masked_matrix(A, m, K, subtract_identity=subtract_identity)
    o_ref[...] = _contract(A_eff, W).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def diffusion_mix(A: jax.Array, active: jax.Array, W: jax.Array, *,
                  tile_m: int = 512, interpret: bool = False) -> jax.Array:
    """Masked combination step over flattened stacked parameters.

    Args:
      A: (K, K) base combination matrix.
      active: (K,) activation mask in {0, 1}.
      W: (K, M) stacked flattened parameters; M % tile_m == 0 (pad upstream).
    Returns:
      (K, M) mixed parameters, dtype of W.
    """
    K, M = W.shape
    if M % tile_m:
        raise ValueError(f"M={M} not divisible by tile_m={tile_m}")
    nm = M // tile_m
    kernel = functools.partial(_mix_kernel, K=K)
    return pl.pallas_call(
        kernel,
        grid=(nm,),
        in_specs=[
            pl.BlockSpec((K, K), lambda mi: (0, 0)),
            pl.BlockSpec((K, 1), lambda mi: (0, 0)),
            pl.BlockSpec((K, tile_m), lambda mi: (0, mi)),
        ],
        out_specs=pl.BlockSpec((K, tile_m), lambda mi: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((K, M), W.dtype),
        interpret=interpret,
    )(A, active.reshape(K, 1), W)


@functools.partial(jax.jit,
                   static_argnames=("tile_m", "interpret",
                                    "subtract_identity"))
def diffusion_mix_int8(A: jax.Array, active: jax.Array, Wq: jax.Array,
                       scales: jax.Array, *, tile_m: int = 512,
                       interpret: bool = False,
                       subtract_identity: bool = False) -> jax.Array:
    """Fused dequantize + masked combination over int8-compressed parameters.

    Args:
      A: (K, K) base combination matrix.
      active: (K,) activation mask in {0, 1}.
      Wq: (K, M) int8 stacked quantized parameters; M % tile_m == 0.
      scales: (K, M // tile_m) float32 dequantization scales, one per
        (agent, tile).
      subtract_identity: emit (A_eff - I)^T C instead of A_eff^T C — the
        combination *delta* consumed by the CommPipeline correction.
    Returns:
      (K, M) float32 mixed (or delta) parameters.
    """
    K, M = Wq.shape
    if Wq.dtype != jnp.int8:
        raise ValueError(f"Wq dtype {Wq.dtype} != int8")
    if M % tile_m:
        raise ValueError(f"M={M} not divisible by tile_m={tile_m}")
    nm = M // tile_m
    if scales.shape != (K, nm):
        raise ValueError(f"scales shape {scales.shape} != ({K}, {nm})")
    kernel = functools.partial(_mix_int8_kernel, K=K,
                               subtract_identity=subtract_identity)
    pad = (-nm) % _SCALE_LANES
    scales = jnp.pad(scales.astype(jnp.float32), ((0, 0), (0, pad)))
    return pl.pallas_call(
        kernel,
        grid=(nm,),
        in_specs=[
            pl.BlockSpec((K, K), lambda mi: (0, 0)),
            pl.BlockSpec((K, 1), lambda mi: (0, 0)),
            pl.BlockSpec((K, tile_m), lambda mi: (0, mi)),
            pl.BlockSpec((K, _SCALE_LANES),
                         lambda mi: (0, mi // _SCALE_LANES)),
        ],
        out_specs=pl.BlockSpec((K, tile_m), lambda mi: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((K, M), jnp.float32),
        interpret=interpret,
    )(A, active.reshape(K, 1), Wq, scales)


# ---------------------------------------------------------------------------
# bounded-degree gather kernels (neighbor-table path, K >= 1024)
# ---------------------------------------------------------------------------

def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _bitonic_sort(rows: list) -> list:
    """Ascending per-lane bitonic sort of a power-of-2 list of equal-shape
    rows, built from jnp.minimum/maximum compare-exchanges only (static
    network — the TPU-lowerable replacement for jnp.sort over a tiny,
    statically known slot axis)."""
    n = len(rows)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            for i in range(n):
                partner = i ^ j
                if partner > i:
                    lo = jnp.minimum(rows[i], rows[partner])
                    hi = jnp.maximum(rows[i], rows[partner])
                    if (i & k) == 0:
                        rows[i], rows[partner] = lo, hi
                    else:
                        rows[i], rows[partner] = hi, lo
            j //= 2
        k *= 2
    return rows


def _row_block(K: int) -> int:
    """Target rows per output block: the TPU lowering wants blocks of at
    least 8 rows (or the whole agent axis)."""
    return 8 if K % 8 == 0 else K


def _gather_rows(idx_ref, w_ref, k, D: int) -> list:
    """The D contributor rows of target k, via SMEM-prefetched indices
    (the (K, D) table arrives flattened row-major)."""
    return [w_ref[pl.ds(idx_ref[k * D + j], 1), :] for j in range(D)]


def _for_each_row(R: int, body) -> None:
    """Run ``body(r, k)`` for the R target rows k of this output block."""
    k0 = pl.program_id(1) * R

    def step(r, carry):
        body(r, k0 + r)
        return carry

    jax.lax.fori_loop(0, R, step, 0)


def _gather_mix_kernel(idx_ref, gw_ref, w_ref, o_ref, *, D: int, R: int):
    def row(r, k):
        rows = _gather_rows(idx_ref, w_ref, k, D)
        acc = gw_ref[k * D] * rows[0]
        for j in range(1, D):
            acc = acc + gw_ref[k * D + j] * rows[j]
        o_ref[pl.ds(r, 1), :] = acc

    _for_each_row(R, row)


def _gather_robust_kernel(idx_ref, mem_ref, ws_ref, act_ref, w_ref, o_ref, *,
                          D: int, R: int):
    P = _next_pow2(D)

    def row(r, k):
        rows = _gather_rows(idx_ref, w_ref, k, D)
        own = rows[0]                                 # slot 0 is self
        # non-members (and padding slots) to +inf so the S_k live values
        # occupy the first S_k ascending slots, like the all-slots sort
        vals = [jnp.where(mem_ref[k * D + j] > 0, rows[j], jnp.inf)
                for j in range(D)]
        vals += [jnp.full_like(own, jnp.inf)] * (P - D)
        srt = _bitonic_sort(vals)
        # weights are zero on every slot >= S_k (those hold +inf); the
        # where keeps 0 * inf = nan out of the contraction
        acc = jnp.zeros_like(own)
        for j in range(D):                            # slots >= D unweighted
            wj = ws_ref[k * D + j]
            acc = acc + jnp.where(wj > 0, srt[j], 0.0) * wj
        # inactive targets keep their own row exactly (eq.-20 invariant)
        o_ref[pl.ds(r, 1), :] = jnp.where(act_ref[k] > 0, acc, own)

    _for_each_row(R, row)


def _gather_grid(K: int, nm: int, R: int, tile_m: int, *,
                 num_scalar_prefetch: int):
    """Grid of the gather kernels: (tiles, row blocks) with the row blocks
    innermost, so the (K, tile_m) parameter tile stays resident in VMEM
    across the sweep.  The per-row operands (index table, weights, masks)
    are scalar-prefetched into SMEM, flattened to 1-D."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=(nm, K // R),
        in_specs=[pl.BlockSpec((K, tile_m), lambda mi, kb, *_: (0, mi))],
        out_specs=pl.BlockSpec((R, tile_m), lambda mi, kb, *_: (kb, mi)),
    )


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def gather_mix(idx: jax.Array, gw: jax.Array, W: jax.Array, *,
               tile_m: int = 512, interpret: bool = False) -> jax.Array:
    """Bounded-degree linear combination over flattened stacked parameters.

    Args:
      idx: (K, D) int32 neighbor table (slot 0 = self; padding = self).
      gw: (K, D) float32 realized gathered weights
        ``A_eff[idx[k, j], k] * valid[k, j]`` — padding slots exactly 0.
      W: (K, M) float32 stacked flattened parameters; M % tile_m == 0.
    Returns:
      (K, M) mixed parameters: out[k] = sum_j gw[k, j] * W[idx[k, j]].
    """
    K, M = W.shape
    D = idx.shape[1]
    if M % tile_m:
        raise ValueError(f"M={M} not divisible by tile_m={tile_m}")
    nm = M // tile_m
    R = _row_block(K)
    return pl.pallas_call(
        functools.partial(_gather_mix_kernel, D=D, R=R),
        grid_spec=_gather_grid(K, nm, R, tile_m, num_scalar_prefetch=2),
        out_shape=jax.ShapeDtypeStruct((K, M), jnp.float32),
        interpret=interpret,
    )(idx.reshape(-1), gw.astype(jnp.float32).reshape(-1),
      W.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def gather_robust_mix(idx: jax.Array, member: jax.Array, wslot: jax.Array,
                      active: jax.Array, W: jax.Array, *, tile_m: int = 512,
                      interpret: bool = False) -> jax.Array:
    """Fused neighborhood gather + trimmed top-b selection + mix.

    Args:
      idx: (K, D) int32 neighbor table (slot 0 = self; padding = self).
      member: (K, D) float32 {0,1} realized membership (self slot always 1,
        padding slots always 0).
      wslot: (K, D) float32 order-statistic slot weights over the ascending
        sorted member values (rows of ``_slot_weights(S_k, D)`` — trimmed
        mean or median); zero on every slot >= S_k.
      active: (K,) activation mask in {0, 1}; inactive targets keep their
        own row exactly.
      W: (K, M) float32 stacked flattened parameters; M % tile_m == 0.
    Returns:
      (K, M) robust-aggregated parameters.
    """
    K, M = W.shape
    D = idx.shape[1]
    if M % tile_m:
        raise ValueError(f"M={M} not divisible by tile_m={tile_m}")
    nm = M // tile_m
    R = _row_block(K)
    return pl.pallas_call(
        functools.partial(_gather_robust_kernel, D=D, R=R),
        grid_spec=_gather_grid(K, nm, R, tile_m, num_scalar_prefetch=4),
        out_shape=jax.ShapeDtypeStruct((K, M), jnp.float32),
        interpret=interpret,
    )(idx.reshape(-1), member.astype(jnp.float32).reshape(-1),
      wslot.astype(jnp.float32).reshape(-1),
      active.astype(jnp.float32).reshape(-1), W.astype(jnp.float32))
