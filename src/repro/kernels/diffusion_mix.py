"""Pallas TPU kernels for the paper's combination step (eq. 20 + mixing).

Fuses the per-sample-path masking of the combination matrix (eq. 20) with
the parameter mix  W'_k = sum_l a_lk W_l , so the masked (K, K) matrix is
(re)built in VMEM registers per grid step and never round-trips to HBM, and
the stacked parameter matrix is streamed exactly once.

Layout: the agent-stacked parameter tree is flattened to (K, M); the grid
walks M.  tile_m (a multiple of 128, for lane alignment) is the quantum the
caller pads M to.  :func:`diffusion_mix` sizes its (K, block) blocks from K,
M and the dtype to fill a VMEM budget, so each grid step moves MiBs of the
stack rather than one tile, and contracts them a chunk of tiles at a time;
K is small (<= 64 agents), so the (K, K) mix lives comfortably in VMEM
beside them.  The other kernels move one (K, tile_m) tile per grid step.

Four variants:

* :func:`diffusion_mix` — float32 or bfloat16 buffer.  Materializes the
  (K, K) matrix per block: the right shape when K is small (<= a few
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
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# the combination kernel's blocks.  A grid step costs ~0.5 us on a v5e
# whatever it moves, so a block carries MiBs of the stack.  Inside a block
# each contraction takes a float32 operand of ~128 KiB, and one loop
# iteration runs 16 of them, which the scheduler overlaps (at K=4 on a v5e,
# 16 x 4096 columns per iteration beat one 65536-column contraction by 9%).
_MIX_VMEM_BUDGET = 48 * 2**20     # of a v5e's 128 MiB of VMEM
_VMEM_DEFAULT_LIMIT = 16 * 2**20  # a v5e's default scoped VMEM limit
_MIX_CHUNK_BYTES = 128 * 2**10    # float32 operand of one contraction
_MIX_UNROLL = 16                  # contractions per loop iteration
_MIX_F32_TEMPS = 4                # float32 temporaries per contraction

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


def _mix_kernel(a_ref, m_ref, w_ref, o_ref, *, K: int, chunk: int,
                unroll: int):
    A = a_ref[...].astype(jnp.float32)                  # (K, K)
    m = m_ref[...].astype(jnp.float32)[:, 0]            # (K,)
    A_eff = _masked_matrix(A, m, K)

    # W'_k = sum_l A_eff[l, k] W[l]  ==  A_eff^T @ W, over the block's
    # columns one chunk at a time, ``unroll`` chunks per loop iteration
    def body(j, carry):
        for u in range(unroll):
            start = pl.multiple_of((j * unroll + u) * chunk, chunk)
            cols = pl.ds(start, chunk)
            W = w_ref[:, cols].astype(jnp.float32)      # (K, chunk)
            o_ref[:, cols] = _contract(A_eff, W).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, w_ref.shape[1] // (chunk * unroll), body, 0)


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


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublanes(dtype) -> int:
    """Rows of the dtype's VMEM tile: 8 for float32, 16 for bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def _mix_vmem_bytes(K: int, block: int, chunk: int, unroll: int,
                    dtype) -> int:
    """VMEM the combination kernel holds for (K, block) blocks: the input
    and output blocks double-buffered with K padded to the dtype's sublane
    tile, A and the mask double-buffered, and the float32 temporaries of one
    loop iteration's ``unroll`` contractions of ``chunk`` columns (the
    upcast, the operands split into bfloat16 passes, the result)."""
    rows, rows32 = _round_up(K, _sublanes(dtype)), _round_up(K, 8)
    blocks = 2 * 2 * rows * block * jnp.dtype(dtype).itemsize
    small = 2 * 2 * rows32 * _round_up(K, 128) * 4
    temps = _MIX_F32_TEMPS * rows32 * chunk * unroll * 4
    return blocks + small + temps


def _mix_blocks(K: int, M: int, dtype, tile_m: int) -> tuple[int, int, int]:
    """(block, chunk, unroll): the columns one grid step moves, the columns
    of one contraction, and the contractions per loop iteration.  The chunk
    is the multiple of ``tile_m`` (itself a multiple of 128) nearest below
    ``_MIX_CHUNK_BYTES`` of float32 rows; the block is the widest multiple of
    ``unroll`` chunks whose :func:`_mix_vmem_bytes` fits ``_MIX_VMEM_BUDGET``
    (so it narrows as K grows), and the whole stack where that is wider, in
    chunks and iterations that divide it."""
    rows32 = _round_up(K, 8)
    chunk = max(tile_m, _MIX_CHUNK_BYTES // (4 * rows32) // tile_m * tile_m)
    step = chunk * _MIX_UNROLL
    per_column = (_mix_vmem_bytes(K, 1, 0, 0, dtype)
                  - _mix_vmem_bytes(K, 0, 0, 0, dtype))
    room = _MIX_VMEM_BUDGET - _mix_vmem_bytes(K, 0, chunk, _MIX_UNROLL, dtype)
    block = max(step, room // per_column // step * step)
    if block < M:
        return block, chunk, _MIX_UNROLL
    chunk = tile_m * math.gcd(M // tile_m, chunk // tile_m)
    return M, chunk, math.gcd(M // chunk, _MIX_UNROLL)


def _mix_call(A, active, W, *, block: int, chunk: int, unroll: int,
              interpret: bool):
    K, M = W.shape
    need = _mix_vmem_bytes(K, block, chunk, unroll, W.dtype)
    return pl.pallas_call(
        functools.partial(_mix_kernel, K=K, chunk=chunk, unroll=unroll),
        grid=(pl.cdiv(M, block),),
        in_specs=[
            pl.BlockSpec((K, K), lambda mi: (0, 0)),
            pl.BlockSpec((K, 1), lambda mi: (0, 0)),
            pl.BlockSpec((K, block), lambda mi: (0, mi)),
        ],
        out_specs=pl.BlockSpec((K, block), lambda mi: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((K, M), W.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(need, _VMEM_DEFAULT_LIMIT)),
        interpret=interpret,
    )(A, active.reshape(K, 1), W)


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def diffusion_mix(A: jax.Array, active: jax.Array, W: jax.Array, *,
                  tile_m: int = 512, interpret: bool = False) -> jax.Array:
    """Masked combination step over flattened stacked parameters.

    Each grid step moves one (K, block) block of the stack, the block sized
    from K, M and W's dtype by :func:`_mix_blocks`; the last block may be
    ragged.  Inside a block the contraction runs over chunks of a multiple
    of ``tile_m`` columns.  Every output column is A_eff^T @ W[:, j] in
    float32, rounded to W's dtype once, whatever the block.

    Args:
      A: (K, K) base combination matrix.
      active: (K,) activation mask in {0, 1}.
      W: (K, M) stacked flattened parameters; M % tile_m == 0 (pad upstream),
        tile_m % 128 == 0.
    Returns:
      (K, M) mixed parameters, dtype of W.
    """
    K, M = W.shape
    if tile_m % 128:
        raise ValueError(f"tile_m={tile_m} must be a multiple of 128")
    if M % tile_m:
        raise ValueError(f"M={M} not divisible by tile_m={tile_m}")
    block, chunk, unroll = _mix_blocks(K, M, W.dtype, tile_m)
    return _mix_call(A, active, W, block=block, chunk=chunk, unroll=unroll,
                     interpret=interpret)


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
