"""jit'd public wrappers around the Pallas kernels.

On the CPU, which is for tests, kernels execute in ``interpret=True`` mode
for correctness validation; on TPU they compile natively (run them there
through ``python chip_smoke.py``).  Each wrapper handles
padding to block multiples and pytree flattening so callers never see kernel
layout constraints.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import diffusion_mix as mix_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import ssd_scan as ssd_k

PyTree = Any


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


from functools import partial


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attention_core(q, k, v, causal, window, block_q, block_kv, interpret):
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    pq = (-Sq) % block_q
    pk = (-Skv) % block_kv
    if pq or pk:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    out = fa_k.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv,
                               interpret=interpret)
    return out[:, :Sq]


def _attention_core_fwd(q, k, v, causal, window, block_q, block_kv, interpret):
    return (_attention_core(q, k, v, causal, window, block_q, block_kv,
                            interpret), (q, k, v))


def _attention_core_bwd(causal, window, block_q, block_kv, interpret, res, g):
    # backward through the memory-safe streaming jnp twin (same math; the
    # usual kernel-forward / XLA-backward pattern)
    from repro.models.layers import flash_attention_jnp
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: flash_attention_jnp(
        q_, k_, v_, causal=causal, window=window), q, k, v)
    return vjp(g)


_attention_core.defvjp(_attention_core_fwd, _attention_core_bwd)


def attention_op(q, k, v, *, causal: bool = True, window: int | None = None,
                 block_q: int = 128, block_kv: int = 128,
                 interpret: bool | None = None):
    """Flash attention with automatic sequence padding (differentiable)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _attention_core(q, k, v, causal, window, block_q, block_kv,
                           interpret)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_core(x, dt, A, B, C, chunk, interpret):
    s = x.shape[1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y, final = ssd_k.ssd_chunked_kernel(x, dt, A, B, C, chunk=chunk,
                                        interpret=interpret)
    return y[:, :s], final


def _ssd_core_fwd(x, dt, A, B, C, chunk, interpret):
    return _ssd_core(x, dt, A, B, C, chunk, interpret), (x, dt, A, B, C)


def _ssd_core_bwd(chunk, interpret, res, g):
    from repro.models.ssm import ssd_chunked

    def ref(x, dt, A, B, C):
        s = x.shape[1]
        pad = (-s) % chunk
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
            C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        y, final = ssd_chunked(x, dt, A, B, C, chunk=chunk)
        return y[:, :s], final

    _, vjp = jax.vjp(ref, *res)
    return vjp(g)


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd_op(x, dt, A, B, C, *, chunk: int = 128,
           initial_state=None, interpret: bool | None = None):
    """Chunked SSD (Pallas intra-chunk) with automatic padding.

    Differentiable via the jnp chunked twin (kernel forward / XLA backward).
    ``initial_state`` bypasses the custom-vjp fast path (prefill-continuation
    only; not used in training).
    """
    interpret = _default_interpret() if interpret is None else interpret
    if initial_state is not None:
        s = x.shape[1]
        pad = (-s) % chunk
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
            C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        y, final = ssd_k.ssd_chunked_kernel(x, dt, A, B, C, chunk=chunk,
                                            initial_state=initial_state,
                                            interpret=interpret)
        return y[:, :s], final
    return _ssd_core(x, dt, A, B, C, chunk, interpret)


def mix_op(A, active, params: PyTree, *, tile_m: int = 512,
           interpret: bool | None = None) -> PyTree:
    """Masked combination step over an agent-stacked parameter pytree.

    Flattens all leaves to one (K, M) matrix, runs the fused mask+mix kernel,
    and unflattens.  Semantically identical to
    ``core.sharded.mix_dense(masked_combination(A, active), params)``.
    """
    interpret = _default_interpret() if interpret is None else interpret
    leaves, treedef = jax.tree_util.tree_flatten(params)
    K = leaves[0].shape[0]
    sizes = [int(x.size // K) for x in leaves]
    flat = jnp.concatenate(
        [x.reshape(K, -1).astype(jnp.float32) for x in leaves], axis=1)
    M = flat.shape[1]
    pad = (-M) % tile_m
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    mixed = mix_k.diffusion_mix(A, active, flat, tile_m=tile_m,
                                interpret=interpret)[:, :M]
    outs = []
    off = 0
    for leaf, n in zip(leaves, sizes):
        outs.append(mixed[:, off:off + n].reshape(leaf.shape).astype(leaf.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, outs)
