"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps)."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_topology
from repro.core.participation import masked_combination
from repro.core.sharded import mix_dense
from repro.kernels import diffusion_mix as dm
from repro.kernels.ops import attention_op, mix_op, ssd_op
from repro.kernels.ref import attention_ref, mix_ref, ssd_ref
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,S,H,Kv,D", [
    (1, 128, 4, 4, 64),    # MHA
    (2, 256, 8, 2, 64),    # GQA 4x
    (1, 192, 6, 1, 32),    # MQA, padded seq (192 % 128 != 0)
    (2, 128, 4, 2, 128),   # MXU-width head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, Kv, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Kv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Kv, D), dtype)
    out = attention_op(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 96])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    out = attention_op(q, k, v, causal=True, window=window, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_matches_model_path():
    """Kernel == the model's streaming-jnp attention (same math, two impls)."""
    from repro.models.layers import flash_attention_jnp
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 160, 4, 32))
    k = jax.random.normal(ks[1], (2, 160, 2, 32))
    v = jax.random.normal(ks[2], (2, 160, 2, 32))
    a = attention_op(q, k, v, interpret=True)
    b = flash_attention_jnp(q, k, v, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 2, 16, 8, 32),
    (2, 128, 4, 32, 16, 64),
    (1, 100, 3, 64, 32, 32),   # padded seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_sweep(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n), dtype)
    C = jax.random.normal(ks[4], (b, s, n), dtype)
    y, fin = ssd_op(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, finr = ssd_ref(x, dt, A, B, C)
    tol = 2e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr),
                               atol=tol, rtol=tol)


def test_ssd_kernel_matches_model_chunked():
    """Pallas chunked SSD == the model's jnp chunked SSD."""
    ks = jax.random.split(KEY, 5)
    b, s, h, p, n = 2, 128, 4, 32, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y1, f1 = ssd_op(x, dt, A, B, C, chunk=32, interpret=True)
    y2, f2 = ssd_chunked(x, dt, A, B, C, chunk=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=1e-4)


def test_ssd_with_initial_state():
    ks = jax.random.split(KEY, 6)
    b, s, h, p, n = 1, 64, 2, 16, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    init = jax.random.normal(ks[5], (b, h, p, n))
    y, fin = ssd_op(x, dt, A, B, C, chunk=32, initial_state=init,
                    interpret=True)
    yr, finr = ssd_ref(x, dt, A, B, C, initial_state=init)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-3)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr), atol=2e-3)


@pytest.mark.parametrize("K,shapes", [
    (4, [(5, 3), (17,)]),
    (12, [(33, 7), (129,), (2, 2, 2)]),
    (20, [(64,)]),
])
def test_mix_kernel_sweep(K, shapes):
    topo = make_topology("ring", K)
    A = jnp.asarray(topo.A, jnp.float32)
    active = jax.random.bernoulli(KEY, 0.7, (K,)).astype(jnp.float32)
    params = {f"p{i}": jax.random.normal(jax.random.fold_in(KEY, i),
                                         (K,) + s)
              for i, s in enumerate(shapes)}
    mixed = mix_op(A, active, params, tile_m=128, interpret=True)
    ref = mix_dense(masked_combination(A, active), params)
    for k in params:
        np.testing.assert_allclose(np.asarray(mixed[k]), np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5)


def test_mix_kernel_full_participation_identity():
    """All agents active + identity matrix => no-op."""
    K = 8
    A = jnp.eye(K)
    active = jnp.ones((K,))
    W = jax.random.normal(KEY, (K, 256))
    out = dm.diffusion_mix(A, active, W, tile_m=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(W), atol=1e-6)


TILE = 128
ROOT = Path(__file__).resolve().parents[1]


def _cell_stack(config: str, K: int) -> tuple[int, int]:
    """(K, M) of a benchmark cell's stack: one agent's parameters padded to
    the mixer's 512-column tile."""
    from benchmarks.chip import model
    cfg = model.model_config(model.load_json(
        ROOT / "benchmarks" / "chip" / "configs" / f"{config}.json"))
    n = cfg.total_params()
    return K, n + (-n) % 512


def _mix_case(K, M, dtype):
    W = jax.random.normal(jax.random.fold_in(KEY, K), (K, M), dtype)
    A = jnp.asarray(make_topology("ring", K).A, jnp.float32)
    active = jnp.ones((K,)).at[K - 1].set(0.0)      # one agent sits out
    return A, active, W


@pytest.mark.parametrize("K,dtype,budget", [
    (K, dtype, "small") for K in (2, 4) for dtype in (jnp.float32, jnp.bfloat16)
] + [(2, jnp.float32, "default"), (4, jnp.float32, "default")])
def test_mix_kernel_block_invariance(monkeypatch, K, dtype, budget):
    """Wide blocks with a ragged last one (M = 3 blocks + 640 columns) give
    bit for bit what one 128-column tile per grid step gives, and match the
    dense combination.  ``small`` shrinks the contraction to two tiles and
    the VMEM budget to blocks of two loop iterations, so that the
    tile-per-step reference stays quick in bfloat16 (~40 s here at the
    default budget's blocks)."""
    if budget == "small":
        monkeypatch.setattr(dm, "_MIX_CHUNK_BYTES", 2 * TILE * 8 * 4)
        _, chunk, unroll = dm._mix_blocks(K, 1 << 40, dtype, TILE)
        monkeypatch.setattr(dm, "_MIX_VMEM_BUDGET", dm._mix_vmem_bytes(
            K, 2 * chunk * unroll, chunk, unroll, dtype))
    block, chunk, unroll = dm._mix_blocks(K, 1 << 40, dtype, TILE)
    M = 3 * block + 640
    A, active, W = _mix_case(K, M, dtype)
    # jit afresh, so the (possibly patched) sizes are read at trace time
    mix = jax.jit(dm.diffusion_mix.__wrapped__,
                  static_argnames=("tile_m", "interpret"))
    out = mix(A, active, W, tile_m=TILE, interpret=True)
    assert dm._mix_blocks(K, M, dtype, TILE) == (block, chunk, unroll)
    assert unroll > 1 and block > chunk * unroll
    tiles = dm._mix_call(A, active, W, block=TILE, chunk=TILE, unroll=1,
                         interpret=True)
    assert out.dtype == W.dtype
    np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                  np.asarray(tiles.astype(jnp.float32)))
    ref = mix_dense(masked_combination(A, active), W.astype(jnp.float32))
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
    else:      # one rounding of the float32 result to bfloat16
        np.testing.assert_array_equal(
            np.asarray(out.astype(jnp.float32)),
            np.asarray(ref.astype(jnp.bfloat16).astype(jnp.float32)))


@pytest.mark.parametrize("config,agents", [("smollm-360m", 4),
                                           ("chatglm3-6b-d4v4", 2)])
def test_mix_blocks_at_the_cells_shapes(config, agents):
    """At the cells' bfloat16 stacks each grid step moves >= 1 MiB of the
    stack (read + write), within the VMEM it was sized by; the block is
    lane-aligned and holds whole chunks of whole tiles."""
    K, M = _cell_stack(config, agents)
    block, chunk, unroll = dm._mix_blocks(K, M, jnp.bfloat16, 512)
    assert 2 * K * block * 2 >= 2**20
    assert dm._mix_vmem_bytes(K, block, chunk, unroll, jnp.bfloat16) \
        <= dm._MIX_VMEM_BUDGET
    assert block % 128 == 0 and chunk % 512 == 0
    assert block % (chunk * unroll) == 0
    assert -(-M // block) < M // 512 // 100     # 100x fewer grid steps


def test_mix_blocks_shrink_with_K_and_clamp_to_narrow_stacks():
    wide = dm._mix_blocks(4, 1 << 30, jnp.float32, 512)[0]
    block, chunk, unroll = dm._mix_blocks(64, 1 << 30, jnp.float32, 512)
    assert 512 <= block < wide
    assert dm._mix_vmem_bytes(64, block, chunk, unroll, jnp.float32) \
        <= dm._MIX_VMEM_BUDGET
    # narrower than one block: the whole stack in a single step, in chunks
    # and loop iterations that divide it
    assert dm._mix_blocks(4, 640, jnp.float32, 128) == (640, 128, 1)
    assert dm._mix_blocks(4, 3 * 2048, jnp.bfloat16, 512) == (3 * 2048,
                                                              2048, 1)
    assert dm._mix_blocks(4, 8 * 4096, jnp.bfloat16, 512) == (8 * 4096,
                                                              4096, 8)
