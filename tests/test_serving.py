"""Serving as a product (PR 7): the fused decode loop (sampling inside the
jitted ``lax.scan`` step), cached-decode correctness gates (including the
sliding-window ring buffer wrapping), the continuous slot-batched
:class:`repro.launch.serving.ServeLoop` with double-buffered checkpoint
swaps, int8 consensus extraction, and the serve CLI's preset shim."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.serving import ParamStore, consensus_from_stacked
from repro.launch import serve
from repro.launch.serving import Request, ServeLoop, replay_completion
from repro.models import transformer as tf

KEY = jax.random.PRNGKey(0)


def _smoke(arch):
    cfg = get_config(arch).smoke
    if cfg.num_experts:
        # exact decode-vs-forward parity needs capacity-contention-free
        # routing (same convention as test_arch_smoke)
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    return cfg


# ---------------------------------------------------------------------------
# cached-decode correctness gates
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["smollm_360m", "granite_moe_1b_a400m",
                                  "mamba2_2p7b"])
def test_decode_gate_matches_uncached_forward(arch):
    """prefill + decode_step logits track the uncached full forward over a
    longer horizon than the per-arch smoke test (8 decoded positions)."""
    cfg = _smoke(arch)
    params = tf.init_params(KEY, cfg)
    B, S, n_dec = 2, 16, 8
    toks = jax.random.randint(KEY, (B, S + n_dec), 0, cfg.vocab_size)
    full, _, _ = tf.forward(params, cfg, toks, remat=False)
    lg, cache = tf.prefill(params, cfg, toks[:, :S], max_len=S + n_dec)
    np.testing.assert_allclose(np.asarray(lg[:, -1]),
                               np.asarray(full[:, S - 1]),
                               atol=2e-3, rtol=2e-3)
    for t in range(n_dec):
        lg_t, cache = tf.decode_step(params, cfg, cache,
                                     toks[:, S + t:S + t + 1])
        np.testing.assert_allclose(np.asarray(lg_t[:, 0]),
                                   np.asarray(full[:, S + t]),
                                   atol=5e-3, rtol=5e-3)


def test_decode_gate_sliding_window_ring_wrap():
    """starcoder2 smoke (window=64): decoding past the window wraps the
    ring buffer; cached logits must still match the uncached forward
    (which applies the same sliding-window mask)."""
    cfg = get_config("starcoder2_15b").smoke
    W = cfg.attention_window
    assert W == 64
    params = tf.init_params(KEY, cfg)
    B, S, n_dec = 2, 60, 12                     # reaches position 71 > W
    toks = jax.random.randint(KEY, (B, S + n_dec), 0, cfg.vocab_size)
    full, _, _ = tf.forward(params, cfg, toks, remat=False)
    lg, cache = tf.prefill(params, cfg, toks[:, :S], max_len=S + n_dec)
    np.testing.assert_allclose(np.asarray(lg[:, -1]),
                               np.asarray(full[:, S - 1]),
                               atol=2e-3, rtol=2e-3)
    wrapped = False
    for t in range(n_dec):
        lg_t, cache = tf.decode_step(params, cfg, cache,
                                     toks[:, S + t:S + t + 1])
        wrapped = wrapped or (S + t) >= W
        np.testing.assert_allclose(np.asarray(lg_t[:, 0]),
                                   np.asarray(full[:, S + t]),
                                   atol=5e-3, rtol=5e-3)
    assert wrapped


# ---------------------------------------------------------------------------
# fused decode loop: parity, key-freedom, sampled shapes
# ---------------------------------------------------------------------------

def _py_greedy(params, cfg, cache, logits, n):
    """The legacy per-token loop: eager (key-free) greedy sampling + one
    jitted decode_step dispatch per token."""
    decode1 = jax.jit(lambda p, c, t: tf.decode_step(p, cfg, c, t))
    toks = []
    for _ in range(n):
        nxt = tf.sample_logits(logits, None, 0.0)
        toks.append(np.asarray(nxt))
        tok = nxt[:, None, :] if cfg.num_codebooks else nxt[:, None]
        lg, cache = decode1(params, cache, tok)
        logits = lg[:, 0]
    return np.stack(toks, axis=1)


@pytest.mark.parametrize("arch", ["smollm_360m", "starcoder2_15b",
                                  "mamba2_2p7b", "musicgen_medium"])
def test_fused_py_greedy_token_parity(arch):
    """At temperature 0 the fused lax.scan loop and the per-token py loop
    emit bit-identical tokens, and BOTH are key-free (key=None)."""
    cfg = get_config(arch).smoke
    params = tf.init_params(KEY, cfg)
    B, S, n = 2, 12, 8
    shape = (B, S) if not cfg.num_codebooks else (B, S, cfg.num_codebooks)
    prompts = jax.random.randint(KEY, shape, 0, cfg.vocab_size)
    logits, cache = tf.prefill(params, cfg, prompts, max_len=S + n)
    first = logits[:, -1]
    fused_toks, _, _ = tf.decode_loop(params, cfg, cache, first, None, n,
                                      temperature=0.0)
    py_toks = _py_greedy(params, cfg, cache, first, n)
    np.testing.assert_array_equal(np.asarray(fused_toks), py_toks)


def test_fused_sampled_shapes_and_determinism():
    """temperature > 0: tokens are in-vocab int32 of shape (B, n) and the
    generation is a pure function of the key."""
    cfg = get_config("smollm_360m").smoke
    params = tf.init_params(KEY, cfg)
    B, S, n = 2, 12, 6
    prompts = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    logits, cache = tf.prefill(params, cfg, prompts, max_len=S + n)
    k = jax.random.PRNGKey(7)
    toks, last, _ = tf.decode_loop(params, cfg, cache, logits[:, -1], k, n,
                                   temperature=0.8)
    assert toks.shape == (B, n) and toks.dtype == jnp.int32
    assert last.shape == (B, cfg.vocab_size)
    assert bool((toks >= 0).all()) and bool((toks < cfg.vocab_size).all())
    again, _, _ = tf.decode_loop(params, cfg, cache, logits[:, -1], k, n,
                                 temperature=0.8)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(again))


# ---------------------------------------------------------------------------
# continuous slot-batched serving
# ---------------------------------------------------------------------------

def _single_request_reference(cfg, params, prompt, n, max_len):
    logits, cache = tf.prefill(params, cfg, jnp.asarray(prompt)[None],
                               max_len=max_len)
    toks, _, _ = tf.decode_loop(params, cfg, cache, logits[:, -1], None, n,
                                temperature=0.0)
    return np.asarray(toks[0])


@pytest.mark.parametrize("decode_loop", ["fused", "py"])
def test_serveloop_matches_single_request(decode_loop):
    """Slot-batched continuous serving (more requests than slots, ragged
    prompt lengths, slot reuse after retirement) emits exactly the tokens
    each request would get served alone."""
    cfg = get_config("smollm_360m").smoke
    params = tf.init_params(KEY, cfg)
    max_len = 48
    loop = ServeLoop(cfg, params, slots=2, max_len=max_len,
                     decode_loop=decode_loop, chunk=3)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, max_new_tokens=6 + (i % 3),
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=(7 + 2 * i,)).astype(np.int32))
            for i in range(5)]
    for r in reqs:
        loop.submit(r)
    done = []
    while loop._queue or loop.active:
        done.extend(loop.step())
    assert sorted(c.uid for c in done) == [r.uid for r in reqs]
    for c in done:
        ref = _single_request_reference(cfg, params, reqs[c.uid].prompt,
                                        reqs[c.uid].max_new_tokens, max_len)
        np.testing.assert_array_equal(np.asarray(c.tokens), ref)


@pytest.mark.parametrize("decode_loop", ["fused", "py"])
def test_serveloop_serves_a_bfloat16_model(decode_loop):
    """Published widths keep bfloat16 weights, so the decode logits are
    bfloat16 while the loop carries float32 ones: both loops must still
    emit exactly the single-request tokens."""
    cfg = dataclasses.replace(get_config("smollm_360m").smoke,
                              dtype="bfloat16")
    params = tf.init_params(KEY, cfg)
    loop = ServeLoop(cfg, params, slots=2, max_len=32,
                     decode_loop=decode_loop, chunk=4)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, max_new_tokens=6,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=(8 + 4 * i,)).astype(np.int32))
            for i in range(3)]
    for r in reqs:
        loop.submit(r)
    done = loop.run()
    assert sorted(c.uid for c in done) == [0, 1, 2]
    for c in done:
        ref = _single_request_reference(cfg, params, reqs[c.uid].prompt, 6,
                                        32)
        np.testing.assert_array_equal(np.asarray(c.tokens), ref)


def test_serveloop_swap_under_load_replay():
    """>= 8 double-buffered param swaps while decodes are in flight: every
    emitted token replays exactly under its recorded checkpoint
    generation (no torn update), and completions span generations."""
    cfg = get_config("smollm_360m").smoke
    params = tf.init_params(KEY, cfg)
    loop = ServeLoop(cfg, params, slots=2, max_len=48, chunk=2)
    rng = np.random.default_rng(6)
    reqs = [Request(uid=i, max_new_tokens=10,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=(8 + i,)).astype(np.int32))
            for i in range(4)]
    for r in reqs:
        loop.submit(r)
    params_by_gen, done = {0: params}, []
    while loop._queue or loop.active:
        done.extend(loop.step())
        g = loop.store.generation + 1
        newp = jax.tree.map(lambda x, s=g: x * (1.0 + 0.03 * s), params)
        params_by_gen[loop.store.swap(newp)] = newp
    assert loop.store.generation >= 8
    assert len(done) == len(reqs)
    spans = [replay_completion(cfg, params_by_gen, c, max_len=48)
             for c in done]
    assert max(spans) > 1                       # swaps landed mid-request


def test_param_store_snapshot_is_generation_consistent():
    store = ParamStore({"w": jnp.zeros((2,))})
    p0, g0 = store.snapshot()
    assert g0 == 0
    g1 = store.swap({"w": jnp.ones((2,))})
    assert g1 == 1
    p1, g1b = store.snapshot()
    assert g1b == 1
    np.testing.assert_array_equal(np.asarray(p1["w"]), np.ones((2,)))
    np.testing.assert_array_equal(np.asarray(p0["w"]), np.zeros((2,)))


# ---------------------------------------------------------------------------
# int8 consensus extraction
# ---------------------------------------------------------------------------

def _stacked(K):
    ks = jax.random.split(KEY, 2)
    return {"w": jax.random.normal(ks[0], (K, 32, 16)),
            "b": jax.random.normal(ks[1], (K, 8))}


def test_consensus_int8_close_to_f32_and_deterministic():
    K = 6
    stacked = _stacked(K)
    f32 = consensus_from_stacked(stacked, K, "dense")
    i8 = consensus_from_stacked(stacked, K, "dense", quantize="int8")
    sq_err = sq_ref = 0.0
    for a, b in zip(jax.tree.leaves(f32), jax.tree.leaves(i8)):
        a = np.asarray(a, np.float64)
        sq_err += float(np.sum((a - np.asarray(b, np.float64)) ** 2))
        sq_ref += float(np.sum(a ** 2))
    assert sq_err / sq_ref < 1e-3
    again = consensus_from_stacked(stacked, K, "dense", quantize="int8")
    for a, b in zip(jax.tree.leaves(i8), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_consensus_quantize_rejects_unknown():
    with pytest.raises(ValueError, match="quantize"):
        consensus_from_stacked(_stacked(4), 4, "dense", quantize="int4")


# ---------------------------------------------------------------------------
# serve CLI: preset shim + checkpoint-precedence warning
# ---------------------------------------------------------------------------

def test_preset_without_explicit_agents_errors():
    """serve's --agents=1 deprecation shim must not silently override a
    preset's agent count: --preset now requires an explicit --agents."""
    with pytest.raises(SystemExit):
        serve.main(["--preset", "fedavg_full", "--smoke"])


def test_preset_shim_unit():
    import argparse
    ap = argparse.ArgumentParser()
    ns = argparse.Namespace(preset="fedavg_full", _explicit=set())
    with pytest.raises(SystemExit):
        serve._check_preset_shim(ap, ns)
    ns_ok = argparse.Namespace(preset="fedavg_full", _explicit={"agents"})
    serve._check_preset_shim(ap, ns_ok)        # no error
    ns_none = argparse.Namespace(preset=None, _explicit=set())
    serve._check_preset_shim(ap, ns_none)      # no error


def test_spec_checkpoint_overrides_preset_with_warning(tmp_path):
    """A spec-embedding checkpoint is self-describing; --spec/--preset on
    the command line are ignored for serving, with a warning."""
    import argparse

    from repro.api import ModelSpec, build
    from repro.api.cli import add_spec_args
    from repro.checkpoint import save_experiment
    from repro.core import variants

    K = 2
    spec = variants.vanilla_diffusion(K, mu=0.02).replace(
        model=ModelSpec(kind="transformer", arch="smollm-360m", smoke=True))
    eng = build(spec)
    state = eng.init_state(eng.init_params(jax.random.PRNGKey(0)))
    path = str(tmp_path / "spec_ckpt.npz")
    save_experiment(path, state, spec=spec, step=1)

    ap = argparse.ArgumentParser()
    add_spec_args(ap)
    ap.add_argument("--checkpoint", default=None)
    ap.set_defaults(agents=1)
    args = ap.parse_args(["--checkpoint", path, "--preset", "fedavg_full"])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        params, cfg = serve.load_params(args, jax.random.PRNGKey(1))
    assert any("takes precedence" in str(w.message) for w in caught)
    assert cfg.d_model == get_config("smollm-360m").smoke.d_model
