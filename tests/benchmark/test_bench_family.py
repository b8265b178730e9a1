"""A configuration of another model family joins the benchmark by new files
only, and the token stream's Zipf draw.

Everything the second family needs is written under ``tmp_path``: a
manifest, a configuration of the program's ``moe`` family at smoke size (4
experts, top-2, a capacity that drops no token, no auxiliary loss), traffic
with a Zipf token draw, limits, the family module and a plain float32
reference.  The harness is given that directory and finds each by name."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import counts, harness, model, trace

FAMILY = "routed_lm"
CELL = "tiny-routed.t2"

LAYOUT = '''
"""Weight layout and model FLOP of a decoder whose every layer routes its
tokens over experts (the program's ``moe`` family)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def make_weights(key, cfg):
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.num_layers
    E, F = cfg.num_experts, cfg.moe_d_ff
    Hq, Hkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 10)
    s = 1.0 / np.sqrt(D)
    return {
        "embed": _normal(ks[0], (V, D), 0.02).astype(dt),
        "layers": {
            "ln1": jnp.ones((L, D), dt),
            "wq": _normal(ks[1], (L, D, Hq), s).astype(dt),
            "wk": _normal(ks[2], (L, D, Hkv), s).astype(dt),
            "wv": _normal(ks[3], (L, D, Hkv), s).astype(dt),
            "wo": _normal(ks[4], (L, Hq, D), s).astype(dt),
            "ln2": jnp.ones((L, D), dt),
            "router": _normal(ks[5], (L, D, E), s),
            "w_gate": _normal(ks[6], (L, E, D, F), s).astype(dt),
            "w_up": _normal(ks[7], (L, E, D, F), s).astype(dt),
            "w_down": _normal(ks[8], (L, E, F, D), 1 / np.sqrt(F)).astype(dt),
        },
        "final_norm": jnp.ones((D,), dt),
        "lm_head": _normal(ks[9], (D, V), s).astype(dt),
    }


_ATTN = ("wq", "wk", "wv", "wo")
_MOE = ("router", "w_gate", "w_up", "w_down")


def to_program(w, cfg):
    lw = w["layers"]
    seg = {"ln1": {"scale": lw["ln1"]}, "attn": {k: lw[k] for k in _ATTN},
           "ln2": {"scale": lw["ln2"]}, "moe": {k: lw[k] for k in _MOE}}
    return {"embed": w["embed"],
            "segments": {f"00.moe.{cfg.num_layers:03d}": seg},
            "final_norm": {"scale": w["final_norm"]},
            "lm_head": w["lm_head"]}


def from_program(p, cfg):
    (seg,) = p["segments"].values()
    return {"embed": p["embed"],
            "layers": {"ln1": seg["ln1"]["scale"], **seg["attn"],
                       "ln2": seg["ln2"]["scale"], **seg["moe"]},
            "final_norm": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]}


def flops_per_token(cfg, seq):
    D, F = cfg.d_model, cfg.moe_d_ff
    attn = 2 * D * (cfg.num_heads + cfg.num_kv_heads) * cfg.head_dim
    routed = D * cfg.num_experts + 3 * D * F * cfg.num_experts_per_token
    matmul = cfg.num_layers * (attn + routed) + cfg.vocab_size * D
    return float(6 * matmul
                 + 6 * cfg.num_layers * cfg.num_heads * cfg.head_dim * seq)


def smoke(cfg):
    return dataclasses.replace(cfg, num_layers=1, d_model=32, head_dim=8,
                               moe_d_ff=16, vocab_size=64, dtype="float32")
'''

REFERENCE = '''
"""Plain float32 reference of the routed decoder's loss: the dense
reference's attention, with every token's top-k experts (softmax scores,
the top k renormalised) computed for every token and weighted by its
gates."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference.dense_lm import _mm, _rms, _rope


def loss(w, m, tokens, labels, precision="highest"):
    mm = _mm(precision)
    H, Kv, Dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    E, k = m["num_experts"], m["num_experts_per_token"]
    eps = m.get("norm_eps", 1e-5)
    B, S = tokens.shape
    causal = np.tril(np.ones((S, S), bool))

    def layer(x, lw):
        h = _rms(x, lw["ln1"], eps)
        q = mm(h, lw["wq"], "bsd,de->bse").reshape(B, S, H, Dh)
        kk = mm(h, lw["wk"], "bsd,de->bse").reshape(B, S, Kv, Dh)
        v = mm(h, lw["wv"], "bsd,de->bse").reshape(B, S, Kv, Dh)
        q = _rope(q, m["rotary_pct"], m["rope_theta"])
        kk = _rope(kk, m["rotary_pct"], m["rope_theta"])
        kk = jnp.repeat(kk, H // Kv, axis=2)
        v = jnp.repeat(v, H // Kv, axis=2)
        s = mm(q, kk, "bqhd,bkhd->bhqk") / np.sqrt(Dh)
        s = jnp.where(causal, s, -jnp.inf)
        o = mm(jax.nn.softmax(s, -1), v, "bhqk,bkhd->bqhd")
        x = x + mm(o.reshape(B, S, H * Dh), lw["wo"], "bse,ed->bsd")
        h = _rms(x, lw["ln2"], eps)
        probs = jax.nn.softmax(mm(h, lw["router"], "bsd,de->bse"), -1)
        top, idx = jax.lax.top_k(probs, k)
        top = top / top.sum(-1, keepdims=True)
        gate = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], -2)
        f = (jax.nn.silu(mm(h, lw["w_gate"], "bsd,edf->bsef"))
             * mm(h, lw["w_up"], "bsd,edf->bsef"))
        y = mm(f, lw["w_down"], "bsef,efd->bsed")
        return x + jnp.einsum("bsed,bse->bsd", y, gate), None

    x = w["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["layers"])
    x = _rms(x, w["final_norm"], eps)
    logp = jax.nn.log_softmax(mm(x, w["lm_head"], "bsd,dv->bsv"), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
'''

CONFIG = {
    "name": "tiny-routed", "source": "test fixture", "reduced": [],
    "reference": FAMILY,
    "model": {
        "name": "tiny-routed", "family": "moe", "num_layers": 2,
        "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
        "d_ff": 0, "vocab_size": 256, "num_experts": 4,
        "num_experts_per_token": 2, "moe_d_ff": 32,
        # E / k: every expert can take every token, so none is dropped
        "capacity_factor": 2.0, "aux_loss_coef": 0.0,
        "rope_theta": 10000.0, "rotary_pct": 1.0, "mlp_act": "silu",
        "tie_embeddings": False, "norm_eps": 1e-05, "dtype": "float32"}}

TRAFFIC = {"agents": 4, "topology": "ring", "participation": 0.75,
           "local_steps": 2, "batch": 1, "seq": 32, "mixer": "auto",
           "optimizer": "sgd", "step_size": 0.25, "chips": 1,
           "check_blocks": 2, "reduced": [], "token_zipf": 1.1}


@pytest.fixture(scope="module")
def family_dir(tmp_path_factory):
    """The second family's files, and nothing else, in a directory of
    their own."""
    d = tmp_path_factory.mktemp("family")
    for sub, text in (("layouts", LAYOUT), ("reference", REFERENCE)):
        (d / sub).mkdir()
        (d / sub / f"{FAMILY}.py").write_text(text)
    for sub, name, obj in (
            ("configs", "tiny-routed", CONFIG),
            ("traffic", "tiny_zipf", TRAFFIC),
            ("limits", CELL, {"change1_gap": 0.002, "changeN_gap": 0.002,
                              "changeN_median_gap": 0.002,
                              "inactive_moved": 0})):
        (d / sub).mkdir(exist_ok=True)
        (d / sub / f"{name}.json").write_text(json.dumps(obj))
    e2e = [{"name": "train_tokens_per_s", "unit": "tokens/s",
            "better": "higher", "bound": 0.05, "source": "host_clock"},
           {"name": "setup_s", "unit": "s", "better": "lower",
            "bound": 0.25, "source": "host_clock"}]
    manifest = {
        "command": ["python3", "benchmarks/chip/cell.py"],
        "paths": ["benchmarks/chip"], "run_seconds": 1,
        "configs": [{"name": "tiny-routed", "source": "test fixture",
                     "file": "configs/tiny-routed.json", "reduced": [],
                     "why": "a second family"}],
        "workloads": [{"name": CELL, "config": "tiny-routed",
                       "traffic": "tiny_zipf", "chips": 1,
                       "why": "K=4 ring, T=2, seq 32, Zipf tokens"}],
        "end_to_end": e2e,
        "per_layer": [{"name": "step_mfu", "unit": "%", "better": "higher",
                       "source": "device_trace", "layer": "block step",
                       "moves": "train_tokens_per_s"}]}
    (d / "BENCHMARK.json").write_text(json.dumps(manifest))
    return d


def _run(family_dir, monkeypatch, fault="none", **kw):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(family_dir / "cache"))
    return harness.run(CELL, 2**33 + 29, 0.0, False,
                       t_start=time.perf_counter(),
                       manifest_path=family_dir / "BENCHMARK.json",
                       bench_dir=family_dir, require_tpu=False, fault=fault,
                       **kw)


def test_the_family_is_found_by_name(family_dir):
    cell = harness.load_cell(CELL, family_dir / "BENCHMARK.json", family_dir)
    assert harness._code(cell, "layouts", FAMILY) == (
        family_dir / "layouts" / f"{FAMILY}.py")
    assert not (harness.BENCH / "layouts" / f"{FAMILY}.py").exists()
    layout = harness.load_layout(cell)
    cfg = model.model_config(cell.config)
    model.check_program_layout(cfg, layout)
    model.check_program_layout(layout.smoke(cfg), layout)


def test_sound_run_of_the_family_is_correct(family_dir, monkeypatch):
    """The program meets the reference (it reads ~1e-6 here) where the
    reference computed in fp8 in its place does not (~0.07)."""
    out = _run(family_dir, monkeypatch, control=True)
    assert out["correct"], out["checks"]
    assert out["checks"]["change1_gap"]["value"] < 1e-4
    assert not out["control"]["correct"]


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch",
                                   "frozen_state"])
def test_planted_fault_in_the_family_is_not_correct(family_dir, monkeypatch,
                                                    fault):
    out = _run(family_dir, monkeypatch, fault)
    assert not out["correct"], out["checks"]


def test_step_mfu_takes_the_familys_count(family_dir):
    cell = harness.load_cell(CELL, family_dir / "BENCHMARK.json", family_dir)
    cfg = model.model_config(cell.config)
    layout = harness.load_layout(cell)
    ms = 1_000_000
    events = [(trace.HOST, "bench.block", 0, 40 * ms),
              ("/device:TPU:0", "fusion.1", 1 * ms, 30 * ms)]
    peaks = {"bf16_flops": 197e12}
    ctx = harness.reader_context(cell, events, cfg, layout, None, peaks,
                                 1000, 4)
    read = harness._load_module(harness._code(cell, "metrics", "step_mfu"),
                                "t_family_mfu").read
    flops = counts.block_tokens(cell.traffic) * layout.flops_per_token(
        cfg, cell.traffic["seq"])
    assert read(ctx) == pytest.approx(100.0 * flops / (0.04 * 197e12),
                                      rel=1e-12)
    dense = harness._load_module(harness.BENCH / "layouts" / "dense_lm.py",
                                 "t_family_dense")
    assert flops != counts.block_tokens(cell.traffic) * dense.flops_per_token(
        cfg, cell.traffic["seq"])


# -- the token draw -----------------------------------------------------------

def _traffic(**kw):
    return dict({"local_steps": 2, "agents": 4, "batch": 2, "seq": 4095},
                **kw)


@pytest.mark.parametrize("V,s", [(1000, 1.1), (163840, 1.1), (20480, 0.8)])
def test_zipf_top_ranks_take_their_share(V, s):
    tr = _traffic(token_zipf=s)
    key = model.seed_key(2**32 + 77)
    toks = np.asarray(model.make_block(key, 5, tr, V)["tokens"]).ravel()
    assert toks.min() >= 0 and toks.max() < V
    n = toks.size
    H = np.sum(np.arange(1, V + 1, dtype=np.float64) ** -s)
    for r in range(5):
        p = (r + 1) ** -s / H
        share = np.mean(toks == r)
        assert abs(share - p) < 5 * np.sqrt(p * (1 - p) / n), (r, share, p)
    again = model.make_block(key, 5, tr, V)["tokens"]
    assert np.array_equal(np.asarray(again).ravel(), toks)


def test_draw_without_the_key_is_uniform_randint():
    tr = _traffic()
    key = model.seed_key(2**33 + 1)
    got = model.make_block(key, 3, tr, 49152)
    toks = jax.random.randint(jax.random.fold_in(key, 3), (2, 4, 2, 4096),
                              0, 49152, jnp.int32)
    assert np.array_equal(got["tokens"], toks[..., :-1])
    assert np.array_equal(got["labels"], toks[..., 1:])


@pytest.mark.parametrize("s", [0, -1.0])
def test_zipf_exponent_must_be_positive(s):
    with pytest.raises(ValueError):
        model.make_block(jax.random.PRNGKey(0), 0, _traffic(token_zipf=s),
                         100)


@pytest.mark.parametrize("where", ["benchmark", "family"])
def test_a_family_module_has_what_the_harness_reads(family_dir, where):
    if where == "benchmark":
        manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
        cells = [harness.load_cell(w["name"]) for w in manifest["workloads"]]
    else:
        cells = [harness.load_cell(CELL, family_dir / "BENCHMARK.json",
                                   family_dir)]
    for cell in cells:
        layout = harness.load_layout(cell)
        for fn in ("make_weights", "to_program", "from_program",
                   "flops_per_token", "smoke"):
            assert callable(getattr(layout, fn)), (cell.name, fn)
