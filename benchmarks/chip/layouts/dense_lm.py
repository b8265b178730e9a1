"""The weight layout and model-FLOP count of the dense decoder family.

A family module (``layouts/<reference>.py``, named like the configuration's
plain reference) is what the harness knows of a model family:

* :func:`make_weights` draws one agent's weights on the device from a key,
  in the canonical layout the plain reference reads: one array per kind of
  tensor, stacked over layers;
* :func:`to_program` renames them into the program's parameter tree and
  :func:`from_program` back (leading axes, such as the agent axis, pass
  through);
* :func:`flops_per_token` is the model FLOP of one training token, the
  numerator of ``step_mfu``;
* :func:`smoke` cuts a configuration's widths to a size the CPU tests can
  hold.

This module covers dense SwiGLU decoders without qk-norm
(``repro.models.transformer``: one scanned segment of attention blocks).
The training count is the arithmetic of ``benchmarks/roofline.py``'s
``analytic_flops`` for a train shape: 6 FLOP per matmul parameter per token
for the forward and backward passes, plus the causal attention term
6 * layers * heads * head_dim * seq per token.  Recomputation is not
counted: this is model FLOP.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["make_weights", "to_program", "from_program", "flops_per_token",
           "smoke", "param_count", "matmul_params"]


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def make_weights(key: jax.Array, cfg) -> dict:
    """One agent's weights in the canonical layout, in the configuration's
    dtype.  Scales follow the program's initialiser (embedding 0.02,
    projections 1/sqrt(fan-in) of d_model, the down projection 1/sqrt(d_ff),
    norms 1), so the benchmark trains what the program would."""
    D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_layers
    Hq = cfg.num_heads * cfg.head_dim
    Hkv = cfg.num_kv_heads * cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 9)
    s = 1.0 / np.sqrt(D)
    w = {
        "embed": _normal(ks[0], (V, D), 0.02).astype(dt),
        "layers": {
            "ln1": jnp.ones((L, D), dt),
            "wq": _normal(ks[1], (L, D, Hq), s).astype(dt),
            "wk": _normal(ks[2], (L, D, Hkv), s).astype(dt),
            "wv": _normal(ks[3], (L, D, Hkv), s).astype(dt),
            "wo": _normal(ks[4], (L, Hq, D), s).astype(dt),
            "ln2": jnp.ones((L, D), dt),
            "w_gate": _normal(ks[5], (L, D, F), s).astype(dt),
            "w_up": _normal(ks[6], (L, D, F), s).astype(dt),
            "w_down": _normal(ks[7], (L, F, D), 1.0 / np.sqrt(F)).astype(dt),
        },
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        w["lm_head"] = _normal(ks[8], (D, V), s).astype(dt)
    return w


def to_program(w: dict, cfg) -> dict:
    """The canonical weights renamed into the program's parameter tree."""
    if cfg.family != "dense" or cfg.mlp_act != "silu" or cfg.qk_norm:
        raise ValueError("the benchmark's weights cover dense SwiGLU "
                         f"decoders without qk-norm, not {cfg.name}")
    lw = w["layers"]
    seg = {"ln1": {"scale": lw["ln1"]},
           "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
           "ln2": {"scale": lw["ln2"]},
           "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}
    p = {"embed": w["embed"],
         "segments": {f"00.attn.{cfg.num_layers:03d}": seg},
         "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"]
    return p


def from_program(p: dict, cfg) -> dict:
    """The inverse of :func:`to_program`; this family needs nothing of
    ``cfg`` for it."""
    (seg,) = p["segments"].values()
    w = {"embed": p["embed"],
         "layers": {"ln1": seg["ln1"]["scale"], **seg["attn"],
                    "ln2": seg["ln2"]["scale"], **seg["mlp"]},
         "final_norm": p["final_norm"]["scale"]}
    if "lm_head" in p:
        w["lm_head"] = p["lm_head"]
    return w


def param_count(cfg) -> int:
    """Parameters of a dense decoder (norms excluded), as the program's
    ``ModelConfig.total_params`` counts them."""
    D, V = cfg.d_model, cfg.vocab_size
    attn = (2 * D * cfg.num_heads * cfg.head_dim
            + 2 * D * cfg.num_kv_heads * cfg.head_dim)
    mlp = (3 if cfg.mlp_act == "silu" else 2) * D * cfg.d_ff
    emb = V * D * (1 if cfg.tie_embeddings else 2)
    return emb + cfg.num_layers * (attn + mlp)


def matmul_params(cfg) -> int:
    """Parameters that take part in a matrix product: all but an untied
    input embedding, which is a table lookup."""
    lookup = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.d_model
    return param_count(cfg) - lookup


def flops_per_token(cfg, seq: int) -> float:
    """Model FLOP of one training token (forward + backward)."""
    window = min(seq, cfg.attention_window or seq)
    attn = 6 * cfg.num_layers * cfg.num_heads * cfg.head_dim * window
    return float(6 * matmul_params(cfg) + attn)


def smoke(cfg):
    """``cfg`` with every width cut to a size the CPU tests can hold; the
    grouping of query over kv heads (one or several kv heads) is kept."""
    return dataclasses.replace(
        cfg, name=f"{cfg.name}-smoke", num_layers=1, d_model=64, num_heads=4,
        num_kv_heads=2 if cfg.num_kv_heads < 4 else 4, head_dim=16,
        d_ff=96, vocab_size=128, dtype="float32")
