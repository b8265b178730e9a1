"""The system under test, as the benchmark builds and feeds it.

A configuration file (``configs/<name>.json``) holds the model's sizes under
``"model"``; :func:`register_model` turns them into a
:class:`repro.configs.ModelConfig` and registers it as a model kind of the
program's ``MODELS`` registry, so ``repro.api.build(spec)`` assembles the
normal sharded engine around it, rematerialising every layer as the
published-width configurations do.

Weights and token blocks are the benchmark's own, made on the device from
the seed: :func:`make_weights` draws one agent's weights in a canonical
layout (one array per kind of tensor, stacked over layers), which the
plain reference reads directly and :func:`to_program` renames into the
program's parameter tree.  Every agent starts from the same weights, as a
deployment broadcasts one initial model.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["load_json", "model_config", "register_model", "seed_key",
           "make_weights", "to_program", "from_program", "make_block",
           "check_program_layout"]


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def model_config(cfg_file: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    m = cfg_file["model"]
    unknown = sorted(set(m) - fields)
    if unknown:
        raise ValueError(f"configuration keys not in ModelConfig: {unknown}")
    return ModelConfig(**m)


def register_model(cfg, *, half_batch: bool = False) -> str:
    """Register ``cfg`` as a model kind of the program's registry and
    return the kind: the transformer's own loss with every layer
    rematerialised.  The kind is named by the configuration and the fault,
    so registering the same pair twice is a no-op.  ``half_batch`` plants
    the harness's fault of that name in the loss, for the calibration and
    the tests: the mean is taken over the first half of each sequence
    only."""
    kind = f"bench.{cfg.name}.{'half_batch' if half_batch else 'sound'}"
    from repro.api import MODELS
    from repro.api.build import ModelBundle
    from repro.models import transformer as tf

    def loss(p, b):
        if half_batch:
            half = b["tokens"].shape[-1] // 2
            b = {k: v[..., :half] for k, v in b.items()}
        return tf.train_loss(p, cfg, b, remat=True)

    bundle = ModelBundle(cfg=cfg, loss=loss,
                         loss_rng=lambda p, b, rng: loss(p, b),
                         init_params=lambda k: tf.init_params(k, cfg))
    if kind not in MODELS:
        MODELS.register(kind)(lambda spec: bundle)
    return kind


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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
    """The canonical weights renamed into the program's parameter tree
    (``repro.models.transformer``: one scanned segment of attention
    blocks).  Leading axes, such as the agent axis, pass through."""
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


def from_program(p: dict) -> dict:
    """The inverse of :func:`to_program`."""
    (seg,) = p["segments"].values()
    w = {"embed": p["embed"],
         "layers": {"ln1": seg["ln1"]["scale"], **seg["attn"],
                    "ln2": seg["ln2"]["scale"], **seg["mlp"]},
         "final_norm": p["final_norm"]["scale"]}
    if "lm_head" in p:
        w["lm_head"] = p["lm_head"]
    return w


def check_program_layout(cfg) -> None:
    """Fail loudly when the program's parameter tree no longer matches
    :func:`to_program` (names, shapes or dtypes)."""
    from repro.models import transformer as tf
    want = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    have = jax.eval_shape(lambda k: to_program(make_weights(k, cfg), cfg),
                          jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise RuntimeError("the program's parameter tree differs from the "
                           "benchmark's weight layout")


def make_block(key: jax.Array, index, traffic: dict, vocab_size: int) -> dict:
    """Block ``index`` of the training stream: for each of T local steps
    and K agents, ``batch`` sequences of ``seq`` next-token pairs drawn
    uniformly from the vocabulary.  Every row of every block differs."""
    T, K = traffic["local_steps"], traffic["agents"]
    B, S = traffic["batch"], traffic["seq"]
    toks = jax.random.randint(jax.random.fold_in(key, index),
                              (T, K, B, S + 1), 0, vocab_size, jnp.int32)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
