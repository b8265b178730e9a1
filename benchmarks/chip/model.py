"""The system under test, as the benchmark builds and feeds it.

A configuration file (``configs/<name>.json``) holds the model's sizes under
``"model"``; :func:`register_model` turns them into a
:class:`repro.configs.ModelConfig` and registers it as a model kind of the
program's ``MODELS`` registry, so ``repro.api.build(spec)`` assembles the
normal sharded engine around it, rematerialising every layer as the
published-width configurations do.

Weights and token blocks are the benchmark's own, made on the device from
the seed.  The weights' layout belongs to the configuration's family, whose
module (``layouts/<reference>.py``) draws one agent's weights in the
layout the plain reference reads and renames them into the program's
parameter tree; :func:`check_program_layout` holds the two trees to each
other.  Every agent starts from the same weights, as a deployment
broadcasts one initial model.  :func:`make_block` draws the token stream.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp

__all__ = ["load_json", "model_config", "register_model", "seed_key",
           "make_block", "check_program_layout"]


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


def check_program_layout(cfg, layout) -> None:
    """Fail loudly when the program's parameter tree no longer matches the
    family module ``layout``'s ``to_program`` (names, shapes or dtypes)."""
    from repro.models import transformer as tf
    want = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    have = jax.eval_shape(
        lambda k: layout.to_program(layout.make_weights(k, cfg), cfg),
        jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise RuntimeError("the program's parameter tree differs from the "
                           "benchmark's weight layout")


def make_block(key: jax.Array, index, traffic: dict, vocab_size: int) -> dict:
    """Block ``index`` of the training stream: for each of T local steps
    and K agents, ``batch`` sequences of ``seq`` next-token pairs.  Every
    row of every block differs.

    Ids are uniform over the vocabulary, unless the traffic gives
    ``"token_zipf": s`` (s > 0): then id r is drawn with probability
    proportional to (r + 1)^-s, as the ranks of real text fall, by the
    inverse of one (V,) float32 distribution function."""
    T, K = traffic["local_steps"], traffic["agents"]
    B, S = traffic["batch"], traffic["seq"]
    shape = (T, K, B, S + 1)
    k = jax.random.fold_in(key, index)
    if "token_zipf" in traffic:
        s = float(traffic["token_zipf"])
        if not s > 0:
            raise ValueError(f"token_zipf must be above 0, not {s}")
        cdf = jnp.cumsum(jnp.arange(1, vocab_size + 1, dtype=jnp.float32)
                         ** -s)
        u = jax.random.uniform(k, shape, jnp.float32) * cdf[-1]
        toks = jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                           vocab_size - 1).astype(jnp.int32)
    else:
        toks = jax.random.randint(k, shape, 0, vocab_size, jnp.int32)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
