"""Plain reference of a dense decoder's training loss, in float32.

Independent of the program: it reads the benchmark's canonical weights
(``layouts/dense_lm.py``) and a configuration file's ``"model"`` sizes, and
nothing else.  Pre-norm decoder layers: RMSNorm, grouped-query causal
attention with rotary position embedding on the first ``rotary_pct`` of
each head (adjacent pairs rotated together), a SwiGLU feed-forward, a
final RMSNorm and a tied or separate output projection; the loss is the
mean next-token cross-entropy over all positions.

Every matrix product goes through ``mm``: at ``"highest"`` it is a float32
product at full precision.  ``"fp8"`` is the control: both operands of every
product, forward and backward, rounded to fp8 e4m3 with one scale per
tensor, as an fp8 training recipe would run it.

Departures from the published models, which the program shares: no bias
on the query/key/value projections (ChatGLM3 has one), and SmolLM's
rotary embedding rotates adjacent pairs, where the published Llama layout
rotates the two halves of each head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["loss", "PRECISIONS"]

PRECISIONS = ("highest", "fp8")
#: largest normal of a float8 with 4 exponent and 3 mantissa bits, IEEE-like
_F8_MAX = 240.0


def _q8(x):
    """Round to an fp8 e4m3 grid with one scale for the whole tensor.
    ``reduce_precision`` rounds where a cast to float8 and back may be
    folded away by the compiler."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / _F8_MAX, 1.0)
    q = jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3)
    return q * scale


def _dot(a, b, spec):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dot8(a, b, spec):
    return _dot(_q8(a), _q8(b), spec)


def _dot8_fwd(a, b, spec):
    return _dot8(a, b, spec), (a, b)


def _dot8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: _dot(x, y, spec), _q8(a), _q8(b))
    return vjp(_q8(g))


_dot8.defvjp(_dot8_fwd, _dot8_bwd)


def _mm(precision):
    if precision == "highest":
        return _dot
    if precision == "fp8":
        return _dot8
    raise ValueError(f"unknown precision {precision!r}; known: {PRECISIONS}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pct, theta):
    """Rotate adjacent pairs of the first ``pct`` of the last axis.
    x: (B, S, H, D)."""
    D = x.shape[-1]
    rot = int(D * pct)
    rot -= rot % 2
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    pos = np.arange(x.shape[1], dtype=np.float64)
    ang = jnp.asarray(np.outer(pos, inv), jnp.float32)[None, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([r.reshape(x[..., :rot].shape), x[..., rot:]], -1)


def loss(w: dict, m: dict, tokens, labels, precision: str = "highest"):
    """Mean next-token cross-entropy of one agent.  ``w``: canonical
    weights in float32; ``m``: the configuration's ``"model"`` sizes;
    tokens, labels: (B, S) int32."""
    mm = _mm(precision)
    H, Kv, Dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m.get("norm_eps", 1e-5)
    pct, theta = m.get("rotary_pct", 1.0), m.get("rope_theta", 1e4)
    B, S = tokens.shape
    causal = np.tril(np.ones((S, S), bool))

    def layer(x, lw):
        h = _rms(x, lw["ln1"], eps)
        q = mm(h, lw["wq"], "bsd,de->bse").reshape(B, S, H, Dh)
        k = mm(h, lw["wk"], "bsd,de->bse").reshape(B, S, Kv, Dh)
        v = mm(h, lw["wv"], "bsd,de->bse").reshape(B, S, Kv, Dh)
        q, k = _rope(q, pct, theta), _rope(k, pct, theta)
        k = jnp.repeat(k, H // Kv, axis=2)
        v = jnp.repeat(v, H // Kv, axis=2)
        s = mm(q, k, "bqhd,bkhd->bhqk") / np.sqrt(Dh)
        s = jnp.where(causal, s, -jnp.inf)
        o = mm(jax.nn.softmax(s, -1), v, "bhqk,bkhd->bqhd")
        x = x + mm(o.reshape(B, S, H * Dh), lw["wo"], "bse,ed->bsd")
        h = _rms(x, lw["ln2"], eps)
        f = (jax.nn.silu(mm(h, lw["w_gate"], "bsd,df->bsf"))
             * mm(h, lw["w_up"], "bsd,df->bsf"))
        return x + mm(f, lw["w_down"], "bsf,fd->bsd"), None

    x = w["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["layers"])
    x = _rms(x, w["final_norm"], eps)
    if "lm_head" in w:
        logits = mm(x, w["lm_head"], "bsd,dv->bsv")
    else:
        logits = mm(x, w["embed"], "bsd,vd->bsv")
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
