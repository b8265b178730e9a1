"""Config-driven decoder assembly covering all six assigned families.

Layers are grouped into *segments* — maximal runs of identical block type —
and executed with ``lax.scan`` over stacked per-layer parameters, which keeps
HLO size O(num_segments) instead of O(num_layers) (essential for compiling
61-layer/64-layer configs against a 512-device mesh).

Block types: ``attn`` (attention + dense MLP), ``moe`` (attention + MoE FFN),
``mamba`` (Mamba2 SSD mixer).  Zamba2's *shared* attention block is stored
once and applied at every attention slot.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib

PyTree = Any

__all__ = ["init_params", "param_specs", "forward", "train_loss",
           "Cache", "init_cache", "cache_specs", "prefill", "decode_step",
           "sample_logits", "decode_loop"]


def _seg_key(index: int, kind: str, n: int) -> str:
    """Segment metadata lives in the dict key (static, not a pytree leaf)."""
    return f"{index:02d}.{kind}.{n:03d}"


def _seg_items(segments: dict):
    """Yield (kind, n, seg_params) in layer order."""
    for key in sorted(segments):
        _, kind, n = key.split(".")
        yield kind, int(n), segments[key]


def _adims(cfg: ModelConfig) -> L.AttnDims:
    return L.AttnDims(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)


def _ssm_kw(cfg: ModelConfig) -> dict:
    return dict(expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
                state=cfg.ssm_state, conv_kernel=cfg.conv_kernel)


VISION_DIM = 1024  # stubbed vision-encoder output width (CLIP-large)


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, kind: str, dtype) -> dict:
    ks = jax.random.split(key, 4)
    if kind == "mamba":
        return {"ln1": L.init_rms_norm(cfg.d_model, dtype),
                "ssm": ssm_lib.init_ssm(ks[0], cfg.d_model, dtype=dtype,
                                        **_ssm_kw(cfg))}
    p = {"ln1": L.init_rms_norm(cfg.d_model, dtype),
         "attn": L.init_attention(ks[0], cfg.d_model, _adims(cfg),
                                  cfg.qk_norm, dtype),
         "ln2": L.init_rms_norm(cfg.d_model, dtype)}
    if kind == "moe":
        p["moe"] = moe_lib.init_moe(ks[1], cfg.d_model, cfg.moe_d_ff,
                                    cfg.num_experts, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
    return p


def _block_specs(cfg: ModelConfig, kind: str, dtype) -> dict:
    sds = jax.ShapeDtypeStruct
    norm = {"scale": sds((cfg.d_model,), dtype)}
    if kind == "mamba":
        return {"ln1": norm,
                "ssm": ssm_lib.ssm_specs(cfg.d_model, dtype=dtype, **_ssm_kw(cfg))}
    p = {"ln1": norm,
         "attn": L.attention_specs(cfg.d_model, _adims(cfg), cfg.qk_norm, dtype),
         "ln2": {"scale": sds((cfg.d_model,), dtype)}}
    if kind == "moe":
        p["moe"] = moe_lib.moe_specs(cfg.d_model, cfg.moe_d_ff,
                                     cfg.num_experts, dtype)
    else:
        p["mlp"] = L.mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
    return p


def _stack(trees: list) -> PyTree:
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def init_params(key: jax.Array, cfg: ModelConfig) -> PyTree:
    dtype = cfg.param_dtype
    keys = jax.random.split(key, cfg.num_layers + 8)
    p: dict = {}
    nq = max(1, cfg.num_codebooks)
    ke = keys[-1]
    if cfg.num_codebooks:
        p["embed"] = (jax.random.normal(ke, (nq, cfg.vocab_size, cfg.d_model))
                      * 0.02).astype(dtype)
    else:
        p["embed"] = (jax.random.normal(ke, (cfg.vocab_size, cfg.d_model))
                      * 0.02).astype(dtype)
    if cfg.img_tokens:
        p["projector"] = {
            "w": (jax.random.normal(keys[-2], (VISION_DIM, cfg.d_model))
                  / np.sqrt(VISION_DIM)).astype(dtype),
            "b": jnp.zeros((cfg.d_model,), dtype)}

    segs = {}
    li = 0
    for si, (kind, n) in enumerate(cfg.segments()):
        if kind == "attn" and cfg.shared_attention:
            segs[_seg_key(si, "shared_attn", n)] = {}
            li += n
            continue
        blocks = [_block_init(keys[li + j], cfg, kind, dtype) for j in range(n)]
        segs[_seg_key(si, kind, n)] = _stack(blocks)
        li += n
    p["segments"] = segs
    if cfg.shared_attention:
        p["shared_attn"] = _block_init(keys[-3], cfg, "attn", dtype)
    p["final_norm"] = L.init_rms_norm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        if cfg.num_codebooks:
            p["lm_head"] = (jax.random.normal(keys[-4],
                            (nq, cfg.d_model, cfg.vocab_size))
                            / np.sqrt(cfg.d_model)).astype(dtype)
        else:
            p["lm_head"] = (jax.random.normal(keys[-4],
                            (cfg.d_model, cfg.vocab_size))
                            / np.sqrt(cfg.d_model)).astype(dtype)
    return p


def param_specs(cfg: ModelConfig) -> PyTree:
    """ShapeDtypeStruct tree matching init_params — zero allocation."""
    dtype = cfg.param_dtype
    sds = jax.ShapeDtypeStruct
    nq = max(1, cfg.num_codebooks)
    p: dict = {}
    if cfg.num_codebooks:
        p["embed"] = sds((nq, cfg.vocab_size, cfg.d_model), dtype)
    else:
        p["embed"] = sds((cfg.vocab_size, cfg.d_model), dtype)
    if cfg.img_tokens:
        p["projector"] = {"w": sds((VISION_DIM, cfg.d_model), dtype),
                          "b": sds((cfg.d_model,), dtype)}
    segs = {}
    for si, (kind, n) in enumerate(cfg.segments()):
        if kind == "attn" and cfg.shared_attention:
            segs[_seg_key(si, "shared_attn", n)] = {}
            continue
        block = _block_specs(cfg, kind, dtype)
        segs[_seg_key(si, kind, n)] = jax.tree.map(
            lambda s: sds((n,) + s.shape, s.dtype), block)
    p["segments"] = segs
    if cfg.shared_attention:
        p["shared_attn"] = _block_specs(cfg, "attn", dtype)
    p["final_norm"] = {"scale": sds((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        if cfg.num_codebooks:
            p["lm_head"] = sds((nq, cfg.d_model, cfg.vocab_size), dtype)
        else:
            p["lm_head"] = sds((cfg.d_model, cfg.vocab_size), dtype)
    return p


# ---------------------------------------------------------------------------
# block application (no cache — train / loss path)
# ---------------------------------------------------------------------------

def _apply_block(cfg: ModelConfig, kind: str, bp: dict, x: jax.Array,
                 positions: jax.Array, window: int | None,
                 q_chunk: int, kv_chunk: int):
    aux = jnp.zeros((), jnp.float32)
    if kind == "mamba":
        h = L.rms_norm(x, bp["ln1"]["scale"], cfg.norm_eps)
        x = x + ssm_lib.ssm_forward(bp["ssm"], h, chunk=cfg.ssm_chunk,
                                    norm_eps=cfg.norm_eps,
                                    use_kernel=cfg.use_kernels, **_ssm_kw(cfg))
        return x, aux
    h = L.rms_norm(x, bp["ln1"]["scale"], cfg.norm_eps)
    q, k, v = L.qkv_project(bp["attn"], h, _adims(cfg), positions=positions,
                            rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta,
                            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)
    # scores, softmax and the weighted values; the projections stay outside
    with jax.named_scope("attention"):
        if cfg.use_kernels:
            from repro.kernels.ops import attention_op
            o = attention_op(q, k, v, causal=True, window=window)
        else:
            o = L.flash_attention_jnp(q, k, v, causal=True, window=window,
                                      q_chunk=q_chunk, kv_chunk=kv_chunk)
    B, S = x.shape[:2]
    o_proj = o.reshape(B, S, -1) @ bp["attn"]["wo"]
    if cfg.tp_barrier:
        o_proj = jax.lax.optimization_barrier(o_proj)
    x = x + o_proj
    h = L.rms_norm(x, bp["ln2"]["scale"], cfg.norm_eps)
    if kind == "moe":
        y, aux = moe_lib.moe_forward(bp["moe"], h,
                                     top_k=cfg.num_experts_per_token,
                                     capacity_factor=cfg.capacity_factor,
                                     cap_shard_axis=cfg.moe_cap_shard)
        x = x + y
    else:
        m_out = L.mlp_forward(bp["mlp"], h, cfg.mlp_act)
        if cfg.tp_barrier:
            m_out = jax.lax.optimization_barrier(m_out)
        x = x + m_out
    return x, aux


def _embed_inputs(params: PyTree, cfg: ModelConfig, tokens: jax.Array,
                  img_embeds: jax.Array | None):
    """Token (+codebook / +image-prefix) embedding.  Returns (x, n_prefix)."""
    if cfg.num_codebooks:
        # tokens: (B, S, nq) — sum per-codebook embeddings (MusicGen)
        per_cb = jax.vmap(lambda e, t: jnp.take(e, t, axis=0),
                          in_axes=(0, 2))(params["embed"], tokens)
        x = per_cb.sum(axis=0)                            # (B, S, D)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)     # (B, S, D)
    n_prefix = 0
    if cfg.img_tokens and img_embeds is not None:
        proj = img_embeds @ params["projector"]["w"] + params["projector"]["b"]
        x = jnp.concatenate([proj.astype(x.dtype), x], axis=1)
        n_prefix = img_embeds.shape[1]
    return x, n_prefix


def forward(params: PyTree, cfg: ModelConfig, tokens: jax.Array, *,
            img_embeds: jax.Array | None = None, window: int | None = None,
            remat: bool = True, q_chunk: int = 512, kv_chunk: int = 512):
    """Full-sequence forward.  Returns (logits, aux_loss).

    tokens: (B, S) int32, or (B, S, nq) for multi-codebook audio.
    """
    window = window if window is not None else cfg.attention_window
    x, n_prefix = _embed_inputs(params, cfg, tokens, img_embeds)
    B, S = x.shape[:2]
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    aux_total = jnp.zeros((), jnp.float32)

    def seg_body(kind):
        def body(carry, bp):
            x, aux = carry
            x, a = _apply_block(cfg, kind, bp, x, positions, window,
                                q_chunk, kv_chunk)
            return (x, aux + a), None
        return body

    for kind, n, seg_params in _seg_items(params["segments"]):
        if kind == "shared_attn":
            bp = params["shared_attn"]
            fn = lambda x_, bp_: _apply_block(cfg, "attn", bp_, x_, positions,
                                              window, q_chunk, kv_chunk)
            if remat:
                fn = jax.checkpoint(fn)
            for _ in range(n):
                x, a = fn(x, bp)
                aux_total = aux_total + a
        else:
            body = seg_body(kind)
            if remat:
                body = jax.checkpoint(body)
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), seg_params)

    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = _lm_logits(params, cfg, x)
    return logits, aux_total, n_prefix


def _lm_logits(params: PyTree, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        W = params["embed"]
        if cfg.num_codebooks:
            return jnp.einsum("bsd,qvd->bsqv", x, W)
        return x @ W.T
    W = params["lm_head"]
    if cfg.num_codebooks:
        return jnp.einsum("bsd,qdv->bsqv", x, W)
    return x @ W


def train_loss(params: PyTree, cfg: ModelConfig, batch: dict,
               rng: jax.Array | None = None, *, remat: bool = True) -> jax.Array:
    """Next-token cross-entropy (+ MoE aux).  batch: tokens/labels (+ img)."""
    del rng
    logits, aux, n_prefix = forward(params, cfg, batch["tokens"],
                                    img_embeds=batch.get("img_embeds"),
                                    remat=remat)
    if n_prefix:
        logits = logits[:, n_prefix:]
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = nll.mean()
    if cfg.num_experts:
        loss = loss + cfg.aux_loss_coef * aux
    return loss


# ---------------------------------------------------------------------------
# serving: cache + prefill + decode
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cache:
    """Pytree decode cache.  segments mirrors params['segments'] order.

    ``pos``/``slot_pos`` come in two layouts chosen at :func:`init_cache`
    time: the whole-batch layout (scalar ``pos``, ``(C,)`` ``slot_pos``)
    where every sequence sits at the same position, and the *per-slot*
    layout (``(B,)`` / ``(B, C)``) used by the continuous-batching serve
    loop, where each batch row is an independent request at its own
    position (see :mod:`repro.launch.serving`).
    """
    segments: tuple
    pos: jax.Array        # () or (B,) int32 — next write position (absolute)
    slot_pos: jax.Array   # (C,) or (B, C) int32 — absolute position per slot

    def tree_flatten(self):
        return (self.segments, self.pos, self.slot_pos), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    Cache, Cache.tree_flatten, Cache.tree_unflatten)


def _cache_len(cfg: ModelConfig, max_seq: int, window: int | None) -> int:
    w = window if window is not None else cfg.attention_window
    return min(max_seq, w) if w else max_seq


def _seg_cache_spec(cfg: ModelConfig, kind: str, n: int, batch: int,
                    C: int, dtype, make):
    Kv, Dh = cfg.num_kv_heads, cfg.head_dim
    if kind in ("attn", "shared_attn", "moe"):
        return {"k": make((n, batch, C, Kv, Dh), dtype),
                "v": make((n, batch, C, Kv, Dh), dtype)}
    s_shape, c_shape = ssm_lib.ssm_state_shapes(batch, cfg.d_model, dtype=dtype,
                                                **_ssm_kw(cfg))
    return {"ssm": make((n,) + s_shape, jnp.float32),
            "conv": make((n,) + c_shape, dtype)}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, *,
               window: int | None = None, per_slot: bool = False) -> Cache:
    C = _cache_len(cfg, max_seq, window)
    make = lambda shape, dt: jnp.zeros(shape, dt)
    segs = tuple(
        _seg_cache_spec(cfg, kind, n, batch, C, cfg.param_dtype, make)
        for kind, n in cfg.segments())
    return Cache(segments=segs,
                 pos=jnp.zeros((batch,) if per_slot else (), jnp.int32),
                 slot_pos=jnp.full((batch, C) if per_slot else (C,), -1,
                                   jnp.int32))


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int, *,
                window: int | None = None, per_slot: bool = False) -> Cache:
    C = _cache_len(cfg, max_seq, window)
    make = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
    segs = tuple(
        _seg_cache_spec(cfg, kind, n, batch, C, cfg.param_dtype, make)
        for kind, n in cfg.segments())
    return Cache(segments=segs,
                 pos=make((batch,) if per_slot else (), jnp.int32),
                 slot_pos=make((batch, C) if per_slot else (C,), jnp.int32))


def _attn_block_decode(cfg: ModelConfig, bp: dict, x: jax.Array,
                       kc: jax.Array, vc: jax.Array, pos: jax.Array,
                       slot_pos: jax.Array, window: int | None, kind: str):
    """One attention block for a single new token with ring-buffer cache.

    ``pos`` is either a scalar (whole-batch position) or ``(B,)`` per-slot
    positions (each batch row an independent request — the serve loop);
    ``slot_pos`` is ``(C,)`` / ``(B, C)`` to match.
    """
    B = x.shape[0]
    C = kc.shape[1]
    per_slot = pos.ndim == 1
    h = L.rms_norm(x, bp["ln1"]["scale"], cfg.norm_eps)
    positions = pos[:, None] if per_slot else pos[None, None].repeat(B, 0)
    q, k, v = L.qkv_project(bp["attn"], h, _adims(cfg),
                            positions=positions,
                            rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta,
                            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)
    slot = pos % C
    if per_slot:
        rows = jnp.arange(B)
        kc = kc.at[rows, slot].set(k[:, 0])
        vc = vc.at[rows, slot].set(v[:, 0])
        new_slot_pos = slot_pos.at[rows, slot].set(pos)
        valid = (new_slot_pos >= 0) & (new_slot_pos <= pos[:, None])
        if window:
            valid = valid & (new_slot_pos > (pos - window)[:, None])
    else:
        kc = jax.lax.dynamic_update_index_in_dim(kc, k[:, 0], slot, axis=1)
        vc = jax.lax.dynamic_update_index_in_dim(vc, v[:, 0], slot, axis=1)
        new_slot_pos = slot_pos.at[slot].set(pos)
        valid = (new_slot_pos >= 0) & (new_slot_pos <= pos)
        if window:
            valid = valid & (new_slot_pos > pos - window)
    o = L.decode_attention_jnp(q, kc, vc, valid)
    x = x + o.reshape(B, 1, -1) @ bp["attn"]["wo"]
    h = L.rms_norm(x, bp["ln2"]["scale"], cfg.norm_eps)
    if kind == "moe":
        y, _ = moe_lib.moe_forward(bp["moe"], h,
                                   top_k=cfg.num_experts_per_token,
                                   capacity_factor=cfg.capacity_factor)
        x = x + y
    else:
        x = x + L.mlp_forward(bp["mlp"], h, cfg.mlp_act)
    return x, kc, vc, new_slot_pos


#: partial-unroll factor for the per-layer scan in decode_step: one decode
#: step is a few dozen tiny ops per layer, so the scan's per-iteration
#: bookkeeping is a real fraction of the step on CPU/small models; a small
#: constant unroll removes most of it while the HLO stays O(segments * 4)
#: (never O(num_layers) — the 61/64-layer configs still compile small)
_DECODE_LAYER_UNROLL = 4

#: segments at most this deep skip the lax.scan entirely and unroll as a
#: Python loop over STATICALLY indexed layer weights.  The scan's dynamic
#: xs-slicing re-materializes every layer's weights each call — inside the
#: fused token loop that is ~800 KB of weight copies per generated token on
#: the serve smoke config, and it cannot be hoisted because the slice index
#: is the scan counter.  Static slices of loop-invariant weights hoist out
#: of the enclosing token `while` for free (measured ~1.8x per-token on
#: bench_serve).  Deep stacks (the 61/64-layer configs) keep the scan so
#: compiled HLO stays O(segments * _DECODE_LAYER_UNROLL), not O(layers).
_DECODE_STATIC_LAYERS = 8


def decode_step(params: PyTree, cfg: ModelConfig, cache: Cache,
                tokens: jax.Array, *, window: int | None = None):
    """One decode step: tokens (B, 1) or (B, 1, nq) -> (logits, new_cache)."""
    window = window if window is not None else cfg.attention_window
    x, _ = _embed_inputs(params, cfg, tokens, None)
    pos = cache.pos
    new_slot_pos = cache.slot_pos
    new_segs = []
    for (kind, n, seg_params), seg_cache in zip(
            _seg_items(params["segments"]), cache.segments):
        if kind == "shared_attn":
            bp = params["shared_attn"]
            kcs, vcs = [], []
            for j in range(n):
                x, kc, vc, new_slot_pos = _attn_block_decode(
                    cfg, bp, x, seg_cache["k"][j], seg_cache["v"][j],
                    pos, cache.slot_pos, window, "attn")
                kcs.append(kc)
                vcs.append(vc)
            new_segs.append({"k": jnp.stack(kcs), "v": jnp.stack(vcs)})
        elif kind == "mamba":
            if n <= _DECODE_STATIC_LAYERS:
                sts, cvs = [], []
                for j in range(n):
                    bp = jax.tree.map(lambda a, j=j: a[j], seg_params)
                    h = L.rms_norm(x, bp["ln1"]["scale"], cfg.norm_eps)
                    o, st, cv = ssm_lib.ssm_decode_step(
                        bp["ssm"], h, seg_cache["ssm"][j],
                        seg_cache["conv"][j], norm_eps=cfg.norm_eps,
                        **_ssm_kw(cfg))
                    x = x + o
                    sts.append(st)
                    cvs.append(cv)
                new_segs.append({"ssm": jnp.stack(sts),
                                 "conv": jnp.stack(cvs)})
            else:
                def body(carry, xs):
                    x_ = carry
                    bp, st, cv = xs
                    h = L.rms_norm(x_, bp["ln1"]["scale"], cfg.norm_eps)
                    o, st, cv = ssm_lib.ssm_decode_step(
                        bp["ssm"], h, st, cv, norm_eps=cfg.norm_eps,
                        **_ssm_kw(cfg))
                    return x_ + o, (st, cv)
                x, (sts, cvs) = jax.lax.scan(
                    body, x, (seg_params, seg_cache["ssm"],
                              seg_cache["conv"]),
                    unroll=min(n, _DECODE_LAYER_UNROLL))
                new_segs.append({"ssm": sts, "conv": cvs})
        else:
            if n <= _DECODE_STATIC_LAYERS:
                kcs, vcs = [], []
                for j in range(n):
                    bp = jax.tree.map(lambda a, j=j: a[j], seg_params)
                    x, kc, vc, new_slot_pos = _attn_block_decode(
                        cfg, bp, x, seg_cache["k"][j], seg_cache["v"][j],
                        pos, cache.slot_pos, window, kind)
                    kcs.append(kc)
                    vcs.append(vc)
                new_segs.append({"k": jnp.stack(kcs), "v": jnp.stack(vcs)})
            else:
                def body(carry, xs):
                    x_, sp = carry
                    bp, kc, vc = xs
                    x_, kc, vc, sp = _attn_block_decode(cfg, bp, x_, kc, vc,
                                                        pos, cache.slot_pos,
                                                        window, kind)
                    return (x_, sp), (kc, vc)
                (x, new_slot_pos), (kcs, vcs) = jax.lax.scan(
                    body, (x, new_slot_pos), (seg_params, seg_cache["k"],
                                              seg_cache["v"]),
                    unroll=min(n, _DECODE_LAYER_UNROLL))
                new_segs.append({"k": kcs, "v": vcs})

    # all layers share slot geometry; recompute canonical slot_pos update once
    C = cache.slot_pos.shape[-1]
    if pos.ndim == 1:
        new_slot_pos = cache.slot_pos.at[
            jnp.arange(pos.shape[0]), pos % C].set(pos)
    else:
        new_slot_pos = cache.slot_pos.at[pos % C].set(pos)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = _lm_logits(params, cfg, x)
    new_cache = Cache(segments=tuple(new_segs), pos=pos + 1,
                      slot_pos=new_slot_pos)
    return logits, new_cache


def prefill(params: PyTree, cfg: ModelConfig, tokens: jax.Array, *,
            img_embeds: jax.Array | None = None, window: int | None = None,
            max_len: int | None = None,
            q_chunk: int = 512, kv_chunk: int = 512):
    """Process a prompt, returning (logits, cache) for subsequent decode.

    Implemented as a full forward that additionally captures per-layer K/V
    (and final SSM states).  The cache is sized for ``max_len`` total
    positions (default: prompt length — pass prompt + decode budget).
    """
    window = window if window is not None else cfg.attention_window
    x, n_prefix = _embed_inputs(params, cfg, tokens, img_embeds)
    B, S = x.shape[:2]
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    C = _cache_len(cfg, max(max_len or S, S), window)

    new_segs = []
    for kind, n, seg_params in _seg_items(params["segments"]):
        if kind == "shared_attn":
            bp = params["shared_attn"]
            kcs, vcs = [], []
            for _ in range(n):
                x, kv = _attn_block_prefill(cfg, bp, x, positions, window,
                                            q_chunk, kv_chunk, C, "attn")
                kcs.append(kv[0])
                vcs.append(kv[1])
            new_segs.append({"k": jnp.stack(kcs), "v": jnp.stack(vcs)})
        elif kind == "mamba":
            def body(x_, bp):
                h = L.rms_norm(x_, bp["ln1"]["scale"], cfg.norm_eps)
                o, (st, cv) = ssm_lib.ssm_forward(
                    bp["ssm"], h, chunk=cfg.ssm_chunk, norm_eps=cfg.norm_eps,
                    return_state=True, **_ssm_kw(cfg))
                return x_ + o, (st, cv.astype(cfg.param_dtype))
            x, (sts, cvs) = jax.lax.scan(body, x, seg_params)
            new_segs.append({"ssm": sts, "conv": cvs})
        else:
            def body(x_, bp):
                x_, kv = _attn_block_prefill(cfg, bp, x_, positions, window,
                                             q_chunk, kv_chunk, C, kind)
                return x_, kv
            x, (kcs, vcs) = jax.lax.scan(body, x, seg_params)
            new_segs.append({"k": kcs, "v": vcs})

    slot_pos = _prefill_slot_positions(S, C)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = _lm_logits(params, cfg, x)
    cache = Cache(segments=tuple(new_segs),
                  pos=jnp.asarray(S, jnp.int32), slot_pos=slot_pos)
    return logits, cache


def _prefill_slot_positions(S: int, C: int) -> jax.Array:
    """Absolute position stored in each ring slot after prefilling S tokens."""
    j = jnp.arange(C)
    if C >= S:
        return jnp.where(j < S, j, -1)
    # slot j holds the largest p < S with p % C == j
    last = S - 1
    return last - ((last - j) % C)


def _attn_block_prefill(cfg: ModelConfig, bp: dict, x: jax.Array,
                        positions: jax.Array, window: int | None,
                        q_chunk: int, kv_chunk: int, C: int, kind: str):
    B, S = x.shape[:2]
    h = L.rms_norm(x, bp["ln1"]["scale"], cfg.norm_eps)
    q, k, v = L.qkv_project(bp["attn"], h, _adims(cfg), positions=positions,
                            rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta,
                            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)
    o = L.flash_attention_jnp(q, k, v, causal=True, window=window,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
    x = x + o.reshape(B, S, -1) @ bp["attn"]["wo"]
    h = L.rms_norm(x, bp["ln2"]["scale"], cfg.norm_eps)
    if kind == "moe":
        y, _ = moe_lib.moe_forward(bp["moe"], h,
                                   top_k=cfg.num_experts_per_token,
                                   capacity_factor=cfg.capacity_factor)
        x = x + y
    else:
        x = x + L.mlp_forward(bp["mlp"], h, cfg.mlp_act)
    # ring-buffer the last C positions
    if C >= S:
        kc = jnp.pad(k, ((0, 0), (0, C - S), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, C - S), (0, 0), (0, 0)))
    else:
        # place position p at slot p % C; the last C tokens survive
        kc = _ring_scatter(k, C)
        vc = _ring_scatter(v, C)
    return x, (kc, vc)


def _ring_scatter(k: jax.Array, C: int) -> jax.Array:
    """Scatter a (B, S, ...) sequence into its (B, C, ...) ring buffer."""
    S = k.shape[1]
    tail = k[:, S - C:]                        # last C tokens, positions S-C..S-1
    roll = (S - C) % C
    return jnp.roll(tail, shift=roll, axis=1)


# ---------------------------------------------------------------------------
# serving: fused decode loop
# ---------------------------------------------------------------------------

def sample_logits(logits: jax.Array, key: jax.Array | None,
                  temperature: float) -> jax.Array:
    """Next-token sampling from last-position logits (always in float32).

    ``temperature <= 0`` is greedy argmax and consumes NO key (``key`` may
    be ``None`` — greedy decoding is fully deterministic and key-free in
    both the fused and the py serving loops); otherwise
    ``jax.random.categorical`` at the given temperature.

    logits: ``(B, V)`` or ``(B, nq, V)`` -> ``(B,)`` / ``(B, nq)`` int32.
    """
    lg = logits.astype(jnp.float32)
    if temperature <= 0:
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, lg / temperature,
                                  axis=-1).astype(jnp.int32)


def decode_loop(params: PyTree, cfg: ModelConfig, cache: Cache,
                first_logits: jax.Array, key: jax.Array | None, n: int, *,
                temperature: float = 0.0, window: int | None = None,
                unroll: int = 8):
    """Fused n-token generation: sampling lives INSIDE the jitted step and
    ``lax.scan`` drives the n decode steps, so tokens, cache, and PRNG
    state stay on device and a whole generation is ONE dispatch — the
    per-token py loop (``launch/serve.py --decode-loop py``) pays one
    dispatch plus a host sync per token instead.

    Args:
      first_logits: the last-position logits from :func:`prefill` —
        ``(B, V)``, or ``(B, nq, V)`` for multi-codebook audio.
      key: PRNG key for sampled decoding; unused (may be ``None``) at
        ``temperature <= 0``, where the loop is greedy and key-free.
      n: number of tokens to generate (static).
      unroll: partial unroll of the token scan (same trade as the
        per-layer ``_DECODE_LAYER_UNROLL``: decode steps are tiny, so the
        scan bookkeeping between them is measurable; 8 steps per loop
        iteration removes most of it at bounded HLO cost — measured the
        knee of the unroll sweep on the bench_serve gate shape).

    Returns ``(tokens, last_logits, cache)`` with ``tokens`` int32
    ``(B, n)`` or ``(B, n, nq)``, and ``last_logits`` the logits the
    (n+1)-th token would be sampled from — carry it into the next call to
    continue the generation (the serve loop's chunked decode).
    """
    greedy = temperature <= 0

    def step(carry, ks):
        lg, c = carry
        nxt = sample_logits(lg, ks, temperature)       # (B,) or (B, nq)
        tok = nxt[:, None] if not cfg.num_codebooks else nxt[:, None, :]
        new_lg, c = decode_step(params, cfg, c, tok, window=window)
        # the carry keeps first_logits' dtype (the serve loop holds float32
        # logits; a bfloat16 model's upcast is exact)
        return (new_lg[:, 0].astype(lg.dtype), c), nxt

    xs = None if greedy else jax.random.split(key, n)
    (last_lg, cache), toks = jax.lax.scan(
        step, (first_logits, cache), xs, length=n, unroll=min(n, unroll))
    return jnp.moveaxis(toks, 0, 1), last_lg, cache
