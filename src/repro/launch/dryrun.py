"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against placeholder devices and capture memory / cost /
collective statistics for the roofline analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun
"""
# The VERY FIRST two lines — before ANY other import (jax locks the device
# count on first init):
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.api.build import train_block_struct
from repro.api.cli import add_spec_args, spec_from_args
from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.configs.base import ArchBundle, InputShape, ModelConfig
from repro.core.diffusion import DiffusionConfig
from repro.core.sharded import make_block_step
from repro.core.state import EngineState
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tf
from repro.sharding import rules as sh

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; zero allocation)
# ---------------------------------------------------------------------------

def agent_count(bundle: ArchBundle, multi_pod: bool) -> tuple[int, str | None]:
    pc = bundle.parallel
    if multi_pod:
        k, ax = pc.num_agents_multi, pc.agent_axis_multi
    else:
        k, ax = pc.num_agents_single, pc.agent_axis_single
    return k, (ax if k > 1 else None)


def serve_window(cfg: ModelConfig, shape: InputShape) -> int | None:
    """Attention window for serving: long-context forces the sub-quadratic
    sliding-window variant on attention archs (DESIGN.md §4)."""
    if shape.name == "long_500k":
        return cfg.long_context_window if cfg.family != "ssm" else None
    return cfg.attention_window


def input_specs(arch: str, shape_name: str, *, multi_pod: bool = False,
                mesh=None, tp: bool | None = None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this combo."""
    bundle = get_config(arch)
    tp = bundle.parallel.tp if tp is None else tp
    cfg = bundle.model
    shape = INPUT_SHAPES[shape_name]
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    K, agent_axis = agent_count(bundle, multi_pod)
    T = bundle.parallel.local_steps

    if shape.kind == "train":
        B_a = shape.global_batch // K
        # one source of truth for the block layout: the same helper the
        # DATASETS providers compile their shapes from (repro.api.build),
        # so the roofline path cannot drift from the data path
        struct = train_block_struct(cfg, T=T, K=K, batch=B_a,
                                    seq=shape.seq_len,
                                    img_dtype=jnp.bfloat16)
        bp = sh.batch_pspec(mesh, agent_axis=agent_axis,
                            ndim=struct["tokens"].ndim, tp=tp, batch=B_a)
        batch = {
            "tokens": SDS(struct["tokens"].shape, struct["tokens"].dtype,
                          sharding=jax.NamedSharding(mesh, bp)),
            "labels": SDS(struct["labels"].shape, struct["labels"].dtype,
                          sharding=jax.NamedSharding(mesh, bp)),
        }
        if "img_embeds" in struct:
            ip = sh.batch_pspec(mesh, agent_axis=agent_axis,
                                ndim=struct["img_embeds"].ndim,
                                tp=tp, batch=B_a)
            batch["img_embeds"] = SDS(
                struct["img_embeds"].shape, struct["img_embeds"].dtype,
                sharding=jax.NamedSharding(mesh, ip))
        return {"batch": batch, "key": SDS((2,), jnp.uint32)}

    B = shape.global_batch
    if shape.kind == "prefill":
        tok_shape = (B, shape.seq_len)
        if cfg.num_codebooks:
            tok_shape = tok_shape + (cfg.num_codebooks,)
        tok_ps = sh.serve_batch_pspec(mesh, B, len(tok_shape))
        out = {"tokens": SDS(tok_shape, jnp.int32,
                             sharding=jax.NamedSharding(mesh, tok_ps))}
        if cfg.img_tokens:
            ip = sh.serve_batch_pspec(mesh, B, 3)
            out["img_embeds"] = SDS((B, cfg.img_tokens, tf.VISION_DIM),
                                    jnp.bfloat16,
                                    sharding=jax.NamedSharding(mesh, ip))
        return out

    # decode: ONE new token against a seq_len cache
    window = serve_window(cfg, shape)
    cache = tf.cache_specs(cfg, B, shape.seq_len, window=window)
    cache_ps = sh.cache_pspecs(cache, mesh, B)
    cache = jax.tree.map(
        lambda s, p: SDS(s.shape, s.dtype,
                         sharding=jax.NamedSharding(mesh, p)),
        cache, cache_ps, is_leaf=lambda x: isinstance(x, SDS))
    tok_shape = (B, 1) if not cfg.num_codebooks else (B, 1, cfg.num_codebooks)
    tok_ps = sh.serve_batch_pspec(mesh, B, len(tok_shape))
    return {"cache": cache,
            "tokens": SDS(tok_shape, jnp.int32,
                          sharding=jax.NamedSharding(mesh, tok_ps))}


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_train_step(bundle: ArchBundle, shape: InputShape, mesh,
                     multi_pod: bool, mix_override: str | None = None,
                     tp: bool | None = None, compress: str | None = None,
                     compress_ratio: float = 0.1, compress_sigma: float = 0.0,
                     error_feedback: bool = False, graph: str = "static",
                     graph_kwargs: tuple = (), trim: int = 1,
                     robust_scope: str = "global",
                     robust_gather: str = "auto", asynchrony=None):
    cfg = bundle.model
    pc = bundle.parallel
    tp = pc.tp if tp is None else tp
    K, agent_axis = agent_count(bundle, multi_pod)
    topo_cfg = DiffusionConfig(
        num_agents=K, local_steps=pc.local_steps, step_size=1e-3,
        topology=pc.topology if K > 2 else "full",
        graph=graph if K > 1 else "static", graph_kwargs=graph_kwargs,
        participation=pc.participation)
    if K > 1:
        topo = topo_cfg.make_topology()
        A = jnp.asarray(topo.A, jnp.float32)
    else:
        topo, A = None, jnp.eye(1)
    mix = mix_override or (pc.mix_path if K > 1 else "none")

    # shardings
    inner = sh.param_pspecs(tf.param_specs(cfg), mesh, fsdp=pc.fsdp, tp=tp)
    pspec = sh.add_agent_axis(inner, agent_axis)
    param_sds = jax.tree.map(
        lambda s, p: SDS((K,) + s.shape, s.dtype,
                         sharding=jax.NamedSharding(mesh, p)),
        tf.param_specs(cfg), pspec, is_leaf=lambda x: isinstance(x, SDS))

    specs = input_specs(bundle.model.name, shape.name, multi_pod=multi_pod,
                        mesh=mesh, tp=tp)
    param_shardings = jax.tree.map(lambda s: s.sharding, param_sds,
                                   is_leaf=lambda x: isinstance(x, SDS))

    if asynchrony is not None and asynchrony.enabled:
        return _build_async_train_step(cfg, pc, topo_cfg, asynchrony, mesh,
                                       K, param_sds, param_shardings, specs)

    def loss_fn(agent_params, agent_batch, rng):
        return tf.train_loss(agent_params, cfg, agent_batch, rng,
                             remat=pc.remat)

    block_step = make_block_step(loss_fn, topo_cfg, A, mix=mix,
                                 topology=topo, compress=compress,
                                 compress_ratio=compress_ratio,
                                 compress_sigma=compress_sigma,
                                 error_feedback=error_feedback,
                                 trim=trim, robust_scope=robust_scope,
                                 robust_gather=robust_gather,
                                 mesh=mesh, agent_axis=agent_axis)

    comm_sds = comm_shardings = None
    if block_step.pipeline.stateful:
        # comm state: params-shaped leaves (EF residual / diff-mode
        # reference) shard like the param they mirror, in flatten order;
        # scalar bookkeeping (the adaptive-gamma EMA) replicates
        state_struct = jax.eval_shape(block_step.pipeline.init_state,
                                      param_sds)
        p_sh = jax.tree.leaves(param_shardings)
        replicated = jax.NamedSharding(mesh, P())
        s_leaves, s_def = jax.tree_util.tree_flatten(state_struct)
        array_count = sum(1 for l in s_leaves if l.ndim >= 1)
        assert array_count == len(p_sh), "comm state != params layout"
        p_iter = iter(p_sh)
        s_sh = [next(p_iter) if l.ndim >= 1 else replicated
                for l in s_leaves]
        comm_sds = jax.tree_util.tree_unflatten(
            s_def, [SDS(l.shape, l.dtype, sharding=s)
                    for l, s in zip(s_leaves, s_sh)])
        comm_shardings = jax.tree_util.tree_unflatten(s_def, s_sh)

    graph_sds = graph_shardings = None
    if block_step.graph.stateful:
        # graph state (the (K, K) link mask) is tiny: replicate it
        g_struct = jax.eval_shape(block_step.graph.init_state,
                                  SDS((2,), jnp.uint32))
        replicated = jax.NamedSharding(mesh, P())
        graph_sds = jax.tree.map(
            lambda l: SDS(l.shape, l.dtype, sharding=replicated), g_struct)
        graph_shardings = jax.tree.map(lambda l: replicated, g_struct)

    # the unified step contract: ONE EngineState in, one out — absent
    # components (opt/part state here) are None leaves, so a single
    # signature covers the stateless and comm/graph-stateful paths
    state_sds = EngineState(param_sds, None, None, comm_sds, graph_sds)
    state_shardings = EngineState(param_shardings, None, None,
                                  comm_shardings, graph_shardings)

    def step(state, key, batch):
        new_state, metrics = block_step(state, batch, key)
        return new_state, metrics["active"]

    args = (state_sds, specs["key"], specs["batch"])
    return step, args, (state_shardings, None)


def _build_async_train_step(cfg, pc, topo_cfg, asynchrony, mesh, K,
                            param_sds, param_shardings, specs):
    """Compile path for ``--engine async``: the event-driven engine's step
    against ShapeDtypeStruct stand-ins, including the staleness-buffer
    component of the state (buffer leaves shard like the params they
    mirror, with the neighbor-slot axis replicated)."""
    from repro.core.async_engine import AsyncEngine

    if K < 2:
        raise ValueError("--engine async needs a multi-agent arch (K >= 2)")

    def loss_fn(agent_params, agent_batch):
        return tf.train_loss(agent_params, cfg, agent_batch, remat=pc.remat)

    eng = AsyncEngine(topo_cfg, loss_fn, async_spec=asynchrony)
    D = int(eng._idx.shape[1])
    replicated = jax.NamedSharding(mesh, P())

    def _buf_sharding(s):
        spec = tuple(s.sharding.spec)
        agent = spec[0] if spec else None
        return jax.NamedSharding(mesh, P(agent, None, *spec[1:]))

    buffer_sds = jax.tree.map(
        lambda s: SDS((K, D) + s.shape[1:], s.dtype,
                      sharding=_buf_sharding(s)),
        param_sds, is_leaf=lambda x: isinstance(x, SDS))
    async_sds = {
        "t_local": SDS((K,), jnp.float32, sharding=replicated),
        "ages": SDS((K, D), jnp.int32, sharding=replicated),
        "buffer": buffer_sds,
    }
    async_shardings = jax.tree.map(lambda s: s.sharding, async_sds,
                                   is_leaf=lambda x: isinstance(x, SDS))
    graph_sds = graph_shardings = None
    if eng.graph.stateful:
        g_struct = jax.eval_shape(eng.graph.init_state,
                                  SDS((2,), jnp.uint32))
        graph_sds = jax.tree.map(
            lambda l: SDS(l.shape, l.dtype, sharding=replicated), g_struct)
        graph_shardings = jax.tree.map(lambda l: replicated, g_struct)

    state_sds = EngineState(param_sds, None, None, None, graph_sds,
                            async_sds)
    state_shardings = EngineState(param_shardings, None, None, None,
                                  graph_shardings, async_shardings)

    def step(state, key, batch):
        new_state, metrics = eng.step(state, batch, key)
        return new_state, metrics["active"]

    args = (state_sds, specs["key"], specs["batch"])
    return step, args, (state_shardings, None)


def build_prefill_step(bundle: ArchBundle, shape: InputShape, mesh,
                       multi_pod: bool):
    cfg = bundle.model

    def step(params, tokens, img_embeds=None):
        logits, cache = tf.prefill(params, cfg, tokens,
                                   img_embeds=img_embeds,
                                   window=serve_window(cfg, shape))
        # return last-position logits + cache (serving contract)
        return logits[:, -1], cache

    inner = sh.param_pspecs(tf.param_specs(cfg), mesh,
                            fsdp=bundle.parallel.fsdp)
    param_sds = jax.tree.map(
        lambda s, p: SDS(s.shape, s.dtype, sharding=jax.NamedSharding(mesh, p)),
        tf.param_specs(cfg), inner, is_leaf=lambda x: isinstance(x, SDS))
    specs = input_specs(cfg.name, shape.name, multi_pod=multi_pod, mesh=mesh)
    args = (param_sds, specs["tokens"])
    if cfg.img_tokens:
        args = args + (specs["img_embeds"],)
    return step, args, None


def build_decode_step(bundle: ArchBundle, shape: InputShape, mesh,
                      multi_pod: bool):
    cfg = bundle.model
    window = serve_window(cfg, shape)

    def step(params, cache, tokens):
        return tf.decode_step(params, cfg, cache, tokens, window=window)

    inner = sh.param_pspecs(tf.param_specs(cfg), mesh,
                            fsdp=bundle.parallel.fsdp)
    param_sds = jax.tree.map(
        lambda s, p: SDS(s.shape, s.dtype, sharding=jax.NamedSharding(mesh, p)),
        tf.param_specs(cfg), inner, is_leaf=lambda x: isinstance(x, SDS))
    specs = input_specs(cfg.name, shape.name, multi_pod=multi_pod, mesh=mesh)
    return step, (param_sds, specs["cache"], specs["tokens"]), None


# ---------------------------------------------------------------------------
# HLO collective analysis
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo_text: str) -> dict:
    """Split HLO module text into named computations."""
    comps: dict[str, list[str]] = {}
    name = None
    for line in hlo_text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$", line)
        if m and not line.startswith(" "):
            name = ("ENTRY" if m.group(1) else m.group(2))
            comps[name] = []
            continue
        if line.strip() == "}" and not line.startswith("  "):
            name = None
            continue
        if name is not None:
            comps[name].append(line.strip())
    return comps


def _trip_count(cond_lines: list[str]) -> int:
    """Heuristic trip count of a while loop: largest s32 constant compared
    against in the condition computation (lax.scan emits `i < T`)."""
    best = 1
    for line in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            best = max(best, int(m.group(1)))
    return best


def collective_stats(hlo_text: str) -> dict:
    """Bytes moved by every collective in post-SPMD HLO, *trip-count aware*:
    collectives inside while-loop (lax.scan) bodies are multiplied by the
    loop's trip count, recursively.  Byte counts use the op's output shape
    (for all-gather that is the gathered size; a faithful proxy for link
    traffic up to the reduction algorithm's constant factor)."""
    comps = _split_computations(hlo_text)

    per_comp: dict[str, dict] = {}
    calls: dict[str, list[tuple[str, int]]] = {}
    for name, lines in comps.items():
        stats = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
        sub: list[tuple[str, int]] = []
        for line in lines:
            m = re.match(r"^[%\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", line)
            if m:
                type_str, op = m.groups()
                op_base = op.split(".")[0]
                for c in _COLLECTIVES:
                    if op_base == c or op_base == c + "-start":
                        stats[c]["count"] += 1
                        stats[c]["bytes"] += _shape_bytes(type_str)
                        break
                if op_base == "while":
                    mb = re.search(r"body=%?([\w.\-]+)", line)
                    mc = re.search(r"condition=%?([\w.\-]+)", line)
                    trips = _trip_count(comps.get(mc.group(1), [])) if mc else 1
                    if mb:
                        sub.append((mb.group(1), trips))
                elif op_base in ("call", "fusion", "conditional", "custom-call"):
                    for mm in re.finditer(r"(?:to_apply|calls)=%?([\w.\-]+)", line):
                        sub.append((mm.group(1), 1))
                    for mm in re.finditer(r"branch_computations=\{([^}]*)\}", line):
                        for nm in mm.group(1).split(","):
                            sub.append((nm.strip().lstrip("%"), 1))
        per_comp[name] = stats
        calls[name] = sub

    def accumulate(name: str, seen: tuple) -> dict:
        if name not in per_comp or name in seen:
            return {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
        total = {c: dict(per_comp[name][c]) for c in _COLLECTIVES}
        for child, mult in calls.get(name, []):
            child_tot = accumulate(child, seen + (name,))
            for c in _COLLECTIVES:
                total[c]["count"] += mult * child_tot[c]["count"]
                total[c]["bytes"] += mult * child_tot[c]["bytes"]
        return total

    root = "ENTRY" if "ENTRY" in per_comp else next(iter(per_comp), None)
    stats = accumulate(root, ()) if root else {
        c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    stats["total_bytes"] = sum(v["bytes"] for v in stats.values()
                               if isinstance(v, dict))
    return stats


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def dryrun_one(arch: str, shape_name: str, mesh_kind: str,
               mix_override: str | None = None,
               save_hlo: str | None = None,
               tp: bool | None = None, compress: str | None = None,
               compress_ratio: float = 0.1, compress_sigma: float = 0.0,
               error_feedback: bool = False, graph: str = "static",
               graph_kwargs: tuple = (), trim: int = 1,
               robust_scope: str = "global",
               robust_gather: str = "auto", asynchrony=None) -> dict:
    multi_pod = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi_pod)
    bundle = get_config(arch)
    shape = INPUT_SHAPES[shape_name]

    t0 = time.time()
    if shape.kind == "train":
        step, args, out_sh = build_train_step(bundle, shape, mesh, multi_pod,
                                              mix_override, tp=tp,
                                              compress=compress,
                                              compress_ratio=compress_ratio,
                                              compress_sigma=compress_sigma,
                                              error_feedback=error_feedback,
                                              graph=graph,
                                              graph_kwargs=graph_kwargs,
                                              trim=trim,
                                              robust_scope=robust_scope,
                                              robust_gather=robust_gather,
                                              asynchrony=asynchrony)
    elif shape.kind == "prefill":
        step, args, out_sh = build_prefill_step(bundle, shape, mesh, multi_pod)
    else:
        step, args, out_sh = build_decode_step(bundle, shape, mesh, multi_pod)

    with mesh:
        jitted = jax.jit(step, out_shardings=out_sh) if out_sh else jax.jit(step)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    t1 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_stats(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)

    mem_dict = {}
    if mem is not None:
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
            mem_dict[field] = int(getattr(mem, field, 0) or 0)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "mix": mix_override or "default",
        "engine": ("async" if asynchrony is not None and asynchrony.enabled
                   else "sharded"),
        "graph": graph,
        "compress": compress or "none",
        "compress_ratio": compress_ratio,
        "error_feedback": error_feedback,
        "tp": tp if tp is not None else get_config(arch).parallel.tp,
        "devices": int(len(mesh.devices.reshape(-1))),
        "compile_seconds": round(t1 - t0, 2),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "memory": mem_dict,
        "model_params_total": get_config(arch).model.total_params(),
        "model_params_active": get_config(arch).model.active_params(),
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    # the spec-mapped flags are the SAME shared set train/serve use
    # (repro/api/cli.py) — drivers cannot drift on names or defaults.
    # dryrun-specific knobs (shapes, mesh, sweep, output) stay local.
    add_spec_args(ap)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--arch-default-mix", action="store_true",
                    help="deprecation shim: use the arch bundle's production "
                         "mix path instead of the shared --mix flag")
    ap.add_argument("--no-tp", action="store_true",
                    help="replicate params over the model axis (pure DP)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()
    spec = spec_from_args(args)
    mix = None if args.arch_default_mix else spec.mixer.kind
    compress = spec.compression.kind

    os.makedirs(args.out, exist_ok=True)
    combos = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in INPUT_SHAPES:
                for mesh_kind in ("single", "multi"):
                    combos.append((arch, shape, mesh_kind))
    else:
        combos.append((spec.model.arch, args.shape, args.mesh))

    failures = 0
    for arch, shape, mesh_kind in combos:
        tag = (f"{arch}_{shape}_{mesh_kind}"
               + (f"_{mix}" if mix else "")
               + ("_async" if spec.asynchrony.enabled else "")
               + (f"_{spec.graph.kind}" if spec.graph.kind != "static"
                  else "")
               + (f"_{compress}" if compress != "none" else "")
               + ("_ef" if spec.compression.error_feedback else "")
               + ("_notp" if args.no_tp else ""))
        out_path = os.path.join(args.out, tag + ".json")
        try:
            res = dryrun_one(arch, shape, mesh_kind, mix_override=mix,
                             save_hlo=args.save_hlo,
                             tp=False if args.no_tp else None,
                             compress=compress,
                             compress_ratio=spec.compression.ratio,
                             compress_sigma=spec.compression.sigma,
                             error_feedback=spec.compression.error_feedback,
                             graph=spec.graph.kind,
                             graph_kwargs=spec.graph_kwargs(),
                             trim=spec.mixer.trim,
                             robust_scope=spec.mixer.scope,
                             robust_gather=spec.mixer.gather,
                             asynchrony=spec.asynchrony)
            with open(out_path, "w") as f:
                json.dump(res, f, indent=1)
            print(f"OK   {tag}: compile={res['compile_seconds']}s "
                  f"flops={res['flops']:.3e} coll={res['collectives']['total_bytes']:.3e}B")
        except Exception as e:  # noqa: BLE001 — report, continue sweep
            failures += 1
            print(f"FAIL {tag}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} dry-run combinations failed")


if __name__ == "__main__":
    main()
