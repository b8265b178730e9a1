"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, its traffic file (``traffic/<traffic>.json``), its
limits (``limits/<cell>.json``), its configuration's plain reference
(``reference/<reference>.py``) and family module
(``layouts/<reference>.py``: the weights' layout and the model FLOP of a
token), and each per-layer metric's reader (``metrics/<metric>.py``).  A
new cell, and a configuration of a new family, is new files and entries
only.

A run:

1. builds the program's engine from the cell (``repro.api.build``), makes
   the weights (by the family module) and the token stream on the device
   from the seed, and jits the engine's block step with the state donated;
2. drives that compiled step through the first ``check_blocks`` blocks,
   keeping what :mod:`check` compares;
3. measures: blocks back to back for ``seconds``, with about ``AHEAD_S``
   seconds of them dispatched ahead of the one waited for, then waits for
   every block sent; the profiler is on when ``trace``, and a traced run
   then hands the readers the trace and the compiled block step's scope
   map (``scopes.parse_hlo``) in ``ctx``;
4. reads the peak device memory, frees the program's state, and runs the
   plain reference over the checked blocks.

The peak device memory is the larger of the runtime's
``peak_bytes_in_use`` and the compiled block step's own footprint
(arguments, outputs and temporaries, less what the donation aliases), as
JAX reports each: the runtime's figure has been seen to leave out the
step's temporaries.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import math
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

import numpy as np

from benchmarks.chip import check, counts, model, scopes, trace
from benchmarks.chip.peaks import peaks_for

__all__ = ["Cell", "load_cell", "load_layout", "reader_context", "run",
           "NoChip", "ROOT", "BENCH"]

ROOT = Path(__file__).resolve().parents[2]
BENCH = Path(__file__).resolve().parent
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: faults a test or the calibration plants in the timed path; runs of the
#: benchmark itself never do
FAULTS = ("none", "half_batch", "frozen_state", "no_exchange")
#: seconds of device work the window keeps dispatched ahead of the block it
#: waits for
AHEAD_S = 4.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: list
    per_layer: list
    bench_dir: Path


def _moves_cells(manifest: dict, metric: str) -> list:
    m = next(e for e in manifest["end_to_end"] if e["name"] == metric)
    return m.get("workloads", [w["name"] for w in manifest["workloads"]])


def load_cell(workload: str, manifest_path: Path = ROOT / "BENCHMARK.json",
              bench_dir: Path = BENCH) -> Cell:
    """The cell named ``workload``, with every file it names loaded."""
    manifest = model.load_json(manifest_path)
    try:
        w = next(c for c in manifest["workloads"] if c["name"] == workload)
    except StopIteration:
        raise KeyError(f"no cell {workload!r} in {manifest_path}") from None
    entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    config = model.load_json(Path(manifest_path).parent / entry["file"])
    traffic = model.load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    if traffic["chips"] != w["chips"]:
        raise ValueError(f"{workload}: traffic asks for {traffic['chips']} "
                         f"chips, the cell for {w['chips']}")
    limits = model.load_json(bench_dir / "limits" / f"{workload}.json")
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in manifest["per_layer"]
                 if workload in m.get("workloads",
                                      _moves_cells(manifest, m["moves"]))]
    return Cell(workload, config, traffic, limits, w["chips"], e2e,
                per_layer, Path(bench_dir))


def _code(cell: Cell, sub: str, name: str) -> Path:
    """``<sub>/<name>.py`` of the cell's benchmark directory, else of this
    one."""
    path = cell.bench_dir / sub / f"{name}.py"
    return path if path.exists() else BENCH / sub / f"{name}.py"


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_layout(cell: Cell):
    """The family module of the cell's configuration,
    ``layouts/<reference>.py``."""
    name = cell.config["reference"]
    return _load_module(_code(cell, "layouts", name), "bench_layout_" + name)


def run(workload: str, seed: int, seconds: float, trace_on: bool, *,
        t_start: float, manifest_path: Path = ROOT / "BENCHMARK.json",
        bench_dir: Path = BENCH, require_tpu: bool = True,
        fault: str = "none", control: bool = False, raw: bool = False
        ) -> dict:
    """Run one cell and return the result line's object.  Raises
    :class:`NoChip` where the chip is missing.  ``fault`` plants one of
    :data:`FAULTS` in the timed path; ``control`` adds the control's
    readings, judged against the same limits, under ``"control"``; ``raw``
    adds the compared arrays under ``"raw"``.  The benchmark's own runs
    use none of them."""
    import jax

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    cell = load_cell(workload, manifest_path, bench_dir)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX sees "
                     f"{len(devs)}")
    devs = devs[:cell.chips]
    kind = devs[0].device_kind
    tag = f"{kind} x{len(devs)}"

    def log(msg: str) -> None:
        """A line of the run, naming the device."""
        print(f"[{tag}] {msg}", flush=True)

    peaks = peaks_for(kind) if require_tpu else None

    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = {"on": False, "window": 0, "hits": 0, "misses": 0}

    def on_duration(event, duration, **_):
        if counter["on"] and event == BACKEND_COMPILE:
            counter["window"] += 1

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            counter["hits"] += 1
        elif event.endswith("/cache_misses"):
            counter["misses"] += 1

    log(f"cell {cell.name}; seed {seed}; jax {jax.__version__}; compile "
        f"cache {cache_dir}")
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        return _run(cell, devs, log, peaks, counter, seed, seconds,
                    trace_on, fault, control, raw, t_start)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def _run(cell, devs, log, peaks, counter, seed, seconds, trace_on, fault,
         control, raw, t_start) -> dict:
    import jax
    import jax.numpy as jnp

    tr = cell.traffic
    K, T = tr["agents"], tr["local_steps"]
    cfg = model.model_config(cell.config)
    layout = load_layout(cell)
    model.check_program_layout(cfg, layout)
    mkind = model.register_model(cfg, half_batch=fault == "half_batch")
    eng, mesh = _build(cell, cfg, mkind, seed, devs)
    if fault == "frozen_state":
        real = eng.step
        eng.step = lambda s, b, k: (s, real(s, b, k)[1])
    elif fault == "no_exchange":
        from repro.core.mixing import NullMixer
        eng.pipeline.mixer = NullMixer()

    from jax.sharding import NamedSharding, PartitionSpec as P
    stack = NamedSharding(mesh, P("data")) if mesh is not None else None
    per_step = (NamedSharding(mesh, P(None, "data"))
                if mesh is not None else None)
    key = model.seed_key(seed)
    k_w, k_data, k_step = jax.random.split(key, 3)

    init = jax.jit(lambda k: layout.to_program(
        check.broadcast_agents(layout.make_weights(k, cfg), K),
        cfg), out_shardings=stack)
    gen = jax.jit(lambda k, i: model.make_block(k, i, tr, cfg.vocab_size),
                  out_shardings=per_step)
    fp = jax.jit(lambda p: check.fingerprint(layout.from_program(p, cfg)))
    # the initial weights are drawn anew for each comparison, so no copy of
    # them is held while the step runs; they are drawn by a call of their
    # own, whose output is rounded to the weights' dtype: inside one fused
    # program the compiler may keep their unrounded value
    one_agent = jax.jit(lambda k: layout.make_weights(k, cfg))
    chg = jax.jit(lambda p, w0: check.sq_change(layout.from_program(p, cfg),
                                                w0))

    params = init(k_w)
    state = eng.init_state(params, eng.optimizer.init(params),
                           key=jax.random.fold_in(key, 0x5EED))
    del params
    # the block step is compiled once, here, and that program runs every
    # block; its memory analysis is the step's own footprint
    step = jax.jit(eng.step, donate_argnums=0).lower(
        state, gen(k_data, 0), jax.random.fold_in(k_step, 0)).compile()
    footprint = _footprint(step)
    prints = [np.asarray(fp(state.params))]
    actives, changes = [], []
    n_check = tr["check_blocks"]
    for b in range(n_check):
        t_b = time.perf_counter()
        state, met = step(state, gen(k_data, b), jax.random.fold_in(k_step, b))
        actives.append(np.asarray(met["active"]))
        # one block's wall time, data and step, waited for: it sizes how
        # many blocks the window keeps in flight
        block_est = time.perf_counter() - t_b
        prints.append(np.asarray(fp(state.params)))
        if b in (0, n_check - 1):
            changes.append(np.asarray(chg(state.params, one_agent(k_w))))
        if b == 0:
            stats = devs[0].memory_stats() or {}
            log("memory after the first block: " + ", ".join(
                f"{k} {v}" for k, v in sorted(stats.items())
                if "bytes" in k))
    jax.block_until_ready(state)

    # -- the measured window -------------------------------------------------
    # blocks are dispatched ahead of the one waited for, about AHEAD_S of
    # device work, so the chip stays fed while the host stands still; when
    # the time is up nothing more is sent, every block sent is waited for,
    # and the clock is read after that wait: all of them count, over all of
    # that time
    depth = max(1, math.ceil(AHEAD_S / block_est))
    n_blocks, drawn, done_at, pending = 0, [], [], collections.deque()
    prof_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace_on else None
    # the interpreter's garbage collections inside the window, each as
    # (generation, seconds): a host stall the log can then name
    gc_pauses, gc_start = [], []

    def on_gc(phase, info):
        if phase == "start":
            gc_start.append(time.perf_counter())
        elif gc_start:
            gc_pauses.append((info["generation"],
                              time.perf_counter() - gc_start.pop()))

    counter["on"] = True
    gc.callbacks.append(on_gc)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    try:
        if trace_on:
            jax.profiler.start_trace(prof_dir)
        b = n_check
        up = False
        while not up:
            # the last block's span holds the final wait, so the spans run
            # from the first dispatch to the end of the device's work
            with jax.profiler.TraceAnnotation("bench.block"):
                with jax.profiler.TraceAnnotation("bench.data"):
                    batch = gen(k_data, b)
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, met = step(state, batch,
                                      jax.random.fold_in(k_step, b))
                drawn.append(met["active"])
                pending.append(met)
                n_blocks += 1
                b += 1
                with jax.profiler.TraceAnnotation("bench.wait"):
                    if len(pending) > depth:
                        jax.block_until_ready(pending.popleft())
                        done_at.append(time.perf_counter())
                    up = time.perf_counter() - t0 >= seconds
                    if up:
                        jax.block_until_ready((state, list(pending)))
        elapsed = time.perf_counter() - t0
        if trace_on:
            jax.profiler.stop_trace()
    finally:
        gc.callbacks.remove(on_gc)
        counter["on"] = False
    realized = (float(np.sum(jax.device_get(drawn))) * T * tr["batch"]
                * tr["seq"])
    tokens = n_blocks * counts.block_tokens(tr)
    log(f"window: {n_blocks} blocks in {elapsed:.4f} s "
        f"({elapsed / n_blocks:.4f} s per block); tokens at configured "
        f"q={tr['participation']}: {tokens:.0f}, realized: {realized:.0f}")
    # the times between the ends of blocks waited for, once the queue is
    # full: one block's device time each, unless a host stall outlasts the
    # queue
    walls = np.diff(done_at) if len(done_at) > 1 else np.array([elapsed])
    log(f"{depth} blocks dispatched ahead ({block_est:.4f} s per checked "
        f"block); block wall times once full: median "
        f"{np.median(walls):.4f} s, slowest {walls.max():.4f} s; "
        f"garbage collections in the window: {len(gc_pauses)}, of "
        f"generation 2: {sum(g == 2 for g, _ in gc_pauses)}, longest "
        f"{max((d for _, d in gc_pauses), default=0.0):.4f} s")
    log(f"compilations inside the window: {counter['window']}; compile "
        f"cache over the run: {counter['hits']} hits, "
        f"{counter['misses']} misses")
    runtime_peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devs)
    peak = max(runtime_peak, footprint)
    log(f"peak device memory {peak} bytes: runtime peak_bytes_in_use "
        f"{runtime_peak}, block step footprint {footprint}")
    agent_params = sum(x.size // K for x in jax.tree.leaves(state.params))
    itemsize = jnp.dtype(cfg.dtype).itemsize
    del state, met, batch

    metrics = {}
    if trace_on:
        t1 = time.perf_counter()
        step_hlo = scopes.parse_hlo(step.as_text())
        log(f"scope map of the block step: {len(step_hlo.op_names)} "
            f"instructions in {time.perf_counter() - t1:.3f} s")
        events = trace.events_from_xplane(prof_dir)
        shutil.rmtree(prof_dir, ignore_errors=True)
        ctx = reader_context(cell, events, cfg, layout, step_hlo, peaks,
                             agent_params, itemsize)
        metrics, dev_info, breakdown = _per_layer(cell, ctx, log)
    else:
        e2e = {"train_tokens_per_s": tokens / elapsed / cell.chips,
               "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    checks, ctl, arrays = _check(cell, cfg, layout, k_w,
                                 lambda b: gen(k_data, b),
                                 actives, prints, changes, control=control)
    correct = _passes(checks)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": n_blocks,
           "failed": 0 if correct else n_blocks, "metrics": metrics,
           "device": device}
    if trace_on:
        device.update(dev_info)
        out["breakdown"] = breakdown
    if ctl is not None:
        out["control"] = {"correct": _passes(ctl), "checks": ctl}
    if raw:
        out["raw"] = arrays
    out["checks"] = checks
    return out


def _passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def _footprint(compiled) -> int:
    """Bytes the compiled program holds while it runs, by its own memory
    analysis; 0 where the backend gives none."""
    ma = compiled.memory_analysis()
    if ma is None:
        return 0
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _build(cell: Cell, cfg, mkind: str, seed: int, devs):
    from repro.api import build
    from repro.api.spec import (ExperimentSpec, MixerSpec, ModelSpec,
                                OptimizerSpec, ParticipationSpec, RunSpec,
                                TopologySpec)
    from repro.launch.mesh import make_agent_mesh
    tr = cell.traffic
    mesh = make_agent_mesh(tr["agents"], devs) if cell.chips > 1 else None
    if cell.chips > 1 and mesh is None:
        raise ValueError(f"{cell.name}: no agent mesh for K={tr['agents']} "
                         f"over {len(devs)} devices")
    spec = ExperimentSpec(
        topology=TopologySpec(kind=tr["topology"]),
        participation=ParticipationSpec(kind="iid", q=tr["participation"]),
        mixer=MixerSpec(kind=tr["mixer"]),
        optimizer=OptimizerSpec(kind=tr["optimizer"]),
        model=ModelSpec(kind=mkind, arch=cfg.name, smoke=False),
        run=RunSpec(num_agents=tr["agents"], local_steps=tr["local_steps"],
                    step_size=tr["step_size"], batch=tr["batch"],
                    seq=tr["seq"], seed=seed % (1 << 31)))
    return build(spec, mesh=mesh), mesh


def reader_context(cell, events, cfg, layout, step_hlo, peaks,
                   agent_params, itemsize) -> types.SimpleNamespace:
    """What the per-layer readers get: the trace's events, devices and
    window of blocks; the cell's configuration, traffic, chips and family
    module (``layout``); the compiled block step's scope map
    (``step_hlo``); the chip's peaks; one agent's parameter count and their
    bytes each; and ``note``, which keeps a line for the run's log
    (``notes``)."""
    devices = trace.devices(events)
    if not devices:
        raise RuntimeError("the trace holds no device operation")
    lo, hi = trace.window(events)
    n = sum(1 for e in events if e[0] == trace.HOST
            and e[1] == trace.SPAN_PREFIX + "block")
    notes = []
    return types.SimpleNamespace(
        events=events, devices=devices, lo=lo, hi=hi, n_blocks=n,
        block_s=(hi - lo) * 1e-9 / n, chips=cell.chips, peaks=peaks,
        cfg=cfg, layout=layout, step_hlo=step_hlo, traffic=cell.traffic,
        agent_params=agent_params, itemsize=itemsize, notes=notes,
        note=notes.append)


def _per_layer(cell, ctx, log):
    """Per-layer metrics, device busy time and the breakdown of a trace."""
    events, devices, lo, hi = ctx.events, ctx.devices, ctx.lo, ctx.hi
    metrics = {}
    for m in cell.per_layer:
        reader = _load_module(_code(cell, "metrics", m["name"]),
                              "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    for line in ctx.notes:
        log(line)
    busy = [trace.busy_ns(events, d, lo, hi) * 1e-9 for d in devices]
    busiest = devices[int(np.argmax(busy))]
    breakdown = {"device_ops": trace.top_ops(events, busiest, lo, hi),
                 "idle_gaps": trace.idle_gaps(events, busiest, lo, hi)}
    log(f"trace: {ctx.n_blocks} blocks in {(hi - lo) * 1e-9:.4f} s; busy "
        f"{[round(x, 4) for x in busy]} s on {devices}")
    log(f"trace: top device ops {breakdown['device_ops']}")
    return (metrics, {"busy_s": float(np.mean(busy)),
                      "window_s": (hi - lo) * 1e-9}, breakdown)


def _check(cell, cfg, layout, k_w, gen, actives, prints, changes, *,
           control: bool = False) -> tuple[dict, dict | None, dict]:
    """Run the plain reference over the checked blocks and compare.  With
    ``control``, also put the reference computed in fp8 in the program's
    place and return its readings.  Also returns the compared arrays."""
    import jax
    ref_mod = _load_module(_code(cell, "reference", cell.config["reference"]),
                           "bench_reference_" + cell.config["reference"])
    K = cell.traffic["agents"]
    w0 = jax.jit(lambda k: layout.make_weights(k, cfg))(k_w)

    def follow(precision):
        ref = check.Reference(ref_mod.loss, cell.config["model"],
                              cell.traffic, precision)
        W = jax.jit(check.broadcast_agents, static_argnums=1)(w0, K)
        sq_change = jax.jit(check.sq_change)
        out = []
        for b in range(len(actives)):
            W = ref.block(W, jax.device_get(gen(b)), actives[b])
            if b in (0, len(actives) - 1):
                out.append(np.asarray(sq_change(W, w0)))
        return out

    ref_changes = follow("highest")
    keep = check.kept_leaves(ref_changes)
    names = check.leaf_names(w0)
    moved = 0
    for b, act in enumerate(actives):
        for k in np.flatnonzero(np.asarray(act) == 0):
            moved += int(not np.array_equal(prints[b + 1][:, k],
                                            prints[b][:, k]))
    lim = cell.limits

    def gaps(got, tag):
        g1, i1 = check.worst_leaf_gap(got[0], ref_changes[0], keep)
        gN, iN = check.worst_leaf_gap(got[-1], ref_changes[-1], keep)
        print(f"{tag}: worst (leaf, agent) {names[i1[0]]} agent {i1[1]} "
              f"(first block), {names[iN[0]]} agent {iN[1]} (block "
              f"{len(actives)}); {int((~keep).sum())} leaves left out",
              file=sys.stderr, flush=True)
        gM = check.median_leaf_gap(got[-1], ref_changes[-1], keep)
        return {"change1_gap": {"value": g1, "limit": lim["change1_gap"]},
                "changeN_gap": {"value": gN, "limit": lim["changeN_gap"]},
                "changeN_median_gap": {"value": gM,
                                       "limit": lim["changeN_median_gap"]}}

    checks = gaps(changes, "check")
    checks["inactive_moved"] = {"value": moved,
                                "limit": lim["inactive_moved"]}
    arrays = {"leaves": names, "keep": keep.tolist(),
              "active": [np.asarray(a).tolist() for a in actives],
              "program": [c.tolist() for c in changes],
              "reference": [c.tolist() for c in ref_changes]}
    ctl = None
    if control:
        ctl_changes = follow("fp8")
        ctl = gaps(ctl_changes, "control")
        arrays["control"] = [c.tolist() for c in ctl_changes]
    return checks, ctl, arrays
