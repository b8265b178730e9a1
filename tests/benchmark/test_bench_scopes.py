"""Attribution of the block step's device time to the program's named
scopes: the HLO parser on the real program, the six readers on a synthetic
trace and through the harness."""
import gzip
import itertools
import json
import types

import pytest

from benchmarks.chip import harness, scopes, trace

D0, H = "/device:TPU:0", trace.HOST
READERS = ("scan_forward_ms", "scan_backward_ms", "scan_recompute_ms",
           "scan_update_ms", "attention_ms", "mix_copies_ms")


def _reader(name):
    return harness._load_module(harness.BENCH / "metrics" / f"{name}.py",
                                "t_scopes_" + name).read


# -- (a) the program names every scope the readers look for -----------------

@pytest.fixture(scope="module")
def tiny_step_hlo(fixtures):
    """The fixture cell's block step with the Pallas mixer (interpret mode
    on the CPU), compiled, as :func:`scopes.parse_hlo` reads it."""
    import jax
    from benchmarks.chip import check, model
    cell = harness.load_cell("tiny.t2", fixtures / "BENCHMARK.json", fixtures)
    cell.traffic = dict(cell.traffic, mixer="pallas")
    cfg = model.model_config(cell.config)
    eng, _ = harness._build(cell, cfg, model.register_model(cfg), 7,
                            jax.devices()[:1])
    layout = harness.load_layout(cell)
    params = layout.to_program(check.broadcast_agents(
        layout.make_weights(jax.random.PRNGKey(0), cfg),
        cell.traffic["agents"]), cfg)
    state = eng.init_state(params, eng.optimizer.init(params),
                           key=jax.random.PRNGKey(1))
    batch = model.make_block(jax.random.PRNGKey(2), 0, cell.traffic,
                             cfg.vocab_size)
    compiled = jax.jit(eng.step, donate_argnums=0).lower(
        state, batch, jax.random.PRNGKey(3)).compile()
    return scopes.parse_hlo(compiled.as_text())


@pytest.mark.parametrize("mark", [
    scopes.SAMPLE, f"{scopes.LOCAL_UPDATE}/*/{scopes.APPLY}",
    scopes.ATTENTION, scopes.COMBINE, f"{scopes.COMBINE}/{scopes.FLATTEN}",
    f"{scopes.COMBINE}/{scopes.UNFLATTEN}", scopes.JVP, scopes.TRANSPOSE,
    scopes.REMAT])
def test_program_names_each_scope(tiny_step_hlo, mark):
    """A rename in the program fails here, not as a metric read as 0."""
    def has(op):
        parts = op.split("/")
        if "/" not in mark:
            return (mark in parts if mark.isidentifier()
                    else any(mark in p for p in parts))
        outer, *_, inner = mark.split("/")
        return (outer in parts
                and inner in parts[parts.index(outer) + 1:])
    assert any(has(op) for op in tiny_step_hlo.op_names.values()), mark


def test_program_fills_every_bucket(tiny_step_hlo):
    seen = {scopes.bucket(op) for op in tiny_step_hlo.op_names.values()}
    assert seen >= set(scopes.BUCKETS) - {"unscoped"}
    entry = set(tiny_step_hlo.entry)
    assert entry and entry <= set(tiny_step_hlo.op_names)


def test_parse_hlo_reads_names_metadata_and_entry_order():
    pad = "jit(f)/combine/flatten/pad"
    text = "\n".join([
        "HloModule jit_block_step, is_scheduled=true",
        "",
        "%fused_computation.1 (p: f32[2]) -> f32[2] {",
        "  %p = f32[2]{0} parameter(0)",
        '  ROOT %m.1 = f32[2]{0} multiply(%p, %p), metadata={op_name="x"}',
        "}",
        "",
        "%wide.body (b: (f32[2])) -> (f32[2]) {",
        "  %b = (f32[2]) parameter(0)",
        "  %gte.7 = f32[2]{0} get-tuple-element(%b), index=0",
        "  ROOT %dynamic-update-slice.4 = f32[2]{0} dynamic-update-slice("
        "%gte.7, %gte.7), backend_config={\"k\":[]}",
        "}",
        "",
        "ENTRY %main.9 (a: f32[2]) -> (f32[2], f32[2]) {",
        '  %a = f32[2]{0} parameter(0), metadata={op_name="arg"}',
        "  %copy-start.3 = (f32[2]{0}, u32[]) copy-start(%a)",
        "  %while.5 = (f32[2]) while(%a), condition=%wide.cond, "
        "body=%wide.body",
        "  %fusion.2 = f32[2]{0} fusion(%while.5), kind=kLoop, "
        f'calls=%fused_computation.1, metadata={{op_name="{pad}" '
        'source_file="m.py" source_line=4}',
        "  %copy.6 = f32[2]{0} copy(%fusion.2)",
        "  %q.8 = f32[2]{0} negate(%a), "
        'metadata={op_name="jit(f)/q\\"uoted"}',
        "  ROOT %t = (f32[2]{0}) tuple(%copy.6, %q.8)",
        "}",
    ])
    hlo = scopes.parse_hlo(text)
    assert hlo.entry == ("a", "copy-start.3", "while.5", "fusion.2",
                         "copy.6", "q.8", "t")
    assert hlo.op_names["fusion.2"] == pad
    assert hlo.op_names["m.1"] == "x"
    assert hlo.op_names["q.8"] == 'jit(f)/q\\"uoted'
    # without metadata: a loop XLA made takes its nearest user's, the
    # loop's body its caller's, an output copy its operand's
    assert hlo.op_names["while.5"] == pad
    assert hlo.op_names["dynamic-update-slice.4"] == pad
    assert hlo.op_names["copy.6"] == pad
    assert hlo.op_names["copy-start.3"] == "arg"
    assert {"while.5", "dynamic-update-slice.4", "copy.6",
            "copy-start.3"} <= hlo.inherited
    assert not {"fusion.2", "m.1", "q.8"} & hlo.inherited


@pytest.mark.parametrize("op_name,expected", [
    ("jit(block_step)/sample/jit(_bernoulli)/jit(_uniform)", "sample"),
    ("jit(block_step)/local_update/while/body/closed_call/vmap(jvp())/"
     "while/body/closed_call/dot_general", "forward"),
    ("jit(block_step)/local_update/while/body/closed_call/"
     "vmap(transpose(jvp()))/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/mul", "recompute"),
    ("jit(block_step)/local_update/while/body/closed_call/"
     "vmap(transpose(jvp()))/while/body/closed_call/checkpoint/add_any",
     "backward"),
    ("jit(block_step)/local_update/while/body/closed_call/apply/sub",
     "update"),
    ("jit(block_step)/local_update/while", "local_update"),
    ("jit(block_step)/combine/flatten/concatenate", "mix_copies"),
    ("jit(block_step)/combine/unflatten/slice", "mix_copies"),
    ("jit(block_step)/combine/jit(diffusion_mix)", "combine"),
    ("jit(block_step)/transpose", "unscoped"),
    ("", "unscoped"),
])
def test_bucket_of_an_op_name(op_name, expected):
    assert scopes.bucket(op_name) == expected


# -- (b) the readers on a synthetic trace ------------------------------------

FWD = "jit(s)/local_update/while/body/vmap(jvp())/closed_call/attention/dot"
HLO = scopes.StepHlo(op_names={
    "copy-start.1": "",
    "fusion": "jit(s)/sample/jit(_bernoulli)",
    "fusion.1": FWD,
    "fusion.2": "jit(s)/local_update/while/body/vmap(transpose(jvp()))/"
                "checkpoint/mul",
    "fusion.3": "jit(s)/local_update/while/body/vmap(transpose(jvp()))/"
                "checkpoint/rematted_computation/attention/exp",
    "fusion.4": "jit(s)/local_update/while/body/apply/sub",
    "while.5": "jit(s)/local_update/while",
    "copy.6": "jit(s)/combine/flatten/concatenate",
    "diffusion_mix": "jit(s)/combine/jit(diffusion_mix)",
    "slice.7": "jit(s)/combine/unflatten/slice",
    "copy.8": "",
}, entry=("a", "copy-start.1", "fusion", "while.5", "copy.6",
          "diffusion_mix", "slice.7", "copy.8"))


def _synthetic():
    """Two blocks of 100 us; in each, other programs run before and after
    the step, with operations named like the step's (``fusion.1``,
    ``copy-start.1``, ``fusion``)."""
    us = 1000
    out = []
    for t0 in (0, 100 * us):
        out += [(H, "bench.block", t0, 100 * us),
                (D0, "fusion.1", t0 + 1 * us, 5 * us),      # other program
                (D0, "copy-start.1", t0 + 6 * us, 1 * us),  # other program
                (D0, "pad_fusion", t0 + 7 * us, 1 * us),    # other program
                (D0, "copy-start.1", t0 + 10 * us, 1 * us),
                (D0, "fusion", t0 + 11 * us, 2 * us),
                (D0, "while.5", t0 + 13 * us, 40 * us)]
        t = t0 + 13 * us
        for _ in range(2):                      # two loop iterations
            for name, d in (("fusion.1", 3), ("fusion.3", 4),
                            ("fusion.2", 6), ("fusion.4", 2)):
                out.append((D0, name, t, d * us))
                t += d * us
        out += [(D0, "copy.6", t0 + 60 * us, 5 * us),
                (D0, "diffusion_mix", t0 + 65 * us, 20 * us),
                (D0, "slice.7", t0 + 85 * us, 3 * us),
                (D0, "copy.8", t0 + 88 * us, 1 * us),
                (D0, "fusion", t0 + 92 * us, 1 * us),       # other program
                (D0, "fusion.1", t0 + 93 * us, 1 * us)]     # other program
    out.append((D0, "fusion.1", 300 * us, 7 * us))  # after the window
    return out


def _ctx(ev, **kw):
    lo, hi = trace.window(ev)
    n = sum(1 for e in ev if e[0] == H and e[1] == "bench.block")
    notes = []
    base = dict(events=ev, devices=trace.devices(ev), lo=lo, hi=hi,
                n_blocks=n, block_s=(hi - lo) * 1e-9 / n, chips=1,
                note=notes.append, notes=notes)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_step_ops_skip_other_programs():
    runs = scopes.step_ops(_synthetic(), HLO, 0, 200_000)[D0]
    assert [len(run) for run in runs] == [3 + 8 + 4, 3 + 8 + 4]
    assert [run[0][1] for run in runs] == [10_000, 110_000]
    assert [run[-1][0] for run in runs] == ["copy.8", "copy.8"]


def test_step_ops_count_only_whole_executions():
    """An execution cut by the window's edge is left out."""
    runs = scopes.step_ops(_synthetic(), HLO, 0, 170_000)[D0]
    assert len(runs) == 1 and runs[0][0][1] == 10_000


@pytest.mark.parametrize("metric,ms", [
    ("scan_forward_ms", 2 * 3e-3), ("scan_backward_ms", 2 * 6e-3),
    ("scan_recompute_ms", 2 * 4e-3), ("scan_update_ms", 2 * 2e-3),
    ("attention_ms", 2 * (3 + 4) * 1e-3), ("mix_copies_ms", (5 + 3) * 1e-3),
])
def test_readers_on_the_synthetic_trace(metric, ms):
    ctx = _ctx(_synthetic(), step_hlo=HLO)
    assert _reader(metric)(ctx) == pytest.approx(ms)


@pytest.mark.parametrize("scope,ms", [
    ("attention", 2 * (3 + 4) * 1e-3), ("apply", 2 * 2e-3),
    ("combine", (5 + 20 + 3) * 1e-3), ("flatten", 5e-3),
    ("checkpoint", 2 * (6 + 4) * 1e-3), ("no_such_scope", None),
])
def test_scope_ms_reads_any_named_scope(scope, ms):
    got = scopes.scope_ms(_ctx(_synthetic(), step_hlo=HLO), scope)
    assert got == (None if ms is None else pytest.approx(ms))


def test_split_is_noted_once_with_its_coverage():
    ctx = _ctx(_synthetic(), step_hlo=HLO)
    for m in READERS:
        _reader(m)(ctx)
    (line,) = ctx.notes
    # per block: 62 us of the step's operations (the loop's wrapper not
    # counted), 2 us of them unscoped
    assert "2 executions, 0.062 ms each" in line
    assert "unscoped 3.23%" in line
    assert "copy-start 1.61%, copy 1.61%" in line


@pytest.mark.parametrize("metric", READERS)
def test_readers_give_none_without_a_scope_map(metric):
    assert _reader(metric)(_ctx(_synthetic())) is None
    assert _reader(metric)(_ctx(_synthetic(), step_hlo=None)) is None
    unscoped = scopes.StepHlo({k: "jit(s)/mul" for k in HLO.op_names},
                              HLO.entry)
    assert _reader(metric)(_ctx(_synthetic(), step_hlo=unscoped)) is None


def test_readers_take_the_step_the_harness_ran(fixtures, monkeypatch,
                                               tmp_path):
    """Through ``harness.run`` with the profiler on, the readers get the
    scope map of the block step the harness compiled, in ``ctx``.  A CPU
    trace has no device plane, so each block gets one 10 ns device
    operation per instruction of that step: its entry computation in
    order, with every other instruction inside its first loop."""
    import dataclasses
    import time
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    load, events = harness.load_cell, trace.events_from_xplane
    parse, parsed = scopes.parse_hlo, []
    counts = {}

    def parse_hlo(text):
        parsed.append(parse(text))
        return parsed[-1]

    def load_cell(*a, **kw):
        cell = load(*a, **kw)
        per_layer = [{"name": m, "unit": "ms"} for m in READERS]
        return dataclasses.replace(
            cell, traffic=dict(cell.traffic, mixer="pallas"),
            per_layer=per_layer)

    def with_step_ops(prof_dir):
        ev = events(prof_dir)
        (hlo,) = parsed
        entry = set(hlo.entry)
        loop = next(n for n in hlo.entry if trace._WRAPPERS.match(n))
        body = [n for n in hlo.op_names if n not in entry
                and not trace._WRAPPERS.match(n)]
        for n in [n for n in hlo.entry if n != loop] + body:
            k = scopes.bucket(hlo.op_names[n])
            counts[k] = counts.get(k, 0) + 1
            counts["attention"] = (
                counts.get("attention", 0)
                + (scopes.ATTENTION in scopes.scope_names(hlo.op_names[n])))
        for _, _, s, _ in [e for e in ev if e[0] == H
                           and e[1] == "bench.block"]:
            t = s + 10
            for n in hlo.entry:
                if n == loop:
                    ev.append((D0, n, t, 10 * len(body)))
                    ev += [(D0, m, t + 10 * i, 10)
                           for i, m in enumerate(body)]
                    t += 10 * len(body)
                else:
                    ev.append((D0, n, t, 10))
                    t += 10
        return ev

    monkeypatch.setattr(harness, "load_cell", load_cell)
    monkeypatch.setattr(scopes, "parse_hlo", parse_hlo)
    monkeypatch.setattr(trace, "events_from_xplane", with_step_ops)
    out = harness.run("tiny.t2", 5, 0.0, True, t_start=time.perf_counter(),
                      manifest_path=fixtures / "BENCHMARK.json",
                      bench_dir=fixtures, require_tpu=False)
    assert out["correct"], out["checks"]
    got = {m: v["value"] for m, v in out["metrics"].items()}
    n = out["attempted"]
    assert n == 1
    expect = {"scan_forward_ms": counts["forward"],
              "scan_backward_ms": counts["backward"],
              "scan_recompute_ms": counts["recompute"],
              "scan_update_ms": counts["update"],
              "attention_ms": counts["attention"],
              "mix_copies_ms": counts["mix_copies"]}
    assert got == pytest.approx({m: c * 1e-5 for m, c in expect.items()})


# -- (c) a block recorded on the chip ----------------------------------------

@pytest.fixture(scope="module")
def silo_block(fixtures):
    """One block of smollm360m.silo_t4 traced on one TPU v5 lite, with the
    block step's scope map (the instructions the block shows), the
    device's module line and the six readings taken on the chip.  The
    events are stored as columns."""
    with gzip.open(fixtures / "trace_silo_block.json.gz", "rt") as f:
        data = json.load(f)
    starts = itertools.accumulate(data["start_step"])
    events = [(data["wheres"][w], data["names"][n], s, d) for w, n, s, d
              in zip(data["where"], data["name"], starts, data["dur"])]
    hlo = scopes.StepHlo(data["op_names"], tuple(data["entry"]),
                         frozenset(data["inherited"]))
    return data, events, hlo


@pytest.mark.parametrize("metric", READERS)
def test_recorded_silo_block_reproduces_the_chip(silo_block, metric):
    data, events, hlo = silo_block
    got = _reader(metric)(_ctx(events, step_hlo=hlo))
    assert got == pytest.approx(data["readings"][metric])
    assert got > 0


def test_recorded_silo_block_step_is_the_module_lines(silo_block):
    """The block step's execution, as found from the program alone, is the
    operations inside the module line's block step; the programs that ran
    before and after it share operation names with the step."""
    data, events, hlo = silo_block
    lo, hi = trace.window(events)
    (dev,) = trace.devices(events)
    step = [(s, s + d) for w, n, s, d in data["modules"]
            if w == dev and "block_step" in n]
    assert len(step) == 1 and len(data["modules"]) > 1
    (a0, b0), ops = step[0], list(trace._clip(events, dev, lo, hi))
    by_module = [o for o in ops if a0 <= o[1] < b0]
    others = {n for n, a, _ in ops if not a0 <= a < b0}
    assert others & set(hlo.op_names)
    (run,) = scopes.step_ops(events, hlo, lo, hi)[dev]
    assert sorted(run) == sorted(by_module)


def test_recorded_silo_block_coverage(silo_block):
    """Operations with a scope of their own hold at least 90% of the block
    step's device time; with those XLA left without metadata, which take
    their ``op_name`` from around them, at least 99%."""
    _, events, hlo = silo_block
    lo, hi = trace.window(events)
    (dev,) = trace.devices(events)
    split = scopes._split(events, hlo, lo, hi)
    t = split.buckets[dev]
    total = sum(t.values())
    inherited = sum(split.inherited[dev].values())
    assert (total - t["unscoped"] - inherited) / total >= 0.9
    assert (total - t["unscoped"]) / total >= 0.99
    assert all(t[k] > 0 for k in t if k != "unscoped")
