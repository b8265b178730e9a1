"""The declarative experiment surface (repro/api): spec JSON round-trips,
registry error messages, build(spec) parity with the legacy constructor
path (bit-identical, per preset), the shared CLI front end (flag parity
across the three launchers), and the spec-carrying checkpoint round trip."""
import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (EngineState, ExperimentSpec, build, get_preset,
                       preset_names, spec_from_args)
from repro.api.cli import add_spec_args
from repro.api.spec import (CompressionSpec, MixerSpec, ModelSpec,
                            ParticipationSpec, Registry, RunSpec,
                            TopologySpec)
from repro.core import variants
from repro.core.diffusion import DiffusionConfig, DiffusionEngine
from repro.core.schedules import CyclicGroups, MarkovAvailability
from repro.data.synthetic import make_block_sampler, make_regression_problem

K = 6

# every Section-IV preset, parameterized the way test fixtures need it
PRESET_SPECS = {
    "fedavg_full": lambda: variants.fedavg_full(K, T=3, mu=0.02),
    "fedavg_partial_uniform":
        lambda: variants.fedavg_partial_uniform(K, T=2, mu=0.05, q=0.6),
    "vanilla_diffusion": lambda: variants.vanilla_diffusion(K, mu=0.05),
    "asynchronous_diffusion":
        lambda: variants.asynchronous_diffusion(K, mu=0.03, q=0.6),
    "decentralized_fedavg":
        lambda: variants.decentralized_fedavg(K, T=4, mu=0.02),
    "cyclic_fedavg":
        lambda: variants.cyclic_fedavg(K, T=2, mu=0.02, num_groups=3),
    "markov_asynchronous_diffusion":
        lambda: variants.markov_asynchronous_diffusion(K, mu=0.02, q=0.6,
                                                       corr=0.5),
    "link_dropout_diffusion":
        lambda: variants.link_dropout_diffusion(K, mu=0.02, drop=0.3,
                                                corr=0.5, q=0.8),
    "compressed_diffusion":
        lambda: variants.compressed_diffusion(K, mu=0.02, T=2, q=0.8,
                                              compress="topk", ratio=0.5),
    "compressed_fedavg":
        lambda: variants.compressed_fedavg(K, T=2, mu=0.02, q=0.8),
    "byzantine_robust_diffusion":
        lambda: variants.byzantine_robust_diffusion(K, mu=0.02, q=0.9,
                                                    num_byzantine=2,
                                                    scale=3.0),
    "private_diffusion":
        lambda: variants.private_diffusion(K, 0.02, T=1, q=0.8),
    "heterogeneous_diffusion":
        lambda: variants.heterogeneous_diffusion(K, 0.02, T=2, q=0.8),
}


# ---------------------------------------------------------------------------
# spec JSON round trip + registry errors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PRESET_SPECS))
def test_spec_json_roundtrip_per_preset(name):
    spec = PRESET_SPECS[name]()
    assert isinstance(spec, ExperimentSpec)
    text = spec.to_json()
    json.loads(text)                         # valid JSON
    assert ExperimentSpec.from_json(text) == spec
    # and through a plain dict (what external tools would produce)
    assert ExperimentSpec.from_dict(json.loads(text)) == spec


def test_spec_roundtrip_exotic_fields():
    """Tuples (vector q, topology kwargs) and None-able fields survive."""
    spec = ExperimentSpec(
        topology=TopologySpec(kind="erdos", kwargs=(("p", 0.3), ("seed", 5))),
        participation=ParticipationSpec(kind="iid",
                                        q=(0.2, 0.9, 0.5, 1.0)),
        mixer=MixerSpec(kind="trimmed_mean", trim=2),
        compression=CompressionSpec(kind="randk", ratio=0.25, gamma=0.7),
        run=RunSpec(num_agents=4, local_steps=3, step_size=0.01,
                    drift_correction=True))
    back = ExperimentSpec.from_json(spec.to_json())
    assert back == spec
    assert back.participation.q == (0.2, 0.9, 0.5, 1.0)
    assert dict(back.topology.kwargs) == {"p": 0.3, "seed": 5}


def test_unknown_registry_keys_error_messages():
    data = make_regression_problem(K=4, N=20)
    loss = data.loss_fn()
    base = ExperimentSpec(run=RunSpec(num_agents=4))
    cases = [
        (base.replace(mixer=MixerSpec(kind="nope")), "mixer"),
        (base.replace(topology=TopologySpec(kind="hypercube")), "topology"),
        (base.replace(participation=ParticipationSpec(kind="poisson")),
         "participation"),
        (base.replace(compression=CompressionSpec(kind="zip")), "compressor"),
        (base.replace(optimizer=dataclasses.replace(base.optimizer,
                                                    kind="lion")),
         "optimizer"),
        (base.replace(model=ModelSpec(kind="diffusion_unet")), "model"),
    ]
    for spec, registry_kind in cases:
        with pytest.raises(ValueError) as exc:
            build(spec, loss)
        msg = str(exc.value)
        # names the registry, the bad key, and the valid alternatives
        assert registry_kind in msg and "registered" in msg, msg
    with pytest.raises(ValueError, match="registered preset"):
        get_preset("nope")


def test_unknown_spec_json_field_rejected():
    bad = json.loads(ExperimentSpec().to_json())
    bad["mixer"]["tile"] = 256               # typo for tile_m
    with pytest.raises(ValueError, match="tile"):
        ExperimentSpec.from_dict(bad)


def test_registry_duplicate_and_register_decorator():
    reg = Registry("thing")

    @reg.register("a")
    def _a():
        return "a"

    assert reg.get("a") is _a and "a" in reg and reg.names() == ("a",)
    with pytest.raises(ValueError, match="already registered"):
        reg.register("a")(lambda: None)


# ---------------------------------------------------------------------------
# build(spec) bit-identical to the legacy constructor path, per preset
# ---------------------------------------------------------------------------

def _legacy_engine(name, loss):
    """The pre-redesign construction: a hand-built DiffusionConfig (the
    exact field values the old factories returned) + explicit process."""
    if name == "fedavg_full":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=3, step_size=0.02, topology="fedavg",
            participation=1.0), loss)
    if name == "fedavg_partial_uniform":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=2, step_size=0.05, topology="fedavg",
            participation=0.6), loss)
    if name == "vanilla_diffusion":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=1, step_size=0.05, topology="ring",
            participation=1.0), loss)
    if name == "asynchronous_diffusion":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=1, step_size=0.03, topology="ring",
            participation=0.6), loss)
    if name == "decentralized_fedavg":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=4, step_size=0.02, topology="ring",
            participation=1.0), loss)
    if name == "cyclic_fedavg":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=2, step_size=0.02, topology="fedavg",
            participation=1.0 / 3), loss,
            participation=CyclicGroups(K, 3))
    if name == "markov_asynchronous_diffusion":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=1, step_size=0.02, topology="ring",
            participation=0.6), loss,
            participation=MarkovAvailability(0.6, 0.5, num_agents=K))
    if name == "link_dropout_diffusion":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=1, step_size=0.02, topology="ring",
            graph="link_dropout",
            graph_kwargs=(("corr", 0.5), ("drop", 0.3)),
            participation=0.8), loss)
    if name == "compressed_diffusion":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=2, step_size=0.02, topology="ring",
            participation=0.8, compress="topk", compress_ratio=0.5,
            error_feedback=True), loss)
    if name == "compressed_fedavg":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=2, step_size=0.02, topology="fedavg",
            participation=0.8, compress="int8", compress_ratio=1.0,
            error_feedback=True), loss)
    if name == "byzantine_robust_diffusion":
        from repro.core.attacks import make_attack
        from repro.core.mixing import TrimmedMeanMixer
        atk = make_attack("sign_flip", K, num_byzantine=2, scale=3.0)
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=1, step_size=0.02, topology="ring",
            participation=0.9, mix="trimmed_mean"), loss,
            grad_transform=atk.update,
            mixer=TrimmedMeanMixer(K, trim=1, scope="neighborhood"))
    if name == "private_diffusion":
        from repro.core.privacy import compile_privacy
        from repro.optim.optimizers import sgd
        p = compile_privacy(PRESET_SPECS[name]())
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=1, step_size=0.02, topology="ring",
            participation=0.8), loss,
            grad_transform=p.wrap(sgd()).update, privacy=p)
    if name == "heterogeneous_diffusion":
        return DiffusionEngine(DiffusionConfig(
            num_agents=K, local_steps=2, step_size=0.02,
            topology="scale_free", participation=0.8,
            local_steps_mode="degree"), loss)
    raise AssertionError(name)


@pytest.mark.parametrize("name", sorted(PRESET_SPECS))
def test_build_bit_identical_to_legacy_path(name):
    """Acceptance gate: every variants preset through build(spec) +
    engine.step(EngineState, ...) is bit-identical to the pre-redesign
    constructor path over several blocks."""
    data = make_regression_problem(K=K, N=40, M=2, rho=0.1, seed=1)
    spec = PRESET_SPECS[name]()
    eng_new = build(spec, data.loss_fn())
    eng_old = _legacy_engine(name, data.loss_fn())
    assert spec.to_diffusion_config() == eng_old.config

    T = spec.run.local_steps
    sampler = make_block_sampler(data, T=T, batch=1)
    params = jax.random.normal(jax.random.PRNGKey(0), (K, 2))
    key0 = jax.random.fold_in(jax.random.PRNGKey(3), 0x5EED)
    # the private preset's clip+noise transform carries a counter state
    # (build() composes it into eng.optimizer; the legacy ctor receives
    # the identical pre-composed transform)
    opt0 = (eng_new.optimizer.init(params)
            if name == "private_diffusion" else None)
    s_new = eng_new.init_state(params, opt0, key=key0)
    s_old = eng_old.init_state(params, opt0, key=key0)
    for i in range(4):
        batch = sampler(jax.random.PRNGKey(100 + i))
        k = jax.random.PRNGKey(200 + i)
        s_new, m_new = eng_new.step(s_new, batch, k)
        s_old, m_old = eng_old.step(s_old, batch, k)
        np.testing.assert_array_equal(np.asarray(m_new["active"]),
                                      np.asarray(m_old["active"]))
        np.testing.assert_array_equal(np.asarray(s_new.params),
                                      np.asarray(s_old.params))


def test_build_sharded_engine_contract_matches_stacked():
    """build(spec, engine="sharded") exposes the same init_state/step
    surface and agrees with the stacked engine on an rng-free loss."""
    data = make_regression_problem(K=K, N=40, M=2, rho=0.1, seed=2)
    spec = variants.decentralized_fedavg(K, T=2, mu=0.02)
    stacked = build(spec, data.loss_fn(), engine="stacked")
    sharded = build(spec, lambda p, b, rng: data.loss_fn()(p, b),
                    engine="sharded")
    sampler = make_block_sampler(data, T=2, batch=2)
    batch = sampler(jax.random.PRNGKey(7))
    params = jax.random.normal(jax.random.PRNGKey(0), (K, 2))
    key = jax.random.PRNGKey(42)
    s1, m1 = stacked.step(stacked.init_state(params), batch, key)
    s2, m2 = jax.jit(sharded.step)(sharded.init_state(params), batch, key)
    np.testing.assert_array_equal(np.asarray(m1["active"]),
                                  np.asarray(m2["active"]))
    np.testing.assert_allclose(np.asarray(s1.params), np.asarray(s2.params),
                               rtol=1e-5, atol=1e-6)


def test_build_external_model_requires_loss():
    spec = ExperimentSpec(run=RunSpec(num_agents=4))
    with pytest.raises(ValueError, match="loss_fn"):
        build(spec)


def test_build_optimizer_spec_threads_grad_transform():
    from repro.api.spec import OptimizerSpec
    data = make_regression_problem(K=4, N=20)
    spec = ExperimentSpec(run=RunSpec(num_agents=4),
                          optimizer=OptimizerSpec(kind="momentum"))
    eng = build(spec, data.loss_fn())
    assert eng.grad_transform is not None
    params = jnp.zeros((4, 2))
    opt_state = eng.optimizer.init(params)
    state = eng.init_state(params, opt_state)
    sampler = make_block_sampler(data, T=1, batch=1)
    state, _ = eng.step(state, sampler(jax.random.PRNGKey(0)),
                        jax.random.PRNGKey(1))
    assert jax.tree.leaves(state.opt_state)[0].shape == (4, 2)


# ---------------------------------------------------------------------------
# CLI front end: the three launchers share one flag set -> one spec
# ---------------------------------------------------------------------------

def _parser_for(driver: str) -> argparse.ArgumentParser:
    """Replicate each launcher's parser construction (shared front end +
    driver-specific extras), without importing the heavy driver modules."""
    ap = argparse.ArgumentParser(prog=driver)
    add_spec_args(ap)
    if driver == "train":
        ap.add_argument("--checkpoint", default=None)
        ap.add_argument("--log-every", type=int, default=1)
    elif driver == "serve":
        ap.add_argument("--prompt-len", type=int, default=64)
        ap.add_argument("--decode", type=int, default=32)
        ap.add_argument("--temperature", type=float, default=1.0)
        ap.add_argument("--checkpoint", default=None)
        ap.set_defaults(agents=1)
    elif driver == "dryrun":
        ap.add_argument("--shape", default=None)
        ap.add_argument("--mesh", default="single",
                        choices=["single", "multi"])
        ap.add_argument("--arch-default-mix", action="store_true")
        ap.add_argument("--no-tp", action="store_true")
        ap.add_argument("--all", action="store_true")
        ap.add_argument("--out", default="experiments/dryrun")
        ap.add_argument("--save-hlo", default=None)
    return ap


FLAG_SETS = [
    [],
    ["--mix", "pallas", "--compress", "int8", "--error-feedback"],
    ["--agents", "8", "--local-steps", "3", "--step-size", "0.01",
     "--topology", "grid", "--participation", "0.5",
     "--participation-process", "markov", "--markov-corr", "0.7",
     "--compress", "randk", "--compress-ratio", "0.25",
     "--comm-gamma", "0.3", "--optimizer", "momentum",
     "--mix", "sparse", "--arch", "smollm-360m"],
    ["--mix", "trimmed_mean", "--trim", "2"],
    ["--mix", "trimmed_mean", "--robust-scope", "neighborhood",
     "--attack", "sign_flip", "--attack-num", "2", "--attack-scale", "4.0"],
    ["--graph", "link_dropout", "--link-drop", "0.4", "--graph-corr",
     "0.2", "--topology-hops", "2", "--compress", "topk",
     "--comm-gamma", "auto"],
]


@pytest.mark.parametrize("flags", FLAG_SETS,
                         ids=[" ".join(f) or "<defaults>" for f in FLAG_SETS])
def test_cli_flag_parity_across_drivers(flags):
    """The fixed drift: serve takes the same --mix/--compress flags train
    has, and identical flags map to the identical ExperimentSpec in all
    three drivers (serve's --agents default stays 1 — a spec-less serve
    checkpoint means a plain single model — so it is pinned explicitly)."""
    specs = {}
    for driver in ("train", "dryrun", "serve"):
        args = _parser_for(driver).parse_args(
            flags + (["--agents", str(_parser_for("train").parse_args(
                flags).agents)] if driver == "serve" else []))
        specs[driver] = spec_from_args(args)
    assert specs["train"] == specs["dryrun"] == specs["serve"], specs


def test_cli_train_dryrun_defaults_identical():
    """The drifted defaults are gone: bare train and bare dryrun denote the
    same experiment."""
    t = spec_from_args(_parser_for("train").parse_args([]))
    d = spec_from_args(_parser_for("dryrun").parse_args([]))
    assert t == d


def test_cli_spec_file_and_preset(tmp_path):
    spec = variants.compressed_fedavg(8, T=2, mu=0.01, q=0.7)
    path = tmp_path / "exp.json"
    path.write_text(spec.to_json())
    args = _parser_for("train").parse_args(["--spec", str(path)])
    assert spec_from_args(args) == spec

    args = _parser_for("train").parse_args(
        ["--preset", "compressed_fedavg", "--agents", "8",
         "--local-steps", "2", "--step-size", "0.01",
         "--participation", "0.7", "--blocks", "5"])
    got = spec_from_args(args)
    # algorithm structure from the preset...
    assert got.topology.kind == "fedavg" and got.compression.kind == "int8"
    assert got.run.num_agents == 8 and got.run.step_size == 0.01
    assert got.participation.q == 0.7
    # ...driver fields from the flags
    assert got.run.blocks == 5 and got.model.kind == "transformer"
    assert set(preset_names()) == set(PRESET_SPECS)


def test_cli_preset_overlays_explicit_flags_only():
    """An explicitly passed structural flag overrides the preset field;
    a flag left at its default does not (compressed_fedavg keeps int8)."""
    args = _parser_for("train").parse_args(
        ["--preset", "compressed_fedavg", "--agents", "8",
         "--mix", "pallas", "--compress-ratio", "0.5"])
    got = spec_from_args(args)
    assert got.mixer.kind == "pallas"          # explicit: overlaid
    assert got.compression.ratio == 0.5        # explicit: overlaid
    assert got.compression.kind == "int8"      # default flag: preset wins
    assert got.compression.error_feedback      # preset's EF choice kept

    # untouched flags never leak their defaults over the preset
    bare = spec_from_args(_parser_for("train").parse_args(
        ["--preset", "compressed_fedavg", "--agents", "8"]))
    assert bare.mixer.kind == "dense" and bare.compression.kind == "int8"
    assert bare.compression.ratio == 1.0       # factory default, not 0.1


def test_cli_topology_kwargs_reach_the_spec():
    """The fixed drop: --topology-hops/-p/-seed/-rows map onto
    TopologySpec.kwargs (they used to be silently unreachable — only the
    kind was forwarded)."""
    got = spec_from_args(_parser_for("train").parse_args(
        ["--topology", "erdos", "--topology-p", "0.4",
         "--topology-seed", "7"]))
    assert dict(got.topology.kwargs) == {"p": 0.4, "seed": 7}
    got = spec_from_args(_parser_for("train").parse_args(
        ["--topology", "ring", "--topology-hops", "3"]))
    assert dict(got.topology.kwargs) == {"hops": 3}
    # the kwargs genuinely reach make_topology through build()
    data = make_regression_problem(K=8, N=20)
    eng = build(got.replace(model=ModelSpec(kind="external"),
                            run=RunSpec(num_agents=8)), data.loss_fn())
    assert set(eng.topology.neighbor_offsets_ring()) == {-3, -2, -1, 1, 2, 3}
    # ...and overlay a preset without clobbering untouched fields
    overlaid = spec_from_args(_parser_for("train").parse_args(
        ["--preset", "vanilla_diffusion", "--agents", "8",
         "--topology-hops", "2"]))
    assert dict(overlaid.topology.kwargs) == {"hops": 2}
    assert overlaid.topology.kind == "ring"


def test_cli_robust_and_attack_flags_reach_the_spec():
    """--robust-scope/--attack* map onto MixerSpec.scope / AttackSpec and
    overlay presets only when explicitly passed."""
    got = spec_from_args(_parser_for("train").parse_args(
        ["--mix", "median", "--robust-scope", "neighborhood",
         "--attack", "noise", "--attack-num", "3", "--attack-scale", "2.5"]))
    assert got.mixer.kind == "median"
    assert got.mixer.scope == "neighborhood"
    assert got.attack.kind == "noise" and got.attack.num_byzantine == 3
    assert got.attack.scale == 2.5
    # preset overlay: untouched flags keep the preset's robust choices
    base = spec_from_args(_parser_for("train").parse_args(
        ["--preset", "byzantine_robust_diffusion", "--agents", "9"]))
    assert base.mixer.kind == "trimmed_mean"
    assert base.mixer.scope == "neighborhood"
    assert base.attack.kind == "sign_flip"
    over = spec_from_args(_parser_for("train").parse_args(
        ["--preset", "byzantine_robust_diffusion", "--agents", "9",
         "--robust-scope", "global", "--attack", "shift"]))
    assert over.mixer.scope == "global" and over.attack.kind == "shift"


def test_cli_trim_rejected_for_non_robust_mixers():
    """The fixed silent forward: --trim / --robust-scope explicitly passed
    with a non-robust builtin mixer kind now error instead of being stored
    on the spec and ignored."""
    for flags in (["--mix", "dense", "--trim", "2"],
                  ["--trim", "2"],                       # default mix=dense
                  ["--mix", "pallas", "--robust-scope", "neighborhood"],
                  ["--preset", "vanilla_diffusion", "--trim", "2"]):
        with pytest.raises(ValueError, match="robust"):
            spec_from_args(_parser_for("serve").parse_args(flags))
    # robust kinds keep taking them, and defaults never trip the check
    ok = spec_from_args(_parser_for("serve").parse_args(
        ["--mix", "trimmed_mean", "--trim", "2"]))
    assert ok.mixer.trim == 2
    spec_from_args(_parser_for("serve").parse_args([]))
    spec_from_args(_parser_for("serve").parse_args(["--mix", "dense"]))
    # same class on the attack sub-flags: tuning a never-built adversary
    for flags in (["--attack-num", "3"], ["--attack-scale", "5.0"]):
        with pytest.raises(ValueError, match="attack"):
            spec_from_args(_parser_for("train").parse_args(flags))
    got = spec_from_args(_parser_for("train").parse_args(
        ["--attack", "sign_flip", "--attack-num", "3"]))
    assert got.attack.num_byzantine == 3
    # ... and on the graph sub-flags: each belongs to exactly one builtin
    for flags in (["--link-drop", "0.5"],                 # default: static
                  ["--graph", "gossip", "--link-drop", "0.5"],
                  ["--graph", "link_dropout", "--graph-p", "0.4"],
                  ["--graph", "tv_erdos", "--graph-corr", "0.2"]):
        with pytest.raises(ValueError, match="graph"):
            spec_from_args(_parser_for("train").parse_args(flags))
    got = spec_from_args(_parser_for("train").parse_args(
        ["--graph", "link_dropout", "--link-drop", "0.5"]))
    assert got.graph.drop == 0.5


def test_cli_graph_flags_reach_the_spec():
    """--graph/--link-drop/--graph-corr/--graph-p map onto GraphSpec and
    overlay presets only when explicitly passed."""
    got = spec_from_args(_parser_for("train").parse_args(
        ["--graph", "link_dropout", "--link-drop", "0.4",
         "--graph-corr", "0.25"]))
    assert got.graph == variants.GraphSpec(kind="link_dropout", drop=0.4,
                                           corr=0.25)
    # preset overlay: an untouched --graph keeps the preset's choice
    base = spec_from_args(_parser_for("train").parse_args(
        ["--preset", "link_dropout_diffusion", "--agents", "8"]))
    assert base.graph.kind == "link_dropout" and base.graph.drop == 0.3
    over = spec_from_args(_parser_for("train").parse_args(
        ["--preset", "link_dropout_diffusion", "--agents", "8",
         "--link-drop", "0.6"]))
    assert over.graph.drop == 0.6
    # --comm-gamma auto parses to the string (not a float)
    auto = spec_from_args(_parser_for("train").parse_args(
        ["--compress", "topk", "--comm-gamma", "auto"]))
    assert auto.compression.gamma == "auto"


# ---------------------------------------------------------------------------
# checkpoint round trip: EngineState as one object + embedded spec
# ---------------------------------------------------------------------------

def test_checkpoint_engine_state_and_spec_roundtrip(tmp_path):
    """save_experiment stores the FULL EngineState (params + opt + part +
    comm state) as one object with the spec alongside; load_spec + build +
    load_experiment rebuild the exact engine and state."""
    from repro.api.spec import OptimizerSpec
    from repro.checkpoint import load_experiment, load_spec, save_experiment
    data = make_regression_problem(K=K, N=40, M=2, rho=0.1, seed=0)
    spec = variants.compressed_diffusion(
        K, mu=0.02, T=2, q=0.8, compress="topk", ratio=0.5).replace(
        participation=ParticipationSpec(kind="cyclic", q=0.5, num_groups=2),
        optimizer=OptimizerSpec(kind="momentum"))
    eng = build(spec, data.loss_fn())
    params = jax.random.normal(jax.random.PRNGKey(0), (K, 2))
    state = eng.init_state(params, eng.optimizer.init(params),
                           key=jax.random.PRNGKey(1))
    sampler = make_block_sampler(data, T=2, batch=1)
    for i in range(3):
        state, _ = eng.step(state, sampler(jax.random.PRNGKey(10 + i)),
                            jax.random.PRNGKey(i))
    assert state.part_state is not None and state.comm_state is not None

    path = str(tmp_path / "exp_ckpt.npz")
    save_experiment(path, state, spec=spec, step=3,
                    metadata={"note": "roundtrip"})

    spec2 = load_spec(path)
    assert spec2 == spec
    eng2 = build(spec2, data.loss_fn())
    like = eng2.init_state(jnp.zeros_like(params),
                           jax.tree.map(jnp.zeros_like, state.opt_state),
                           key=jax.random.PRNGKey(9))
    restored, meta = load_experiment(path, like)
    assert meta["step"] == 3 and meta["note"] == "roundtrip"
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the restored state drives the rebuilt engine bit-identically
    batch = sampler(jax.random.PRNGKey(99))
    k = jax.random.PRNGKey(7)
    s1, _ = eng.step(state, batch, k)
    s2, _ = eng2.step(restored, batch, k)
    np.testing.assert_array_equal(np.asarray(s1.params),
                                  np.asarray(s2.params))


def test_checkpoint_partial_template_restores_params_only(tmp_path):
    """A params-only template restores just the iterate from a full
    EngineState archive (what serving does)."""
    from repro.checkpoint import load_experiment, load_spec, save_experiment
    data = make_regression_problem(K=4, N=20)
    spec = variants.fedavg_full(4, T=1, mu=0.01)
    eng = build(spec, data.loss_fn())
    params = jax.random.normal(jax.random.PRNGKey(0), (4, 2))
    state = EngineState(params, opt_state={"m": jnp.ones((4, 2))})
    path = str(tmp_path / "ck.npz")
    save_experiment(path, state, spec=spec, step=1)
    restored, _ = load_experiment(path, EngineState(jnp.zeros((4, 2))))
    np.testing.assert_array_equal(np.asarray(restored.params),
                                  np.asarray(params))
    assert restored.opt_state is None
    assert load_spec(path) == spec


def test_plain_checkpoint_has_no_spec(tmp_path):
    from repro.checkpoint import load_spec, save_checkpoint
    path = str(tmp_path / "plain.npz")
    save_checkpoint(path, {"w": jnp.zeros((2, 2))}, step=1)
    assert load_spec(path) is None


# ---------------------------------------------------------------------------
# build(spec, mesh=): the agent mesh and the published-width model bundle
# ---------------------------------------------------------------------------

def _lm_spec(smoke=True, K=4):
    return ExperimentSpec(
        model=ModelSpec(kind="transformer", arch="smollm-360m", smoke=smoke),
        mixer=MixerSpec(kind="auto"),
        run=RunSpec(num_agents=K, local_steps=1, batch=1, seq=16))


def test_build_threads_the_mesh_and_picks_a_collective(monkeypatch):
    from jax.sharding import Mesh

    from repro.core import mixing
    monkeypatch.setattr(mixing.jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices() * 2), ("data",))
    eng = build(_lm_spec(), mesh=mesh)
    assert eng.pipeline.mixer.name == "sparse"
    assert eng.pipeline.mesh is mesh
    assert eng.pipeline.mixer._mesh is mesh
    assert eng.pipeline.mixer._agent_axis == "data"
    assert build(_lm_spec()).pipeline.mixer.name == "pallas"
    with pytest.raises(ValueError, match="mesh"):
        build(_lm_spec(), engine="stacked", mesh=mesh)


def test_published_widths_rematerialize_every_block():
    """The full-width bundle follows its ParallelConfig.remat; the smoke
    bundle keeps every activation."""
    from repro.api.build import MODELS
    from repro.api.build import train_block_struct

    def checkpoints(smoke):
        bundle = MODELS.get("transformer")(
            ModelSpec(kind="transformer", arch="smollm-360m", smoke=smoke))
        params = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
        batch = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape[2:], s.dtype),
            train_block_struct(bundle.cfg, T=1, K=1, batch=1, seq=16))
        jaxpr = jax.make_jaxpr(jax.grad(bundle.loss))(params, batch)
        return "checkpoint" in str(jaxpr) or "remat" in str(jaxpr)

    assert checkpoints(smoke=False)
    assert not checkpoints(smoke=True)
