"""The benchmark's configuration files against the repository's configs."""
import dataclasses

import jax
import numpy as np
import pytest

from benchmarks.chip import check, model
from benchmarks.chip.harness import BENCH
from repro.api import build
from repro.api.spec import (ExperimentSpec, MixerSpec, ModelSpec,
                            OptimizerSpec, ParticipationSpec, RunSpec,
                            TopologySpec)
from repro.configs import get_config

# configuration file -> (repository arch, keys of "model" that differ, and
# the published keys "reduced" names for them)
CONFIGS = {
    "smollm-360m": ("smollm-360m", {}),
    "chatglm3-6b-d4v4": ("chatglm3-6b", {"num_layers": "num_layers",
                                         "vocab_size": "padded_vocab_size"}),
}


def _load(name):
    return model.load_json(BENCH / "configs" / f"{name}.json")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_widths_equal_the_repository_config(name):
    arch, changed = CONFIGS[name]
    f = _load(name)
    cfg = model.model_config(f)
    repo = get_config(arch).model
    for field in dataclasses.fields(cfg):
        if field.name == "name":
            continue
        mine, theirs = getattr(cfg, field.name), getattr(repo, field.name)
        if field.name in changed:
            assert mine < theirs, field.name
            assert changed[field.name] in f["reduced"]
        else:
            assert mine == theirs, field.name


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reduced_keys_are_published_keys(name):
    f = _load(name)
    assert f["name"] == name == f["model"]["name"]
    assert set(f["reduced"]) <= set(f["published"])
    assert f["reference"] and (BENCH / "reference"
                               / f"{f['reference']}.py").exists()


def test_chatglm_cut_keeps_a_quarter_of_the_vocabulary():
    f = _load("chatglm3-6b-d4v4")
    assert f["published"]["padded_vocab_size"] == 4 * f["model"]["vocab_size"]
    assert f["published"]["num_layers"] == 7 * f["model"]["num_layers"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_builds_through_the_engine_at_smoke_size(name):
    """The registered model kind drives the program's own build path; the
    widths are cut here only so the CPU can hold a step."""
    f = _load(name)
    cfg = dataclasses.replace(
        model.model_config(f), name=f"{name}-smoke", num_layers=1,
        d_model=64, num_heads=4,
        num_kv_heads=2 if f["model"]["num_kv_heads"] < 4 else 4,
        head_dim=16, d_ff=96, vocab_size=128, dtype="float32")
    model.check_program_layout(cfg)
    kind = model.register_model(cfg)
    spec = ExperimentSpec(
        topology=TopologySpec(kind="ring"),
        participation=ParticipationSpec(kind="iid", q=1.0),
        mixer=MixerSpec(kind="dense"), optimizer=OptimizerSpec(kind="sgd"),
        model=ModelSpec(kind=kind, arch=cfg.name, smoke=False),
        run=RunSpec(num_agents=2, local_steps=1, step_size=0.25, batch=1,
                    seq=8))
    eng = build(spec)
    assert eng.model.cfg is cfg
    key = jax.random.PRNGKey(0)
    w = model.make_weights(key, cfg)
    params = model.to_program(check.broadcast_agents(w, 2), cfg)
    state = eng.init_state(params, None, key=key)
    tr = {"agents": 2, "local_steps": 1, "batch": 1, "seq": 8}
    state, met = jax.jit(eng.step)(state, model.make_block(key, 0, tr, 128),
                                   key)
    moved = check.sq_change(model.from_program(state.params), w)
    assert np.all(np.asarray(moved) > 0)
