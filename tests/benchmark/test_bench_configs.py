"""The benchmark's configuration files against the repository's configs.

Every configuration ``BENCHMARK.json`` lists is checked; each file names
its repository arch (``"repository"``) and maps every ``"model"`` key it
cuts to the published key ``"reduced"`` names for it (``"cut"``)."""
import dataclasses
import hashlib
import json

import jax
import numpy as np
import pytest

from benchmarks.chip import check, model
from benchmarks.chip.harness import BENCH, ROOT, _load_module
from repro.api import build
from repro.api.spec import (ExperimentSpec, MixerSpec, ModelSpec,
                            OptimizerSpec, ParticipationSpec, RunSpec,
                            TopologySpec)
from repro.configs import get_config

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = sorted(c["name"] for c in MANIFEST["configs"])


def _load(name):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    return model.load_json(ROOT / entry["file"])


def _layout(f):
    return _load_module(BENCH / "layouts" / f"{f['reference']}.py",
                        "t_layout_" + f["reference"])


@pytest.mark.parametrize("name", CONFIGS)
def test_widths_equal_the_repository_config(name):
    f = _load(name)
    changed = f["cut"]
    cfg = model.model_config(f)
    repo = get_config(f["repository"]).model
    for field in dataclasses.fields(cfg):
        if field.name == "name":
            continue
        mine, theirs = getattr(cfg, field.name), getattr(repo, field.name)
        if field.name in changed:
            assert mine < theirs, field.name
            assert changed[field.name] in f["reduced"]
        else:
            assert mine == theirs, field.name


@pytest.mark.parametrize("name", CONFIGS)
def test_reduced_keys_are_published_keys(name):
    f = _load(name)
    assert f["name"] == name == f["model"]["name"]
    assert set(f["reduced"]) <= set(f["published"])
    assert set(f["cut"].values()) <= set(f["reduced"])
    assert f["reference"] and (BENCH / "reference"
                               / f"{f['reference']}.py").exists()
    assert (BENCH / "layouts" / f"{f['reference']}.py").exists()


def test_chatglm_cut_keeps_a_quarter_of_the_vocabulary():
    f = _load("chatglm3-6b-d4v4")
    assert f["published"]["padded_vocab_size"] == 4 * f["model"]["vocab_size"]
    assert f["published"]["num_layers"] == 7 * f["model"]["num_layers"]


@pytest.mark.parametrize("name", CONFIGS)
def test_builds_through_the_engine_at_smoke_size(name):
    """The registered model kind drives the program's own build path; the
    widths are cut here, by the family's ``smoke``, only so the CPU can
    hold a step."""
    f = _load(name)
    layout = _layout(f)
    cfg = layout.smoke(model.model_config(f))
    model.check_program_layout(cfg, layout)
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
    w = layout.make_weights(key, cfg)
    params = layout.to_program(check.broadcast_agents(w, 2), cfg)
    state = eng.init_state(params, None, key=key)
    tr = {"agents": 2, "local_steps": 1, "batch": 1, "seq": 8}
    state, met = jax.jit(eng.step)(
        state, model.make_block(key, 0, tr, cfg.vocab_size), key)
    moved = check.sq_change(layout.from_program(state.params, cfg), w)
    assert np.all(np.asarray(moved) > 0)


#: sha256 of the weights drawn from one key at ``smoke`` widths, in float32
#: and in the configuration's dtype, as the cells' recorded runs drew them:
#: the same key, split, draw order, scales and casts give the same bits
WEIGHT_DIGESTS = {
    ("smollm-360m", "float32"):
        "044ce7c9288c5ca5cac44961f2760adf1f437b5e39a435e76f83f6c3f71ef137",
    ("smollm-360m", "bfloat16"):
        "fbbcf18c120f8a5727872b718ee5a61715e9322312e81071f78f07b3ad7fc182",
    ("chatglm3-6b-d4v4", "float32"):
        "bee7333b7a355aa73c53b0a913ae0eaf2dc39ba3e5da6a57d450b1e58cddf678",
    ("chatglm3-6b-d4v4", "bfloat16"):
        "1ce61e026dff4e1bc16149f69bd75c1e3d64a8c18298a0398eb4bda1c16c2685",
}


@pytest.mark.parametrize("name,dtype", sorted(WEIGHT_DIGESTS))
def test_weights_keep_their_bits(name, dtype):
    f = _load(name)
    layout = _layout(f)
    cfg = dataclasses.replace(layout.smoke(model.model_config(f)),
                              dtype=dtype)
    w = layout.make_weights(jax.random.PRNGKey(2**31 + 5), cfg)
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(w)[0]:
        x = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)} {x.dtype} {x.shape}".encode())
        h.update(x.tobytes())
    assert h.hexdigest() == WEIGHT_DIGESTS[name, dtype]
