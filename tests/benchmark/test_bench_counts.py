"""The benchmark's FLOP and byte counts against the program's own counts.

The model FLOP of a token is the configuration's family module's
(``layouts/<reference>.py``); every configuration ``BENCHMARK.json`` lists
is checked against its repository arch."""
import json
import types

import pytest

from benchmarks.chip import counts, model
from benchmarks.chip.harness import BENCH, ROOT, _load_module
from benchmarks.roofline import analytic_flops
from repro.configs import INPUT_SHAPES, get_config

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
FILES = {c["name"]: model.load_json(ROOT / c["file"])
         for c in MANIFEST["configs"]}


def _layout(reference="dense_lm"):
    return _load_module(BENCH / "layouts" / f"{reference}.py",
                        "t_counts_" + reference)


@pytest.mark.parametrize("name", sorted(FILES),
                         ids=lambda n: FILES[n]["repository"])
def test_param_count_matches_model_config(name):
    f = FILES[name]
    cfg = get_config(f["repository"]).model
    assert _layout(f["reference"]).param_count(cfg) == cfg.total_params()


def test_smollm_360m_has_its_published_size():
    dense = _layout()
    cfg = get_config("smollm-360m").model
    assert abs(dense.param_count(cfg) - 361.8e6) < 0.1e6
    # tied: the embedding is the output projection, a matmul
    assert dense.matmul_params(cfg) == dense.param_count(cfg)


def test_untied_embedding_is_no_matmul():
    dense = _layout()
    cfg = model.model_config(model.load_json(
        BENCH / "configs" / "chatglm3-6b-d4v4.json"))
    lookup = cfg.vocab_size * cfg.d_model
    assert dense.matmul_params(cfg) == dense.param_count(cfg) - lookup
    # one 4-layer stage of 203.9 M a layer and two quarter vocabularies
    assert abs(dense.param_count(cfg) - 948.9e6) < 0.5e6


def test_train_flops_follow_roofline_arithmetic():
    """smollm is tied, so its matmul parameters are roofline's N."""
    bundle = get_config("smollm-360m")
    shape = INPUT_SHAPES["train_4k"]
    T = bundle.parallel.local_steps
    tokens = shape.global_batch * shape.seq_len
    want = analytic_flops("smollm-360m", "train_4k")["analytic_flops"]
    got = T * tokens * _layout().flops_per_token(bundle.model, shape.seq_len)
    assert got == pytest.approx(want, rel=1e-12)


def test_block_tokens_count_configured_participation():
    tr = {"agents": 4, "participation": 0.9, "local_steps": 4, "batch": 1,
          "seq": 2048}
    assert counts.block_tokens(tr) == pytest.approx(0.9 * 4 * 4 * 2048)
    cfg = get_config("smollm-360m").model
    # 2.55 GFLOP a token: 2.17 G of 6N and 0.38 G of causal attention
    assert _layout().flops_per_token(cfg, tr["seq"]) == pytest.approx(
        2.548e9, rel=1e-3)


#: model FLOP of one block of each cell, as its recorded runs' ``step_mfu``
#: counted it: block tokens x 6 per matmul parameter plus causal attention
BLOCK_FLOPS = {
    "smollm360m.silo_t4": 75144747810816.0,
    "chatglm3_6b.silo_t4": 81035724954009.61,
    "smollm360m.atc_t1": 4174708211712.0,
}


@pytest.mark.parametrize("cell", sorted(BLOCK_FLOPS))
def test_step_mfu_counts_each_cells_model_flop(cell):
    """``step_mfu`` takes the count from the cell's family module."""
    from benchmarks.chip import harness
    c = harness.load_cell(cell)
    ctx = types.SimpleNamespace(
        traffic=c.traffic, cfg=model.model_config(c.config),
        layout=harness.load_layout(c), block_s=2.5, chips=1,
        peaks={"bf16_flops": 197e12})
    read = _load_module(BENCH / "metrics" / "step_mfu.py", "t_mfu").read
    assert read(ctx) == 100.0 * BLOCK_FLOPS[cell] / (2.5 * 1 * 197e12)


def test_mix_work_reads_and_writes_the_stack_once():
    w = counts.mix_work(4, 1000, 2)
    assert w["bytes"] == 2 * 4 * 1000 * 2 + 4 * 4 * 4
    assert w["flops"] == 2 * 16 * 1000
