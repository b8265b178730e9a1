"""The benchmark's FLOP and byte counts against the program's own counts."""
import dataclasses

import pytest

from benchmarks.chip import counts, model
from benchmarks.chip.harness import BENCH
from benchmarks.roofline import analytic_flops
from repro.configs import INPUT_SHAPES, get_config


@pytest.mark.parametrize("arch", ["smollm-360m", "chatglm3-6b"])
def test_param_count_matches_model_config(arch):
    cfg = get_config(arch).model
    assert counts.param_count(cfg) == cfg.total_params()


def test_smollm_360m_has_its_published_size():
    cfg = get_config("smollm-360m").model
    assert abs(counts.param_count(cfg) - 361.8e6) < 0.1e6
    # tied: the embedding is the output projection, a matmul
    assert counts.matmul_params(cfg) == counts.param_count(cfg)


def test_untied_embedding_is_no_matmul():
    cfg = model.model_config(model.load_json(
        BENCH / "configs" / "chatglm3-6b-d4v4.json"))
    lookup = cfg.vocab_size * cfg.d_model
    assert counts.matmul_params(cfg) == counts.param_count(cfg) - lookup
    # one 4-layer stage of 203.9 M a layer and two quarter vocabularies
    assert abs(counts.param_count(cfg) - 948.9e6) < 0.5e6


def test_train_flops_follow_roofline_arithmetic():
    """smollm is tied, so its matmul parameters are roofline's N."""
    bundle = get_config("smollm-360m")
    shape = INPUT_SHAPES["train_4k"]
    T = bundle.parallel.local_steps
    tokens = shape.global_batch * shape.seq_len
    want = analytic_flops("smollm-360m", "train_4k")["analytic_flops"]
    got = T * tokens * counts.train_flops_per_token(bundle.model,
                                                    shape.seq_len)
    assert got == pytest.approx(want, rel=1e-12)


def test_block_tokens_count_configured_participation():
    tr = {"agents": 4, "participation": 0.9, "local_steps": 4, "batch": 1,
          "seq": 2048}
    assert counts.block_tokens(tr) == pytest.approx(0.9 * 4 * 4 * 2048)
    cfg = dataclasses.replace(get_config("smollm-360m").model)
    flops = counts.block_model_flops(cfg, tr)
    # 2.55 GFLOP a token: 2.17 G of 6N and 0.38 G of causal attention
    assert flops / counts.block_tokens(tr) == pytest.approx(2.548e9,
                                                            rel=1e-3)


def test_mix_work_reads_and_writes_the_stack_once():
    w = counts.mix_work(4, 1000, 2)
    assert w["bytes"] == 2 * 4 * 1000 * 2 + 4 * 4 * 4
    assert w["flops"] == 2 * 16 * 1000
