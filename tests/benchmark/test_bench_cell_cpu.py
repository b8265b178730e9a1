"""A run of a cell end to end on the CPU, at the fixture's size: the look
for a chip is skipped, and faults planted in the timed path must turn
``correct`` false."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmarks.chip import harness

FAULTS = ("frozen_state", "half_batch", "no_exchange")


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch, tmp_path):
    """The harness keeps JAX's cache where this variable says; set after
    JAX is imported, it leaves the cache off for these runs."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def _run(fixtures, fault="none", **kw):
    return harness.run("tiny.t2", 2**33 + 17, 0.0, False,
                       t_start=time.perf_counter(),
                       manifest_path=fixtures / "BENCHMARK.json",
                       bench_dir=fixtures, require_tpu=False, fault=fault,
                       **kw)


def test_sound_run_is_correct(fixtures):
    out = _run(fixtures)
    assert out["correct"], out["checks"]
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fixtures, fault):
    out = _run(fixtures, fault)
    assert not out["correct"], (fault, out["checks"])


def test_no_tpu_no_result(fixtures):
    with pytest.raises(harness.NoChip):
        harness.run("tiny.t2", 1, 0.0, False, t_start=0.0,
                    manifest_path=fixtures / "BENCHMARK.json",
                    bench_dir=fixtures)


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/cell.py", "--workload",
         "smollm360m.atc_t1", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_command_without_a_chip_exits_nonzero_with_no_result():
    p = _cli(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_without_the_program_exits_nonzero(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's
    paths has no system under test."""
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(harness.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_control_is_not_correct(fixtures):
    """The reference computed in fp8 in the program's place fails a limit
    that the sound program meets."""
    out = _run(fixtures, control=True)
    assert out["correct"]
    assert not out["control"]["correct"]
    assert any(c["value"] > c["limit"]
               for c in out["control"]["checks"].values())
