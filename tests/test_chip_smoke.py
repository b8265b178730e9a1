"""``chip_smoke.py`` at smoke size on the CPU.

The script's phases (train, mixer check, consensus, serve) run here on the
2-layer architecture of the same family, with the Pallas mixer in interpret
mode; the script itself must refuse to run without a TPU, and must fail
when it is copied out of the repository; its four-chip path runs in a
subprocess on four forced host devices.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses resolve through it
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop(spec.name, None)


@pytest.fixture(scope="module")
def spec(cs):
    return cs.smoke_spec(smoke=True, seq=32)


@pytest.fixture(scope="module")
def trained(cs, spec):
    return cs.train(spec)


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_train_phase_checks_every_block(cs, spec, trained):
    K = spec.run.num_agents
    assert len(trained.losses) == len(trained.actives) == spec.run.blocks
    assert all(np.isfinite(l).all() and l.shape == (K,)
               for l in trained.losses)
    # the default seed has an agent sit out, so the frozen check bites
    assert trained.frozen == sum(int((a == 0).sum()) for a in trained.actives)
    assert trained.frozen > 0
    assert trained.compile_s > 0 and len(trained.block_s) == spec.run.blocks
    # on the CPU "auto" picks the sparse mixer: nothing native to find
    assert trained.engine.pipeline.mixer.name == "sparse"
    assert "tpu_custom_call" not in trained.hlo


def test_train_phase_rejects_a_moving_inactive_agent(cs, spec, monkeypatch):
    """An engine that moves an agent that sat out fails the phase."""
    real = cs.build

    def leaky_build(*args, **kwargs):
        eng = real(*args, **kwargs)
        step = eng.step

        def nudged(state, batch, key):
            state, metrics = step(state, batch, key)
            params = jax.tree.map(lambda p: p + 1e-3, state.params)
            return state.replace(params=params), metrics
        eng.step = nudged
        return eng

    monkeypatch.setattr(cs, "build", leaky_build)
    with pytest.raises(cs.SmokeFailure, match="inactive agent"):
        cs.train(spec)


def _check_inputs(cs, trained):
    stack = cs.flatten_f32(trained.state.params, multiple=512)
    A = jnp.asarray(trained.engine.graph.base_matrix(), jnp.float32)
    active = jnp.asarray(trained.actives[0], jnp.float32)
    return stack, active, A


def test_mixer_check_agrees_on_the_trained_stack(cs, trained):
    stack, active, A = _check_inputs(cs, trained)
    assert stack.dtype == jnp.float32 and stack.shape[1] % 512 == 0
    assert not bool(active.all())
    assert cs.mixer_check(stack, active, A) <= cs.MIXER_RTOL


def test_mixer_check_sees_a_wrong_mask(cs, trained, monkeypatch):
    """The check is not vacuous: a mixer that ignores the mask fails it."""
    stack, active, A = _check_inputs(cs, trained)
    real = cs.PallasFusedMixer

    class MaskBlind(real):
        def __call__(self, params, active, A_t):
            return super().__call__(params, jnp.ones_like(active), A_t)

    monkeypatch.setattr(cs, "PallasFusedMixer", MaskBlind)
    assert cs.mixer_check(stack, active, A) > 1e-3


def test_serve_phase_from_the_consensus(cs, spec, trained):
    params = cs.consensus(spec, trained.state.params)
    cfg = trained.engine.model.cfg
    stacked = jax.tree.leaves(trained.state.params)
    consensus = jax.tree.leaves(params)
    assert [l.shape for l in consensus] == [l.shape[1:] for l in stacked]
    np.testing.assert_allclose(
        np.asarray(consensus[0], np.float32),
        np.asarray(stacked[0], np.float32).mean(axis=0), rtol=2e-2,
        atol=1e-3)
    done = cs.serve(cfg, params, prompt_lens=(16, 24), new_tokens=8)
    assert sorted(c.uid for c in done) == [0, 1, 2, 3]
    assert sorted(len(c.prompt) for c in done) == [16, 16, 24, 24]
    assert all(len(c.tokens) == 8 for c in done)


def test_serve_phase_counts_tokens(cs, spec, trained, monkeypatch):
    """A loop that drops a token fails the serving check."""
    real = cs.ServeLoop

    class Short(real):
        def run(self, **kw):
            return [c.__class__(c.uid, c.prompt, c.tokens[:-1],
                                c.generations[:-1])
                    for c in super().run(**kw)]

    monkeypatch.setattr(cs, "ServeLoop", Short)
    params = cs.consensus(spec, trained.state.params)
    with pytest.raises(cs.SmokeFailure, match="tokens"):
        cs.serve(trained.engine.model.cfg, params, prompt_lens=(16,),
                 new_tokens=4)


def test_script_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, str(SCRIPT)], cwd=ROOT,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_script_fails_outside_the_repository(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    r = subprocess.run([sys.executable, SCRIPT.name], cwd=tmp_path,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_four_chip_path_on_forced_host_devices():
    """The ``--chips 4`` path: one agent per device on a 1-D mesh, a
    collective mixer, and the same run on one device to compare with."""
    prog = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import jax
        import chip_smoke as cs
        assert len(jax.devices()) == 4
        cs.four_chips(cs.smoke_spec(smoke=True, seq=32, blocks=2),
                      jax.devices())
    """)
    r = subprocess.run(
        [sys.executable, "-c", prog], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "mixer sparse" in r.stdout
    assert r.stdout.count("holds") == 4           # one line per device
    diff = float(r.stdout.split("max relative difference ")[1].split()[0])
    assert diff <= 1e-5                           # same mixer on the CPU
