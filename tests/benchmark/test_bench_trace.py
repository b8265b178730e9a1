"""The reduction from trace events to device metrics."""
import json
import types

import pytest

from benchmarks.chip import trace

D0, D1, H = "/device:TPU:0", "/device:TPU:1", trace.HOST


def _synthetic():
    ms = 1_000_000
    return [
        (H, "bench.block", 0, 10 * ms), (H, "bench.block", 10 * ms, 10 * ms),
        (H, "bench.data", 0, 1 * ms), (H, "bench.dispatch", 1 * ms, 1 * ms),
        (H, "bench.wait", 2 * ms, 8 * ms),
        (H, "bench.data", 10 * ms, 3 * ms),
        (D0, "fusion.1", 1 * ms, 4 * ms), (D0, "fusion.2", 3 * ms, 4 * ms),
        (D0, "while.3", 1 * ms, 8 * ms),
        (D0, "_mix_kernel", 8 * ms, 1 * ms),
        (D0, "_mix_kernel", 14 * ms, 2 * ms),
        (D0, "collective-permute-start.4", 16 * ms, 1 * ms),
        (D1, "fusion.1", 2 * ms, 1 * ms),
        (D0, "fusion.9", 25 * ms, 1 * ms),   # after the window
    ]


def test_window_busy_and_idle():
    ev = _synthetic()
    lo, hi = trace.window(ev)
    assert (lo, hi) == (0, 20_000_000)
    assert trace.devices(ev) == [D0, D1]
    # union on D0: [1, 9] + [14, 17] ms; the while op overlaps its body
    assert trace.busy_ns(ev, D0, lo, hi) == 11_000_000
    assert trace.busy_ns(ev, D1, lo, hi) == 1_000_000


def test_kernel_time_by_name():
    ev = _synthetic()
    lo, hi = trace.window(ev)
    assert trace.kernel_ns(ev, D0, lo, hi, r"mix_kernel") == 3_000_000
    assert trace.kernel_ns(ev, D1, lo, hi, r"mix_kernel") == 0
    assert trace.kernel_ns(ev, D0, lo, hi, r"collective-permute") == 1_000_000


def test_top_ops_skip_wrappers_and_group_instances():
    ev = _synthetic()
    lo, hi = trace.window(ev)
    top = dict(trace.top_ops(ev, D0, lo, hi))
    assert "while" not in top
    assert top["fusion"] == pytest.approx(8e-3)
    assert top["_mix_kernel"] == pytest.approx(3e-3)


def test_idle_gaps_are_named_by_the_host_span():
    ev = _synthetic()
    lo, hi = trace.window(ev)
    gaps = trace.idle_gaps(ev, D0, lo, hi)
    # [9, 14] ms: the middle, 11.5 ms, lies in bench.data of block 2
    assert gaps[0] == ["bench.data", pytest.approx(5e-3)]
    assert sum(g for _, g in gaps) == pytest.approx(9e-3)
    assert ["bench.data", pytest.approx(1e-3)] in gaps   # [0, 1] ms


def _ctx(ev, **kw):
    lo, hi = trace.window(ev)
    n = sum(1 for e in ev if e[0] == H and e[1] == "bench.block")
    base = dict(events=ev, devices=trace.devices(ev), lo=lo, hi=hi,
                n_blocks=n, block_s=(hi - lo) * 1e-9 / n, chips=1,
                peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                note=lambda s: None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_readers_on_the_synthetic_trace():
    from benchmarks.chip import harness
    ev = _synthetic()
    read = {m: harness._load_module(harness.BENCH / "metrics" / f"{m}.py",
                                    "t_" + m.replace(".", "_")).read
            for m in ("device_idle_share.train", "mix_kernel_ms")}
    ctx = _ctx(ev)
    assert read["device_idle_share.train"](ctx) == pytest.approx(45.0)
    assert read["mix_kernel_ms"](ctx) == pytest.approx(1.5)
    none = [e for e in ev if "mix" not in e[1]]
    assert read["mix_kernel_ms"](_ctx(none)) is None


@pytest.fixture(scope="module")
def recorded(fixtures):
    """One block of smollm360m.atc_t1 traced on one TPU v5 lite."""
    data = json.loads((fixtures / "trace_atc_block.json").read_text())
    return [tuple(e) for e in data["events"]]


def test_recorded_trace_busy_share_by_timeline(recorded):
    import numpy as np
    lo, hi = trace.window(recorded)
    (dev,) = trace.devices(recorded)
    us = np.zeros((hi - lo) // 1000 + 1, bool)
    for w, _, s, d in recorded:
        if w == dev:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                us[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    busy = trace.busy_ns(recorded, dev, lo, hi)
    assert busy / (hi - lo) == pytest.approx(us.mean(), abs=0.01)
    assert 0.9 < busy / (hi - lo) <= 1.0


def test_recorded_trace_kernel_and_ops(recorded):
    from benchmarks.chip import harness
    lo, hi = trace.window(recorded)
    (dev,) = trace.devices(recorded)
    mix = [d for w, n, s, d in recorded if w == dev
           and n.startswith("diffusion_mix")]
    assert len(mix) == 1
    reader = harness._load_module(harness.BENCH / "metrics"
                                  / "mix_kernel_ms.py", "t_mix")
    assert reader.read(_ctx(recorded)) == pytest.approx(mix[0] * 1e-6)
    top = trace.top_ops(recorded, dev, lo, hi)
    assert top[0][0] == "diffusion_mix"
    assert not any(name.startswith("while") for name, _ in top)
    gaps = trace.idle_gaps(recorded, dev, lo, hi)
    assert all(name.startswith("bench.") or name == "none"
               for name, _ in gaps)
