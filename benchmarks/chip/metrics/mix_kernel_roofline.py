"""The combination's least time on this chip over the kernel's device time,
in percent.  Least time: the larger of the (K, M) stack read and written
once at its dtype (plus A) over HBM bandwidth, and 2 K^2 M FLOP over the
bf16 peak; M is the parameters of one agent."""
import importlib.util
from pathlib import Path

from benchmarks.chip import counts

_spec = importlib.util.spec_from_file_location(
    "bench_metric_mix_kernel_ms", Path(__file__).with_name("mix_kernel_ms.py"))
_kernel = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_kernel)


def read(ctx):
    s = _kernel.kernel_s_per_block(ctx)
    if s is None:
        return None
    work = counts.mix_work(ctx.traffic["agents"], ctx.agent_params,
                           ctx.itemsize)
    mem = work["bytes"] / ctx.peaks["hbm_bytes_per_s"]
    flop = work["flops"] / ctx.peaks["bf16_flops"]
    ctx.note(f"mix_kernel_roofline bound by "
             f"{'HBM bytes' if mem >= flop else 'FLOP'}: least "
             f"{1e3 * max(mem, flop):.4f} ms, kernel {1e3 * s:.4f} ms")
    return 100.0 * max(mem, flop) / s
