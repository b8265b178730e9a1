"""Reduction of a profiler trace to the benchmark's device numbers.

:func:`events_from_xplane` reads the ``.xplane.pb`` the JAX profiler
writes, with ``jax.profiler.ProfileData``, into plain event tuples
``(where, name, start_ns, dur_ns)``: ``where`` is the device plane's name
(``/device:TPU:0``) for operations on the device's "XLA Ops" line, and
``"host"`` for the benchmark's own host spans (names starting ``bench.``).
An operation's name is its HLO instruction's (``fusion.12``), cut from the
instruction text the profiler gives.
Everything below works on such tuples, so a trimmed recorded trace in JSON
tests it.
"""
from __future__ import annotations

import glob
import os
import re

__all__ = ["events_from_xplane", "op_name", "devices", "window", "busy_ns",
           "op_time", "kernel_ns", "top_ops",
           "idle_gaps", "HOST", "SPAN_PREFIX"]

HOST = "host"
SPAN_PREFIX = "bench."
_OPS_LINE = "XLA Ops"
#: ops that only wrap others on the ops line; their time is their body's
_WRAPPERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(text: str) -> str:
    """``%diffusion_mix.1 = bf16[...] custom-call(...)`` ->
    ``diffusion_mix.1``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def events_from_xplane(profile_dir: str) -> list[tuple]:
    """Every device operation and benchmark host span of the one
    ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {profile_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    out.extend((plane.name, op_name(e.name),
                                int(e.start_ns), int(e.duration_ns))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((HOST, e.name, int(e.start_ns),
                            int(e.duration_ns)) for e in line.events
                           if e.name.startswith(SPAN_PREFIX))
    return out


def devices(events) -> list[str]:
    return sorted({e[0] for e in events if e[0] != HOST})


def window(events, span: str = SPAN_PREFIX + "block") -> tuple[int, int]:
    """First start and last end of the host spans named ``span``."""
    spans = [e for e in events if e[0] == HOST and e[1] == span]
    if not spans:
        raise ValueError(f"no host span {span!r} in the trace")
    return (min(e[2] for e in spans), max(e[2] + e[3] for e in spans))


def _clip(events, where, lo, hi):
    for w, name, s, d in events:
        if w == where:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                yield name, a, b


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events, where: str, lo: int, hi: int) -> int:
    """Length of the union of ``where``'s operations inside [lo, hi]."""
    return sum(b - a for a, b in _union(
        (a, b) for _, a, b in _clip(events, where, lo, hi)))


def op_time(events, where: str, lo: int, hi: int, match) -> int:
    """Summed duration of ``where``'s operations whose name ``match``es,
    wrappers excluded."""
    return sum(b - a for name, a, b in _clip(events, where, lo, hi)
               if not _WRAPPERS.match(name) and match(name))


def kernel_ns(events, where, lo, hi, pattern: str) -> int:
    rx = re.compile(pattern)
    return op_time(events, where, lo, hi, lambda n: bool(rx.search(n)))


def _family(name: str) -> str:
    """An operation's name without its instance number and clone marks."""
    return re.sub(r"([.\-](\d+|clone|sunk))+$", "", name)


def top_ops(events, where, lo, hi, n: int = 10) -> list[list]:
    """The ``n`` operation families that took most device time."""
    tot: dict[str, int] = {}
    for name, a, b in _clip(events, where, lo, hi):
        if not _WRAPPERS.match(name):
            tot[_family(name)] = tot.get(_family(name), 0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(events, where, lo, hi, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of [lo, hi] in which ``where`` ran no
    operation, each named by the innermost benchmark host span that covers
    its middle (``"none"`` where none does)."""
    busy = _union((a, b) for _, a, b in _clip(events, where, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [(s, s + d, name) for w, name, s, d in events if w == HOST]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) // 2
        cover = [(e - s, name) for s, e, name in spans if s <= mid < e]
        out.append([min(cover)[1] if cover else "none", (b - a) * 1e-9])
    return out
