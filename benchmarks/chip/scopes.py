"""Attribution of the block step's device time to the layers the program
names.

The program labels the layers of its block step with ``jax.named_scope``
(``sample`` and ``combine`` in ``core/sharded.py``, ``local_update`` and
``local_update/.../apply`` in ``core/diffusion.py``, ``attention`` in
``models/transformer.py``, ``flatten`` and ``unflatten`` in
``core/mixing.py``).  Forward, backward and recompute need no scope: JAX
marks them in the same name stack, ``jvp(`` for the forward pass,
``transpose(`` for the backward one, ``rematted_computation`` for the
forward recomputed under ``jax.checkpoint``.  The name stack reaches the
compiled program as each HLO instruction's ``op_name`` metadata, for
example ``jit(block_step)/local_update/while/body/closed_call/
vmap(transpose(jvp()))/while/body/closed_call/checkpoint/
rematted_computation/attention/...``; a fusion carries its own, and an
instruction XLA made without any takes one from around it
(:func:`parse_hlo`).

:func:`parse_hlo` reads that metadata from the compiled block step's
optimized HLO text (``Compiled.as_text()``).  A profiler trace names each
device operation by its instruction (``fusion.8``), so the trace's
operations are put down to scopes.  Instruction names repeat across
programs, and the window also runs the data generator's program, so only
operations inside the block step's own executions count
(:func:`step_ops`); a metric is their time per execution, one a block.

The readers get the harness's ``ctx``, whose ``step_hlo`` is the scope map
of the block step the run compiled.  :func:`step_split` caches the split
as ``ctx.step_split`` the first time a reader asks.  Where there is no map,
or it names none of these scopes (a program without them), every reader
returns None.  :func:`scope_ms` gives the device time under any scope the
program names, so a reader of a new scope is one line.
"""
from __future__ import annotations

import dataclasses
import re

from benchmarks.chip import trace

__all__ = ["StepHlo", "parse_hlo", "bucket", "BUCKETS", "scope_names",
           "step_ops", "Split", "step_split", "per_block_ms", "scope_ms",
           "SAMPLE", "LOCAL_UPDATE", "APPLY", "ATTENTION", "COMBINE",
           "FLATTEN", "UNFLATTEN", "JVP", "TRANSPOSE", "REMAT"]

#: scope names the program gives (one path component each)
SAMPLE = "sample"
LOCAL_UPDATE = "local_update"
APPLY = "apply"
ATTENTION = "attention"
COMBINE = "combine"
FLATTEN = "flatten"
UNFLATTEN = "unflatten"
#: JAX's own marks, found inside a path component (``vmap(jvp())``)
JVP = "jvp("
TRANSPOSE = "transpose("
#: a path component of its own
REMAT = "rematted_computation"

#: every operation of the block step falls in exactly one bucket
BUCKETS = ("sample", "forward", "recompute", "backward", "update",
           "local_update", "mix_copies", "combine", "unscoped")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")


@dataclasses.dataclass(frozen=True)
class StepHlo:
    """What the attribution needs of one compiled program: each
    instruction's ``op_name``, and the entry computation's instructions in
    their scheduled order.  ``inherited`` names the instructions XLA left
    without metadata (copies and loops its passes made), whose ``op_name``
    is taken from the program around them (:func:`parse_hlo`)."""
    op_names: dict
    entry: tuple
    inherited: frozenset = frozenset()


def parse_hlo(text: str) -> StepHlo:
    """Read a compiled program's optimized HLO text (``as_text()``).

    An instruction without metadata takes the ``op_name`` of the
    instruction that calls its computation (a loop XLA made inside a
    scope), else of its nearest user that has one (a copy or buffer made
    for what consumes it), else of its nearest operand (the program's
    outputs); it stays empty where none has one."""
    own, entry, comp_of, caller = {}, [], {}, {}
    users: dict = {}
    operands: dict = {}
    comp = entry_comp = None
    for line in text.splitlines():
        if not line.startswith((" ", "}")):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            if line.startswith("ENTRY"):
                entry_comp = comp
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = m.group(1)
        rhs = line[m.end():]
        meta = _OP_NAME.search(rhs)
        own[name] = meta.group(1) if meta else ""
        comp_of[name] = comp
        if comp == entry_comp:
            entry.append(name)
        callees = set(_CALLS.findall(rhs))
        for group in _BRANCHES.findall(rhs):
            callees.update(_REF.findall(group))
        for c in callees:
            caller.setdefault(c, name)
        refs = _REF.findall(rhs.split(", metadata=", 1)[0])
        operands[name] = [r for r in refs if r not in callees]
        for r in operands[name]:
            users.setdefault(r, []).append(name)
    op_names, inherited = {}, set()
    for name, op in own.items():
        if not op:
            op = (_nearest(name, own, comp_of, caller, users)
                  or _nearest(name, own, comp_of, caller, operands))
            inherited.add(name)
        op_names[name] = op
    return StepHlo(op_names, tuple(entry), frozenset(inherited))


def _nearest(name, own, comp_of, caller, edges) -> str:
    """The ``op_name`` of the nearest instruction, over the caller of each
    instruction's computation first and then ``edges``, that has one."""
    seen, queue = {name}, [name]
    for n in queue:
        if own.get(n):
            return own[n]
        for m in [caller.get(comp_of.get(n))] + edges.get(n, []):
            if m is not None and m not in seen:
                seen.add(m)
                queue.append(m)
    return ""


def bucket(op_name: str) -> str:
    """The one bucket of :data:`BUCKETS` an ``op_name`` falls in."""
    parts = op_name.split("/")
    if LOCAL_UPDATE in parts:
        rest = parts[parts.index(LOCAL_UPDATE) + 1:]
        if APPLY in rest:
            return "update"
        if REMAT in rest:
            return "recompute"
        if any(TRANSPOSE in p for p in rest):
            return "backward"
        if any(JVP in p for p in rest):
            return "forward"
        return "local_update"
    if COMBINE in parts:
        rest = parts[parts.index(COMBINE) + 1:]
        return ("mix_copies" if FLATTEN in rest or UNFLATTEN in rest
                else "combine")
    if SAMPLE in parts:
        return "sample"
    return "unscoped"


def scope_names(op_name: str) -> set:
    """The scopes an ``op_name`` lies under: each component of its path."""
    return {p for p in op_name.split("/") if p}


def step_ops(events, hlo: StepHlo, lo: int, hi: int) -> dict:
    """``{device: [[(instruction, start, end), ...], ...]}``: each of the
    block step's executions in ``[lo, hi]``, as the operations it ran.

    Found from the program alone: an execution starts with the first of
    the entry computation's scheduled instructions that the trace shows.
    After it each entry instruction runs once, in schedule order, and every
    other instruction inside the loop (or call) of the entry computation
    that runs it; the first operation that breaks this ends the execution,
    which counts if it reached the last entry instruction the trace shows.
    So another program's operation that shares a name with one of the
    step's (the data generator's ``fusion``, which runs between two
    executions) is never counted."""
    rank = {name: i for i, name in enumerate(hlo.entry)}
    out = {}
    for dev in trace.devices(events):
        ops = sorted((a, a - b, name, b)
                     for name, a, b in trace._clip(events, dev, lo, hi))
        shown = [rank[o[2]] for o in ops if o[2] in rank]
        done = []
        if shown:
            first, last = min(shown), max(shown)
            run = None
            for a, _, name, b in ops:
                k = rank.get(name)
                if k == first:
                    run, r, until = [], -1, -1
                if run is None:
                    continue
                if k is not None and k > r:
                    r = k
                    if trace._WRAPPERS.match(name):
                        until = max(until, b)
                elif k is not None or name not in hlo.op_names or a >= until:
                    run = None
                    continue
                run.append((name, a, b))
                if k == last:
                    done.append(run)
        out[dev] = done
    return out


@dataclasses.dataclass
class Split:
    """Device time (ns) of the block step on each device: by bucket, under
    each named scope (``scopes``: every component of the operations'
    ``op_name``, so one operation counts under each scope around it), and
    by operation family for the unscoped operations and for those whose
    ``op_name`` is inherited; with the number of the step's executions it
    sums over."""
    buckets: dict
    scopes: dict
    unscoped: dict
    inherited: dict
    runs: dict


def _split(events, hlo: StepHlo, lo: int, hi: int) -> Split:
    split = Split({}, {}, {}, {}, {})
    for dev, runs in step_ops(events, hlo, lo, hi).items():
        per_op: dict = {}
        for name, a, b in (op for run in runs for op in run):
            if not trace._WRAPPERS.match(name):
                per_op[name] = per_op.get(name, 0) + (b - a)
        t = dict.fromkeys(BUCKETS, 0)
        under, unscoped, inherited = {}, {}, {}
        for name, ns in per_op.items():
            op = hlo.op_names[name]
            k = bucket(op)
            t[k] += ns
            for scope in scope_names(op):
                under[scope] = under.get(scope, 0) + ns
            for fam, hit in ((unscoped, k == "unscoped"),
                             (inherited, name in hlo.inherited)):
                if hit:
                    f = trace._family(name)
                    fam[f] = fam.get(f, 0) + ns
        split.buckets[dev], split.scopes[dev] = t, under
        split.unscoped[dev], split.inherited[dev] = unscoped, inherited
        split.runs[dev] = len(runs)
    return split


def _note(ctx, split: Split, dev: str) -> None:
    t = split.buckets[dev]
    total = sum(t.values())
    if not total:
        return

    def pct(ns):
        return f"{100.0 * ns / total:.2f}%"

    def top(fam):
        best = sorted(fam.items(), key=lambda kv: -kv[1])[:3]
        return ", ".join(f"{k} {pct(v)}" for k, v in best) or "none"

    shares = ", ".join(f"{k} {pct(v)}" for k, v in t.items())
    ctx.note(f"block step split on {dev}: {split.runs[dev]} executions, "
             f"{1e-6 * total / split.runs[dev]:.3f} ms each; {shares}; "
             f"attention {pct(split.scopes[dev].get(ATTENTION, 0))} "
             f"(overlaps); scoped {pct(total - t['unscoped'])}; largest "
             f"unscoped families: {top(split.unscoped[dev])}; without "
             f"metadata of their own "
             f"{pct(sum(split.inherited[dev].values()))}, largest: "
             f"{top(split.inherited[dev])}")


def step_split(ctx) -> Split | None:
    """The block step's split of ``ctx``'s trace, computed once per
    ``ctx``; None where there is no scope map, or it names no scope."""
    if not hasattr(ctx, "step_split"):
        hlo = getattr(ctx, "step_hlo", None)
        split = None
        if hlo is not None and any(bucket(op) != "unscoped"
                                   for op in hlo.op_names.values()):
            split = _split(ctx.events, hlo, ctx.lo, ctx.hi)
            busiest = max(split.buckets,
                          key=lambda d: sum(split.buckets[d].values()))
            _note(ctx, split, busiest)
        ctx.step_split = split
    return ctx.step_split


def per_block_ms(ctx, of) -> float | None:
    """``of(split, device)`` ns per execution of the block step (one a
    block) in ms, on the device with the most; None where there is no
    split or nothing was counted."""
    split = step_split(ctx)
    if split is None:
        return None
    ms = max((1e-6 * of(split, d) / split.runs[d] for d in split.runs
              if split.runs[d]), default=0.0)
    return ms or None


def scope_ms(ctx, name: str) -> float | None:
    """Device time per block under the scope ``name``, in ms, on the device
    with the most; None as :func:`per_block_ms`."""
    return per_block_ms(ctx, lambda s, d: s.scopes[d].get(name, 0))
