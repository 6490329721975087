"""Device time of each phase of the megaloop round, from the program's own
phase names.

The program puts every device operation of a round under one
``jax.named_scope`` (``repro.obs.profile.PHASES``), which reaches the
compiled module as the ``op_name`` of each instruction's metadata.  A TPU
trace names an operation by its instruction only (``%fusion.12 = ...``,
no metadata), so the map from operation to phase comes from the module's
own text: ``repro.core.controller.dispatched_megaloop_hlo()`` compiles the
megaloop again from the shapes it was dispatched with, and ``phase_table``
reads each instruction's phase from it.  The trace writes each operand
with its shape (``copy(s32[4]{0} %p)``) where the module's text writes the
name alone (``copy(%p)``), so an operation belongs to the megaloop when
its result shape and the names it refers to (operands, called
computations) agree with the instruction's: a same-named instruction of
another module (a ``jit_copy``) is not taken for it.

An operation's self time is its duration minus the union of the
operations nested inside it, so a ``while`` that wraps a scan is not
counted twice; the self times of a chip's operations sum to its busy time.
The reduction is one sort and one pass, for the million operations of a
traced slice of the dense cells.

Where the program has no phase names (a checkout from before them) or the
trace has no device operations, every reading is None.
"""
from __future__ import annotations

import dataclasses
import re
from typing import NamedTuple

import numpy as np


class Instr(NamedTuple):
    """One instruction of a compiled module: its phase (or None) and its
    text as ``%name = ...`` without metadata, the form a device trace names
    the operation by."""
    phase: str | None
    text: str


_PHASE = re.compile(r"(?<![\w.])vp\.[a-z_]+(?![\w.])")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) \(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"(?:body|condition|to_apply)=%([\w.-]+)"
                     r"|branch_computations=\{([^}]*)\}")
_FUSED = re.compile(r"calls=%([\w.-]+)")
_WHILE = re.compile(r"\bwhile\(")


def phase_of(op_name: str) -> str | None:
    """The innermost ``vp.*`` phase on an ``op_name`` path, or None.
    Under ``vmap`` a scope appears as ``vmap(vp.inbox)``."""
    found = _PHASE.findall(op_name)
    return found[-1] if found else None


def strip_metadata(line: str) -> str:
    """An HLO instruction's text without its ``metadata={...}``."""
    i = line.find(", metadata={")
    if i < 0:
        return line
    depth, j = 0, i + len(", metadata=")
    quoted = False
    while j < len(line):
        c = line[j]
        if c == '"' and line[j - 1] != "\\":
            quoted = not quoted
        elif not quoted and c == "{":
            depth += 1
        elif not quoted and c == "}":
            depth -= 1
            if depth == 0:
                return line[:i] + line[j + 1:]
        j += 1
    return line[:i]


def phase_table(hlo_text: str) -> dict:
    """``{instruction: Instr}`` for every instruction of an HLO module's
    text.

    XLA makes some instructions with no ``op_name`` at all: a fusion built
    around a scatter, a fusion inside a widened scan body.  Such an
    instruction takes the phase of the computation it fuses (of its root,
    else the most common phase in it), else of the ``while`` or
    ``conditional`` that runs the computation it sits in."""
    rows, callers, fused = [], {}, {}
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        rows.append((m.group(1), comp, op.group(1) if op else None,
                     strip_metadata(f"%{m.group(1)} = {m.group(2)}"),
                     line.lstrip().startswith("ROOT ")))
        for body, branches in _CALLEE.findall(line):
            for callee in [body] if body else branches.split(","):
                callers.setdefault(callee.strip().lstrip("%"), m.group(1))
        calls = _FUSED.search(line)
        if calls is not None:
            fused[m.group(1)] = calls.group(1)
    own = {row[0]: phase_of(row[2]) if row[2] else None for row in rows}
    where = {row[0]: row[1] for row in rows}
    inside: dict = {}   # computation -> [root phase, phases of the rest]
    for name, comp, _, _, root in rows:
        slot = inside.setdefault(comp, [None, []])
        if root:
            slot[0] = own[name]
        elif own[name]:
            slot[1].append(own[name])

    def of_fused(name):
        root_phase, rest = inside.get(fused.get(name), (None, []))
        return root_phase or (max(set(rest), key=rest.count) if rest
                              else None)

    def of_caller(name, depth=0):
        caller = callers.get(where.get(name))
        if caller is None or depth > 64:
            return None
        return own.get(caller) or of_caller(caller, depth + 1)

    return {name: Instr(own[name] if op else of_fused(name) or of_caller(name),
                        text)
            for name, _, op, text, _ in rows}


@dataclasses.dataclass
class Phases:
    """Seconds of device self time in the window, averaged over the chips.

    ``by_phase[p]``: the megaloop's operations under phase ``p``
    (``by_phase[None]``: under none); ``outside``: every operation that
    matches no instruction of the megaloop.  ``whole[p]``: how many
    ``while`` operations under ``p`` start and end inside the window."""
    by_phase: dict
    outside: float
    whole: dict = dataclasses.field(default_factory=dict)

    def megaloop_s(self) -> float:
        return float(sum(self.by_phase.values()))

    def covered_s(self) -> float:
        return float(sum(t for p, t in self.by_phase.items() if p))


def _hlo_texts():
    """The compiled text of every megaloop the program dispatched, or None
    where the program cannot give it."""
    try:
        from repro.core.controller import dispatched_megaloop_hlo
    except ImportError:
        return None
    return dispatched_megaloop_hlo()


def _table():
    """``{instruction: [Instr, ...]}`` over the dispatched megaloops (one
    entry from each module that has the name), or None."""
    texts = _hlo_texts()
    if not texts:
        return None
    table: dict = {}
    for text in texts:
        for name, entry in phase_table(text).items():
            table.setdefault(name, []).append(entry)
    return table


def self_times(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Each interval's time in which it is the innermost one open: every
    instant goes to the interval that started last among those covering it
    (the shorter at a tie).  Where intervals nest this is an interval's
    length minus the union of those nested in it; the self times always sum
    to the union of all."""
    starts = np.asarray(starts, np.float64)
    ends = np.asarray(ends, np.float64)
    own = np.zeros(len(starts))
    stack: list = []   # open intervals, the last started on top
    t = -np.inf

    def advance(to):
        nonlocal t
        while stack and t < to:
            top = stack[-1]
            if ends[top] > t:
                step = min(ends[top], to)
                own[top] += step - t
                t = step
            if ends[top] <= t:
                stack.pop()
        t = max(t, to)

    for k in np.lexsort((-ends, starts)).tolist():
        advance(starts[k])
        stack.append(k)
    advance(np.inf)
    return own


_REF = re.compile(r"%[\w.-]+")


def _ident(text: str) -> tuple:
    """(instruction, result shape, the names it refers to) of an
    instruction's text as either the trace or the module writes it."""
    head, _, rest = text.partition(" = ")
    refs = tuple(_REF.findall(rest))
    return (head[1:] if head.startswith("%") else head,
            rest.split(" ", 1)[0], refs)


def _agrees(name: str, text: str) -> bool:
    """Whether the trace's ``name`` and the module's ``text`` are one
    instruction: the same result shape and the same names referred to."""
    _, shape, refs = _ident(name)
    _, t_shape, t_refs = _ident(text)
    return t_shape == shape and t_refs[:len(refs)] == refs


def _match(name: str, table: dict, seen: dict):
    """The table's entry for the operation ``name``, or None where the
    operation is not the megaloop's; ``seen`` caches by name, since a trace
    repeats each of a few thousand names many times."""
    if name not in seen:
        seen[name] = next(
            (entry for entry in table.get(_ident(name)[0], ())
             if " = " not in name or _agrees(name, entry.text)), None)
    return seen[name]


def reduce_phases(trace, table: dict) -> Phases:
    """Phases of ``trace`` (a ``trace_reduce.Trace``) under ``table``
    (``_table``: each instruction name's entries of ``phase_table``)."""
    lo, hi = trace.window
    chips = []
    seen: dict = {}
    whole: dict = {}
    for ops in trace.ops:
        kept = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        tot: dict = {}
        outside = 0.0
        if kept:
            own = self_times([max(s, lo) for _, s, _ in kept],
                             [min(e, hi) for _, _, e in kept])
            for (name, s, e), t in zip(kept, own.tolist()):
                entry = _match(name, table, seen)
                if entry is None:
                    outside += t
                    continue
                tot[entry.phase] = tot.get(entry.phase, 0.0) + t
                if (entry.phase and lo <= s and e <= hi
                        and _WHILE.search(entry.text)):
                    whole[entry.phase] = whole.get(entry.phase, 0) + 1
        chips.append((tot, outside))
    n = max(len(chips), 1)
    keys = {k for tot, _ in chips for k in tot}
    return Phases(
        {k: sum(tot.get(k, 0.0) for tot, _ in chips) / n / 1e9
         for k in keys},
        sum(o for _, o in chips) / n / 1e9, whole)


_MEMO: dict = {}


def phases(ctx) -> Phases | None:
    """The phases of ``ctx.trace``, computed once per trace; None where the
    trace has no device operations or no operation of the megaloop (the
    program has no phase names, or the map from trace to module found
    nothing)."""
    tr = ctx.trace
    if not any(tr.ops):
        return None
    hit = _MEMO.get(id(tr))
    if hit is not None and hit[0] is tr:
        return hit[1]
    table = _table()
    found = None if table is None else reduce_phases(tr, table)
    if found is not None and found.megaloop_s() <= 0:
        found = None
    _MEMO.clear()
    _MEMO[id(tr)] = (tr, found)
    return found


def phase_self_s(ctx, phase: str) -> float | None:
    """Seconds of device self time under ``phase`` in the window."""
    ph = phases(ctx)
    return None if ph is None else ph.by_phase.get(phase, 0.0)


def ms_per_round(ctx, phase: str) -> float | None:
    """Device ms under ``phase`` per megaloop round of the window."""
    t = phase_self_s(ctx, phase)
    if t is None or not ctx.rounds:
        return None
    return 1000.0 * t / ctx.rounds


def busy_pct(ctx, phase: str) -> float | None:
    """Share of the device's busy time spent under ``phase``, in %.  For
    the dense cells' short traced slice: None unless the slice holds a
    whole store-log replay (``vp.store_replay``, the round's last loop), so
    a slice that ends inside the first round reads as not measured, not as
    a round without a replay."""
    ph = phases(ctx)
    busy = ctx.trace.busy_s()
    if ph is None or busy <= 0 or not ph.whole.get("vp.store_replay"):
        return None
    return 100.0 * ph.by_phase.get(phase, 0.0) / busy


def coverage_pct(ctx) -> float | None:
    """Share of the device's busy time in the window that falls under some
    phase, in %.  The base is every device operation, the megaloop's and
    the few others (the state copies of controller construction), so an
    operation the map from trace to module misses reads lower here instead
    of as a faster phase.  (The megaloop's own ``while`` cannot bound the
    base: a trace writes an operation when it ends, and a dense cell's
    slice ends inside the loop.)"""
    ph = phases(ctx)
    if ph is None:
        return None
    return 100.0 * ph.covered_s() / (ph.megaloop_s() + ph.outside)
