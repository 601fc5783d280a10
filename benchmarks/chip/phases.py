"""Which phase of the batch program each device op of a traced window
belongs to, and how many passes over the slot table the window's bursts
made. Both are read from the program after the run, through
``repro.core.api.RECENT_CALLS``: each ``apply_batch`` call's
``BatchStats`` and the program it ran, whose compiled HLO text names
every instruction's phase.

The program wraps each phase in a ``jax.named_scope`` named
``coremaint.<phase>`` (``core/engine.py``, ``remove.py``, ``insert.py``,
``order.py``); the scope reaches every compiled instruction's
``metadata.op_name``, e.g. ``jit(apply_batch)/while/body/
coremaint.remove.stats/scatter-add``. An op's phase is the last
``coremaint.*`` element of that path. A fusion that carries no scope of
its own takes the first scope found inside its fused computation, and an
instruction with neither (a copy the compiler added) takes the phase of
the loop or branch whose body holds it.

A device op of the trace is matched to an instruction by its name and
its result shape, so an op of another program that happens to share a
name (the harness's snapshot copies) stays unscoped. (Opcodes are not
compared: the trace calls an async slice ``async-start`` where the text
prints ``slice-start``.) A program
without ``RECENT_CALLS`` or without the wave counters gives ``None``
everywhere, and the metrics that read this module fall silent.
"""
from __future__ import annotations

import re
from typing import Optional

from . import trace as tr

SCOPE = "coremaint."
# the phases the per-layer metrics read, in order
PHASES = ("table", "remove.stats", "promote.seed", "promote.forward",
          "promote.evict", "promote.stats", "labels")
# passes over the slot table: one per removal round, forward wave, evict
# wave, and promotion round (its closing statistics pass)
PASS_PHASES = ("remove.stats", "promote.forward", "promote.evict",
               "promote.stats")

_OP = re.compile(r"^%(\S+) = (.*?) [a-z][a-z0-9-]*\(")
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%([\w.-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def phase_of(op_name: str) -> Optional[str]:
    """The last ``coremaint.*`` element of an ``op_name`` path, without
    the prefix; ``None`` where there is none."""
    found = None
    for part in op_name.split("/"):
        if part.startswith(SCOPE):
            found = part[len(SCOPE):]
    return found


def _shape(text: str) -> str:
    return _LAYOUT.sub("", text).replace(" ", "")


def phase_map(hlo_text: str) -> dict:
    """``{instruction name: (result shape, opcode, phase or None)}`` for
    every instruction of one module's HLO text."""
    comps: dict = {}  # computation -> [(name, shape, opcode, phase, callees)]
    entry = cur = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            if line.startswith("ENTRY"):
                entry = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        op_name = _OP_NAME.search(line)
        callees = _CALLED.findall(line)
        for group in _BRANCHES.findall(line):
            callees += [b.strip().lstrip("%") for b in group.split(",")]
        cur.append((m.group(1), _shape(m.group(2)), m.group(3),
                    phase_of(op_name.group(1)) if op_name else None,
                    callees))

    def inner(comp, seen=()):
        # the first scope inside a fused computation (or its callees)
        rows = comps.get(comp, ())
        for row in rows:
            if row[3]:
                return row[3]
        for row in rows:
            for c in row[4]:
                if c not in seen:
                    ph = inner(c, seen + (comp,))
                    if ph:
                        return ph
        return None

    out = {}
    # top-down from the entry: a body inherits its caller's phase
    todo, done = [(entry, None)], set()
    while todo:
        comp, outer = todo.pop()
        if comp in done or comp not in comps:
            continue
        done.add(comp)
        for name, shape, opcode, ph, callees in comps[comp]:
            if ph is None and opcode == "fusion":
                ph = next(filter(None, (inner(c) for c in callees)), None)
            ph = ph or outer
            out[name] = (shape, opcode, ph)
            if opcode != "fusion":
                todo.extend((c, ph) for c in callees)
    return out


def op_phase(maps: list, op: tr.Op) -> Optional[str]:
    """The phase of one trace op under the first of ``maps`` that holds
    an instruction of its name and result shape; ``None`` where none
    does."""
    m = _OP.match(op.name)
    if m is None:
        return None
    for phases in maps:
        hit = phases.get(m.group(1))
        if hit is not None and hit[0] == _shape(m.group(2)):
            return hit[2]
    return None


def window_calls(run) -> Optional[list]:
    """The ``apply_batch`` calls of the window's bursts, oldest first:
    the last ``len(run.bursts)`` calls the program recorded, where their
    counts agree with the bursts'. ``None`` where the program keeps no
    record of its calls or the record does not match."""
    try:
        from repro.core.api import RECENT_CALLS
    except ImportError:
        return None
    n = len(run.bursts)
    calls = list(RECENT_CALLS)[-n:] if n else []
    if n == 0 or len(calls) < n:
        return None
    for b, c in zip(run.bursts, calls):
        s = c.stats
        if (int(s.n_removed), int(s.n_inserted), int(s.remove_rounds),
                int(s.insert_rounds)) != (b.removed, b.inserted,
                                          b.remove_rounds, b.insert_rounds):
            return None
    return calls


def passes(run) -> Optional[list]:
    """Passes over the slot table per burst of the window:
    ``remove_rounds + insert_rounds + forward_waves + evict_waves``;
    ``None`` where the program does not count its waves."""
    calls = window_calls(run)
    if calls is None or not all(hasattr(c.stats, "forward_waves")
                                for c in calls):
        return None
    return [int(c.stats.remove_rounds) + int(c.stats.insert_rounds)
            + int(c.stats.forward_waves) + int(c.stats.evict_waves)
            for c in calls]


# (run, its phase_seconds) of the last run read: eight readers share it
_last: list = [None, None]


def phase_seconds(run) -> Optional[dict]:
    """Device self seconds in the traced window per phase (``None``
    collects the ops of no phase), averaged over the devices that ran
    any; ``None`` where the run was not traced, the program recorded no
    calls, or no op of the window maps to a phase. Computed once per
    run: the programs' texts are compiled or fetched from JAX's cache."""
    if run.trace is None:
        return None
    if _last[0] is run:
        return _last[1]
    texts = []
    for c in window_calls(run) or ():
        text = c.compiled_text()
        if text is not None and text not in texts:
            texts.append(text)
    maps = [phase_map(t) for t in texts]
    out = None
    if maps:
        out = tr.op_seconds(run.trace, key=lambda op: op_phase(maps, op))
        if not any(k is not None for k in out):
            out = None
    _last[:] = [run, out]
    return out


def share_pct(run, phases) -> Optional[float]:
    """Percent of the traced window's device self time in ``phases``;
    ``None`` where ``phase_seconds`` is."""
    per = phase_seconds(run)
    if per is None:
        return None
    total = sum(per.values())
    return 100.0 * sum(per.get(p, 0.0) for p in phases) / total
