"""Reduction of a JAX profiler trace to device busy time, per-op time
and idle gaps, for the per-layer metrics and the ``breakdown``.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
planes are named ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
event per executed HLO op, named by the op's HLO text
(``%fusion.499 = pred[1048576]{...} fusion(...), kind=kCustom, ...``).
A ``while`` op's event spans its body's ops, so time per op is self
time: an event's duration less that of the events nested in it. Host
spans (``jax.profiler.TraceAnnotation``) share the trace's clock: the
harness's are named ``bench.*``, and ``bench.window`` bounds the
measured window; the program's are named ``coremaint.*``
(``coremaint.apply_batch`` and its steps ``coremaint.validate``,
``coremaint.transfer``, ``coremaint.dispatch``, ...). The innermost of
either over an idle gap says what the host was doing in it.
"""
from __future__ import annotations

import gzip
import re
from dataclasses import dataclass
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = ("bench.", "coremaint.")
WINDOW_SPAN = "bench.window"
_HLO = re.compile(r"^%(\S+) = (.*?) ([a-z][a-z0-9-]*)\((.*)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"kind=(k\w+)")


@dataclass
class Op:
    name: str          # the op's HLO text
    start_ns: float
    dur_ns: float
    self_ns: float = 0.0

    @property
    def opcode(self) -> str:
        m = _HLO.match(self.name)
        return m.group(3) if m else self.name

    @property
    def kind(self) -> str:
        """A fusion's kind (``kLoop``, ``kCustom``, ...), else ``""``."""
        m = _KIND.search(self.name) if self.opcode == "fusion" else None
        return m.group(1) if m else ""

    @property
    def short(self) -> str:
        """``fusion.499 kCustom pred[1048576] <- (pred[65536], s32[...])``:
        the instruction, its kind or opcode, and its shapes without
        layouts or operand names."""
        m = _HLO.match(self.name)
        if not m:
            return self.name[:120]
        args = _LAYOUT.sub("", m.group(4)).split(")")[0]
        shapes = [a.split(" %")[0].strip() for a in args.split(",")]
        out = _LAYOUT.sub("", m.group(2))
        return (f"{m.group(1)} {self.kind or self.opcode} {out} <- "
                f"({', '.join(x for x in shapes if x)})")[:160]


@dataclass
class Trace:
    ops: dict            # device plane name -> [Op] on its XLA Ops line
    spans: list          # host (name, start_ns, dur_ns), SPAN_PREFIX only

    def window(self):
        """(start_ns, end_ns) of the ``bench.window`` span."""
        for name, t0, dur in self.spans:
            if name == WINDOW_SPAN:
                return t0, t0 + dur
        raise ValueError("the trace holds no bench.window span")

    def window_ops(self) -> dict:
        """Per device, the ops that overlap the window, clipped to it (an
        op cut by an edge of the window keeps the share of its self time
        that lies inside)."""
        lo, hi = self.window()
        out = {}
        for dev, ops in self.ops.items():
            kept = []
            for op in ops:
                a, b = max(op.start_ns, lo), min(op.start_ns + op.dur_ns, hi)
                if b > a:
                    keep = (b - a) / op.dur_ns
                    kept.append(Op(op.name, a, b - a, op.self_ns * keep))
            out[dev] = kept
        return out


def read_xspace(data: bytes) -> Trace:
    """A Trace from serialized XSpace bytes (gzip accepted)."""
    from jax.profiler import ProfileData

    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    prof = ProfileData.from_serialized_xspace(data)
    ops, spans = {}, []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = [Op(ev.name, ev.start_ns, ev.duration_ns)
                       for ev in line.events]
                ops[plane.name] = _with_self_time(evs)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.duration_ns))
    return Trace(ops=ops, spans=spans)


def _with_self_time(ops: list) -> list:
    """``ops`` sorted by start, each with its self time: events on one
    line nest (a loop's body inside the loop), so each event's duration
    is taken off the innermost event that encloses it."""
    ops = sorted(ops, key=lambda op: (op.start_ns, -op.dur_ns))
    stack = []
    for op in ops:
        while stack and stack[-1].start_ns + stack[-1].dur_ns <= op.start_ns:
            stack.pop()
        if stack:
            stack[-1].self_ns -= op.dur_ns
        op.self_ns += op.dur_ns
        stack.append(op)
    return ops


def find_xspace(log_dir: Path) -> bytes:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1].read_bytes()


def busy_intervals(ops: list) -> list:
    """Union of the ops' intervals as sorted, disjoint (start, end)."""
    out = []
    for a, b in sorted((op.start_ns, op.start_ns + op.dur_ns) for op in ops):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which an op ran, averaged over the device
    planes that ran any (0 where none did)."""
    per = [sum(b - a for a, b in busy_intervals(ops)) * 1e-9
           for ops in trace.window_ops().values() if ops]
    return sum(per) / len(per) if per else 0.0


def window_s(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) * 1e-9


def op_seconds(trace: Trace, key=lambda op: op.short) -> dict:
    """Device self seconds in the window per ``key(op)`` (by default the
    op's short name), averaged over the devices that ran any."""
    per = [ops for ops in trace.window_ops().values() if ops]
    tot: dict = {}
    for ops in per:
        for op in ops:
            k = key(op)
            tot[k] = tot.get(k, 0.0) + op.self_ns * 1e-9 / len(per)
    return tot


def share_pct(trace: Trace, pred):
    """Percent of the window's device self time in ops where ``pred(op)``
    holds; ``None`` where no op ran."""
    per = op_seconds(trace, key=pred)
    total = sum(per.values())
    return 100.0 * per.get(True, 0.0) / total if total > 0 else None


def idle_gaps(trace: Trace) -> list:
    """``[(host span, seconds)]`` for every idle stretch of the first
    busy device inside the window, longest first. The host span is the
    innermost ``bench.*`` or ``coremaint.*`` span covering the gap's
    midpoint."""
    lo, hi = trace.window()
    per = [ops for ops in trace.window_ops().values() if ops]
    edges = [lo] + [t for iv in busy_intervals(per[0] if per else [])
                    for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        inner = [(dur, name) for name, t0, dur in trace.spans
                 if t0 <= mid <= t0 + dur]
        out.append((min(inner)[1] if inner else "outside bench spans",
                    (b - a) * 1e-9))
    return sorted(out, key=lambda g: -g[1])


def breakdown(trace: Trace, top: int = 10) -> dict:
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in idle_gaps(trace)[:top]],
    }
