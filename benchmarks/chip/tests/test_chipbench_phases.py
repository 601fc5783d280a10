"""The split of device time by batch-program phase and the passes per
burst: the HLO-text reader on hand-written and compiled modules, the
op-to-phase attribution and the metric readers on hand-made runs, and
all of it on a short trace recorded on a TPU v5e with the scoped
program."""
import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

from benchmarks.chip import phases as ph  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402
from benchmarks.chip.harness import Bench, Burst, RunData  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
# ba16.burst25k, seed 2147483801, a one-burst window (--seconds 1) on a
# TPU v5 lite: the profile (which kept the module's HloProto), that
# module's HLO text as XLA prints it, the burst's BatchStats as the run
# logged them, and the result line the run printed
RECORDED = DATA / "ba16_burst25k_phases.xplane.pb.gz"
RECORDED_HLO = DATA / "ba16_burst25k_phases.hlo.txt.gz"
RECORDED_STATS = DATA / "ba16_burst25k_phases.stats.json"
RECORDED_LINE = DATA / "ba16_burst25k_phases.line.json"
DEV = "/device:TPU:0"
SHARES = {"phase.table_pct": ("table",),
          "phase.remove_pct": ("remove.stats",),
          "phase.forward_pct": ("promote.forward",),
          "phase.evict_pct": ("promote.evict",),
          "phase.promote_pct": ("promote.seed", "promote.stats"),
          "phase.labels_pct": ("labels",)}
NEW = tuple(SHARES) + ("program.passes_per_burst", "program.ms_per_pass")

HAND_HLO = """\
HloModule jit_f, is_scheduled=true

%fc (p.0: s32[8]) -> s32[8] {
  %p.0 = s32[8]{0} parameter(0)
  ROOT %x = s32[8]{0} add(%p.0, %p.0), metadata={op_name="jit(f)/coremaint.table/add"}
}

%b (t.0: (s32[8])) -> (s32[8]) {
  %t.0 = (s32[8]{0}) parameter(0)
  ROOT %copy.1 = (s32[8]{0}) copy(%t.0)
}

%c (t.1: (s32[8])) -> pred[] {
  %t.1 = (s32[8]{0}) parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

ENTRY %main (p: s32[8]) -> (s32[8]) {
  %p = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0:T(1024)} fusion(%p), kind=kLoop, calls=%fc
  %tuple.1 = (s32[8]{0}) tuple(%fusion.1)
  ROOT %while.1 = (s32[8]{0}) while(%tuple.1), condition=%c, body=%b, metadata={op_name="jit(f)/coremaint.promote.seed/while/body/coremaint.labels/while"}
}
"""


def test_phase_of_takes_the_innermost_scope():
    assert ph.phase_of("jit(apply_batch)/coremaint.promote.seed/while/"
                       "body/coremaint.promote.forward/scatter-add") == \
        "promote.forward"
    assert ph.phase_of("jit(apply_batch)/while/body/add") is None
    assert ph.phase_of("") is None


def test_phase_map_reads_hand_written_hlo():
    phases = {k: v[2] for k, v in ph.phase_map(HAND_HLO).items()}
    # a fusion without a scope takes its fused computation's; a copy in
    # a loop body takes the loop's innermost scope; the entry's
    # parameter none; the fused add runs inside its fusion and the
    # condition's constant inherits the loop's phase
    assert phases == {"p": None, "fusion.1": "table", "tuple.1": None,
                      "while.1": "labels", "t.0": "labels",
                      "copy.1": "labels", "t.1": "labels",
                      "constant.1": "labels"}
    assert ph.phase_map(HAND_HLO)["fusion.1"][:2] == ("s32[8]", "fusion")


def test_phase_map_covers_a_compiled_batch_program():
    import jax.numpy as jnp

    from repro.core.engine import apply_batch

    n, cap, lanes = 40, 128, 8
    z = jnp.zeros
    args = (z(cap, jnp.int32), jnp.ones(cap, jnp.int32), z(cap, bool),
            z(n, jnp.int32), z(n, jnp.int64), jnp.int32(0),
            z(lanes, jnp.int32), jnp.ones(lanes, jnp.int32), z(lanes, bool),
            z(lanes, jnp.int32), jnp.ones(lanes, jnp.int32), z(lanes, bool))
    text = apply_batch.lower(*args, n, n + 2, 64).compile().as_text()
    phases = ph.phase_map(text)
    assert {v[2] for v in phases.values()} - {None} == set(ph.PHASES)
    for ins, (_, opcode, phase) in phases.items():
        if opcode in ("sort", "scatter", "while"):
            assert phase is not None, ins


def _op(name, shape, opcode, start, dur):
    return tr.Op(f"%{name} = {shape} {opcode}(s32[8] %a)", start, dur)


def _hand_made_trace():
    ops = tr._with_self_time([
        _op("fusion.1", "s32[8]{0}", "fusion", 100, 40),
        _op("while.2", "(s32[8])", "while", 140, 300),
        _op("fusion.3", "s32[8]", "fusion", 150, 100),
        _op("sort.4", "s32[8]", "sort", 260, 60),
        # the snapshot program's op of a name the batch program has too
        _op("fusion.1", "s64[8]", "fusion", 500, 20),
        _op("fusion.1", "s32[8]", "fusion", 600, 30),
    ])
    spans = [("bench.window", 50, 700), ("bench.plan", 50, 45),
             ("bench.wait", 95, 400), ("bench.plan", 495, 100)]
    return tr.Trace(ops={DEV: ops}, spans=spans)


HAND_MAP = {"fusion.1": ("s32[8]", "fusion", "table"),
            "while.2": ("(s32[8])", "while", "remove.stats"),
            "fusion.3": ("s32[8]", "fusion", "promote.forward"),
            "sort.4": ("s32[8]", "sort", "labels")}


def test_hand_made_trace_by_phase():
    t = _hand_made_trace()
    per = tr.op_seconds(t, key=lambda op: ph.op_phase([HAND_MAP], op))
    assert per["table"] == pytest.approx(70e-9)      # 40 + 30
    assert per["remove.stats"] == pytest.approx(140e-9)  # 300 - 160
    assert per["promote.forward"] == pytest.approx(100e-9)
    assert per["labels"] == pytest.approx(60e-9)
    assert per[None] == pytest.approx(20e-9)         # the other program
    assert sum(per.values()) == pytest.approx(tr.busy_s(t))


class _Call:
    """A recorded ``apply_batch`` call: its stats and its program's
    text."""

    def __init__(self, text, **stats):
        self.stats = SimpleNamespace(**stats)
        self.text = text

    def compiled_text(self):
        return self.text


def _hand_made_run(monkeypatch, calls, bursts):
    import repro.core.api as api

    monkeypatch.setattr(api, "RECENT_CALLS", calls, raising=False)
    return RunData(setup_s=1.0, window_s=1.0, bursts=bursts,
                   compiles_in_window=0, trace=_hand_made_trace())


def _read(run):
    bench = Bench.load(REPO)
    return {name: bench.reader(name).read(run) for name in NEW}


def _burst(rm_rounds, in_rounds):
    return Burst(0.0, 1.0, 3, 2, removed=3, inserted=2,
                 remove_rounds=rm_rounds, insert_rounds=in_rounds)


def _stats(rm_rounds, in_rounds, fwd, ev):
    return dict(n_removed=3, n_inserted=2, remove_rounds=rm_rounds,
                insert_rounds=in_rounds, forward_waves=fwd, evict_waves=ev)


def test_readers_on_a_hand_made_run(monkeypatch):
    hlo = "ENTRY %main () -> s32[8] {\n" + "".join(
        f'  %{k} = {s} {o}(), metadata={{op_name="coremaint.{p}/x"}}\n'
        for k, (s, o, p) in HAND_MAP.items()) + "}\n"
    older = _Call(None, **_stats(9, 9, 9, 9))  # before the window
    calls = [older, _Call(hlo, **_stats(2, 1, 3, 4)),
             _Call(hlo, **_stats(1, 1, 2, 2))]
    run = _hand_made_run(monkeypatch, calls, [_burst(2, 1), _burst(1, 1)])
    got = _read(run)
    assert got["program.passes_per_burst"] == (10 + 6) / 2
    # remove.stats and promote.forward pass over the table: 240 ns
    assert got["program.ms_per_pass"] == pytest.approx(1e3 * 240e-9 / 16)
    assert got["phase.table_pct"] == pytest.approx(100 * 70 / 390)
    assert got["phase.evict_pct"] == 0.0
    assert got["phase.promote_pct"] == 0.0
    assert sum(got[k] for k in SHARES) == pytest.approx(100 * 370 / 390)


def test_readers_fall_silent_without_the_programs_record(monkeypatch):
    import repro.core.api as api

    calls = [_Call("", **_stats(2, 1, 3, 4))]
    # counts that disagree with the burst's: another call's record
    run = _hand_made_run(monkeypatch, calls, [_burst(5, 1)])
    assert all(v is None for v in _read(run).values())
    # a program without the record, as the parent commit is
    run = _hand_made_run(monkeypatch, calls, [_burst(2, 1)])
    monkeypatch.delattr(api, "RECENT_CALLS")
    assert all(v is None for v in _read(run).values())
    # a program that counts rounds but not waves
    old = dict(_stats(2, 1, 0, 0))
    del old["forward_waves"], old["evict_waves"]
    run = _hand_made_run(monkeypatch, [_Call("", **old)], [_burst(2, 1)])
    assert _read(run)["program.passes_per_burst"] is None
    # an untraced run reads no phase
    run.trace = None
    assert _read(run)["phase.table_pct"] is None


def test_recorded_tpu_trace_by_phase(monkeypatch):
    t = tr.read_xspace(RECORDED.read_bytes())
    line = json.loads(RECORDED_LINE.read_text())
    stats = json.loads(RECORDED_STATS.read_text())
    hlo = gzip.decompress(RECORDED_HLO.read_bytes()).decode()
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert tr.busy_s(t) == pytest.approx(line["device"]["busy_s"], rel=1e-9)
    maps = [ph.phase_map(hlo)]
    per = tr.op_seconds(t, key=lambda op: ph.op_phase(maps, op))
    assert set(per) - {None} == set(ph.PHASES)
    total = sum(per.values())
    shares = {k: 100 * sum(per.get(p, 0.0) for p in v) / total
              for k, v in SHARES.items()}
    assert sum(shares.values()) + 100 * per.get(None, 0.0) / total == \
        pytest.approx(100)
    assert sum(shares.values()) >= 99
    # the readers, on the same trace and record, print the run's line
    burst = Burst(0.0, 1.0, stats["n_removed"], stats["n_inserted"],
                  removed=stats["n_removed"], inserted=stats["n_inserted"],
                  remove_rounds=stats["remove_rounds"],
                  insert_rounds=stats["insert_rounds"])
    import repro.core.api as api

    monkeypatch.setattr(api, "RECENT_CALLS", [_Call(hlo, **stats)],
                        raising=False)
    run = RunData(setup_s=1.0, window_s=1.0, bursts=[burst],
                  compiles_in_window=0, trace=t)
    got = _read(run)
    for k in NEW:
        assert got[k] == pytest.approx(m[k], rel=1e-9), k
    for k, v in shares.items():
        assert v == pytest.approx(m[k], rel=1e-9), k
    assert got["program.passes_per_burst"] >= m["program.rounds_per_burst"]
