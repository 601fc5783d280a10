"""The reduction from a profiler trace to busy time, per-op time and idle
gaps: on a hand-made trace with known answers, and on a small trace
recorded on a TPU v5e and committed with the benchmark."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))

from benchmarks.chip import trace as tr  # noqa: E402

# rmat16.burst25k, 2 bursts in a 20 s window, TPU v5 lite; that run's
# line read busy_s 31.704014433 and window_s 31.714706697
RECORDED = (Path(__file__).resolve().parent / "data"
            / "rmat16_burst25k.xplane.pb.gz")
DEV = "/device:TPU:0"


def _hand_made():
    ops = tr._with_self_time([
        tr.Op("sort.1", 100, 50), tr.Op("fusion.2", 150, 30),
        tr.Op("scatter.3", 300, 100), tr.Op("sort.1", 950, 100),
        tr.Op("early", 0, 20), tr.Op("loop", 290, 210),
    ])
    spans = [("bench.window", 50, 950), ("bench.plan", 50, 40),
             ("bench.wait", 90, 700), ("bench.plan", 790, 20),
             ("bench.wait", 810, 190)]
    return tr.Trace(ops={DEV: ops}, spans=spans)


def test_hand_made_trace():
    t = _hand_made()
    assert t.window() == (50, 1000)
    assert tr.window_s(t) == pytest.approx(950e-9)
    # union: [100, 180] + [290, 500] + [950, 1000] (clipped) = 340 ns
    assert tr.busy_s(t) == pytest.approx(340e-9)
    per = tr.op_seconds(t)
    assert per["sort.1"] == pytest.approx(100e-9)  # 50 + 50 clipped
    assert per["loop"] == pytest.approx(110e-9)    # 210 less its body
    assert per["fusion.2"] == pytest.approx(30e-9)
    assert "early" not in per
    assert tr.share_pct(t, lambda op: op.name == "loop") == pytest.approx(
        100 * 110 / 340)
    gaps = tr.idle_gaps(t)
    assert sum(g for _, g in gaps) == pytest.approx(610e-9)
    assert gaps[0] == ("bench.wait", pytest.approx(450e-9))  # 500..950
    assert ("bench.plan", pytest.approx(50e-9)) in gaps      # 50..100
    b = tr.breakdown(t, top=1)
    assert b["device_ops"] == [["loop", pytest.approx(110e-9)]]
    assert len(b["idle_gaps"]) == 1


def test_trace_without_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.Trace(ops={}, spans=[("bench.plan", 0, 1)]).window()


def test_recorded_tpu_trace():
    t = tr.read_xspace(RECORDED.read_bytes())
    assert list(t.ops) == [DEV]
    busy, win = tr.busy_s(t), tr.window_s(t)
    assert busy == pytest.approx(31.704014433, abs=1e-9)
    assert win == pytest.approx(31.714706697, abs=1e-9)
    # self times partition the busy time: loops hold their bodies' ops
    per = tr.op_seconds(t)
    assert sum(per.values()) == pytest.approx(busy, rel=1e-9)
    gaps = tr.idle_gaps(t)
    assert sum(g for _, g in gaps) == pytest.approx(win - busy, rel=1e-6)
    assert {name for name, _ in gaps} <= {"bench.plan", "bench.wait"}
    b = tr.breakdown(t)
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
    top = b["device_ops"][0][0]
    assert top.startswith("fusion.") and " kCustom pred[1048576] <- " in top
    sort = tr.share_pct(t, lambda op: op.opcode == "sort")
    custom = tr.share_pct(t, lambda op: op.kind == "kCustom")
    assert 0.5 < sort < 1 and 98 < custom < 99.5


def test_short_name_of_an_hlo_op():
    op = tr.Op("%fusion.499 = pred[1048576]{0:T(1024)(128)(4,1)} fusion("
               "pred[65536]{0:T(1024)(128)(4,1)S(1)} %copy-done.24, "
               "s32[1048576]{0:T(1024)S(1)} %broadcast_clamp_fusion.45), "
               "kind=kCustom, calls=%fused_computation.4.clone.clone", 0, 1)
    assert op.opcode == "fusion" and op.kind == "kCustom"
    assert op.short == ("fusion.499 kCustom pred[1048576] <- "
                        "(pred[65536], s32[1048576])")
    assert tr.Op("%sort.1 = (s32[8]{0}) sort(s32[8]{0} %x)", 0, 1).kind == ""


def test_idle_gaps_inside_apply_batch_take_the_programs_span_names():
    # ba16.burst25k, one burst, TPU v5 lite, with the program's host spans
    t = tr.read_xspace((RECORDED.parent / "ba16_burst25k_phases.xplane.pb.gz")
                       .read_bytes())
    names = {name for name, _, _ in t.spans}
    assert {"bench.window", "coremaint.apply_batch",
            "coremaint.transfer"} <= names
    # the window is still the harness's span, not one of the program's
    assert tr.window_s(t) == pytest.approx(11.388835933, abs=1e-9)
    gaps = tr.idle_gaps(t)
    assert sum(g for _, g in gaps) == pytest.approx(
        tr.window_s(t) - tr.busy_s(t), rel=1e-6)
    # the gap while apply_batch plans the burst, named by its step
    assert gaps[0] == ("coremaint.transfer", pytest.approx(3.1626e-3,
                                                           rel=1e-3))
    assert {name for name, _ in gaps} <= {"bench.plan", "bench.wait",
                                          "coremaint.transfer"}
