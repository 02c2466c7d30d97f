"""The reduction from a profiler trace to the per-layer numbers: known
numbers on hand-made intervals, and on a small trace recorded on a v5e
chip (three miniApp bursts at a 1024-row queue) committed beside it."""
from pathlib import Path

import pytest

import tracefile
from tracefile import Events

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps():
    assert tracefile.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracefile.union([]) == []


def test_summary_of_hand_made_intervals():
    ms = 1_000_000
    ev = Events(
        device={
            "/device:TPU:0": [("fusion.1", 0, 3 * ms), ("all-to-all.2", 3 * ms, 6 * ms),
                              ("sort.3", 8 * ms, 9 * ms)],
            "/device:TPU:1": [("fusion.1", 0, 2 * ms), ("all-reduce.7", 2 * ms, 3 * ms)],
        },
        host=[("bench.window", 0, 10 * ms), ("bench.readback", 6 * ms, 8 * ms),
              ("bench.dispatch", 9 * ms, 10 * ms)],
    )
    s = tracefile.summarize(ev, window_s=0.010)
    assert s.busy_s == pytest.approx((7 + 3) / 2 * 1e-3)
    assert s.collective_s == pytest.approx((3 + 1) / 2 * 1e-3)
    assert s.top_ops[0] == ("fusion.1", pytest.approx(2.5e-3))
    assert s.idle_gaps == [("bench.readback", pytest.approx(2e-3)),
                           ("bench.dispatch", pytest.approx(1e-3))]


def test_no_device_no_summary():
    assert tracefile.summarize(Events(device={}, host=[]), 1.0) is None


def test_recorded_trace():
    """Three bursts of 92, 50 and 159 deliveries at a 1024-row queue on one
    v5e chip.  Read by hand from the trace: the three ``jit_burst`` modules
    ran 324,386 + 278,117 + 417,273 ns, the traced window (``bench.window``)
    spans 6,580,709 ns, and the device line holds 678 op events."""
    ev = tracefile.load(str(DATA / "trace_small.xplane.pb"))
    assert list(ev.device) == ["/device:TPU:0"]
    assert len(ev.device["/device:TPU:0"]) == 678
    s = tracefile.summarize(ev, window_s=0.006573429)
    modules = (324_386 + 278_117 + 417_273) * 1e-9
    assert 0.99 * modules <= s.busy_s <= modules
    assert s.busy_s == pytest.approx(0.001015936, abs=1e-12)
    assert s.collective_s == 0.0
    assert sum(t for _, t in s.top_ops) <= s.busy_s + 1e-12
    assert any(label.endswith(" sort") for label, _ in s.top_ops)
    assert [g[0] for g in s.idle_gaps[:3]] == ["bench.readback"] * 3
    assert {g[0] for g in s.idle_gaps} <= {"bench.seed", "bench.dispatch", "bench.readback", "host"}
    spans = [(n, s0, e0) for n, s0, e0 in ev.host if n == "bench.window"]
    assert spans and spans[0][2] - spans[0][1] == 6_580_709


def test_op_labels_are_short():
    name = ("%fusion.146 = u32[128000,2]{1,0:T(8,128)S(1)} fusion(s32[128000]{0:T(1024)S(1)} "
            "%get-tuple-element.231), kind=kCustom, calls=%fused_computation.20.clone.clone")
    assert tracefile.op_label(name) == "%fusion.146 fusion/kCustom u32[128000,2]"
    tup = "%sort.22 = (u32[128000]{0:T(1024)S(1)}, s32[128000]{0:T(1024)}) sort(u32[128000]{0} %x)"
    assert tracefile.op_label(tup) == "%sort.22 sort"


def test_self_time_of_nested_ops():
    ops = [("%while.1", 0, 10), ("%a", 1, 3), ("%b", 4, 8), ("%c", 5, 6), ("%d", 12, 13)]
    assert dict(tracefile.self_times(ops)) == {"%while.1": 4, "%a": 2, "%b": 3, "%c": 1, "%d": 1}
