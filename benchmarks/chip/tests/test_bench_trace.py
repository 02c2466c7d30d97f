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


# ---------------------------------------------------------------- scopes

MS = 1_000_000


def test_innermost_rafi_scope_wins():
    name = "jit(burst)/rafi.drive/while/body/rafi.app/rafi.enqueue/jit(remainder)/rem:"
    assert tracefile.scope_of(name) == "rafi.enqueue"
    assert tracefile.scope_of("jit(burst)/rafi.drive/rafi.forward/rafi.spill_extract/x") \
        == "rafi.spill_extract"
    assert tracefile.scope_of("jit(burst)/reduce_sum:") == "unscoped"
    assert tracefile.scope_of(None) == "unscoped"


OP_NAMES = {
    "%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop":
        "jit(burst)/rafi.drive/while/body/rafi.forward/rafi.marshal/gather",
    "%copy.4 = s32[8]{0} copy(s32[8]{0} %reduce-window.29)":
        "jit(burst)/rafi.drive/while/body/rafi.app/copy",
}


def _scoped_events():
    ops = [
        ("%while.1 = (s32[8]) while(%t), body=%b", 0, 10 * MS),
        ("%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop", 1 * MS, 4 * MS),
        ("%reduce-window.29 = s32[8]{0} reduce-window(s32[8]{0} %fusion.3)", 5 * MS, 6 * MS),
        ("%copy.4 = s32[8]{0} copy(s32[8]{0} %reduce-window.29)", 6 * MS, 9 * MS),
        ("%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop", 12 * MS, 14 * MS),
    ]
    return Events(device={"/device:TPU:0": ops, "/device:TPU:1": ops[1:2]}, host=[])


def test_scopes_of_hand_made_intervals():
    """Each op's self time goes to the innermost scope of its op_name; an
    op with no scope, and the ``while`` around the others, go to
    ``unscoped``; the scopes sum to busy time."""
    ev = _scoped_events()
    ev.op_names = dict(OP_NAMES)
    s = tracefile.summarize(ev, window_s=0.014)
    # chip 0: marshal 3 + 2 ms, app 3 ms, unscoped 1 (reduce-window) + 3 (while's own) ms;
    # chip 1: marshal 3 ms.  Mean over the two chips.
    assert s.scopes == {"rafi.marshal": pytest.approx(4e-3), "rafi.app": pytest.approx(1.5e-3),
                        "unscoped": pytest.approx(2e-3)}
    assert list(s.scopes) == ["rafi.marshal", "unscoped", "rafi.app"]
    assert sum(s.scopes.values()) == pytest.approx(s.busy_s)
    assert s.busy_s == pytest.approx((12 + 3) / 2 * 1e-3)


def test_without_op_names_everything_is_unscoped():
    s = tracefile.summarize(_scoped_events(), window_s=0.014)
    assert s.scopes == {"unscoped": pytest.approx(s.busy_s)}


def _pb(num, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def test_op_names_recorded_in_the_trace():
    """The ``tf_op`` stat of an op's event metadata, as a string or as a
    reference to a stat's name, read from a hand-made XSpace; a host plane
    is not read."""
    stat_meta = b"".join(_pb(5, _pb(1, k) + _pb(2, _pb(1, k) + _pb(2, n)))
                         for k, n in [(7, "tf_op"), (8, "flops"), (9, "jit(f)/rafi.plan/sort:")])
    event_meta = (
        _pb(4, _pb(1, 1) + _pb(2, _pb(1, 1) + _pb(2, "%sort.1 = s32[8] sort(%p)")
                                 + _pb(5, _pb(1, 8) + _pb(4, 12))
                                 + _pb(5, _pb(1, 7) + _pb(7, 9))))
        + _pb(4, _pb(1, 2) + _pb(2, _pb(1, 2) + _pb(2, "%fusion.2 = s32[8] fusion(%p)")
                                 + _pb(5, _pb(1, 7) + _pb(5, "jit(f)/rafi.marshal/gather:"))))
        + _pb(4, _pb(1, 3) + _pb(2, _pb(1, 3) + _pb(2, "%copy.3 = s32[8] copy(%p)"))))
    space = (_pb(1, _pb(2, "/device:TPU:0") + _pb(3, b"\x08\x01") + event_meta + stat_meta)
             + _pb(1, _pb(2, "/host:CPU") + event_meta + stat_meta))
    assert tracefile.recorded_op_names(space) == {
        "%sort.1 = s32[8] sort(%p)": "jit(f)/rafi.plan/sort:",
        "%fusion.2 = s32[8] fusion(%p)": "jit(f)/rafi.marshal/gather:",
    }
    host_only = _pb(1, _pb(2, "/host:CPU") + event_meta + stat_meta)
    assert tracefile.recorded_op_names(host_only) == {}


def test_recorded_trace_scopes():
    """The recorded trace predates the ``rafi.*`` scopes: 59 of its op kinds
    carry an ``op_name`` (``jit(burst)/...``), none names a scope, so all of
    its busy time is unscoped."""
    ev = tracefile.load(str(DATA / "trace_small.xplane.pb"))
    assert len(ev.op_names) == 59
    assert all(n.startswith(("jit(burst)/", "reduce_window_sum")) for n in ev.op_names.values())
    s = tracefile.summarize(ev, window_s=0.006573429)
    assert s.scopes == {"unscoped": pytest.approx(s.busy_s, rel=1e-12)}


@pytest.mark.parametrize("metric,scope", [("marshal_ms.drain", "rafi.marshal"),
                                          ("enqueue_ms.burst", "rafi.enqueue")])
def test_scope_reader_gives_ms_per_round(metric, scope):
    """A scope's reader divides the scope's device time by the rounds of
    the traced jobs, and gives nothing, never 0, where the scope has no
    time or the traced jobs no round."""
    import harness

    ev = _scoped_events()
    ev.op_names = {n: f"jit(burst)/rafi.drive/{scope}/x" for n in OP_NAMES}
    bench = harness.load_bench()
    cell = harness.resolve(bench, next(m for m in bench["per_layer"]
                                       if m["name"] == metric)["workloads"][0])
    run = harness.Run(cell, setup_s=1.0, window_s=1.0, jobs=[])
    run.trace = tracefile.summarize(ev, window_s=0.014)
    run.traced = [{"rounds": 3}, {"rounds": 2}]
    read = harness.metric_reader(metric)
    # chip 0: 3 + 2 + 3 ms in the scope, chip 1: 3 ms; mean 5.5 ms over 5 rounds
    assert read(run) == pytest.approx(5.5 / 5)
    run.traced = [{"rounds": 0}]
    assert read(run) is None
    run.traced = [{"rounds": 3}]
    run.trace = tracefile.summarize(_scoped_events(), window_s=0.014)
    assert read(run) is None
