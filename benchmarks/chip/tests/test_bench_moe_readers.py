"""The readers of the expert-parallel cells' per-layer metrics on hand-made
traces: the grouped matmul's roofline share, RaFI's dispatch time per
layer, the rounds per job and the payload pass's fill."""
import pytest

import harness
import peaks
import tracefile
from tracefile import Events

BENCH = harness.load_bench()
MS = 1_000_000
V5E = peaks.PEAKS["TPU v5 lite"]


def _cell(metric):
    m = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    return harness.resolve(BENCH, m["workloads"][0])


def _run(metric, scoped_ms, traced, chips=1):
    """A run of the metric's cell whose trace holds one op per scope,
    ``scoped_ms`` {scope: ms} on every chip."""
    ops, names, t = [], {}, 0
    for i, (scope, ms) in enumerate(scoped_ms.items()):
        name = f"%fusion.{i} = f32[8] fusion(%p)"
        ops.append((name, t, t + int(ms * MS)))
        names[name] = f"jit(job)/rafi.drive/{scope}/x"
        t += int(ms * MS) + MS
    ev = Events(device={f"/device:TPU:{c}": ops for c in range(chips)}, host=[])
    ev.op_names = names
    run = harness.Run(_cell(metric), setup_s=1.0, window_s=1.0, jobs=traced)
    run.trace = tracefile.summarize(ev, window_s=t * 1e-9)
    run.traced = traced
    return run


@pytest.mark.parametrize("metric", ["expert_roofline_pct.prefill_r1", "expert_roofline_pct.prefill_r4"])
def test_roofline_share_reaches_100_only_at_the_bound(metric, monkeypatch):
    """With the scope's time equal to the least time the work allows (the
    larger of its operations at the bf16 peak and its bytes at the HBM
    peak) the share reads 100%; any longer time reads less, so a scope that
    holds the whole kernel cannot read above 100%."""
    monkeypatch.setattr(peaks, "lookup", lambda kind: V5E)
    read = harness.metric_reader(metric)
    cell = _cell(metric)
    traced = [{"layers": 4, "processed": 140_000 * cell.chips}, {"layers": 4, "processed": 90_000 * cell.chips}]
    mod = harness.load_module(harness.HERE / "metrics" / "expert_roofline_pct.py")
    flops, nbytes = mod.work(cell.spec, 230_000 * cell.chips, 8, cell.chips)
    least_ms = max(flops / V5E["bf16_flop_per_s"], nbytes / V5E["hbm_bytes_per_s"]) * 1e3
    exact = read(_run(metric, {"rafi.moe_experts": least_ms, "rafi.marshal": 5.0}, traced, cell.chips))
    assert exact == pytest.approx(100.0, rel=1e-5)
    for slower in (1.01, 1.5, 4.0):
        share = read(_run(metric, {"rafi.moe_experts": least_ms * slower}, traced, cell.chips))
        assert share == pytest.approx(100.0 / slower, rel=1e-5) and share < 100.0


def test_roofline_counts_processed_rows_not_padding():
    """6·D·F operations per processed assignment; bytes are the held
    weights once a layer plus each processed row in and out."""
    mod = harness.load_module(harness.HERE / "metrics" / "expert_roofline_pct.py")
    spec = {"hidden_size": 7168, "moe_intermediate_size": 2048, "n_routed_experts": 8}
    flops, nbytes = mod.work(spec, processed=4 * 1000, layers=2, chips=4)
    assert flops == 6 * 7168 * 2048 * 1000
    assert nbytes == 2 * 8 * 3 * 7168 * 2048 * 2 + 1000 * 2 * 7168 * 2


@pytest.mark.parametrize("metric", ["dispatch_ms.prefill_r1", "dispatch_ms.prefill_r4"])
def test_dispatch_ms_sums_rafi_core_scopes_per_layer(metric):
    scoped = {"rafi.marshal": 6.0, "rafi.forward": 1.0, "rafi.enqueue": 2.0, "rafi.app": 1.0,
              "rafi.moe_experts": 30.0, "rafi.moe_shared": 20.0, "rafi.moe_combine": 3.0}
    run = _run(metric, scoped, [{"layers": 4}, {"layers": 4}])
    assert harness.metric_reader(metric)(run) == pytest.approx((6 + 1 + 2 + 1) / 8)
    # a trace with no MoE layer in it reads nothing
    assert harness.metric_reader(metric)(_run(metric, {"rafi.marshal": 6.0}, [{"layers": 4}])) is None


def test_rounds_is_the_mean_per_job():
    """The cells' jobs carry their body rounds over both trips of every
    layer as ``rounds``, which the drive loop's reader averages."""
    run = harness.Run(_cell("rounds.prefill_r4"), setup_s=1.0, window_s=1.0,
                      jobs=[{"rounds": 12, "layers": 4}, {"rounds": 10, "layers": 4}])
    assert harness.metric_reader("rounds.prefill_r4")(run) == pytest.approx(11)


@pytest.mark.parametrize("metric", ["payload_fill_pct.prefill_r1", "payload_fill_pct.prefill_r4"])
def test_payload_fill_counts_both_trips(metric, monkeypatch):
    """Each delivered assignment rides out and back; a job of ``rounds``
    body rounds over ``layers`` layers runs ``rounds + 2 · layers``
    forwards of ``rows_per_forward`` rows on every rank."""
    from repro.obs import metrics

    registry = metrics.Registry()
    monkeypatch.setattr(metrics, "REGISTRY", registry)
    cell = _cell(metric)
    jobs = [{"rounds": 10, "layers": 4, "deliveries": 500},
            {"rounds": 8, "layers": 4, "deliveries": 300}]
    run = harness.Run(cell, setup_s=1.0, window_s=1.0, jobs=jobs)
    read = harness.metric_reader(metric)
    assert read(run) is None  # nothing declared
    registry.set_gauge("rafi_payload_rows_per_forward", 100)
    want = 100 * 2 * 800 / ((18 + 16) * cell.chips * 100)
    assert read(run) == pytest.approx(want)


def test_moe_scopes_are_whole_scope_names():
    """The MoE layer's scopes are single words after ``rafi.``, so the
    trace's scope pattern keeps each apart (a dotted name would end at its
    first inner dot)."""
    name = "jit(job)/shard_map/while/body/closed_call/rafi.moe_experts/jit(gmm)/pallas_call"
    assert tracefile.scope_of(name) == "rafi.moe_experts"
    combine = "jit(job)/rafi.drive/while/body/rafi.app/rafi.moe_combine/scatter-add"
    assert tracefile.scope_of(combine) == "rafi.moe_combine"
