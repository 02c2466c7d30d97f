"""``payload_fill_pct``: the share of the payload pass's rows that carry a
delivery, read from the gauge the program declares while it is traced."""
import pytest

import harness
from small_cells import CELLS, cell, run

BENCH = harness.load_bench()
# the cells that report a payload fill, and the name they report it under
FILL = {c: m["name"] for c in CELLS for m in harness.resolve(BENCH, c).per_layer
        if m["name"].startswith("payload_fill_pct.")}


@pytest.fixture
def registry(monkeypatch):
    """A fresh gauge registry in place of the process-wide one."""
    from repro.obs import metrics

    fresh = metrics.Registry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


@pytest.mark.parametrize("name", list(FILL))
def test_traced_run_reports_the_fill(name, registry):
    res = run(name, trace=1)
    c = cell(name)
    rows = registry.get("rafi_payload_rows_per_forward")
    if c.spec.get("exchange") == "padded" and "queue_rows_per_mesh_rank" in c.spec:
        # the flat padded exchange: R peer segments of one queue each
        C = c.spec["queue_rows_per_mesh_rank"] * c.chips
        assert rows == c.chips * C
    else:
        assert rows > 0
    value = res["metrics"][FILL[name]]["value"]
    assert 0 < value <= 100


def test_fill_of_hand_made_jobs(registry):
    registry.set_gauge("rafi_payload_rows_per_forward", 100)
    c = cell("miniapp_upstream.r4")
    jobs = [{"rounds": 3, "deliveries": 40}, {"rounds": 1, "deliveries": 8}]
    run_ = harness.Run(c, setup_s=1.0, window_s=1.0, jobs=jobs)
    # 48 deliveries over (4 + 2) forwards of 4 ranks × 100 rows
    assert harness.metric_reader(FILL[c.name])(run_) == pytest.approx(100 * 48 / 2400)


def test_nothing_declared_reads_nothing(registry):
    c = cell("miniapp_upstream.r1")
    run_ = harness.Run(c, setup_s=1.0, window_s=1.0, jobs=[{"rounds": 3, "deliveries": 5}])
    assert harness.metric_reader(FILL[c.name])(run_) is None
