"""Chaos-harness property tests (ISSUE 6): the lossless law, end to end.

Every test drives a deterministic fault-injection :class:`repro.chaos.Scenario`
through the REAL on-device drive loop (``RafiContext.run_until_done`` over the
configured exchange backend) and checks it against oracles that share no code
with the forwarding stack:

* retain mode delivers EXACTLY the schedule's per-destination checksums —
  zero drops, zero lost, clean termination — on flat and 2-/3-level routes;
* the flat retain *trajectory* (rounds to drain, per-burst retained rows,
  anti-starvation age) matches the numpy twin ``simulate_flat_retain``
  round for round;
* drop mode (the §3.3 oracle semantics) keeps the conservation identity
  ``emitted == delivered + resident + drops`` — every loss is counted,
  nothing vanishes silently;
* the measured ``age_max`` respects the ``spill_drain_model`` bound, so
  "bounded-delay anti-starvation" is a checked number, not a slogan.

Sizing note: the lossless law's precondition is that local capacity bounds
the resident population (see ``ForwardConfig.overflow``).  The flat cases
need only ``capacity=128``; hierarchical routes park mid-route backlog at
relay ranks, so they get ``capacity=256``.
"""
import jax
import numpy as np
import pytest

from repro import compat
from repro.chaos import (
    all_scenarios,
    convergecast,
    expected_by_rank,
    run_scenario,
    simulate_flat_retain,
)
from repro.roofline.analysis import spill_drain_model

pytestmark = pytest.mark.chaos

R = 8
S = 2          # starved per-peer send budget — every scenario spills
FLAT_CAP = 128
HIER_CAP = 256

SCENARIOS = {sc.name: sc for sc in all_scenarios(R)}
SCENARIO_IDS = sorted(SCENARIOS)


# ------------------------------------------------------------- flat retain
@pytest.mark.parametrize("marshal", ["sort", "scatter"])
@pytest.mark.parametrize("name", SCENARIO_IDS)
def test_flat_retain_matches_numpy_twin(mesh8, name, marshal):
    """Retain mode on the flat padded exchange is bit-exact with the numpy
    simulator: same deliveries, same number of rounds to drain, same total
    retained rows and same worst-case age — the whole trajectory, not just
    the end state."""
    sc = SCENARIOS[name]
    sim = simulate_flat_retain(sc, peer_capacity=S, capacity=FLAT_CAP)
    assert sim["done"] and sim["drops"] == 0  # the oracle itself is lossless
    res = run_scenario(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        marshal=marshal, max_rounds=64,
    )
    np.testing.assert_array_equal(res["delivered"], expected_by_rank(sc))
    np.testing.assert_array_equal(res["delivered"], sim["delivered"])
    assert res["drops"] == 0 and res["lost"] == 0 and res["done"]
    assert res["resident"] == 0
    assert res["rounds"] == sim["rounds"]
    assert res["retained_rows"] == sim["retained_rows"]
    assert res["age_max"] == sim["age_max"]


def test_flat_retain_pallas_kernels(mesh8):
    """Retention over the Pallas kernel path (bucket-scatter marshal plan +
    scatter placement) agrees with the XLA path and the oracle on the
    worst-case convergecast."""
    sc = SCENARIOS["convergecast"]
    sim = simulate_flat_retain(sc, peer_capacity=S, capacity=FLAT_CAP)
    res = run_scenario(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        marshal="scatter", use_pallas=True, max_rounds=64,
    )
    np.testing.assert_array_equal(res["delivered"], expected_by_rank(sc))
    assert res["drops"] == 0 and res["lost"] == 0 and res["done"]
    assert (res["rounds"], res["retained_rows"], res["age_max"]) == (
        sim["rounds"], sim["retained_rows"], sim["age_max"]
    )


def test_flat_retain_age_respects_drain_bound(mesh8):
    """Anti-starvation is BOUNDED delay: with FIFO retention the oldest row
    waits at most the time to drain the whole backlog through the clamp
    allowance, plus the emission span that keeps refilling it."""
    sc = SCENARIOS["convergecast"]
    res = run_scenario(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        marshal="sort", max_rounds=64,
    )
    backlog = sc.rounds * sc.emits_per_round  # one sender's worst backlog
    bound = spill_drain_model(backlog, S)["age_bound"] + sc.rounds
    assert 0 < res["age_max"] <= bound, (res["age_max"], bound)


# ---------------------------------------------------- per-round trajectories
@pytest.mark.telemetry
@pytest.mark.parametrize("name", SCENARIO_IDS)
def test_flat_retain_trace_matches_twin_per_round(mesh8, name):
    """The full-window stats ring replays the burst round for round, not
    just in aggregate: the chronological retained-row and age-max traces
    equal the numpy twin's entry by entry, every forward of the burst is
    recorded (``rounds + 1`` entries — the initial forward plus one per body
    round), and the receiver-arrival trace accounts for every delivery."""
    sc = SCENARIOS[name]
    sim = simulate_flat_retain(sc, peer_capacity=S, capacity=FLAT_CAP)
    res = run_scenario(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        max_rounds=64,
    )
    assert len(res["retained_trace"]) == res["rounds"] + 1
    np.testing.assert_array_equal(res["retained_trace"], sim["retained_trace"])
    np.testing.assert_array_equal(res["age_trace"], sim["age_trace"])
    assert int(np.sum(res["recv_trace"])) == res["delivered_total"]


# ----------------------------------------------------- hierarchical retain
HIER = [
    ("mesh_nodes24", ("node", "device"), (8, 8)),
    ("mesh_pods222", ("pod", "node", "device"), (8, 8, 8)),
]


@pytest.mark.parametrize("fixture,axes,caps", HIER, ids=["2level", "3level"])
@pytest.mark.parametrize("name", SCENARIO_IDS)
def test_hierarchical_retain_is_lossless(request, fixture, axes, caps, name):
    """On multi-tier routes a clamped row parks at the intermediate rank it
    reached and resumes next round — the schedule's checksums still arrive
    exactly, with zero drops, on every scenario."""
    mesh = request.getfixturevalue(fixture)
    sc = SCENARIOS[name]
    res = run_scenario(
        mesh, sc, capacity=HIER_CAP, axis_name=axes, exchange="hierarchical",
        level_capacities=caps, overflow="retain", marshal="sort",
        max_rounds=128,
    )
    np.testing.assert_array_equal(res["delivered"], expected_by_rank(sc))
    assert res["drops"] == 0 and res["lost"] == 0 and res["done"]
    assert res["resident"] == 0


@pytest.mark.parametrize("fixture,axes,caps", HIER, ids=["2level", "3level"])
def test_hierarchical_retain_scatter_marshal(request, fixture, axes, caps):
    """The sort-free scatter marshal preserves the lossless law on the
    worst-case convergecast too."""
    mesh = request.getfixturevalue(fixture)
    sc = SCENARIOS["convergecast"]
    res = run_scenario(
        mesh, sc, capacity=HIER_CAP, axis_name=axes, exchange="hierarchical",
        level_capacities=caps, overflow="retain", marshal="scatter",
        max_rounds=128,
    )
    np.testing.assert_array_equal(res["delivered"], expected_by_rank(sc))
    assert res["drops"] == 0 and res["lost"] == 0 and res["done"]


@pytest.mark.telemetry
@pytest.mark.parametrize("fixture,axes,caps", HIER, ids=["2level", "3level"])
def test_hierarchical_ring_telemetry_accounts_exactly(request, fixture, axes, caps):
    """Telemetry + retain on multi-tier routes: the ring's receiver-arrival
    trace sums to EXACTLY the delivered total (a row parked mid-route is
    retained, never double-counted as received), retention really fired and
    fully drained by the last forward, and the burst summary agrees with the
    chronological trace it was folded from."""
    mesh = request.getfixturevalue(fixture)
    sc = SCENARIOS["convergecast"]
    res = run_scenario(
        mesh, sc, capacity=HIER_CAP, axis_name=axes, exchange="hierarchical",
        level_capacities=caps, overflow="retain", max_rounds=128,
    )
    assert res["drops"] == 0 and res["lost"] == 0 and res["done"]
    assert len(res["recv_trace"]) == res["rounds"] + 1
    assert int(np.sum(res["recv_trace"])) == res["delivered_total"] == sc.emitted
    assert res["retained_trace"][-1] == 0  # drained clean
    assert int(np.sum(res["retained_trace"])) > 0  # the clamp really bit
    # summary (raw ring fold) vs trace (chronological view): one ring, two
    # independent reductions, same answer
    assert res["retained_rows"] == int(np.sum(res["retained_trace"]))
    assert res["age_max"] == int(np.max(res["age_trace"]))


# ------------------------------------------------------- drop conservation
@pytest.mark.parametrize("name", SCENARIO_IDS)
def test_drop_mode_conserves_padded(mesh8, name):
    """Drop mode under the same starved budgets: losses are allowed but
    every single one is COUNTED — delivered + resident + drops == emitted."""
    sc = SCENARIOS[name]
    res = run_scenario(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="drop",
        max_rounds=64,
    )
    assert res["lost"] == 0, res
    assert res["done"]


def test_drop_mode_conserves_onehot(mesh8):
    """The all-gather oracle backend has only a receiver clamp; starve the
    queue capacity instead and the identity must still balance."""
    sc = SCENARIOS["convergecast"]
    res = run_scenario(
        mesh8, sc, capacity=32, overflow="drop", exchange="onehot",
        max_rounds=64,
    )
    assert res["drops"] > 0  # the clamp really fired
    assert res["lost"] == 0, res


def test_drop_mode_conserves_hierarchical(mesh_nodes24):
    sc = SCENARIOS["convergecast"]
    res = run_scenario(
        mesh_nodes24, sc, capacity=FLAT_CAP, axis_name=("node", "device"),
        exchange="hierarchical", level_capacities=(2, 2), overflow="drop",
        max_rounds=64,
    )
    assert res["drops"] > 0
    assert res["lost"] == 0, res


def test_drop_mode_conserves_ragged(mesh8):
    if not compat.ragged_executes():
        pytest.skip(f"the {jax.default_backend()} backend cannot execute ragged_all_to_all")
    sc = SCENARIOS["convergecast"]
    res = run_scenario(
        mesh8, sc, capacity=FLAT_CAP, overflow="drop", exchange="ragged",
        max_rounds=64,
    )
    assert res["lost"] == 0, res


def test_retain_beats_drop_where_it_matters(mesh8):
    """The headline contrast the benchmark gate codifies: on the convergecast
    with starved budgets, drop mode loses a large fraction of the traffic
    while retain mode loses nothing (it just takes more rounds)."""
    sc = convergecast(R)
    kw = dict(capacity=FLAT_CAP, peer_capacity=S, max_rounds=64)
    dropped = run_scenario(mesh8, sc, overflow="drop", **kw)
    retained = run_scenario(mesh8, sc, overflow="retain", **kw)
    assert dropped["drops"] > 0.2 * sc.emitted, dropped
    assert retained["drops"] == 0 and retained["lost"] == 0
    assert retained["delivered_total"] == sc.emitted
    assert retained["rounds"] > dropped["rounds"]  # the price: extra rounds


# ------------------------------------------------- pipelined (the overlap law)
@pytest.mark.pipeline
@pytest.mark.parametrize("marshal", ["sort", "scatter"])
@pytest.mark.parametrize("name", SCENARIO_IDS)
def test_flat_retain_pipelined_matches_numpy_twin(mesh8, name, marshal):
    """The overlap law under chaos: micro-shard pipelining
    (``pipeline_shards=2`` — the starved ``peer_capacity=2`` splits into
    1-row chunks, the worst case) keeps every scenario's retain trajectory
    bit-exact with the numpy twin — same deliveries, same rounds to drain,
    same retained rows, same worst-case age as the bulk round."""
    sc = SCENARIOS[name]
    sim = simulate_flat_retain(sc, peer_capacity=S, capacity=FLAT_CAP)
    res = run_scenario(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        marshal=marshal, max_rounds=64, pipeline_shards=2,
    )
    np.testing.assert_array_equal(res["delivered"], expected_by_rank(sc))
    np.testing.assert_array_equal(res["delivered"], sim["delivered"])
    assert res["drops"] == 0 and res["lost"] == 0 and res["done"]
    assert res["resident"] == 0
    assert res["rounds"] == sim["rounds"]
    assert res["retained_rows"] == sim["retained_rows"]
    assert res["age_max"] == sim["age_max"]
