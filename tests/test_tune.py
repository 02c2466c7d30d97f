"""Adaptive capacity controller (ISSUE 5): planning law + convergence.

Unit-tests the quantile → capacity solver against hand-built summaries, the
``ForwardConfig`` re-planning (flat ``peer_capacity`` and hierarchical
``level_capacities``), and the end-to-end property the subsystem exists for:
on a DRIFTING hot-spot workload (the hot destination rotates mid-run) a
deliberately undersized config converges, over a few bursts, to a VERIFIED
drop-free fixed point whose modeled padded wire bytes undercut the static
worst-case sizing — at every tier of a 3-level route.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import telemetry as TM
from repro.core import (
    DISCARD,
    ForwardConfig,
    enqueue,
    make_queue,
    run_until_done,
    work_item,
)
from repro.roofline.analysis import occupancy_waste_model, padded_wire_rows
from repro.tune import TunePolicy, autotune_forward, plan_capacities, solve_capacities

pytestmark = pytest.mark.telemetry

R = 8
AXES3 = ("pod", "node", "device")
BUCKETS = 8


# ------------------------------------------------------------- solver units
def _summary(hist_rows, dmax, caps):
    hist = np.asarray(hist_rows, np.int64)
    return {
        "tier_capacities": tuple(caps),
        "buckets": hist.shape[1],
        "demand_hist": hist,
        "demand_max": np.asarray(dmax, np.int64),
        "stage_drops": np.zeros(hist.shape[0], np.int64),
        "recv_drops": 0,
        "drops": 0,
        "rounds": 1,
        "window_filled": 1,
        "demand_total": hist.sum(axis=1),
        "sent_rows": hist.sum(axis=1),
        "recv_total_max": 0,
    }


def test_solver_quantile_one_uses_exact_max():
    s = _summary([[10, 2, 0, 0, 0, 0, 0, 1]], [37], caps=(16,))
    got = solve_capacities(s, (16,), TunePolicy(headroom=1.0, granularity=1, min_capacity=1))
    assert got == (37,)


def test_solver_headroom_and_granularity():
    s = _summary([[0, 0, 3, 0, 0, 0, 0, 0]], [20], caps=(64,))
    got = solve_capacities(
        s, (64,), TunePolicy(headroom=1.25, granularity=8, min_capacity=8)
    )
    assert got == (32,)  # ceil(20 * 1.25) = 25 → round up to 32


def test_solver_bounds_cap_the_headroom():
    """headroom must never push past the §6.3 provable worst case."""
    s = _summary([[0, 0, 0, 0, 0, 0, 0, 4]], [120], caps=(64,))
    pol = TunePolicy(headroom=1.5, granularity=8)
    assert solve_capacities(s, (64,), pol) == (184,)  # ceil(180)→184
    assert solve_capacities(s, (64,), pol, bounds=(128,)) == (128,)


def test_solver_keeps_capacity_without_observations():
    """No recorded segments (extent-1 tier / idle backend) ≠ zero demand."""
    s = _summary([[0] * 8, [5, 0, 0, 0, 0, 0, 0, 0]], [0, 3], caps=(32, 16))
    got = solve_capacities(
        s, (32, 16), TunePolicy(headroom=1.0, granularity=1, min_capacity=1)
    )
    assert got == (32, 3)


def test_solver_no_shrink_policy():
    s = _summary([[6, 0, 0, 0, 0, 0, 0, 0]], [2], caps=(64,))
    grow_only = TunePolicy(headroom=1.0, granularity=1, min_capacity=1, allow_shrink=False)
    assert solve_capacities(s, (64,), grow_only) == (64,)
    shrink = dataclasses.replace(grow_only, allow_shrink=True)
    assert solve_capacities(s, (64,), shrink) == (2,)


def test_plan_capacities_builds_valid_configs():
    flat = ForwardConfig("data", R, 64, exchange="padded", peer_capacity=4, telemetry=True)
    s = _summary([[0, 0, 0, 0, 0, 0, 0, 8]], [40], caps=(4,))
    planned = plan_capacities(s, flat, policy=TunePolicy(headroom=1.0, granularity=8))
    assert planned.peer_capacity == 40 and planned.telemetry
    hier = ForwardConfig(
        AXES3, R, 64, exchange="hierarchical", level_sizes=(2, 2, 2),
        level_capacities=(4, 4, 4), telemetry=True,
    )
    s3 = _summary(
        [[0] * 7 + [2], [0] * 7 + [2], [0] * 7 + [2]], [30, 20, 10], caps=(4, 4, 4)
    )
    planned3 = plan_capacities(s3, hier, policy=TunePolicy(headroom=1.0, granularity=8, min_capacity=8))
    assert planned3.level_capacities == (32, 24, 16)
    assert planned3.level_sizes == (2, 2, 2)
    with pytest.raises(ValueError, match="no per-peer segment capacities"):
        plan_capacities(s, ForwardConfig("data", R, 64, exchange="onehot", telemetry=True))


def test_occupancy_waste_model_populations_match():
    """wire_B and useful_B must cover the same population: summarize()'s
    sent_rows is summed over ranks AND rounds, so the model takes num_ranks
    and rounds and the waste fraction stays in [0, 1]."""
    item_b = 36
    # 8 ranks, 2 rounds, each rank ships 100 useful rows into 8×16 slots
    m = occupancy_waste_model(
        (8,), (16,), item_b,
        useful_rows=[8 * 2 * 100], rounds=2, num_ranks=8,
    )
    assert m["wire_B"] == 8 * 16 * 2 * 8 * item_b
    assert m["useful_B"] == 8 * 2 * 100 * item_b
    assert 0.0 <= m["waste_frac"] <= 1.0
    assert m["waste_frac"] == pytest.approx(1 - 100 / 128)
    # static single-rank single-round view unchanged
    assert occupancy_waste_model((8,), (16,), item_b)["wire_B"] == 128 * item_b


def test_autotune_requires_telemetry():
    cfg = ForwardConfig("data", R, 64, exchange="padded")
    with pytest.raises(ValueError, match="telemetry=True"):
        autotune_forward(lambda c: (None, None), cfg)


# ------------------------------------------- end-to-end drifting hot-spot
@work_item
@dataclasses.dataclass
class Unit:
    val: jax.Array


PROTO = Unit(val=jnp.zeros(()))
CAP, N_EMIT, ROUNDS = 1024, 96, 8


def _drift_emits(me, rnd, num_ranks):
    """Half of each rank's emits chase a rotating hot destination."""
    lane = jnp.arange(N_EMIT)
    hot = (rnd // 2) % num_ranks
    dest = jnp.where(lane % 2 == 0, hot, (me + lane) % num_ranks)
    return Unit(val=jnp.ones(N_EMIT)), dest.astype(jnp.int32)


def _make_run_burst(mesh, axes):
    def round_fn(q_in, acc, rnd):
        me = jax.lax.axis_index(axes)
        items, dest = _drift_emits(me, rnd + 1, R)
        out = make_queue(PROTO, CAP)
        out = enqueue(
            out, items, jnp.where(rnd + 1 < ROUNDS, dest, DISCARD),
            jnp.ones(N_EMIT, bool),
        )
        return out, acc

    @functools.lru_cache(maxsize=None)
    def compiled(cfg):
        def drive(_x):
            me = jax.lax.axis_index(axes)
            items, dest = _drift_emits(me, 0, R)
            q0 = enqueue(make_queue(PROTO, CAP), items, dest, jnp.ones(N_EMIT, bool))
            q, _acc, _rounds, _done, ring = run_until_done(
                round_fn, q0, jnp.zeros((), jnp.int32), cfg,
                max_rounds=ROUNDS + 2,
            )
            return q.drops[None], TM.stack_ring(ring)

        ring_spec = jax.tree.map(
            lambda _: P(axes),
            TM.make_ring(
                TM.num_tiers(cfg), window=cfg.telemetry_window,
                buckets=cfg.telemetry_buckets,
            ),
        )
        return jax.jit(
            jax.shard_map(
                drive, mesh=mesh, in_specs=P(axes),
                out_specs=(P(axes), ring_spec),
            )
        )

    def run_burst(cfg):
        drops, ring = compiled(cfg)(jnp.arange(8.0))
        return int(np.asarray(drops).sum()), ring

    return run_burst


def test_autotune_converges_drop_free_flat(mesh8):
    """Undersized flat config → converged, verified drop-free, and cheaper
    on the wire than the provable worst-case static sizing (peer slots of
    n_emit rows — every emit could share one destination)."""
    run_burst = _make_run_burst(mesh8, "data")
    cfg0 = ForwardConfig(
        "data", R, CAP, exchange="padded", peer_capacity=8,
        telemetry=True, telemetry_window=ROUNDS + 2, telemetry_buckets=BUCKETS,
    )
    bounds = (N_EMIT,)
    final, report = autotune_forward(
        run_burst, cfg0, policy=TunePolicy(headroom=1.25, granularity=8),
        bounds=bounds, max_bursts=6,
    )
    assert report.converged, [dataclasses.asdict(s) for s in report.steps]
    assert report.steps[0].drops > 0          # the cold start really dropped
    assert report.final_drops == 0
    # drop-free with strictly less wire than the worst-case static config
    tuned = occupancy_waste_model((R,), (final.peer_capacity,), 36)
    static = occupancy_waste_model((R,), bounds, 36)
    assert tuned["wire_B"] < static["wire_B"]
    # and the tuned capacity actually covers the recorded max demand
    assert final.peer_capacity >= report.steps[-1].demand_max[0]


def test_autotune_converges_drop_free_hierarchical(mesh_pods222):
    """The 3-level route: every tier's capacity is adapted; later tiers'
    demand only becomes visible once earlier clamps open (convergence takes
    >1 re-plan), and the tuned wire undercuts worst-case sizing per tier."""
    run_burst = _make_run_burst(mesh_pods222, AXES3)
    cfg0 = ForwardConfig(
        AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 2, 2),
        level_capacities=(8, 8, 8),
        telemetry=True, telemetry_window=ROUNDS + 2, telemetry_buckets=BUCKETS,
    )
    # §6.3 worst case per tier: a slot at tier l concatenates the emits of
    # prod(level_sizes[l+1:]) source sub-segments, each ≤ n_emit rows
    bounds = (4 * N_EMIT, 2 * N_EMIT, N_EMIT)
    final, report = autotune_forward(
        run_burst, cfg0, policy=TunePolicy(headroom=1.25, granularity=8),
        bounds=bounds, max_bursts=8,
    )
    assert report.converged, [dataclasses.asdict(s) for s in report.steps]
    assert report.steps[0].drops > 0
    assert report.final_drops == 0
    assert report.bursts > 2  # staged clamps reveal demand over bursts
    assert all(
        c <= b for c, b in zip(final.level_capacities, bounds)
    ), (final.level_capacities, bounds)
    tuned = occupancy_waste_model((2, 2, 2), final.level_capacities, 36)
    static = occupancy_waste_model((2, 2, 2), bounds, 36)
    assert tuned["wire_B"] < static["wire_B"]
    assert padded_wire_rows((2, 2, 2), final.level_capacities) == [
        2 * c for c in final.level_capacities
    ]
