"""Traffic-telemetry flight recorder (ISSUE 5): oracle consistency + ring.

The acceptance properties:

* recorded ``RoundStats`` agree with an ONEHOT-DERIVED oracle — per-segment
  demands recomputed in numpy from the global (source, dest) picture, using
  the routing invariant (before stage ``l`` an item sits on the rank whose
  faster digits match its destination and slower digits match its source),
  bucketed with the ONE shared bucketing law (``telemetry.bucket_width``);
* per-stage recorded drops reproduce the PR-4 count-each-drop-exactly-once
  numbers (one segment clamped at every tier of a (2, 2, 2) route: 48 at the
  device stage, 16 at the node stage, 8 at the pod stage), per rank;
* ``stage_drops + recv_drops`` always equals the queue's drop counter (the
  stats and the §3.3 accounting are the same numbers, never a second count);
* the ``StatsRing`` in the ``run_until_done`` while-loop carry records every
  round (initial routing round included) and overwrites beyond the window.

Everything here runs with both marshal modes where it matters — the stats
are derived from the control plane, which the marshal law keeps identical.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import telemetry as TM
from repro.core import (
    DISCARD,
    ForwardConfig,
    WorkQueue,
    enqueue,
    forward_work,
    make_queue,
    run_until_done,
)

from helpers import make_rays, ray_proto

pytestmark = pytest.mark.telemetry

R, CAP = 8, 64
AXES3 = ("pod", "node", "device")
BUCKETS = 8


# ----------------------------------------------------------------- plumbing
def _stats_specs(cfg, axes):
    proto = TM.make_stats(TM.num_tiers(cfg), cfg.telemetry_buckets)
    return jax.tree.map(lambda _: P(axes), proto)


def _forward_fn(mesh, cfg, axes="data"):
    """Jitted: (dest (R*CAP,), counts (R,)) -> (counts, drops, stacked stats)."""

    def fwd(dest, counts):
        me = jax.lax.axis_index(axes)
        q = WorkQueue(
            items=make_rays(CAP),
            dest=dest,
            count=counts[0],
            drops=jnp.zeros((), jnp.int32),
        )
        nq, _total, stats = forward_work(q, cfg)
        return nq.count[None], nq.drops[None], TM.stack_ring(stats)

    return jax.jit(
        jax.shard_map(
            fwd, mesh=mesh,
            in_specs=(P(axes), P(axes)),
            out_specs=(P(axes), P(axes), _stats_specs(cfg, axes)),
        )
    )


# ------------------------------------------------------------------ oracles
def _digits(rank, level_sizes):
    ds = []
    for a in reversed(level_sizes[1:]):
        ds.append(rank % a)
        rank //= a
    ds.append(rank)
    return tuple(reversed(ds))


def _hier_demand_oracle(dest, counts, level_sizes):
    """No-clamp per-rank, per-tier, per-slot-column demand from the global
    (source, dest) picture.  Routing invariant: before stage ``l`` (stages
    run fastest first) an item (s, d) sits on the rank with digits
    ``(s_0, …, s_l, d_{l+1}, …, d_{L-1})``; stage ``l``'s slot column ``j``
    collects the ones with ``d_l == j``."""
    L = len(level_sizes)
    items = [
        (s, int(d))
        for s in range(R)
        for lane, d in enumerate(dest[s])
        if lane < counts[s] and 0 <= d < R
    ]
    digits = {r: _digits(r, level_sizes) for r in range(R)}
    demand = {}
    for l in range(L):
        if level_sizes[l] <= 1:
            continue
        for r in range(R):
            rd = digits[r]
            col = np.zeros(level_sizes[l], np.int64)
            for s, d in items:
                sd, dd = digits[s], digits[d]
                if all(sd[m] == rd[m] for m in range(l + 1)) and all(
                    dd[m] == rd[m] for m in range(l + 1, L)
                ):
                    col[dd[l]] += 1
            demand[(r, l)] = col
    return demand


def _oracle_hist(demands, cap, buckets):
    w = TM.bucket_width(cap, buckets)
    hist = np.zeros(buckets, np.int64)
    for d in demands:
        # the shared bucketing law: bucket B-1 is exactly the at-or-above-
        # capacity (clamping) segments, interior buckets tile [0, capacity)
        b = buckets - 1 if d >= cap else min(int(d) // w, buckets - 2)
        hist[b] += 1
    return hist


def test_overflow_bucket_collects_exactly_at_capacity_demand():
    """demand_hist[:, -1] is read as 'segments that hit the §3.3 clamp' —
    an exactly-at-capacity demand must land there even when capacity is not
    divisible by buckets-1 (e.g. cap 8, 8 buckets, width ceil(8/7) = 2)."""
    hist = np.asarray(TM.occupancy_histogram(jnp.array([7, 8, 9]), 8, 8))
    assert hist[-1] == 2, hist        # 8 and 9 clamp; 7 does not
    assert hist.sum() == 3
    assert int(TM.occupancy_bucket(jnp.array([8]), 8, 8)[0]) == 7


def _spread_dest(seed, hot=None, hot_frac=0.0):
    """(R, CAP) destinations + per-rank counts; optionally a hot-spot."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(4, 13, R).astype(np.int32)
    dest = rng.integers(0, R, (R, CAP)).astype(np.int32)
    if hot is not None:
        mask = rng.random((R, CAP)) < hot_frac
        dest = np.where(mask, hot, dest).astype(np.int32)
    return dest, counts


# ------------------------------------------------- flat-backend consistency
@pytest.mark.parametrize("marshal", ["sort", "scatter"])
def test_padded_stats_match_destination_oracle(mesh8, marshal):
    """Flat tier demand == my per-destination send counts, oracle-derived
    from the raw dest vector; hist/max/total all agree; drops conserve."""
    cfg = ForwardConfig(
        "data", R, CAP, exchange="padded", marshal=marshal,
        telemetry=True, telemetry_buckets=BUCKETS,
    )
    fn = _forward_fn(mesh8, cfg)
    dest, counts = _spread_dest(seed=1, hot=3, hot_frac=0.4)
    _cnt, drops, st = fn(jnp.asarray(dest).reshape(-1), jnp.asarray(counts))
    hist = np.asarray(st.demand_hist)      # (R, 1, B)
    dmax = np.asarray(st.demand_max)       # (R, 1)
    dtot = np.asarray(st.demand_total)
    sdrop = np.asarray(st.stage_drops)
    rdrop = np.asarray(st.recv_drops)
    for r in range(R):
        valid = dest[r][: counts[r]]
        valid = valid[(valid >= 0) & (valid < R)]
        per_dest = np.bincount(valid, minlength=R)
        np.testing.assert_array_equal(
            hist[r, 0], _oracle_hist(per_dest, cfg.peer_capacity, BUCKETS)
        )
        assert dmax[r, 0] == per_dest.max()
        assert dtot[r, 0] == per_dest.sum()
    # stats drops ARE the queue drops — same numbers, counted once
    assert int(sdrop.sum() + rdrop.sum()) == int(np.asarray(drops).sum())


def test_padded_stats_identical_across_marshal_modes(mesh8):
    """The stats come from the control plane, which the marshal law keeps
    identical — sort and scatter must record the same RoundStats."""
    dest, counts = _spread_dest(seed=2, hot=0, hot_frac=0.5)
    got = {}
    for marshal in ("sort", "scatter"):
        cfg = ForwardConfig(
            "data", R, CAP, exchange="padded", marshal=marshal,
            telemetry=True, telemetry_buckets=BUCKETS,
        )
        fn = _forward_fn(mesh8, cfg)
        *_rest, st = fn(jnp.asarray(dest).reshape(-1), jnp.asarray(counts))
        got[marshal] = st
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        got["sort"],
        got["scatter"],
    )


# ----------------------------------------------- hierarchical consistency
@pytest.mark.parametrize(
    "mesh_fixture,axes,sizes",
    [
        ("mesh_pods222", AXES3, (2, 2, 2)),
        ("mesh_nodes24", ("node", "device"), (2, 4)),
        ("mesh_nodes42", ("node", "device"), (4, 2)),
    ],
)
@pytest.mark.parametrize("marshal", ["sort", "scatter"])
def test_hierarchical_stats_match_routing_oracle(
    request, mesh_fixture, axes, sizes, marshal
):
    """Per-tier recorded demand (ample capacities, so no clamp distorts any
    stage) equals the numpy routing oracle at EVERY tier, histogram included
    — the 'onehot-derived per-segment counts' acceptance property."""
    mesh = request.getfixturevalue(mesh_fixture)
    cfg = ForwardConfig(
        axes, R, CAP, exchange="hierarchical", level_sizes=sizes,
        marshal=marshal, telemetry=True, telemetry_buckets=BUCKETS,
    )
    fn = _forward_fn(mesh, cfg, axes)
    dest, counts = _spread_dest(seed=3, hot=5, hot_frac=0.3)
    _cnt, drops, st = fn(jnp.asarray(dest).reshape(-1), jnp.asarray(counts))
    oracle = _hier_demand_oracle(dest, counts, sizes)
    hist = np.asarray(st.demand_hist)   # (R, L, B)
    dmax = np.asarray(st.demand_max)
    dtot = np.asarray(st.demand_total)
    for (r, l), col in oracle.items():
        np.testing.assert_array_equal(
            hist[r, l],
            _oracle_hist(col, cfg.level_capacities[l], BUCKETS),
            err_msg=f"rank {r} tier {l}",
        )
        assert dmax[r, l] == col.max(), (r, l, col)
        assert dtot[r, l] == col.sum(), (r, l, col)
    assert int(
        np.asarray(st.stage_drops).sum() + np.asarray(st.recv_drops).sum()
    ) == int(np.asarray(drops).sum())


def test_extent1_tier_records_nothing(mesh_pods222):
    """A skipped (extent-1) stage must leave its tier row all-zero — the
    controller reads 'no observation', never 'zero demand'."""
    from repro.launch.mesh import make_pod_mesh

    sizes = (2, 1, 4)
    mesh = make_pod_mesh(*sizes)
    cfg = ForwardConfig(
        AXES3, R, CAP, exchange="hierarchical", level_sizes=sizes,
        telemetry=True, telemetry_buckets=BUCKETS,
    )
    fn = _forward_fn(mesh, cfg, AXES3)
    dest, counts = _spread_dest(seed=4)
    *_rest, st = fn(jnp.asarray(dest).reshape(-1), jnp.asarray(counts))
    assert np.asarray(st.demand_hist)[:, 1].sum() == 0
    assert np.asarray(st.demand_max)[:, 1].max() == 0
    assert np.asarray(st.demand_hist)[:, 0].sum() > 0
    assert np.asarray(st.demand_hist)[:, 2].sum() > 0


# --------------------------------------------- per-stage drop attribution
@pytest.mark.parametrize("marshal", ["sort", "scatter"])
def test_stage_drops_reproduce_multi_tier_clamp_numbers(mesh_pods222, marshal):
    """The PR-4 drop-accounting scenario, now attributed per stage by the
    recorder: everyone sends 10 rows to rank 0 through a (2, 2, 2) route with
    level_capacities=(4, 4, 4).  Device stage drops 6 on every rank (48),
    node stage 4 on each device-digit-0 rank (16), pod stage 4 on ranks 0
    and 4 (8) — and the recorded post-clamp demands at the later stages see
    exactly the survivors (8 rows), never the clamped originals."""
    cfg = ForwardConfig(
        AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 2, 2),
        level_capacities=(4, 4, 4), marshal=marshal,
        telemetry=True, telemetry_buckets=BUCKETS,
    )
    fn = _forward_fn(mesh_pods222, cfg, AXES3)
    counts = np.full(R, 10, np.int32)
    dest = np.zeros((R, CAP), np.int32)
    _cnt, drops, st = fn(jnp.asarray(dest).reshape(-1), jnp.asarray(counts))
    sdrop = np.asarray(st.stage_drops)  # (R, 3) — tier 0 = pod (slowest)
    np.testing.assert_array_equal(sdrop[:, 2], np.full(R, 6))     # device
    np.testing.assert_array_equal(sdrop[:, 1], [4, 0, 4, 0, 4, 0, 4, 0])
    np.testing.assert_array_equal(sdrop[:, 0], [4, 0, 0, 0, 4, 0, 0, 0])
    assert sdrop.sum() == 48 + 16 + 8
    assert np.asarray(st.recv_drops).sum() == 0  # 8 arrivals ≤ capacity
    assert int(np.asarray(drops).sum()) == 72
    # post-clamp demand: device stage saw the raw 10-row segment, node and
    # pod stages see only the 4+4 survivors of the faster clamp
    dmax = np.asarray(st.demand_max)
    np.testing.assert_array_equal(dmax[:, 2], np.full(R, 10))
    np.testing.assert_array_equal(dmax[:, 1], [8, 0, 8, 0, 8, 0, 8, 0])
    np.testing.assert_array_equal(dmax[:, 0], [8, 0, 0, 0, 8, 0, 0, 0])


# -------------------------------------------------------- ring in the loop
def test_run_until_done_carries_ring_and_overwrites_window(mesh8):
    """5 hops + the initial routing round = 6 recorded rounds through a
    window of 4: pos counts all 6, the ring keeps the last 4."""
    cfg = ForwardConfig(
        "data", R, CAP, exchange="padded",
        telemetry=True, telemetry_window=4, telemetry_buckets=BUCKETS,
    )

    def round_fn(q_in, acc, rnd):
        me = jax.lax.axis_index("data")
        out = make_queue(ray_proto(), CAP)
        lane = jnp.arange(CAP)
        valid = lane < q_in.count
        keep = valid & (rnd < 4)
        dest = jnp.where(keep, (me + 1) % R, DISCARD).astype(jnp.int32)
        return enqueue(out, q_in.items, dest, valid), acc

    def drive(_x):
        me = jax.lax.axis_index("data")
        q0 = make_queue(ray_proto(), CAP)
        q0 = enqueue(q0, make_rays(3), me * jnp.ones(3, jnp.int32), jnp.ones(3, bool))
        q, acc, rounds, _done, ring = run_until_done(
            round_fn, q0, jnp.zeros(()), cfg, max_rounds=16
        )
        return rounds[None], TM.stack_ring(ring)

    ring_proto = TM.make_ring(1, window=4, buckets=BUCKETS)
    f = jax.jit(
        jax.shard_map(
            drive, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), jax.tree.map(lambda _: P("data"), ring_proto)),
        )
    )
    rounds, ring = f(jnp.arange(8.0))
    assert int(np.asarray(rounds)[0]) == 5
    np.testing.assert_array_equal(np.asarray(ring.pos), np.full(R, 6))
    assert ring.window == 4
    # 6 pushes through a window of 4 leave slots holding rounds [4, 5, 2, 3];
    # every round forwards 3 rows per rank except the final empty
    # termination round (push 5, landing in slot 1)
    np.testing.assert_array_equal(
        np.asarray(ring.stats.demand_total).reshape(R, 4),
        np.tile([3, 0, 3, 3], (R, 1)),
    )
    summary = TM.summarize(ring, tier_capacities=TM.tier_capacities(cfg))
    assert summary["rounds"] == 6
    assert summary["window_filled"] == 4
    assert summary["demand_max"][0] == 3
    assert summary["drops"] == 0


def test_summarize_and_quantile_roundtrip():
    """Host-side quantile inversion: q=1 returns the exact max; a mid
    quantile lands on a conservative bucket upper edge."""
    ring = TM.make_ring(1, window=8, buckets=BUCKETS)
    for occ in (1, 2, 2, 3, 3, 3, 50):
        st = TM.single_tier_stats(
            jnp.array([occ], jnp.int32), 32, BUCKETS,
            sent_rows=jnp.int32(occ), stage_drops=jnp.int32(0),
            recv_total=jnp.int32(occ), recv_drops=jnp.int32(0),
        )
        ring = TM.ring_push(ring, st)
    summary = TM.summarize(ring, tier_capacities=(32,))
    assert summary["demand_max"][0] == 50
    assert TM.demand_quantile(summary, 0, 1.0) == 50
    # 6 of 7 demands are <= 3; the 0.8 quantile sits in the first bucket
    # (width ceil(32/7) = 5) whose exclusive upper edge is 5
    q80 = TM.demand_quantile(summary, 0, 0.8)
    assert 3 <= q80 <= TM.bucket_width(32, BUCKETS)
    # any quantile reaching the overflow bucket falls back to the exact max
    assert TM.demand_quantile(summary, 0, 0.999) == 50


def test_cycling_records_per_hop_occupancy(mesh8):
    """deliver_by_cycling with telemetry: one RoundStats per ring hop, the
    in-flight occupancy trace shrinking as ranks absorb their items.  The
    ring window is num_ranks (one slot per hop) REGARDLESS of
    telemetry_window, so the full trace survives even when the configured
    window is smaller than the ring."""
    from repro.core import deliver_by_cycling

    cfg = ForwardConfig(
        "data", R, CAP, exchange="padded",
        telemetry=True, telemetry_window=R // 2, telemetry_buckets=BUCKETS,
    )

    def drive(_x):
        me = jax.lax.axis_index("data")
        q = make_queue(ray_proto(), CAP)
        n = 4
        q = enqueue(
            q, make_rays(n), ((me + 1 + jnp.arange(n)) % R).astype(jnp.int32),
            jnp.ones(n, bool),
        )
        absorbed, total, ring = deliver_by_cycling(q, cfg)
        return absorbed.count[None], total, TM.stack_ring(ring)

    ring_proto = TM.make_ring(1, window=R, buckets=BUCKETS)
    f = jax.jit(
        jax.shard_map(
            drive, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), P(), jax.tree.map(lambda _: P("data"), ring_proto)),
        )
    )
    cnt, total, ring = f(jnp.arange(8.0))
    assert int(total) == 8 * 4
    np.testing.assert_array_equal(np.asarray(ring.pos), np.full(R, R))
    # hop occupancies are monotonically non-increasing per rank as the ring
    # drains (each rank absorbs one of the 4 items per hop window)
    occ = np.asarray(ring.stats.demand_total).reshape(R, R)
    assert (np.diff(occ, axis=1) <= 0).all(), occ
    assert occ[:, 0].max() == 4 and occ[:, -1].max() == 0


def test_rebalance_returns_stats_with_telemetry(mesh_pods222):
    """rebalance() propagates telemetry on both the global topology-aware
    round and the intra-scope round (whose stats bind to the fast tier)."""
    from repro.core import rebalance

    cfg = ForwardConfig(
        AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 2, 2),
        telemetry=True, telemetry_buckets=BUCKETS,
    )

    def drive_scope(scope):
        def bal(_x):
            me = jax.lax.axis_index(AXES3)
            n = jnp.where(me % 2 == 0, 20, 2)
            q = WorkQueue(
                items=make_rays(CAP),
                dest=jnp.full((CAP,), DISCARD, jnp.int32),
                count=n.astype(jnp.int32),
                drops=jnp.zeros((), jnp.int32),
            )
            nq, total, stats = rebalance(q, cfg, scope=scope)
            return nq.count[None], total, TM.stack_ring(stats)

        sub_tiers = 3 if scope == "global" else 1
        proto = TM.make_stats(sub_tiers, BUCKETS)
        return jax.jit(
            jax.shard_map(
                bal, mesh=mesh_pods222, in_specs=P(AXES3),
                out_specs=(P(AXES3), P(), jax.tree.map(lambda _: P(AXES3), proto)),
            )
        )

    cnt, total, st = drive_scope("global")(jnp.arange(8.0))
    assert int(total) == 8 * 11  # 88 residents spread 11 per rank
    assert np.asarray(st.demand_hist).sum() > 0
    cnt_i, total_i, st_i = drive_scope("intra")(jnp.arange(8.0))
    assert int(total_i) == 8 * 11
    assert st_i.tiers == 1  # intra stats bind to the fast-axis sub-config
