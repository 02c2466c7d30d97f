"""Rebalance tests (ISSUE 3): destination-preserving semantics + topology.

Covers the PR-3 bugfix — ``rebalance()`` must re-destinate ONLY resident
items (``dest == DISCARD``); pending items (``dest >= 0``) keep their
addressed destination and ride the same round — and the topology-aware
hierarchical plan: equalize within the fastest-axis group first, cross the
slower fabrics only with true surplus, and (``scope="intra"``) lower to a
program with ZERO payload bytes on any slower tier.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import DISCARD, ForwardConfig, WorkQueue, rebalance, work_item

R, CAP = 8, 64


@work_item
@dataclasses.dataclass
class Item:
    val: jax.Array
    src: jax.Array


def _run_rebalance(mesh, cfg, axes, count_of, dest_of, val_of, scope="global"):
    """Per-rank queue from the given builders; returns (counts, vals, srcs,
    total) gathered to the host."""

    def bal(_x):
        me = jax.lax.axis_index(axes)
        lane = jnp.arange(CAP, dtype=jnp.int32)
        n = count_of(me)
        q = WorkQueue(
            items=Item(val=val_of(me, lane), src=me * jnp.ones(CAP, jnp.int32)),
            dest=jnp.where(lane < n, dest_of(me, lane), DISCARD).astype(jnp.int32),
            count=n.astype(jnp.int32),
            drops=jnp.zeros((), jnp.int32),
        )
        nq, total = rebalance(q, cfg, scope=scope)
        return nq.count[None], nq.items.val, nq.items.src, total

    f = jax.jit(
        jax.shard_map(
            bal, mesh=mesh, in_specs=P(axes),
            out_specs=(P(axes), P(axes), P(axes), P()),
        )
    )
    counts, vals, srcs, total = f(jnp.arange(8.0))
    return (
        np.asarray(counts),
        np.asarray(vals).reshape(R, CAP),
        np.asarray(srcs).reshape(R, CAP),
        int(total),
    )


# ------------------------------------------- bugfix: pending dests preserved
def test_rebalance_preserves_pending_destinations(mesh8):
    """Regression for the clobbering bug: a mixed queue of pending
    (dest >= 0) and resident (dest == DISCARD) items.  Pending items MUST
    arrive where addressed; only residents get balanced."""
    cfg = ForwardConfig("data", R, CAP, exchange="padded")
    N_PEND = 5
    n_res_np = np.array([30, 0, 0, 0, 0, 0, 0, 0])
    n_res_j = jnp.asarray(n_res_np)

    counts, vals, srcs, total = _run_rebalance(
        mesh8, cfg, "data",
        count_of=lambda me: N_PEND + n_res_j[me],
        # lanes [0, N_PEND): pending, addressed to me+1; the rest resident
        dest_of=lambda me, k: jnp.where(k < N_PEND, (me + 1) % R, DISCARD),
        # val encodes provenance: pending = 1000 + me*100 + k, resident = 5000 + k
        val_of=lambda me, k: jnp.where(
            k < N_PEND, 1000.0 + me * 100.0 + k, 5000.0 + k
        ),
    )
    assert total == R * N_PEND + int(n_res_np.sum())
    res_target = -(-int(n_res_np.sum()) // R)  # ceil(30/8) == 4
    for r in range(R):
        got = vals[r][: counts[r]]
        pend = sorted(v for v in got if v < 5000)
        expect_pend = [1000.0 + ((r - 1) % R) * 100.0 + k for k in range(N_PEND)]
        assert pend == expect_pend, (
            f"rank {r}: pending items clobbered — got {pend}, want {expect_pend}"
        )
        n_res_here = int(counts[r]) - N_PEND
        assert 0 <= n_res_here <= res_target
    assert int(counts.sum()) - R * N_PEND == int(n_res_np.sum())


def test_rebalance_all_resident_unchanged_semantics(mesh8):
    """With no pending work the fix must not change the legacy behaviour:
    order-preserving ceil assignment over all ranks."""
    cfg = ForwardConfig("data", R, CAP, exchange="padded")
    n_j = jnp.asarray(np.array([40, 8, 0, 0, 0, 0, 0, 0]))
    counts, _v, _s, total = _run_rebalance(
        mesh8, cfg, "data",
        count_of=lambda me: n_j[me],
        dest_of=lambda me, k: jnp.full_like(k, DISCARD),
        val_of=lambda me, k: k.astype(jnp.float32),
    )
    assert total == 48
    assert counts.max() <= -(-48 // R) and counts.sum() == 48


# ------------------------------------- topology-aware hierarchical rebalance
def test_hierarchical_rebalance_node_local_skew_never_crosses_nodes(mesh_nodes24):
    """Skew confined within each node (node totals already balanced): the
    surplus/deficit plan must move NOTHING across the slow fabric — every
    received item's source rank sits in the receiver's node."""
    F = 4
    cfg = ForwardConfig(
        ("node", "device"), R, CAP, exchange="hierarchical", fast_size=F,
    )
    # lane 0 of each node holds everything: node totals equal (20 each)
    n_j = jnp.asarray(np.array([20, 0, 0, 0, 20, 0, 0, 0]))
    counts, _v, srcs, total = _run_rebalance(
        mesh_nodes24, cfg, ("node", "device"),
        count_of=lambda me: n_j[me],
        dest_of=lambda me, k: jnp.full_like(k, DISCARD),
        val_of=lambda me, k: k.astype(jnp.float32),
    )
    assert total == 40
    np.testing.assert_array_equal(counts.reshape(-1), [5] * R)
    for r in range(R):
        src_nodes = srcs[r][: counts[r]] // F
        assert (src_nodes == r // F).all(), (
            f"rank {r}: items crossed the slow fabric from nodes "
            f"{sorted(set(src_nodes.tolist()))}"
        )


def test_hierarchical_rebalance_moves_only_surplus_across_nodes(mesh_nodes24):
    """Cross-node skew: node 0 holds 40, node 1 none.  Quota = 20 per node,
    so EXACTLY the 20-item surplus crosses — node 0's keepers stay put."""
    F = 4
    cfg = ForwardConfig(
        ("node", "device"), R, CAP, exchange="hierarchical", fast_size=F,
    )
    n_j = jnp.asarray(np.array([10, 10, 10, 10, 0, 0, 0, 0]))
    counts, _v, srcs, total = _run_rebalance(
        mesh_nodes24, cfg, ("node", "device"),
        count_of=lambda me: n_j[me],
        dest_of=lambda me, k: jnp.full_like(k, DISCARD),
        val_of=lambda me, k: k.astype(jnp.float32),
    )
    assert total == 40
    np.testing.assert_array_equal(counts.reshape(-1), [5] * R)
    crossed = sum(
        int((srcs[r][: counts[r]] // F != r // F).sum()) for r in range(R)
    )
    assert crossed == 20, f"want exactly the surplus (20) to cross, got {crossed}"


def test_intra_scope_zero_slow_tier_payload_bytes(mesh_pods222):
    """The acceptance claim: scope='intra' rebalance of a node-local skew
    lowers to a program whose payload-sized collectives ALL bind to the
    fastest tier — zero payload bytes on tier 0, tier 1, or mixed patterns
    (asserted via the per-tier accounting of roofline.analysis) — and still
    equalises the skew within each group."""
    from repro.core import types as T
    from repro.roofline.analysis import per_tier_collective_bytes

    sizes = (2, 2, 2)
    axes = ("pod", "node", "device")
    cfg = ForwardConfig(
        axes, R, CAP, exchange="hierarchical", level_sizes=sizes,
    )

    def bal(_x):
        me = jax.lax.axis_index(axes)
        lane = jnp.arange(CAP, dtype=jnp.int32)
        n = jnp.where(me % 2 == 0, 12, 0)  # lane 0 of every group hoards
        q = WorkQueue(
            items=Item(val=lane.astype(jnp.float32), src=me * jnp.ones(CAP, jnp.int32)),
            dest=jnp.full((CAP,), DISCARD, jnp.int32),
            count=n.astype(jnp.int32),
            drops=jnp.zeros((), jnp.int32),
        )
        nq, total = rebalance(q, cfg, scope="intra")
        return nq.count[None], nq.items.src, total

    jitted = jax.jit(
        jax.shard_map(
            bal, mesh=mesh_pods222, in_specs=P(axes),
            out_specs=(P(axes), P(axes), P()),
        )
    )
    # --- per-tier accounting on the lowered HLO: zero slow payload bytes
    words = T.pack_spec(Item(val=jnp.zeros(()), src=jnp.zeros((), jnp.int32))).total_words
    threshold = min(cfg.level_capacities) * words * 4
    per_tier = per_tier_collective_bytes(
        jitted.lower(jnp.arange(8.0)).as_text(), sizes, min_bytes=threshold
    )
    assert per_tier[0] == 0 and per_tier[1] == 0 and per_tier["cross"] == 0, per_tier
    assert per_tier[2] > 0  # the intra-tier exchange is where the bytes go
    # --- and the node-local skew is fully corrected, intra-group
    counts, srcs, total = jitted(jnp.arange(8.0))
    counts = np.asarray(counts)
    srcs = np.asarray(srcs).reshape(R, CAP)
    assert int(total) == 4 * 12
    np.testing.assert_array_equal(counts.reshape(-1), [6] * R)
    F = sizes[-1]
    for r in range(R):
        assert (srcs[r][: counts[r]] // F == r // F).all()


def test_intra_scope_delivers_in_group_and_holds_cross_group_pending(mesh_nodes24):
    """Pending items through scope='intra': global dests inside the rank's
    fastest-axis group are delivered (rank space translated to lanes);
    cross-group pending cannot ride a fast-axis-only round and must stay in
    the holder's queue with their destination UNTOUCHED — never silently
    dropped or misrouted."""
    F = 4
    cfg = ForwardConfig(
        ("node", "device"), R, CAP, exchange="hierarchical", fast_size=F,
    )
    axes = ("node", "device")

    def bal(_x):
        me = jax.lax.axis_index(axes)
        lane = jnp.arange(CAP, dtype=jnp.int32)
        # each rank: 1 pending to the next lane IN its node, 1 pending to its
        # mirror rank in the OTHER node, 2 residents (skewed onto lane 0)
        in_group_dest = (me // F) * F + (me + 1) % F
        cross_dest = (me + F) % R
        n = jnp.where(me % F == 0, 4, 2)
        dest = jnp.select(
            [lane == 0, lane == 1],
            [in_group_dest, cross_dest],
            DISCARD,
        )
        dest = jnp.where(lane < n, dest, DISCARD)
        q = WorkQueue(
            items=Item(
                val=me * 100.0 + lane.astype(jnp.float32),
                src=me * jnp.ones(CAP, jnp.int32),
            ),
            dest=dest.astype(jnp.int32),
            count=n.astype(jnp.int32),
            drops=jnp.zeros((), jnp.int32),
        )
        nq, total = rebalance(q, cfg, scope="intra")
        return nq.count[None], nq.items.val, nq.dest, nq.drops[None], total

    f = jax.jit(
        jax.shard_map(
            bal, mesh=mesh_nodes24, in_specs=P(axes),
            out_specs=(P(axes), P(axes), P(axes), P(axes), P()),
        )
    )
    counts, vals, dests, drops, total = f(jnp.arange(8.0))
    counts = np.asarray(counts)
    vals = np.asarray(vals).reshape(R, CAP)
    dests = np.asarray(dests).reshape(R, CAP)
    # nothing lost: 8 in-group pending + 8 cross-group pending + 4 residents
    assert int(np.asarray(drops).sum()) == 0
    assert int(total) == 20 and int(counts.sum()) == 20
    for r in range(R):
        got = vals[r][: counts[r]].tolist()
        got_dest = dests[r][: counts[r]].tolist()
        # the in-group pending item addressed to me arrived (lane 0 of the
        # previous lane in my node), delivered → dest reset to DISCARD
        sender = (r // F) * F + (r - 1) % F
        assert sender * 100.0 + 0.0 in got, (r, got)
        # my cross-group pending item is still HERE, dest untouched
        held = [d for v, d in zip(got, got_dest) if v == r * 100.0 + 1.0]
        assert held == [(r + F) % R], (r, got, got_dest)


def test_intra_scope_rejects_flat_config(mesh8):
    cfg = ForwardConfig("data", R, CAP, exchange="padded")
    q = WorkQueue(
        items=Item(val=jnp.zeros(CAP), src=jnp.zeros(CAP, jnp.int32)),
        dest=jnp.full((CAP,), DISCARD, jnp.int32),
        count=jnp.zeros((), jnp.int32),
        drops=jnp.zeros((), jnp.int32),
    )
    with pytest.raises(ValueError, match="intra"):
        rebalance(q, cfg, scope="intra")


def test_rebalance_rejects_unknown_scope(mesh8):
    cfg = ForwardConfig("data", R, CAP, exchange="padded")
    q = WorkQueue(
        items=Item(val=jnp.zeros(CAP), src=jnp.zeros(CAP, jnp.int32)),
        dest=jnp.full((CAP,), DISCARD, jnp.int32),
        count=jnp.zeros((), jnp.int32),
        drops=jnp.zeros((), jnp.int32),
    )
    with pytest.raises(ValueError, match="scope"):
        rebalance(q, cfg, scope="bogus")


def test_hierarchical_rebalance_3level_equalizes(mesh_pods222):
    """Global topology-aware rebalance on a (2,2,2) mesh: heavy skew onto one
    rank ends within the ceil bound everywhere, conserving items."""
    sizes = (2, 2, 2)
    axes = ("pod", "node", "device")
    cfg = ForwardConfig(
        axes, R, CAP, exchange="hierarchical", level_sizes=sizes,
        level_capacities=(4 * CAP, 2 * CAP, CAP),  # ample: no stage clamps
    )
    n_j = jnp.asarray(np.array([41, 0, 0, 7, 0, 0, 0, 0]))
    counts, _v, _s, total = _run_rebalance(
        mesh_pods222, cfg, axes,
        count_of=lambda me: n_j[me],
        dest_of=lambda me, k: jnp.full_like(k, DISCARD),
        val_of=lambda me, k: k.astype(jnp.float32),
    )
    assert total == 48
    assert counts.sum() == 48
    assert counts.max() <= -(-48 // R)
