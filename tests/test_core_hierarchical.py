"""Hierarchical two-stage exchange (ISSUE 2): parity, accounting, config.

The `hierarchical` backend must be *observationally identical* to the flat
backends — same counts, same drops, bit-exact placement — because global
ranks are node-major and both stages preserve (source rank, lane) order.  The
oracle is ``exchange_onehot`` (a deliberately different code path).  With
ample stage capacities the ONLY drops either backend takes are
receiver-capacity clamps, so parity holds even for the all-items-to-one-rank
hot spot; with the default (tight) stage capacities the conservation law
``received + dropped == emitted`` still holds globally.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.core import DISCARD, ForwardConfig, WorkQueue, forward_work, work_item

R, CAP = 8, 64
AXES = ("node", "device")


@work_item
@dataclasses.dataclass
class Item:
    val: jax.Array
    src: jax.Array


def _make_fn(mesh, cfg, axes=AXES):
    def fwd(items_val, dest, counts):
        me = jax.lax.axis_index(axes)
        q = WorkQueue(
            items=Item(val=items_val, src=me * jnp.ones(CAP, jnp.int32)),
            dest=dest,
            count=counts[0],
            drops=jnp.zeros((), jnp.int32),
        )
        nq, total = forward_work(q, cfg)
        return nq.items.val, nq.items.src, nq.count[None], nq.drops[None], total

    return jax.jit(
        jax.shard_map(
            fwd, mesh=mesh,
            in_specs=(P(axes), P(axes), P(axes)),
            out_specs=(P(axes), P(axes), P(axes), P(axes), P()),
        )
    )


def _ample(fast_size, **kw):
    """Stage capacities so large no stage-A/B clamp can ever fire: the only
    remaining drop site is the receiver capacity — same as the oracle's."""
    return ForwardConfig(
        AXES, R, CAP, exchange="hierarchical", fast_size=fast_size,
        peer_capacity=CAP, node_capacity=fast_size * CAP, **kw,
    )


def _run_pair(hier_fn, onehot_fn, counts, dest, val):
    args = (
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(counts),
    )
    h = [np.asarray(x) for x in hier_fn(*args)]
    o = [np.asarray(x) for x in onehot_fn(*args)]
    np.testing.assert_array_equal(h[2], o[2], err_msg="per-rank receive counts")
    hv, hs = h[0].reshape(R, CAP), h[1].reshape(R, CAP)
    ov, os_ = o[0].reshape(R, CAP), o[1].reshape(R, CAP)
    for r in range(R):  # valid prefixes bit-exact; tails are garbage
        n = int(h[2].reshape(-1)[r])
        np.testing.assert_array_equal(hv[r][:n], ov[r][:n])
        np.testing.assert_array_equal(hs[r][:n], os_[r][:n])
    assert int(h[3].sum()) == int(o[3].sum()), "global drops"
    assert int(h[4]) == int(o[4]), "termination total"
    lane = np.arange(CAP)[None, :]
    emitted = int(((lane < counts[:, None]) & (dest >= 0) & (dest < R)).sum())
    assert int(h[2].sum()) + int(h[3].sum()) == emitted, "conservation"


@pytest.fixture(scope="module")
def fns24(mesh_nodes24):
    return (
        _make_fn(mesh_nodes24, _ample(4)),
        _make_fn(mesh_nodes24, ForwardConfig(AXES, R, CAP, exchange="onehot")),
    )


@pytest.fixture(scope="module")
def fns42(mesh_nodes42):
    return (
        _make_fn(mesh_nodes42, _ample(2)),
        _make_fn(mesh_nodes42, ForwardConfig(AXES, R, CAP, exchange="onehot")),
    )


@given(data=st.data())
@settings(max_examples=15, deadline=None)
def test_matches_onehot_bitwise_2x4(fns24, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(-1, R, (R, CAP)).astype(np.int32)  # incl. DISCARD lanes
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(*fns24, counts, dest, val)


@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_matches_onehot_bitwise_4x2(fns42, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(0, R, (R, CAP)).astype(np.int32)
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(*fns42, counts, dest, val)


def test_hotspot_all_to_one_rank_matches_onehot(fns24):
    """Everyone floods rank 0 at full queue: R·CAP items into one CAP-row
    queue.  Receiver clamp is the only drop site for both backends, so
    placement, counts, and drops must match exactly."""
    counts = np.full(R, CAP, np.int32)
    dest = np.zeros((R, CAP), np.int32)
    val = np.random.default_rng(1).normal(size=(R, CAP)).astype(np.float32)
    _run_pair(*fns24, counts, dest, val)


def test_discard_only_is_a_noop(fns24):
    counts = np.full(R, CAP, np.int32)
    dest = np.full((R, CAP), DISCARD, np.int32)
    val = np.zeros((R, CAP), np.float32)
    _run_pair(*fns24, counts, dest, val)


@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_tight_slots_conserve_items_plus_drops(mesh_nodes24, data):
    """With the DEFAULT (tight) stage capacities, stage-A and stage-B clamps
    fire under skew; every clamped item must land in `drops` — globally,
    received + dropped == emitted."""
    fn = _make_fn(
        mesh_nodes24,
        ForwardConfig(AXES, R, CAP, exchange="hierarchical", fast_size=4),
    )
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    # heavy skew: half the ranks route everything to rank 0
    dest = rng.integers(0, R, (R, CAP)).astype(np.int32)
    dest[::2] = 0
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _v, _s, out_counts, out_drops, total = fn(
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(counts),
    )
    received = int(np.asarray(out_counts).sum())
    dropped = int(np.asarray(out_drops).sum())
    assert received + dropped == int(counts.sum())
    assert int(total) == received


def test_pallas_path_matches_xla_path(mesh_nodes24):
    fn_p = _make_fn(mesh_nodes24, _ample(4, use_pallas=True))
    fn_x = _make_fn(mesh_nodes24, _ample(4))
    rng = np.random.default_rng(7)
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(0, R, (R, CAP)).astype(np.int32)
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    args = (
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(counts),
    )
    p = [np.asarray(x) for x in fn_p(*args)]
    x = [np.asarray(x) for x in fn_x(*args)]
    np.testing.assert_array_equal(p[2], x[2])
    for r in range(R):
        n = int(p[2].reshape(-1)[r])
        np.testing.assert_array_equal(
            p[0].reshape(R, CAP)[r][:n], x[0].reshape(R, CAP)[r][:n]
        )
    assert int(p[3].sum()) == int(x[3].sum())


def test_cycling_on_node_mesh_delivers_everything(mesh_nodes42):
    """§6.3 cycling with hierarchical hops: R node-major ring hops (fast-axis
    ppermute + a slow-axis hop at each node boundary) deliver every item."""
    from repro.core import enqueue, make_queue
    from repro.core.cycling import deliver_by_cycling

    cfg = ForwardConfig(AXES, R, CAP, exchange="hierarchical", fast_size=2)

    def kernel(_x):
        proto = Item(val=jnp.zeros(()), src=jnp.zeros((), jnp.int32))
        q = make_queue(proto, CAP)
        me = jax.lax.axis_index(AXES)
        n = 6
        k = jnp.arange(n)
        items = Item(
            val=(k + me * 100).astype(jnp.float32),
            src=me * jnp.ones(n, jnp.int32),
        )
        q = enqueue(q, items, ((me * 3 + k) % R).astype(jnp.int32), jnp.ones(n, bool))
        absorbed, total = deliver_by_cycling(q, cfg)
        return absorbed.count[None], total, absorbed.items.val

    f = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh_nodes42, in_specs=P(AXES),
            out_specs=(P(AXES), P(), P(AXES)),
        )
    )
    counts, total, vals = f(jnp.arange(8.0))
    counts = np.asarray(counts)
    vals = np.asarray(vals).reshape(R, CAP)
    assert int(total) == R * 6
    got = sorted(int(vals[r, i]) for r in range(R) for i in range(counts[r]))
    assert got == sorted(s * 100 + k for s in range(R) for k in range(6))


@pytest.mark.parametrize(
    "nodes,devs",
    [(1, 8), (8, 1)],
    ids=["single-node", "single-lane"],
)
def test_degenerate_axes_match_onehot(nodes, devs):
    """Extent-1 axes take dedicated identity paths (no stage-B collective on
    a single node; sort composed into stage B on a single lane) — both must
    stay bit-exact with the oracle, hot-spot included."""
    from repro.launch.mesh import make_node_mesh

    mesh = make_node_mesh(nodes, devs)
    hier = _make_fn(
        mesh,
        ForwardConfig(
            AXES, R, CAP, exchange="hierarchical", fast_size=devs,
            peer_capacity=CAP, node_capacity=devs * CAP,
        ),
    )
    onehot = _make_fn(mesh, ForwardConfig(AXES, R, CAP, exchange="onehot"))
    rng = np.random.default_rng(nodes * 10 + devs)
    for hotspot in (False, True):
        counts = (
            np.full(R, CAP, np.int32)
            if hotspot
            else rng.integers(0, CAP + 1, R).astype(np.int32)
        )
        dest = (
            np.zeros((R, CAP), np.int32)
            if hotspot
            else rng.integers(0, R, (R, CAP)).astype(np.int32)
        )
        val = rng.normal(size=(R, CAP)).astype(np.float32)
        _run_pair(hier, onehot, counts, dest, val)


# ------------------------------------------------------ 3-level (pod, node, device)
AXES3 = ("pod", "node", "device")


def _ample3(level_sizes, **kw):
    """Per-tier stage capacities so large no stage clamp can ever fire (stage
    l's buffer holds at most CAP · prod(faster sizes) rows): the only
    remaining drop site is the receiver capacity — same as the oracle's."""
    caps, mult = [], 1
    for a in reversed(level_sizes):
        caps.append(CAP * mult)
        mult *= a
    return ForwardConfig(
        AXES3, R, CAP, exchange="hierarchical", level_sizes=level_sizes,
        level_capacities=tuple(reversed(caps)), **kw,
    )


@pytest.fixture(scope="module")
def fns222(mesh_pods222):
    return (
        _make_fn(mesh_pods222, _ample3((2, 2, 2)), AXES3),
        _make_fn(
            mesh_pods222, ForwardConfig(AXES3, R, CAP, exchange="onehot"), AXES3
        ),
    )


@given(data=st.data())
@settings(max_examples=15, deadline=None)
def test_3level_matches_onehot_bitwise(fns222, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(-1, R, (R, CAP)).astype(np.int32)  # incl. DISCARD lanes
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(*fns222, counts, dest, val)


def test_3level_hotspot_matches_onehot(fns222):
    """Everyone floods rank 0 at full queue across all three tiers."""
    counts = np.full(R, CAP, np.int32)
    dest = np.zeros((R, CAP), np.int32)
    val = np.random.default_rng(3).normal(size=(R, CAP)).astype(np.float32)
    _run_pair(*fns222, counts, dest, val)


@given(data=st.data())
@settings(max_examples=8, deadline=None)
def test_3level_tight_slots_conserve_items_plus_drops(mesh_pods222, data):
    """Default (tight, load-proportional) per-tier capacities under skew:
    every stage clamp must land in `drops` — received + dropped == emitted."""
    fn = _make_fn(
        mesh_pods222,
        ForwardConfig(
            AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 2, 2)
        ),
        AXES3,
    )
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(0, R, (R, CAP)).astype(np.int32)
    dest[::2] = 0  # heavy skew across pods and nodes
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _v, _s, out_counts, out_drops, total = fn(
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(counts),
    )
    received = int(np.asarray(out_counts).sum())
    dropped = int(np.asarray(out_drops).sum())
    assert received + dropped == int(counts.sum())
    assert int(total) == received


@pytest.mark.parametrize(
    "shape",
    [(1, 2, 4), (2, 1, 4), (2, 4, 1), (1, 1, 8), (8, 1, 1), (1, 8, 1)],
    ids=lambda s: "x".join(map(str, s)),
)
def test_3level_degenerate_axes_match_onehot(shape):
    """Extent-1 tiers anywhere in the hierarchy skip their stage — the route
    must stay bit-exact with the oracle, hot-spot included."""
    from repro.launch.mesh import make_pod_mesh

    mesh = make_pod_mesh(*shape)
    hier = _make_fn(mesh, _ample3(shape), AXES3)
    onehot = _make_fn(
        mesh, ForwardConfig(AXES3, R, CAP, exchange="onehot"), AXES3
    )
    rng = np.random.default_rng(sum(shape))
    for hotspot in (False, True):
        counts = (
            np.full(R, CAP, np.int32)
            if hotspot
            else rng.integers(0, CAP + 1, R).astype(np.int32)
        )
        dest = (
            np.zeros((R, CAP), np.int32)
            if hotspot
            else rng.integers(0, R, (R, CAP)).astype(np.int32)
        )
        val = rng.normal(size=(R, CAP)).astype(np.float32)
        _run_pair(hier, onehot, counts, dest, val)


def test_3level_pallas_path_matches_xla_path(mesh_pods222):
    fn_p = _make_fn(mesh_pods222, _ample3((2, 2, 2), use_pallas=True), AXES3)
    fn_x = _make_fn(mesh_pods222, _ample3((2, 2, 2)), AXES3)
    rng = np.random.default_rng(11)
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(0, R, (R, CAP)).astype(np.int32)
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    args = (
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(counts),
    )
    p = [np.asarray(x) for x in fn_p(*args)]
    x = [np.asarray(x) for x in fn_x(*args)]
    np.testing.assert_array_equal(p[2], x[2])
    for r in range(R):
        n = int(p[2].reshape(-1)[r])
        np.testing.assert_array_equal(
            p[0].reshape(R, CAP)[r][:n], x[0].reshape(R, CAP)[r][:n]
        )
    assert int(p[3].sum()) == int(x[3].sum())


def test_joint_tier_axes_match_onehot(mesh_pods222):
    """A tier may group several mesh axes into one joint fabric: the 2-level
    route over ((pod, node), device) must equal the oracle on the same mesh."""
    hier = _make_fn(
        mesh_pods222,
        ForwardConfig(
            (("pod", "node"), "device"), R, CAP, exchange="hierarchical",
            level_sizes=(4, 2), level_capacities=(2 * CAP, CAP),
        ),
        AXES3,
    )
    onehot = _make_fn(
        mesh_pods222, ForwardConfig(AXES3, R, CAP, exchange="onehot"), AXES3
    )
    rng = np.random.default_rng(17)
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(0, R, (R, CAP)).astype(np.int32)
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(hier, onehot, counts, dest, val)


def test_joint_tier_rafi_context_forwards(mesh_pods222):
    """RafiContext must accept a joint-tier axis_name end to end: the
    PartitionSpec side flattens the nesting while the config keeps the tier
    structure (regression: P((('pod','node'),'device')) is not a legal spec)."""
    from repro.core import RafiContext, enqueue

    proto = Item(val=jnp.zeros(()), src=jnp.zeros((), jnp.int32))
    ctx = RafiContext(
        mesh_pods222, proto, axis_name=(("pod", "node"), "device"),
        capacity=CAP, exchange="hierarchical",
    )
    assert ctx.cfg.level_sizes == (4, 2)

    def fill(_x):
        from repro.core.context import _stack_queue

        me = jax.lax.axis_index(("pod", "node", "device"))
        lq = ctx.local_queue()
        lq = enqueue(
            lq,
            Item(val=jnp.arange(4.0) + me * 10, src=me * jnp.ones(4, jnp.int32)),
            ((me + jnp.arange(4)) % R).astype(jnp.int32),
            jnp.ones(4, bool),
        )
        return _stack_queue(lq)

    from jax.sharding import PartitionSpec as PS

    q = ctx.shard(
        fill, in_specs=PS(("pod", "node", "device")), out_specs=ctx.queue_specs()
    )(jnp.arange(8.0))
    nq, total = ctx.forward_rays()(q)
    assert int(total) == R * 4
    assert np.asarray(nq.count).sum() == R * 4


def test_joint_tier_cycling_delivers_everything(mesh_pods222):
    """deliver_by_cycling must flatten joint-tier axis names for its
    ppermute/psum (regression: nested tuples are not bindable axis names)."""
    from repro.core import enqueue, make_queue
    from repro.core.cycling import deliver_by_cycling

    axes = ("pod", "node", "device")
    cfg = ForwardConfig(
        (("pod", "node"), "device"), R, CAP, exchange="hierarchical",
        level_sizes=(4, 2),
    )

    def kernel(_x):
        proto = Item(val=jnp.zeros(()), src=jnp.zeros((), jnp.int32))
        q = make_queue(proto, CAP)
        me = jax.lax.axis_index(axes)
        n = 5
        k = jnp.arange(n)
        items = Item(
            val=(k + me * 100).astype(jnp.float32),
            src=me * jnp.ones(n, jnp.int32),
        )
        q = enqueue(q, items, ((me * 3 + k) % R).astype(jnp.int32), jnp.ones(n, bool))
        absorbed, total = deliver_by_cycling(q, cfg)
        return absorbed.count[None], total, absorbed.items.val

    f = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh_pods222, in_specs=P(axes),
            out_specs=(P(axes), P(), P(axes)),
        )
    )
    counts, total, vals = f(jnp.arange(8.0))
    counts = np.asarray(counts)
    vals = np.asarray(vals).reshape(R, CAP)
    assert int(total) == R * 5
    got = sorted(int(vals[r, i]) for r in range(R) for i in range(counts[r]))
    assert got == sorted(s * 100 + k for s in range(R) for k in range(5))


# ------------------------------------------------- ForwardConfig validation


def test_config_rejects_flat_axis():
    with pytest.raises(ValueError, match="slowest"):
        ForwardConfig("data", R, CAP, exchange="hierarchical", fast_size=4)


def test_config_rejects_missing_fast_size():
    with pytest.raises(ValueError, match="fast_size"):
        ForwardConfig(AXES, R, CAP, exchange="hierarchical")


def test_config_rejects_non_dividing_fast_size():
    with pytest.raises(ValueError, match="divide"):
        ForwardConfig(AXES, R, CAP, exchange="hierarchical", fast_size=3)


def test_config_three_axes_need_level_sizes():
    """N>2 tiers cannot be derived from the 2-level fast_size alias alone."""
    with pytest.raises(ValueError, match="level_sizes"):
        ForwardConfig(AXES3, R, CAP, exchange="hierarchical", fast_size=4)
    cfg = ForwardConfig(
        AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 2, 2)
    )
    assert cfg.level_sizes == (2, 2, 2)
    assert len(cfg.level_capacities) == 3
    # legacy aliases mirror the fastest / slowest tiers
    assert cfg.fast_size == 2
    assert cfg.peer_capacity == cfg.level_capacities[-1]
    assert cfg.node_capacity == cfg.level_capacities[0]


def test_config_rejects_bad_level_sizes():
    with pytest.raises(ValueError, match="multiply"):
        ForwardConfig(
            AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 2, 4)
        )
    with pytest.raises(ValueError, match="one rank count per"):
        ForwardConfig(
            AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 4)
        )
    with pytest.raises(ValueError, match="contradicts"):
        ForwardConfig(
            AXES, R, CAP, exchange="hierarchical", level_sizes=(2, 4), fast_size=2
        )
    with pytest.raises(ValueError, match="one segment size per"):
        ForwardConfig(
            AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 2, 2),
            level_capacities=(8, 8),
        )
    with pytest.raises(ValueError, match="contradicts"):
        ForwardConfig(
            AXES, R, CAP, exchange="hierarchical", level_sizes=(2, 4),
            level_capacities=(8, 8), peer_capacity=16,
        )


def test_config_rejects_hierarchical_fields_on_flat_backends():
    """Flat backends would silently ignore topology fields — reject them."""
    for exchange in ("padded", "ragged", "onehot"):
        with pytest.raises(ValueError, match="hierarchical"):
            ForwardConfig("data", R, CAP, exchange=exchange, fast_size=4)
        with pytest.raises(ValueError, match="hierarchical"):
            ForwardConfig("data", R, CAP, exchange=exchange, node_capacity=8)
        with pytest.raises(ValueError, match="hierarchical"):
            ForwardConfig("data", R, CAP, exchange=exchange, level_sizes=(2, 4))
        with pytest.raises(ValueError, match="hierarchical"):
            ForwardConfig(
                "data", R, CAP, exchange=exchange, level_capacities=(8, 8)
            )


def test_config_rejects_peer_capacity_on_slotless_backends():
    """ragged segments are contiguous and onehot gathers everything — a
    peer_capacity there is a config bug, not a tuning knob."""
    for exchange in ("ragged", "onehot"):
        with pytest.raises(ValueError, match="peer_capacity"):
            ForwardConfig("data", R, CAP, exchange=exchange, peer_capacity=8)


def test_config_rejects_nonpositive_shapes():
    with pytest.raises(ValueError, match="positive"):
        ForwardConfig("data", 0, CAP, exchange="padded")
    with pytest.raises(ValueError, match="positive"):
        ForwardConfig("data", R, 0, exchange="padded")
    with pytest.raises(ValueError, match="sort_method"):
        ForwardConfig("data", R, CAP, exchange="padded", sort_method="bogus")


def test_default_capacities_match_backend_fanout():
    """The peer_capacity default must track the backend's true fan-out:
    R per-rank slots for flat padded, fast_size per-lane slots (stage A) and
    R/fast_size per-node segments (stage B) for hierarchical."""
    flat = ForwardConfig("data", R, CAP, exchange="padded")
    assert flat.peer_capacity == 2 * -(-CAP // R)
    hier = ForwardConfig(AXES, R, CAP, exchange="hierarchical", fast_size=4)
    assert hier.peer_capacity == 2 * -(-CAP // 4)  # stage A: F peers
    assert hier.node_capacity == 2 * -(-CAP // 2)  # stage B: N=2 nodes
    hier42 = ForwardConfig(AXES, R, CAP, exchange="hierarchical", fast_size=2)
    assert hier42.peer_capacity == 2 * -(-CAP // 2)
    assert hier42.node_capacity == 2 * -(-CAP // 4)
    # explicit values always win
    explicit = ForwardConfig(
        AXES, R, CAP, exchange="hierarchical", fast_size=4,
        peer_capacity=7, node_capacity=11,
    )
    assert explicit.peer_capacity == 7 and explicit.node_capacity == 11
