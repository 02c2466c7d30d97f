"""The bucket-scatter marshal (ISSUE 4): bit-exactness + drop accounting.

``ForwardConfig(marshal="scatter")`` must be *observationally identical* to
the sort path (and hence to the ``onehot`` oracle): same counts, same drops,
bit-exact placement — the scatter reproduces the §4.2.1 lexicographic stable
source order without ever sorting.  Property-tested on flat and 2/3-level
hierarchical meshes, including the hot-spot, the all-DISCARD round, and
sender/receiver capacity overflow; the Pallas ``bucket_scatter`` path is
pinned against the XLA path (interpret mode off the TPU).

The drop-accounting regression: when ONE overflowing segment is clamped at
MULTIPLE hierarchy tiers, every dropped item must be counted exactly once —
asserted with exact per-stage-derivable numbers, not just conservation.
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
AXES3 = ("pod", "node", "device")


@work_item
@dataclasses.dataclass
class Item:
    val: jax.Array
    src: jax.Array


def _make_fn(mesh, cfg, axes="data"):
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


def _run_pair(fn_a, fn_b, counts, dest, val):
    """Counts, drops, termination total and valid-prefix placement must be
    bit-identical between the two configs (tails are garbage/zeros)."""
    args = (
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(counts),
    )
    a = [np.asarray(x) for x in fn_a(*args)]
    b = [np.asarray(x) for x in fn_b(*args)]
    np.testing.assert_array_equal(a[2], b[2], err_msg="per-rank receive counts")
    av, as_ = a[0].reshape(R, CAP), a[1].reshape(R, CAP)
    bv, bs = b[0].reshape(R, CAP), b[1].reshape(R, CAP)
    for r in range(R):
        n = int(a[2].reshape(-1)[r])
        np.testing.assert_array_equal(av[r][:n], bv[r][:n])
        np.testing.assert_array_equal(as_[r][:n], bs[r][:n])
    assert int(a[3].sum()) == int(b[3].sum()), "global drops"
    assert int(a[4]) == int(b[4]), "termination total"
    lane = np.arange(CAP)[None, :]
    emitted = int(((lane < counts[:, None]) & (dest >= 0) & (dest < R)).sum())
    assert int(a[2].sum()) + int(a[3].sum()) == emitted, "conservation"


# ----------------------------------------------------------- flat exchanges
@pytest.fixture(scope="module")
def flat_fns(mesh8):
    """Four flat configs on the 8-way mesh: scatter/sort at the DEFAULT
    (tight) peer slots pin the sender-clamp behaviour against each other;
    scatter at AMPLE slots (peer_capacity=CAP — the receiver clamp is then
    the only drop site, same as the oracle's) is pinned against onehot."""
    return (
        _make_fn(mesh8, ForwardConfig("data", R, CAP, exchange="padded", marshal="scatter")),
        _make_fn(mesh8, ForwardConfig("data", R, CAP, exchange="padded")),
        _make_fn(
            mesh8,
            ForwardConfig(
                "data", R, CAP, exchange="padded", marshal="scatter",
                peer_capacity=CAP,
            ),
        ),
        _make_fn(mesh8, ForwardConfig("data", R, CAP, exchange="onehot")),
    )


@given(data=st.data())
@settings(max_examples=15, deadline=None)
def test_flat_scatter_matches_sort_and_onehot(flat_fns, data):
    scatter, sort, scatter_ample, onehot = flat_fns
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(-1, R, (R, CAP)).astype(np.int32)  # incl. DISCARD lanes
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(scatter, sort, counts, dest, val)
    _run_pair(scatter_ample, onehot, counts, dest, val)


def test_flat_scatter_hotspot(flat_fns):
    """Everyone floods rank 0 at full queue — receiver clamp fires."""
    scatter, sort, scatter_ample, onehot = flat_fns
    counts = np.full(R, CAP, np.int32)
    dest = np.zeros((R, CAP), np.int32)
    val = np.random.default_rng(1).normal(size=(R, CAP)).astype(np.float32)
    _run_pair(scatter, sort, counts, dest, val)
    _run_pair(scatter_ample, onehot, counts, dest, val)


def test_flat_scatter_all_discard(flat_fns):
    scatter, sort, *_ = flat_fns
    counts = np.full(R, CAP, np.int32)
    dest = np.full((R, CAP), DISCARD, np.int32)
    val = np.zeros((R, CAP), np.float32)
    _run_pair(scatter, sort, counts, dest, val)


def test_flat_scatter_sender_overflow(mesh8):
    """peer_capacity clamp: the scatter's rank >= S cut must drop exactly the
    rows the sort path's segment clamp drops — same items, same counts."""
    scatter = _make_fn(
        mesh8,
        ForwardConfig("data", R, CAP, exchange="padded", marshal="scatter", peer_capacity=3),
    )
    sort = _make_fn(
        mesh8, ForwardConfig("data", R, CAP, exchange="padded", peer_capacity=3)
    )
    rng = np.random.default_rng(5)
    counts = np.full(R, CAP, np.int32)
    dest = rng.integers(0, 3, (R, CAP)).astype(np.int32)  # 3 hot destinations
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(scatter, sort, counts, dest, val)


@pytest.mark.parametrize("exchange", ["padded", "onehot"])
def test_flat_scatter_backend_self_consistency(mesh8, exchange):
    """scatter mode of each flat backend vs its own sort mode."""
    scatter = _make_fn(
        mesh8, ForwardConfig("data", R, CAP, exchange=exchange, marshal="scatter")
    )
    sort = _make_fn(mesh8, ForwardConfig("data", R, CAP, exchange=exchange))
    rng = np.random.default_rng(9)
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(-1, R, (R, CAP)).astype(np.int32)
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(scatter, sort, counts, dest, val)


def test_ragged_scatter_lowers_with_one_ragged_collective(mesh8):
    """The ragged backend's scatter mode must still lower to the single
    ragged_all_to_all + one count all_gather (budget unchanged)."""
    from repro.roofline.analysis import collective_ops

    cfg = ForwardConfig("data", R, CAP, exchange="ragged", marshal="scatter")
    fn = _make_fn(mesh8, cfg)
    txt = fn.lower(
        jnp.zeros(R * CAP), jnp.zeros(R * CAP, jnp.int32), jnp.zeros(R, jnp.int32)
    ).as_text()
    ops = collective_ops(txt)
    assert sum(1 for k, _ in ops if k == "ragged-all-to-all") == 1, ops
    assert sum(1 for k, _ in ops if k == "all-to-all") == 0, ops


# -------------------------------------------------- hierarchical exchanges
def _hier_cfg(level_sizes, ample, **kw):
    if ample:
        caps, mult = [], 1
        for a in reversed(level_sizes):
            caps.append(CAP * mult)
            mult *= a
        kw["level_capacities"] = tuple(reversed(caps))
    axes = AXES3 if len(level_sizes) == 3 else ("node", "device")
    return ForwardConfig(
        axes, R, CAP, exchange="hierarchical", level_sizes=level_sizes, **kw
    )


@pytest.fixture(scope="module")
def hier3_fns(mesh_pods222):
    """(scatter, sort, onehot) on the (2, 2, 2) mesh with ample stage caps."""
    return (
        _make_fn(mesh_pods222, _hier_cfg((2, 2, 2), True, marshal="scatter"), AXES3),
        _make_fn(mesh_pods222, _hier_cfg((2, 2, 2), True), AXES3),
        _make_fn(
            mesh_pods222, ForwardConfig(AXES3, R, CAP, exchange="onehot"), AXES3
        ),
    )


@given(data=st.data())
@settings(max_examples=12, deadline=None)
def test_3level_scatter_matches_sort_and_onehot(hier3_fns, data):
    scatter, sort, onehot = hier3_fns
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(-1, R, (R, CAP)).astype(np.int32)
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(scatter, sort, counts, dest, val)
    _run_pair(scatter, onehot, counts, dest, val)


def test_3level_scatter_hotspot(hier3_fns):
    scatter, sort, onehot = hier3_fns
    counts = np.full(R, CAP, np.int32)
    dest = np.zeros((R, CAP), np.int32)
    val = np.random.default_rng(2).normal(size=(R, CAP)).astype(np.float32)
    _run_pair(scatter, sort, counts, dest, val)
    _run_pair(scatter, onehot, counts, dest, val)


def test_3level_scatter_all_discard(hier3_fns):
    scatter, sort, _ = hier3_fns
    counts = np.full(R, CAP, np.int32)
    dest = np.full((R, CAP), DISCARD, np.int32)
    _run_pair(scatter, sort, counts, dest, np.zeros((R, CAP), np.float32))


@given(data=st.data())
@settings(max_examples=8, deadline=None)
def test_2level_scatter_matches_sort_tight_caps(mesh_nodes24, data):
    """Default (tight) stage capacities under skew: both modes clamp the same
    sub-segments at the same tiers."""
    scatter = _make_fn(
        mesh_nodes24, _hier_cfg((2, 4), False, marshal="scatter"), ("node", "device")
    )
    sort = _make_fn(mesh_nodes24, _hier_cfg((2, 4), False), ("node", "device"))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(0, R, (R, CAP)).astype(np.int32)
    dest[::2] = 0  # heavy skew
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(scatter, sort, counts, dest, val)


@pytest.mark.parametrize(
    "shape", [(1, 2, 4), (2, 1, 4), (2, 4, 1), (1, 1, 8)],
    ids=lambda s: "x".join(map(str, s)),
)
def test_3level_scatter_degenerate_axes(shape):
    """Extent-1 tiers anywhere: the scatter stage composition must follow the
    same skipped-stage structure as the sort path."""
    from repro.launch.mesh import make_pod_mesh

    mesh = make_pod_mesh(*shape)
    scatter = _make_fn(
        mesh, _hier_cfg(shape, True, marshal="scatter"), AXES3
    )
    sort = _make_fn(mesh, _hier_cfg(shape, True), AXES3)
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
        _run_pair(scatter, sort, counts, dest, val)


# ------------------------------------------------------------- Pallas path
@pytest.mark.parametrize("kind", ["flat", "hier3"])
def test_scatter_pallas_path_matches_xla_path(mesh8, mesh_pods222, kind):
    """use_pallas=True routes the plan through kernels/bucket_scatter and the
    payload pass through its scatter kernel — bit-exact with the XLA path."""
    if kind == "flat":
        mesh, axes = mesh8, "data"
        mk = lambda up: ForwardConfig(
            "data", R, CAP, exchange="padded", marshal="scatter", use_pallas=up
        )
    else:
        mesh, axes = mesh_pods222, AXES3
        mk = lambda up: _hier_cfg((2, 2, 2), True, marshal="scatter", use_pallas=up)
    fn_p = _make_fn(mesh, mk(True), axes)
    fn_x = _make_fn(mesh, mk(False), axes)
    rng = np.random.default_rng(13)
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = rng.integers(-1, R, (R, CAP)).astype(np.int32)
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    _run_pair(fn_p, fn_x, counts, dest, val)


# ------------------------------------------------------------------ cycling
@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_cycling_scatter_delivers_everything(mesh8, use_pallas):
    """§6.3 cycling with the sort-free hop compaction delivers every item."""
    from repro.core import enqueue, make_queue
    from repro.core.cycling import deliver_by_cycling

    cfg = ForwardConfig(
        "data", R, CAP, exchange="padded", marshal="scatter", use_pallas=use_pallas
    )

    def kernel(_x):
        proto = Item(val=jnp.zeros(()), src=jnp.zeros((), jnp.int32))
        q = make_queue(proto, CAP)
        me = jax.lax.axis_index("data")
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
            kernel, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), P(), P("data")),
        )
    )
    counts, total, vals = f(jnp.arange(8.0))
    counts = np.asarray(counts)
    vals = np.asarray(vals).reshape(R, CAP)
    assert int(total) == R * 6
    got = sorted(int(vals[r, i]) for r in range(R) for i in range(counts[r]))
    assert got == sorted(s * 100 + k for s in range(R) for k in range(6))


# ---------------------------------------------------------------- rebalance
def test_rebalance_scatter_matches_sort(mesh_pods222):
    """Topology-aware rebalance (global + intra scope) under the scatter
    marshal — including the intra path's derived fast-axis sub-config."""
    from repro.core import rebalance
    from repro.core import types as T  # noqa: F401

    def run(marshal, scope):
        cfg = ForwardConfig(
            AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 2, 2),
            marshal=marshal,
        )

        def bal(_x):
            me = jax.lax.axis_index(AXES3)
            n = jnp.where(me % 2 == 0, 40, 2)
            proto_val = (jnp.arange(CAP) + me * 1000).astype(jnp.float32)
            q = WorkQueue(
                items=Item(val=proto_val, src=me * jnp.ones(CAP, jnp.int32)),
                dest=jnp.full((CAP,), DISCARD, jnp.int32),
                count=n.astype(jnp.int32),
                drops=jnp.zeros((), jnp.int32),
            )
            nq, total = rebalance(q, cfg, scope=scope)
            return nq.items.val, nq.count[None], total

        f = jax.jit(
            jax.shard_map(
                bal, mesh=mesh_pods222, in_specs=P(AXES3),
                out_specs=(P(AXES3), P(AXES3), P()),
            )
        )
        return [np.asarray(x) for x in f(jnp.arange(8.0))]

    for scope in ("global", "intra"):
        a = run("scatter", scope)
        b = run("sort", scope)
        np.testing.assert_array_equal(a[1], b[1], err_msg=scope)
        av, bv = a[0].reshape(R, CAP), b[0].reshape(R, CAP)
        for r in range(R):
            n = int(a[1].reshape(-1)[r])
            np.testing.assert_array_equal(av[r][:n], bv[r][:n], err_msg=scope)
        assert int(a[2]) == int(b[2])


# ------------------------------------------- drop accounting (exactly once)
@pytest.mark.parametrize("marshal", ["sort", "scatter"])
def test_multi_tier_clamps_count_each_drop_exactly_once(mesh_pods222, marshal):
    """One hot segment (everyone → rank 0) overflows EVERY tier of a
    (2, 2, 2) route with level_capacities=(4, 4, 4).  Exact accounting:

      stage device: each of 8 ranks clamps its 10-row dest-0 sub-segment to 4
                    → 6·8 = 48 drops;
      stage node:   ranks with device digit 0 hold [4, 4] rows for dest 0,
                    clamp the 8-row concatenation to 4 → 4·4 = 16 drops;
      stage pod:    ranks 0 and 4 hold [4, 4], clamp to 4 → 4·2 = 8 drops;
      receiver:     rank 0 gets 4 + 4 = 8 ≤ capacity → 0 drops.

    An item clamped at one tier must never re-enter a later tier's (or the
    receiver's) count: globally received + dropped == emitted with these
    EXACT stage numbers — a double count would inflate drops past 72."""
    cfg = ForwardConfig(
        AXES3, R, CAP, exchange="hierarchical", level_sizes=(2, 2, 2),
        level_capacities=(4, 4, 4), marshal=marshal,
    )
    fn = _make_fn(mesh_pods222, cfg, AXES3)
    counts = np.full(R, 10, np.int32)
    dest = np.zeros((R, CAP), np.int32)
    val = np.random.default_rng(4).normal(size=(R, CAP)).astype(np.float32)
    _v, _s, out_counts, out_drops, total = fn(
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(counts),
    )
    out_counts = np.asarray(out_counts).reshape(-1)
    assert out_counts[0] == 8 and out_counts[1:].sum() == 0, out_counts
    assert int(np.asarray(out_drops).sum()) == 48 + 16 + 8, np.asarray(out_drops)
    assert int(total) + int(np.asarray(out_drops).sum()) == 8 * 10
    assert int(total) == 8


@pytest.mark.parametrize("marshal", ["sort", "scatter"])
def test_flat_sender_and_receiver_clamps_count_once(mesh8, marshal):
    """Flat analogue: sender slot clamp (10 → 4 per source) and receiver
    capacity clamp (32 → CAP would not fire at 64, so emit 10 → recv 8·10=80
    > 64) must sum, never overlap, in the drop counter."""
    cfg = ForwardConfig(
        "data", R, CAP, exchange="padded", peer_capacity=10, marshal=marshal
    )
    fn = _make_fn(mesh8, cfg)
    counts = np.full(R, 10, np.int32)
    dest = np.zeros((R, CAP), np.int32)  # everyone → rank 0
    val = np.random.default_rng(6).normal(size=(R, CAP)).astype(np.float32)
    _v, _s, out_counts, out_drops, total = fn(
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(counts),
    )
    out_counts = np.asarray(out_counts).reshape(-1)
    # no sender clamp (10 ≤ 10); receiver: 80 arrive, 64 fit, 16 dropped
    assert out_counts[0] == CAP, out_counts
    assert int(np.asarray(out_drops).sum()) == 8 * 10 - CAP
    assert int(total) == CAP
