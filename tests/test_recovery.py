"""Recovery-law property tests (ISSUE 7): checkpoint/resume, elastic
restore, health-aware draining, and the conservation watchdog.

The load-bearing claims, each checked against independent evidence:

* **Preempt-resume is bit-exact** — a drive halted at a checkpoint boundary
  and resumed from disk publishes byte-identical checkpoints at every
  boundary the uninterrupted run also published (SHA-256 manifest digests
  over EVERY carry leaf: queue payloads, dests, ages, checksums, telemetry
  ring, counters), in every overflow × marshal combination and on a
  hierarchical route.  Not statistically equal — the same trajectory.
* **Elastic restore conserves** — a burst saved on R ranks resumed on
  R′ < R drains to completion with the global delivery checksums equal to
  the schedule's and zero loss.
* **Draining loses nothing** — a mid-burst rank brownout re-addresses
  traffic through the pure-local health remap; the device trajectory
  (deliveries, rounds, retained/age traces) matches the health-aware numpy
  twin exactly and the browned-out ranks receive nothing after the mask
  flips.
* **The watchdog bites** — a carry whose books don't balance raises before
  it can be checkpointed.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import compat
from repro.chaos import (
    boundary_digests,
    brownout_mask,
    capacity_drought,
    convergecast,
    expected_by_rank,
    rank_brownout,
    run_scenario,
    run_scenario_checkpointed,
    simulate_flat_retain,
)
from repro.core import (
    DISCARD,
    ForwardConfig,
    conservation_check,
    health_table,
    make_queue,
    rebalance,
    remap_dest,
)
from repro.core.context import RafiContext
from repro.core.queue import WorkQueue

from helpers import make_rays, ray_proto

pytestmark = pytest.mark.recovery

R = 8
S = 2
FLAT_CAP = 128
_M32 = 1 << 32


@pytest.fixture(scope="module")
def mesh4():
    """A 4-of-8-device mesh — the shrunken world elastic restore lands on."""
    return compat.make_mesh((4,), ("data",))


# ------------------------------------------------------------ health remap
def test_health_table_law():
    """table[d] == d for healthy d; == healthy[d % n_h] for unhealthy d;
    identity when everything (or nothing) is healthy."""
    h = np.array([1, 1, 0, 1, 0, 1, 1, 1], bool)
    healthy = np.nonzero(h)[0]
    table = np.asarray(health_table(jnp.asarray(h)))
    for d in range(R):
        want = d if h[d] else healthy[d % len(healthy)]
        assert table[d] == want, (d, table)
    assert (np.asarray(health_table(jnp.ones(R, bool))) == np.arange(R)).all()
    # all-unhealthy degenerates to the identity (shutdown is not a remap)
    assert (np.asarray(health_table(jnp.zeros(R, bool))) == np.arange(R)).all()


def test_remap_dest_passes_discard_through():
    h = jnp.asarray(np.array([1, 0, 1, 1, 1, 1, 1, 1], bool))
    dest = jnp.array([0, 1, DISCARD, 7, 1], jnp.int32)
    out = np.asarray(remap_dest(dest, h))
    assert out[2] == DISCARD
    assert out[0] == 0 and out[3] == 7
    assert out[1] == out[4] != 1 and bool(h[out[1]])


def test_all_healthy_mask_is_bitidentical_to_no_mask(mesh8):
    """health=None and an all-True mask must produce the same run, bit for
    bit — the remap is provably the identity, not merely harmless."""
    sc = capacity_drought()
    kw = dict(capacity=FLAT_CAP, peer_capacity=S, overflow="retain")
    a = run_scenario(mesh8, sc, **kw)
    b = run_scenario(mesh8, sc, health=np.ones(R, bool), **kw)
    np.testing.assert_array_equal(a["delivered"], b["delivered"])
    assert a["rounds"] == b["rounds"]
    np.testing.assert_array_equal(a["retained_trace"], b["retained_trace"])
    np.testing.assert_array_equal(a["age_trace"], b["age_trace"])


def test_constant_drain_matches_twin_and_starves_drained_ranks(mesh8):
    """A rank unhealthy from round 0 never receives a single row, and the
    whole trajectory matches the health-aware numpy twin."""
    sc = capacity_drought()
    h = np.ones(R, bool)
    h[[2, 5]] = False
    sim = simulate_flat_retain(sc, peer_capacity=S, capacity=FLAT_CAP, health=h)
    res = run_scenario(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        health=h,
    )
    np.testing.assert_array_equal(res["delivered"], sim["delivered"])
    assert res["drops"] == 0 and res["lost"] == 0 and res["done"]
    assert res["rounds"] == sim["rounds"]
    # the drained ranks delivered nothing; the traffic arrived elsewhere
    assert res["delivered"][2].sum() == 0 and res["delivered"][5].sum() == 0
    assert res["delivered_total"] == sc.emitted


# ------------------------------------------------- preempt-resume bit-exact
PREEMPT_CASES = [
    ("drop", "sort"),
    ("drop", "scatter"),
    ("retain", "sort"),
    ("retain", "scatter"),
]


@pytest.mark.parametrize("overflow,marshal", PREEMPT_CASES)
def test_preempt_resume_bitexact_flat(tmp_path, mesh8, overflow, marshal):
    """Kill at a boundary, resume from disk: every boundary checkpoint of
    the resumed run is BYTE-identical (manifest SHA-256 per carry leaf) to
    the uninterrupted run's, and the final accounting matches the plain
    un-checkpointed drive."""
    sc = capacity_drought()
    kw = dict(
        capacity=FLAT_CAP, peer_capacity=S, overflow=overflow, marshal=marshal,
    )
    ref = run_scenario(mesh8, sc, **kw)
    a = run_scenario_checkpointed(
        mesh8, sc, ckpt_dir=tmp_path / "a", checkpoint_every=3, keep=99, **kw
    )
    b = run_scenario_checkpointed(
        mesh8, sc, ckpt_dir=tmp_path / "b", checkpoint_every=3, keep=99,
        preempt_at=5, **kw
    )
    assert b["preempted"] and not a["preempted"]
    np.testing.assert_array_equal(a["delivered"], ref["delivered"])
    np.testing.assert_array_equal(b["delivered"], ref["delivered"])
    assert a["rounds"] == b["rounds"] == ref["rounds"]
    assert a["lost"] == b["lost"] == 0
    da, db = boundary_digests(tmp_path / "a"), boundary_digests(tmp_path / "b")
    common = sorted(set(da) & set(db))
    assert len(common) >= 3  # boundaries 0, 3, … and the final one
    for step in common:
        assert da[step] == db[step], f"state diverged at boundary {step}"
    assert a["steps"] == b["steps"]  # same boundaries published


def test_preempt_resume_bitexact_hierarchical(tmp_path, mesh_pods222):
    """The recovery law composes with the 3-level route + telemetry +
    retain — the carry is bigger (ring, ages) but the digests still agree
    at every common boundary."""
    sc = convergecast(R)
    kw = dict(
        capacity=256, axis_name=("pod", "node", "device"),
        exchange="hierarchical", level_capacities=(8, 8, 8),
        overflow="retain", max_rounds=128,
    )
    a = run_scenario_checkpointed(
        mesh_pods222, sc, ckpt_dir=tmp_path / "a", checkpoint_every=4,
        keep=99, **kw
    )
    b = run_scenario_checkpointed(
        mesh_pods222, sc, ckpt_dir=tmp_path / "b", checkpoint_every=4,
        keep=99, preempt_at=6, **kw
    )
    assert b["preempted"]
    np.testing.assert_array_equal(a["delivered"], expected_by_rank(sc))
    np.testing.assert_array_equal(b["delivered"], expected_by_rank(sc))
    da, db = boundary_digests(tmp_path / "a"), boundary_digests(tmp_path / "b")
    for step in sorted(set(da) & set(db)):
        assert da[step] == db[step], f"state diverged at boundary {step}"


def test_checkpointing_does_not_change_the_answer(tmp_path, mesh8):
    """ckpt_dir=None (segmented drive, no I/O) and a full checkpointed run
    agree with each other — segmentation alone is invisible."""
    sc = capacity_drought()
    kw = dict(capacity=FLAT_CAP, peer_capacity=S, overflow="retain")
    nockpt = run_scenario_checkpointed(
        mesh8, sc, ckpt_dir=None, checkpoint_every=3, **kw
    )
    assert nockpt["steps"] == []  # nothing was written anywhere
    withckpt = run_scenario_checkpointed(
        mesh8, sc, ckpt_dir=tmp_path, checkpoint_every=3, **kw
    )
    np.testing.assert_array_equal(nockpt["delivered"], withckpt["delivered"])
    assert nockpt["rounds"] == withckpt["rounds"]
    np.testing.assert_array_equal(
        nockpt["retained_trace"], withckpt["retained_trace"]
    )


# --------------------------------------------------------- elastic restore
def test_elastic_restore_r8_to_r4_conserves(tmp_path, mesh8, mesh4):
    """Preempt on 8 ranks in the drain phase, resume on 4: the folded
    backlog drains to completion, the GLOBAL delivery checksums equal the
    schedule's, and nothing is lost or dropped."""
    sc = capacity_drought()
    res = run_scenario_checkpointed(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        ckpt_dir=tmp_path, checkpoint_every=3, keep=99,
        preempt_at=7, resume_mesh=mesh4, resume_capacity=256,
    )
    assert res["preempted"] and res["done"]
    assert res["lost"] == 0 and res["drops"] == 0
    exp = expected_by_rank(sc).astype(np.uint64)
    got = res["delivered"].astype(np.uint64)
    assert got.shape[0] == 4  # the resumed world really is 4 ranks
    assert int(got[:, 0].sum()) == int(exp[:, 0].sum())
    assert int(got[:, 1].sum() % _M32) == int(exp[:, 1].sum() % _M32)
    assert int(got[:, 2].sum() % _M32) == int(exp[:, 2].sum() % _M32)


def test_elastic_restore_worst_case_backlog(tmp_path, mesh8, mesh4):
    """Convergecast leaves the biggest possible single-destination backlog
    at the preempt boundary; folding it onto half the ranks must still
    close the books (the slow drain is the price, not loss)."""
    sc = convergecast(R)
    res = run_scenario_checkpointed(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        ckpt_dir=tmp_path, checkpoint_every=3, keep=99,
        preempt_at=7, resume_mesh=mesh4, resume_capacity=256,
    )
    assert res["preempted"] and res["done"]
    assert res["lost"] == 0 and res["drops"] == 0
    assert res["delivered_total"] == sc.emitted


# ------------------------------------------------------------ rank brownout
def test_rank_brownout_loses_nothing_and_matches_twin(tmp_path, mesh8):
    """Mid-burst brownout via the per-segment health schedule: the device
    trajectory equals the numpy twin fed the SAME segment-quantized health
    law, zero rows are lost, and the dark ranks stop receiving within one
    segment of the mask flip."""
    sc = rank_brownout()
    W = 3
    health = brownout_mask(R, down=(2, 5), down_from=3)

    # the segmented drive re-reads health at each boundary: forward 0 uses
    # health(0); forward f >= 1 belongs to the segment starting at boundary
    # W * ((f - 1) // W)
    def twin_health(f):
        return health(0) if f == 0 else health(W * ((f - 1) // W))

    sim = simulate_flat_retain(
        sc, peer_capacity=S, capacity=FLAT_CAP, health=twin_health
    )
    res = run_scenario_checkpointed(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        ckpt_dir=tmp_path, checkpoint_every=W, keep=99, health=health,
    )
    np.testing.assert_array_equal(res["delivered"], sim["delivered"])
    assert res["rounds"] == sim["rounds"]
    assert res["lost"] == 0 and res["drops"] == 0 and res["done"]
    assert res["delivered_total"] == sc.emitted
    np.testing.assert_array_equal(res["retained_trace"], sim["retained_trace"])
    np.testing.assert_array_equal(res["age_trace"], sim["age_trace"])


# ---------------------------------------------------------------- watchdog
def test_watchdog_passes_balanced_books():
    conservation_check(
        {
            "emitted": np.array([10, 10], np.int32),
            "delivered": np.array([7, 5], np.int32),
            "total": np.int32(6),
            "drops": np.array([1, 1], np.int32),
        }
    )


def test_watchdog_raises_on_leak():
    with pytest.raises(RuntimeError, match="conservation violated"):
        conservation_check(
            {
                "emitted": np.array([10, 10], np.int32),
                "delivered": np.array([7, 5], np.int32),
                "total": np.int32(5),  # one row vanished
                "drops": np.array([1, 1], np.int32),
            },
            where="round 4",
        )


# ------------------------------------------------------- resume validation
def test_resume_rejects_mismatched_context(tmp_path, mesh8):
    """A checkpoint written by a retain drive must refuse to resume under a
    drop-mode context (silent semantic drift), with a typed error."""
    from repro.core import recovery
    from repro.chaos.driver import _make_ctx, _make_round_fn

    sc = capacity_drought()
    run_scenario_checkpointed(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        ckpt_dir=tmp_path, checkpoint_every=3, keep=99, preempt_at=5,
    )
    ctx = _make_ctx(
        mesh8, capacity=FLAT_CAP, peer_capacity=S, overflow="drop"
    )
    spec = ctx._spec
    with pytest.raises(ValueError, match="overflow"):
        recovery.resume_run(
            ctx, _make_round_fn(ctx, sc), tmp_path,
            aux_specs=(spec, spec, spec),
            aux_like=tuple(np.zeros((R,), np.uint32) for _ in range(3)),
        )
    with pytest.raises(FileNotFoundError):
        recovery.resume_run(
            ctx, _make_round_fn(ctx, sc), tmp_path / "empty",
            aux_specs=(spec, spec, spec),
            aux_like=tuple(np.zeros((R,), np.uint32) for _ in range(3)),
        )


# ------------------------------------------------- truncated-run age return
def test_truncated_retain_run_returns_live_ages(mesh8):
    """Satellite: a retain drive cut off by ``max_rounds`` hands back the
    REAL per-lane age vector of the still-queued rows, so a continuation
    keeps the FIFO anti-starvation clock instead of resetting it.

    Construction: rank 0 holds 6 rows for rank 1 behind a 2-row clamp and
    nothing else ever emits.  After the initial forward + one body round,
    exactly 2 rows remain retained on rank 0 having waited 2 forwards each
    — the returned ages must say [2, 2], not zeros."""
    ctx = RafiContext(
        mesh8, ray_proto(), capacity=FLAT_CAP, peer_capacity=S,
        exchange="padded", overflow="retain",
    )

    def round_fn(q_in, acc, rnd):
        # pure consumer: arrivals are retired, nothing new is emitted
        return make_queue(ray_proto(), FLAT_CAP), acc + q_in.count

    drive = ctx.run_until_done(
        round_fn, aux_specs=ctx._spec, max_rounds=1
    )
    uid = np.zeros((R * FLAT_CAP,), np.int32)
    dest = np.full((R * FLAT_CAP,), DISCARD, np.int32)
    count = np.zeros((R,), np.int32)
    dest[:6] = 1  # six rows on rank 0, all for rank 1
    count[0] = 6
    rays = jax.tree.map(
        lambda a: jnp.zeros((R * FLAT_CAP,) + a.shape, a.dtype), ray_proto()
    )
    q0 = WorkQueue(
        items=rays, dest=jnp.asarray(dest), count=jnp.asarray(count),
        drops=jnp.zeros((R,), jnp.int32),
    )
    q, acc, rounds, done, age = drive(q0, jnp.zeros((R,), jnp.int32))
    assert int(rounds) == 1 and not bool(done)  # truncated, work in flight
    ages = np.asarray(age)
    assert sorted(ages[ages > 0].tolist()) == [2, 2], ages[:8]
    # the two aged rows sit at rank 0's queue front, dest intact
    assert np.asarray(q.count)[0] == 2
    assert list(np.asarray(q.dest)[:2]) == [1, 1]


# ------------------------------------------------- health-aware rebalance
def test_rebalance_evacuates_unhealthy_rank(mesh8):
    """The drain recipe: mark a rank unhealthy and run one health-aware
    global rebalance — its resident rows land on survivors, it receives
    nothing, and the population stays conserved."""
    cfg = ForwardConfig("data", R, FLAT_CAP, peer_capacity=32, exchange="padded")
    n = 16

    def kernel(_x, h):
        # every rank holds n resident rows (dest DISCARD = unaddressed)
        q = WorkQueue(
            items=make_rays(FLAT_CAP),
            dest=jnp.full((FLAT_CAP,), DISCARD, jnp.int32),
            count=jnp.int32(n),
            drops=jnp.zeros((), jnp.int32),
        )
        balanced, total = rebalance(q, cfg, health=h)
        return balanced.count[None], total, balanced.drops[None]

    from jax.sharding import PartitionSpec as P
    from repro import compat

    f = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh8, in_specs=(P("data"), P()),
            out_specs=(P("data"), P(), P("data")),
        )
    )
    h = np.ones(R, bool)
    h[3] = False
    count, total, drops = f(jnp.arange(8.0), jnp.asarray(h))
    count = np.asarray(count)
    assert count[3] == 0, count  # the draining rank is empty
    assert int(np.asarray(drops).sum()) == 0
    assert count.sum() == R * n == int(total)  # conserved, just moved
    # intra-scope health is rejected loudly, not silently ignored
    hier = ForwardConfig(
        ("node", "device"), R, FLAT_CAP, exchange="hierarchical",
        fast_size=4,
    )
    with pytest.raises(ValueError, match="global"):
        rebalance(
            make_queue(ray_proto(), FLAT_CAP), hier, scope="intra",
            health=jnp.ones(R, bool),
        )


# ------------------------------------------------- pipelined (the overlap law)
@pytest.mark.pipeline
def test_preempt_resume_bitexact_pipelined(tmp_path, mesh8):
    """Recovery law x overlap law: a micro-shard pipelined drive
    (``pipeline_shards=2``) checkpoints and resumes with byte-identical
    boundary digests, and its answer equals the bulk (unsharded) drive's —
    pipelining is invisible to the carry."""
    sc = capacity_drought()
    kw = dict(
        capacity=FLAT_CAP, peer_capacity=S, overflow="retain",
        pipeline_shards=2,
    )
    ref = run_scenario(
        mesh8, sc, capacity=FLAT_CAP, peer_capacity=S, overflow="retain"
    )
    a = run_scenario_checkpointed(
        mesh8, sc, ckpt_dir=tmp_path / "a", checkpoint_every=3, keep=99, **kw
    )
    b = run_scenario_checkpointed(
        mesh8, sc, ckpt_dir=tmp_path / "b", checkpoint_every=3, keep=99,
        preempt_at=5, **kw
    )
    assert b["preempted"] and not a["preempted"]
    np.testing.assert_array_equal(a["delivered"], ref["delivered"])
    np.testing.assert_array_equal(b["delivered"], ref["delivered"])
    assert a["rounds"] == b["rounds"] == ref["rounds"]
    assert a["lost"] == b["lost"] == 0
    da, db = boundary_digests(tmp_path / "a"), boundary_digests(tmp_path / "b")
    common = sorted(set(da) & set(db))
    assert len(common) >= 3
    for step in common:
        assert da[step] == db[step], f"state diverged at boundary {step}"
