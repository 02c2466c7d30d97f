"""Backpressure-law property tests (ISSUE 9): credit-based flow control.

The seventh invariant law — *no wire byte is spent on a row its receiver
cannot admit* — with graceful degradation under sustained overload.  The
load-bearing claims, each checked against independent evidence:

* **Credit is lossless where open flow collapses** — on the two overload
  shapes (fixed hot-pair saturation, full-width incast) open flow wastes
  >30% of its wire rows on receiver drops; credit flow delivers EVERY row
  with ZERO receiver drops, zero emission overflow, goodput exactly 1.0,
  and a first round that ships no payload (cold-start adverts only).
* **The device matches a numpy twin round-for-round** — delivered
  checksums, retained/age/receive traces, and round counts equal
  :func:`repro.chaos.simulate_flat_credit` exactly.  Not statistically
  close — the same trajectory.
* **Apportionment is exact and deterministic** — floor share plus
  rank-ordered residual sums to EXACTLY the advertised space for every
  free value, and the whole credit trajectory is bit-identical across
  marshal modes and shard counts; hierarchical routes (2- and 3-level)
  drain the same overload losslessly.
* **A zero-credit round ships zero payload rows** — a fully un-credited
  forward retains everything at the source, spends no wire on payload,
  and still advertises so the next round can move.
* **Overload accounting splits exactly** — under open flow every counted
  drop is EITHER an emission overflow at the source (the ``emit_overflow``
  counter, satellite 1) or a wasted wire row at the receiver:
  ``drops == emit_overflow + wasted_wire_rows``.  Under credit both terms
  are zero.
* **Recovery composes** — a credit drive preempted at a boundary and
  resumed from disk publishes byte-identical checkpoints (SHA-256 manifest
  digests over every carry leaf, credits included), and resuming a credit
  checkpoint under a different flow mode is refused.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.chaos import (
    boundary_digests,
    expected_by_rank,
    incast_collapse,
    run_scenario,
    run_scenario_checkpointed,
    simulate_flat_credit,
    sustained_overload,
)
from repro.core import ForwardConfig, enqueue, forward_work, make_queue
from repro.core.recovery import resume_run
from repro.chaos.driver import _make_ctx

from helpers import Ray, ray_proto

pytestmark = pytest.mark.backpressure

P = jax.sharding.PartitionSpec

R = 8
_M32 = 1 << 32

# The pinned overload gauntlet: (scenario factory, queue capacity, slot S).
# Both configs make OPEN flow waste >30% of its wire rows at the receivers
# while CREDIT flow drains the identical schedule losslessly.
OVERLOAD = [
    (sustained_overload, 16, 4),
    (incast_collapse, 32, 8),
]
_IDS = ["sustained", "incast"]


def _run(mesh8, sc, cap, S, flow, **kw):
    return run_scenario(
        mesh8, sc, capacity=cap, max_rounds=256, peer_capacity=S,
        overflow="retain", flow=flow, **kw
    )


# ------------------------------------------------ graceful degradation gate
@pytest.mark.parametrize("factory,cap,S", OVERLOAD, ids=_IDS)
def test_credit_lossless_where_open_wastes_wire(mesh8, factory, cap, S):
    """The ISSUE 9 acceptance gate: where open flow sheds >30% of its wire
    rows, credit flow delivers everything — zero receiver drops, zero
    emission overflow, bounded occupancy — and its first round is
    advert-only (the zero-credit cold start risks no payload)."""
    sc = factory(R)
    open_res = _run(mesh8, sc, cap, S, "open")
    cred = _run(mesh8, sc, cap, S, "credit")

    # open flow collapses: real receiver drops, >30% of wire rows wasted
    assert open_res["drops"] > 0
    waste = open_res["wasted_wire_rows"] / open_res["wire_rows"]
    assert waste > 0.30, f"open waste {waste:.2f} too mild to gate on"
    assert open_res["goodput"] < 0.9

    # credit degrades gracefully on the identical schedule
    np.testing.assert_array_equal(cred["delivered"], expected_by_rank(sc))
    assert cred["delivered_total"] == sc.emitted
    assert cred["drops"] == 0 and cred["lost"] == 0 and cred["done"]
    assert cred["emit_overflow"] == 0
    assert cred["goodput"] == 1.0 and cred["wasted_wire_rows"] == 0
    # cold start: round 0 carries adverts only, no payload rows
    assert int(np.asarray(cred["recv_trace"])[0]) == 0
    # bounded queues: the backlog parks at sources, no queue ever overfills
    assert int(np.asarray(cred["retained_trace"]).max()) <= R * cap
    # the price of losslessness is TIME, not loss
    assert cred["rounds"] > open_res["rounds"]


@pytest.mark.parametrize("factory,cap,S", OVERLOAD, ids=_IDS)
def test_credit_matches_numpy_twin(mesh8, factory, cap, S):
    """The device credit trajectory equals the host-side simulator exactly:
    delivered checksums, round count, and the retained/age/receive traces,
    round for round."""
    sc = factory(R)
    dev = _run(mesh8, sc, cap, S, "credit")
    tw = simulate_flat_credit(sc, peer_capacity=S, capacity=cap, max_rounds=256)
    assert dev["rounds"] == tw["rounds"] and tw["done"]
    np.testing.assert_array_equal(dev["delivered"], tw["delivered"])
    for k in ("retained_trace", "age_trace", "recv_trace"):
        np.testing.assert_array_equal(
            np.asarray(dev[k]), np.asarray(tw[k]), err_msg=k
        )


def test_open_overload_baseline_pinned(mesh8):
    """The livelock baseline this PR measures credit against (satellite 2):
    open flow on the hot-pair saturation schedule, numbers pinned per rank.
    The hot pair hoards the deliveries while the cold ranks starve, nearly
    half the wire is spent on rows the receivers throw away, and the books
    still balance (counted loss, not silent loss)."""
    sc = sustained_overload(R)
    res = _run(mesh8, sc, 16, 4, "open")
    assert res["delivered_total"] == 534 and res["rounds"] == 15
    assert res["delivered"][:, 0].tolist() == [159, 143, 36, 29, 39, 44, 41, 43]
    assert res["drops"] == 618 and res["lost"] == 0 and res["done"]
    assert res["emit_overflow"] == 169
    assert res["wire_rows"] == 983 and res["wasted_wire_rows"] == 449
    assert abs(res["goodput"] - (1 - 449 / 983)) < 1e-9


@pytest.mark.parametrize("factory,cap,S", OVERLOAD, ids=_IDS)
def test_drop_ledger_splits_into_emit_and_wire(mesh8, factory, cap, S):
    """Satellite 1: local emission overflow in retain mode surfaces as its
    own ``emit_overflow`` counter, distinct from receiver-side waste — the
    two must add up to EXACTLY the counted drops under open flow, and
    credit+retain drives both to zero."""
    sc = factory(R)
    open_res = _run(mesh8, sc, cap, S, "open")
    assert (
        open_res["drops"]
        == open_res["emit_overflow"] + open_res["wasted_wire_rows"]
    )
    cred = _run(mesh8, sc, cap, S, "credit")
    assert cred["emit_overflow"] == 0 and cred["wasted_wire_rows"] == 0
    assert cred["drops"] == 0


# ------------------------------------------------- apportionment properties
def _grants(free, num_ranks):
    """The CreditGate law, host-side: rank me's grant toward a destination
    advertising ``free`` rows."""
    f = max(int(free), 0)
    return [f // num_ranks + (me < f % num_ranks) for me in range(num_ranks)]


def test_grants_sum_exactly_to_advertised_free():
    """Floor share + rank-ordered residual: the grants over all R senders
    sum to EXACTLY the advertised space — never more (no overshoot), never
    less (no stranded credit) — for every free value including negatives
    (in-flight debt clips to zero)."""
    for Rn in (2, 3, 8, 16):
        for free in list(range(-3, 3 * Rn + 2)) + [10**6, 10**6 + Rn - 1]:
            g = _grants(free, Rn)
            assert sum(g) == max(free, 0)
            assert max(g) - min(g) <= 1  # fair to within one row
            assert g == sorted(g, reverse=True)  # residual is rank-ordered


def test_credit_trajectory_deterministic_across_modes(mesh8):
    """Satellite 3: the whole credit trajectory — deliveries, rounds,
    retained trace — is bit-identical across marshal modes and shard
    counts.  Apportionment is collective-free and replicated, so HOW the
    rows are marshalled cannot change WHAT ships."""
    sc = sustained_overload(R)
    ref = _run(mesh8, sc, 32, 8, "credit", marshal="sort")
    for kw in (dict(marshal="scatter"), dict(marshal="sort", pipeline_shards=2)):
        alt = _run(mesh8, sc, 32, 8, "credit", **kw)
        np.testing.assert_array_equal(alt["delivered"], ref["delivered"])
        assert alt["rounds"] == ref["rounds"]
        np.testing.assert_array_equal(
            np.asarray(alt["retained_trace"]), np.asarray(ref["retained_trace"])
        )


HIER = [
    ("mesh_nodes24", ("node", "device"), (8, 8)),
    ("mesh_pods222", ("pod", "node", "device"), (8, 8, 8)),
]


@pytest.mark.parametrize("fixture,axes,caps", HIER, ids=["2level", "3level"])
def test_hierarchical_credit_drains_overload(request, fixture, axes, caps):
    """Tiered credit relay: the same hot-pair overload through 2- and
    3-level routes drains to the exact delivery checksums with zero drops —
    per-tier adverts aggregate along the route and gate the first clamp."""
    mesh = request.getfixturevalue(fixture)
    sc = sustained_overload(R)
    res = run_scenario(
        mesh, sc, capacity=256, max_rounds=512, axis_name=axes,
        exchange="hierarchical", level_capacities=caps,
        overflow="retain", flow="credit",
    )
    np.testing.assert_array_equal(res["delivered"], expected_by_rank(sc))
    assert res["drops"] == 0 and res["lost"] == 0 and res["done"]


CAP = 64


def test_zero_credit_round_ships_no_payload(mesh8):
    """An all-zero credit vector retains EVERYTHING at the source: zero
    payload rows arrive anywhere, nothing is dropped, and the round still
    advertises fresh credits so the next round can move the backlog."""
    cfg = ForwardConfig(
        "data", R, CAP, overflow="retain", flow="credit", telemetry=True
    )

    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index("data")
        n = 10
        k = jnp.arange(n)
        rays = Ray(
            origin=jnp.ones((n, 3)) * me,
            direction=jnp.zeros((n, 3)),
            tmin=k.astype(jnp.float32),
            pixel=(k + me * 100).astype(jnp.int32),
            integral=jnp.zeros(n),
        )
        dest = ((me + 1 + k) % R).astype(jnp.int32)  # all rows off-rank
        q = enqueue(q, rays, dest, jnp.ones(n, bool))
        nq, total, age, credits_out, stats = forward_work(
            q, cfg, credits=jnp.zeros((R,), jnp.int32)
        )
        return (
            nq.count[None], total, nq.drops[None],
            stats.recv_total[None], credits_out[None], age[None],
        )

    f = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), P(), P("data"), P("data"), P("data"), P("data")),
        )
    )
    counts, total, drops, recv, credits_out, age = f(jnp.arange(8.0))
    assert int(total) == 80  # termination cannot fire with held work
    np.testing.assert_array_equal(np.asarray(counts), np.full(R, 10))
    assert np.asarray(drops).sum() == 0
    np.testing.assert_array_equal(np.asarray(recv), np.zeros(R))  # no payload
    # every rank's fresh advert opens room for the NEXT round
    assert (np.asarray(credits_out) > 0).all()
    # the held rows aged one round
    assert (np.asarray(age).reshape(R, CAP)[:, :10] == 1).all()


def test_credit_requires_retain_and_padded():
    """Config validation: credit flow needs the retain spill path to park
    un-credited tails, and the onehot exchange has no widened count
    collective to ride."""
    with pytest.raises(ValueError):
        ForwardConfig("data", R, CAP, overflow="drop", flow="credit")
    with pytest.raises(ValueError):
        ForwardConfig(
            "data", R, CAP, exchange="onehot", overflow="retain", flow="credit"
        )
    with pytest.raises(ValueError):
        ForwardConfig("data", R, CAP, flow="closed")  # unknown mode
    with pytest.raises(ValueError):
        ForwardConfig(
            "data", R, CAP, overflow="retain", flow="credit", emit_reserve=CAP
        )


# ------------------------------------------------------- recovery composes
def test_preempt_resume_credit_bitexact(tmp_path, mesh8):
    """The recovery law composes with backpressure: a credit drive killed at
    a boundary and resumed from disk re-publishes byte-identical checkpoints
    (the carried credit vector is part of the manifest) and lands on the
    uninterrupted run's exact trajectory."""
    sc = sustained_overload(R)
    kw = dict(
        capacity=16, peer_capacity=4, overflow="retain", flow="credit",
        max_rounds=256,
    )
    ref = run_scenario(mesh8, sc, **kw)
    a = run_scenario_checkpointed(
        mesh8, sc, ckpt_dir=tmp_path / "a", checkpoint_every=8, keep=99, **kw
    )
    b = run_scenario_checkpointed(
        mesh8, sc, ckpt_dir=tmp_path / "b", checkpoint_every=8, keep=99,
        preempt_at=20, **kw
    )
    assert b["preempted"] and not a["preempted"]
    np.testing.assert_array_equal(a["delivered"], ref["delivered"])
    np.testing.assert_array_equal(b["delivered"], ref["delivered"])
    assert a["rounds"] == b["rounds"] == ref["rounds"]
    assert a["lost"] == b["lost"] == 0 and a["drops"] == b["drops"] == 0
    da, db = boundary_digests(tmp_path / "a"), boundary_digests(tmp_path / "b")
    common = sorted(set(da) & set(db))
    assert len(common) >= 3
    for step in common:
        assert da[step] == db[step], f"state diverged at boundary {step}"


def test_resume_refuses_flow_mismatch(tmp_path, mesh8):
    """A checkpoint saved under credit flow names its flow mode in the meta;
    resuming it with an open-flow context must be refused, not silently
    reinterpreted (the carry shapes differ — credits are a carried leaf)."""
    sc = sustained_overload(R)
    run_scenario_checkpointed(
        mesh8, sc, capacity=16, peer_capacity=4, overflow="retain",
        flow="credit", max_rounds=256, ckpt_dir=tmp_path, checkpoint_every=8,
        keep=99,
    )
    ctx = _make_ctx(
        mesh8, capacity=16, peer_capacity=4, overflow="retain", flow="open",
        max_rounds=256,
    )
    aux_like = tuple(np.zeros((R,), np.uint32) for _ in range(3))
    with pytest.raises(ValueError, match="flow"):
        resume_run(
            ctx, lambda q, aux, rnd: (q, aux), tmp_path,
            aux_specs=(ctx._spec,) * 3, aux_like=aux_like,
        )
