"""Overlap-law property tests (ISSUE 8): pipelined forwarding is bit-exact.

``ForwardConfig.pipeline_shards=S`` splits every peer segment of a round
into S micro-shards, each shipped by its own payload+count collective pair
so shard k+1's marshal can overlap shard k's wire time (the stage graph in
``repro.core.stages``).  The law under test: pipelining changes the
SCHEDULE, never the ANSWER —

  * placement, counts, drops, ages and totals are bit-exact with the bulk
    (S=1) round on every backend that supports sharding (flat padded,
    2-/3-level hierarchical, ragged when available), for BOTH marshal
    modes, BOTH overflow modes, and adversarial traffic (hotspot overflow
    included);
  * configs that cannot shard fail loudly at construction/call time with a
    message naming the limitation (onehot oracle, cycling ring), and the
    shard count must divide every capacity it tiles — never a silent
    rounding.

The collective-budget side of the law (S payload + S count collectives per
mesh axis, S=1 lowering bit-identical to the pre-stage-graph HLO) lives in
``test_collective_budget.py``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from helpers import make_rays, ray_proto
from repro import compat
from repro.core import (
    DISCARD,
    ForwardConfig,
    WorkQueue,
    enqueue,
    forward_work,
    make_queue,
    work_item,
)
from repro.core.cycling import cycle_step

pytestmark = pytest.mark.pipeline

R, CAP = 8, 64


# ---------------------------------------------------------------- runners
def _dest_fn(pattern, seed, n_emit):
    """Per-rank destination pattern (traced inside shard_map)."""

    def f(me):
        i = jnp.arange(n_emit)
        if pattern == "uniform":
            # includes out-of-range dests (R, R+1) — the enqueue discard path
            return ((me * 7 + seed + i**2) % (R + 2)).astype(jnp.int32)
        if pattern == "hotspot":
            # every rank floods one destination — clamp/spill under pressure
            return jnp.full((n_emit,), seed % R, jnp.int32)
        return ((me + 1 + (i % 2)) % R).astype(jnp.int32)  # neighbour

    return f


def _run(mesh, cfg, pattern="uniform", seed=0, n_emit=24):
    """One forwarding round; returns every observable of the result."""
    axes = cfg.axis_name
    flat = axes if isinstance(axes, str) else tuple(axes)

    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index(axes)
        q = enqueue(
            q, make_rays(n_emit), _dest_fn(pattern, seed, n_emit)(me),
            jnp.ones(n_emit, bool),
        )
        res = forward_work(q, cfg)
        nq = res[0]
        out = [
            nq.count[None], nq.drops[None], nq.dest, nq.items.tmin,
            nq.items.pixel, nq.items.integral, res[1],
        ]
        if cfg.overflow == "retain":
            out.append(res[2])  # per-lane age
        return tuple(out)

    spec = P(flat)
    n_sharded = 6
    out_specs = [spec] * n_sharded + [P()]
    if cfg.overflow == "retain":
        out_specs.append(spec)
    f = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh, in_specs=spec, out_specs=tuple(out_specs)
        )
    )
    return jax.device_get(f(jnp.arange(8.0)))


def _assert_same(ref, got, label):
    for i, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(
            a, b, err_msg=f"{label}: output {i} diverged from bulk round"
        )


_REF_CACHE = {}


def _flat_ref(mesh8, marshal, overflow, pattern, seed):
    key = (marshal, overflow, pattern, seed)
    if key not in _REF_CACHE:
        base = ForwardConfig(
            "data", R, CAP, exchange="padded", marshal=marshal,
            overflow=overflow,
        )
        _REF_CACHE[key] = _run(mesh8, base, pattern, seed)
    return _REF_CACHE[key]


# ------------------------------------------------------- flat padded exact
@pytest.mark.parametrize("S", [2, 4])
@pytest.mark.parametrize("overflow", ["drop", "retain"])
@pytest.mark.parametrize("marshal", ["sort", "scatter"])
def test_flat_padded_bitexact(mesh8, marshal, overflow, S):
    """Flat padded round: S micro-shards land every row in the SAME slot as
    the bulk round — payload, dest, count, drops, ages all equal, under
    benign and hotspot (overflowing) traffic."""
    for pattern, seed in [("uniform", 0), ("hotspot", 3)]:
        ref = _flat_ref(mesh8, marshal, overflow, pattern, seed)
        cfg = ForwardConfig(
            "data", R, CAP, exchange="padded", marshal=marshal,
            overflow=overflow, pipeline_shards=S,
        )
        got = _run(mesh8, cfg, pattern, seed)
        _assert_same(ref, got, f"{marshal}/{overflow}/{pattern}/S={S}")


def test_flat_pallas_bitexact(mesh8):
    """The Pallas kernel path shards too: fused bucket-scatter marshal per
    micro-shard, placement identical to the bulk kernel round."""
    base = ForwardConfig(
        "data", R, CAP, exchange="padded", marshal="scatter",
        overflow="retain", use_pallas=True,
    )
    cfg = dataclasses.replace(base, pipeline_shards=2)
    _assert_same(
        _run(mesh8, base, "hotspot", 3), _run(mesh8, cfg, "hotspot", 3),
        "pallas/S=2",
    )


@pytest.mark.parametrize("S", [2, 4])
def test_flat_ragged_bitexact(mesh8, S):
    """Ragged backend: S ragged_all_to_all slices conserve placement."""
    if not compat.ragged_executes():
        pytest.skip(f"the {jax.default_backend()} backend cannot execute ragged_all_to_all")
    base = ForwardConfig("data", R, CAP, exchange="ragged")
    cfg = dataclasses.replace(base, pipeline_shards=S)
    _assert_same(
        _run(mesh8, base), _run(mesh8, cfg, seed=0), f"ragged/S={S}"
    )


# ------------------------------------------------------ hierarchical exact
HIER = [
    ("mesh_nodes24", ("node", "device"), (2, 4), (6, 8)),
    ("mesh_pods222", ("pod", "node", "device"), (2, 2, 2), (4, 6, 8)),
]


@pytest.mark.parametrize("overflow", ["drop", "retain"])
@pytest.mark.parametrize("marshal", ["sort", "scatter"])
@pytest.mark.parametrize(
    "fixture,axes,sizes,caps", HIER, ids=["2level", "3level"]
)
def test_hierarchical_bitexact(
    request, fixture, axes, sizes, caps, marshal, overflow
):
    """Dimension-ordered route: per-tier micro-shards (chunk = tier slot /
    S) reassemble each stage buffer exactly, so the multi-hop placement —
    including mid-route retain parking — matches the bulk round bit for
    bit.  Uneven per-tier capacities exercise distinct chunk sizes."""
    mesh = request.getfixturevalue(fixture)
    base = ForwardConfig(
        axes, R, CAP, exchange="hierarchical", level_sizes=sizes,
        level_capacities=caps, marshal=marshal, overflow=overflow,
    )
    cfg = dataclasses.replace(base, pipeline_shards=2)
    _assert_same(
        _run(mesh, base, "hotspot", 3), _run(mesh, cfg, "hotspot", 3),
        f"hier{len(sizes)}/{marshal}/{overflow}",
    )


# -------------------------------------------------- property (hypothesis)
@work_item
@dataclasses.dataclass
class Probe:
    val: jax.Array
    src: jax.Array


def _make_pair(mesh8, S):
    """(bulk, pipelined) jitted rounds over runtime-fed queues — compiled
    once, hypothesis drives the data."""

    def build(shards):
        cfg = ForwardConfig(
            "data", R, CAP, exchange="padded", pipeline_shards=shards
        )

        def fwd(val, dest, counts):
            me = jax.lax.axis_index("data")
            q = WorkQueue(
                items=Probe(val=val, src=me * jnp.ones(CAP, jnp.int32)),
                dest=dest,
                count=counts[0],
                drops=jnp.zeros((), jnp.int32),
            )
            nq, total = forward_work(q, cfg)
            return (
                nq.items.val, nq.items.src, nq.dest, nq.count[None],
                nq.drops[None], total,
            )

        return jax.jit(
            jax.shard_map(
                fwd, mesh=mesh8,
                in_specs=(P("data"), P("data"), P("data")),
                out_specs=(
                    P("data"), P("data"), P("data"), P("data"), P("data"),
                    P(),
                ),
            )
        )

    return build(1), build(S)


@pytest.fixture(scope="module")
def fwd_pair(mesh8):
    return _make_pair(mesh8, 2)


@given(data=st.data())
@settings(max_examples=20, deadline=None)
def test_pipelined_placement_property(fwd_pair, data):
    """For arbitrary queue fills — random counts, random destinations, a
    coin-flip hotspot that overflows one rank — the S=2 round equals the
    bulk round on every output array."""
    bulk, piped = fwd_pair
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = np.full((R, CAP), DISCARD, np.int32)
    for r in range(R):
        if rng.random() < 0.3:  # hotspot: everyone floods one destination
            dest[r, : counts[r]] = rng.integers(0, R)
        else:
            dest[r, : counts[r]] = rng.integers(0, R, counts[r])
    val = rng.standard_normal((R, CAP)).astype(np.float32)
    args = (
        jnp.asarray(val.reshape(-1)),
        jnp.asarray(dest.reshape(-1)),
        jnp.asarray(counts),
    )
    _assert_same(
        jax.device_get(bulk(*args)), jax.device_get(piped(*args)),
        "property/S=2",
    )


# ------------------------------------------------------------- validation
def test_pipeline_shards_must_be_positive():
    with pytest.raises(ValueError, match="pipeline_shards"):
        ForwardConfig("data", R, CAP, pipeline_shards=0)


def test_pipeline_shards_must_divide_capacity():
    with pytest.raises(ValueError, match="divide"):
        ForwardConfig("data", R, CAP, pipeline_shards=3)  # 3 does not divide 64


def test_pipeline_shards_must_divide_peer_capacity():
    with pytest.raises(ValueError, match="peer_capacity"):
        ForwardConfig("data", R, CAP, peer_capacity=6, pipeline_shards=4)


def test_pipeline_shards_must_divide_level_capacities():
    with pytest.raises(ValueError, match="level_capacities"):
        ForwardConfig(
            ("node", "device"), R, CAP, exchange="hierarchical",
            level_sizes=(2, 4), level_capacities=(7, 8), pipeline_shards=2,
        )


def test_onehot_rejects_pipelining():
    with pytest.raises(ValueError, match="onehot"):
        ForwardConfig("data", R, CAP, exchange="onehot", pipeline_shards=2)


def test_cycling_rejects_pipelining():
    cfg = ForwardConfig("data", R, CAP, pipeline_shards=2)
    q = make_queue(ray_proto(), CAP)
    with pytest.raises(ValueError, match="cycling"):
        cycle_step(q, q, cfg)
