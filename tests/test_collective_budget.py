"""Collective-budget regression tests (ISSUE 1 + ISSUE 2 acceptance).

One ``forward_work`` round must lower to exactly ONE payload-sized collective
and ONE count collective — the whole point of the packed wire format.  If a
refactor reintroduces per-leaf collectives (the old code issued one
all_to_all per pytree leaf) or splits the ragged control plane back into
chained count exchanges, these tests fail.

The hierarchical two-stage round is budgeted at exactly TWO payload + TWO
count collectives, with the single slow-axis payload collective (stage B)
carrying ALL bulk bytes that cross the inter-node fabric — verified from the
ops' replica groups (fast axis: groups inside one node; slow axis: one lane
across nodes).

The inventory comes from ``roofline.analysis.collective_ops`` over the
lowered StableHLO of a shard_map'ed round.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import ForwardConfig, enqueue, forward_work, make_queue
from repro.core import types as T
from repro.roofline.analysis import collective_ops, group_axis

from helpers import make_rays, ray_proto

R, CAP = 8, 64
WORDS = T.pack_spec(ray_proto()).total_words  # 9 for the 36-byte test ray


def _lower_one_round(mesh8, cfg):
    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index("data")
        q = enqueue(
            q, make_rays(10), ((me + jnp.arange(10)) % R).astype(jnp.int32),
            jnp.ones(10, bool),
        )
        nq, total = forward_work(q, cfg)
        return nq.count[None], total, nq.items.tmin

    return jax.jit(
        jax.shard_map(
            kernel, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), P(), P("data")),
        )
    ).lower(jnp.arange(8.0)).as_text()


def _payload_threshold(cfg):
    """Anything at least one peer-slot of packed rows is payload; the count
    exchange is R (or R×R) int32 — orders of magnitude smaller."""
    return cfg.peer_capacity * WORDS * 4


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_padded_round_has_one_payload_and_one_count_collective(mesh8, use_pallas):
    cfg = ForwardConfig("data", R, CAP, exchange="padded", use_pallas=use_pallas)
    ops = collective_ops(_lower_one_round(mesh8, cfg))
    a2a = [b for k, b in ops if k == "all-to-all"]
    payload = [b for b in a2a if b >= _payload_threshold(cfg)]
    counts = [b for b in a2a if b < _payload_threshold(cfg)]
    assert len(payload) == 1, f"want ONE payload all_to_all, got {a2a}"
    # the one payload collective carries the whole packed send buffer
    assert payload[0] == R * cfg.peer_capacity * WORDS * 4
    assert len(counts) == 1, f"want ONE count all_to_all, got {a2a}"
    assert counts[0] == R * 4
    # no stray payload movement on other collectives (psum of the scalar
    # count is the only other traffic)
    others = [(k, b) for k, b in ops if k != "all-to-all"]
    assert all(b <= R * R * 4 for _k, b in others), others


def test_ragged_round_has_one_payload_and_one_count_collective(mesh8):
    cfg = ForwardConfig("data", R, CAP, exchange="ragged")
    ops = collective_ops(_lower_one_round(mesh8, cfg))
    ragged = [b for k, b in ops if k == "ragged-all-to-all"]
    assert len(ragged) == 1, f"want ONE ragged_all_to_all, got {ops}"
    # control plane: exactly one all_gather of the (R,) count vector —
    # NOT the three chained count all_to_alls of the naive Alltoallv plan
    assert sum(1 for k, _ in ops if k == "all-to-all") == 0, ops
    gathers = [b for k, b in ops if k == "all-gather"]
    assert gathers == [R * R * 4], ops


def _lower_hier_round(mesh, cfg):
    axes = cfg.axis_name

    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index(axes)
        q = enqueue(
            q, make_rays(10), ((me + jnp.arange(10)) % R).astype(jnp.int32),
            jnp.ones(10, bool),
        )
        nq, total = forward_work(q, cfg)
        return nq.count[None], total, nq.items.tmin

    return jax.jit(
        jax.shard_map(
            kernel, mesh=mesh, in_specs=P(axes),
            out_specs=(P(axes), P(), P(axes)),
        )
    ).lower(jnp.arange(8.0)).as_text()


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_hierarchical_round_budget_two_payload_two_count(mesh_nodes24, use_pallas):
    """Two-stage budget guard: exactly 2 payload all_to_alls (one per mesh
    axis) + 2 tiny count all_to_alls, and ZERO payload collectives on the
    slow axis beyond stage B — all bulk inter-node bytes cross exactly once,
    padded per node."""
    F = 4
    cfg = ForwardConfig(
        ("node", "device"), R, CAP, exchange="hierarchical", fast_size=F,
        use_pallas=use_pallas,
    )
    ops = collective_ops(_lower_hier_round(mesh_nodes24, cfg), with_groups=True)
    a2a = [(b, group_axis(g, F)) for k, b, g in ops if k == "all-to-all"]
    threshold = min(cfg.peer_capacity, cfg.node_capacity) * WORDS * 4
    payload = [(b, ax) for b, ax in a2a if b >= threshold]
    counts = [(b, ax) for b, ax in a2a if b < threshold]
    assert len(payload) == 2, f"want TWO payload all_to_alls, got {a2a}"
    assert len(counts) == 2, f"want TWO count all_to_alls, got {a2a}"
    # stage A: the full (F, S_a, W) send buffer moves on the FAST axis only
    fast_payload = [b for b, ax in payload if ax == "fast"]
    assert fast_payload == [F * cfg.peer_capacity * WORDS * 4], payload
    # stage B: the ONE slow-axis payload collective carries the per-node
    # segments — (N, S_b, W), padded per node, never per rank
    N = R // F
    slow_payload = [b for b, ax in payload if ax == "slow"]
    assert slow_payload == [N * cfg.node_capacity * WORDS * 4], payload
    # nothing else ships payload-sized data across the slow fabric
    slow_bulk = [
        (k, b) for k, b, g in ops
        if b >= threshold and group_axis(g, F) in ("slow", "cross")
        and k != "all-to-all"
    ]
    assert slow_bulk == [], slow_bulk
    # control plane: one count exchange per axis
    assert sorted(ax for _b, ax in counts) == ["fast", "slow"], counts


def test_3level_round_budget_one_payload_one_count_per_axis(mesh_pods222):
    """N-level budget guard: on a (pod, node, device) mesh, exactly THREE
    payload all_to_alls (one per mesh axis, each a pure single-tier pattern)
    + three tiny count collectives, and no other payload-sized op touches a
    slower fabric."""
    from repro.roofline.analysis import group_tier

    sizes = (2, 2, 2)
    cfg = ForwardConfig(
        ("pod", "node", "device"), R, CAP, exchange="hierarchical",
        level_sizes=sizes,
    )
    txt = _lower_hier_round(mesh_pods222, cfg)
    ops = collective_ops(txt, with_groups=True)
    threshold = min(cfg.level_capacities) * WORDS * 4
    a2a = [(b, group_tier(g, sizes)) for k, b, g in ops if k == "all-to-all"]
    payload = [(b, t) for b, t in a2a if b >= threshold]
    counts = [(b, t) for b, t in a2a if b < threshold]
    assert len(payload) == 3, f"want THREE payload all_to_alls, got {a2a}"
    assert len(counts) == 3, f"want THREE count all_to_alls, got {a2a}"
    # one payload collective per tier, each of the padded per-segment size
    assert sorted(t for _b, t in payload) == [0, 1, 2], payload
    for b, t in payload:
        assert b == sizes[t] * cfg.level_capacities[t] * WORDS * 4, payload
    assert sorted(t for _b, t in counts) == [0, 1, 2], counts
    # nothing else ships payload-sized data across tier 0 or 1 (or mixed)
    stray = [
        (k, b) for k, b, g in ops
        if b >= threshold and group_tier(g, sizes) in (0, 1, "cross")
        and k != "all-to-all"
    ]
    assert stray == [], stray


def test_3level_extent1_axis_skips_its_stage():
    """An extent-1 tier must contribute NO collective at all — its stage is
    the identity, so a (2, 1, 4) mesh budgets like a 2-level route."""
    from repro.launch.mesh import make_pod_mesh
    from repro.roofline.analysis import group_tier

    sizes = (2, 1, 4)
    mesh = make_pod_mesh(*sizes)
    cfg = ForwardConfig(
        ("pod", "node", "device"), R, CAP, exchange="hierarchical",
        level_sizes=sizes,
    )
    txt = _lower_hier_round(mesh, cfg)
    ops = collective_ops(txt, with_groups=True)
    a2a = [(b, group_tier(g, sizes)) for k, b, g in ops if k == "all-to-all"]
    assert sorted({t for _b, t in a2a}) == [0, 2], a2a  # tier 1 never appears
    threshold = min(cfg.level_capacities[0], cfg.level_capacities[2]) * WORDS * 4
    assert sum(1 for b, _t in a2a if b >= threshold) == 2, a2a


def test_hierarchical_slow_axis_padding_is_per_node(mesh_nodes24):
    """The headline claim: slow-axis bytes are padded per NODE segment.  At
    EQUAL burst tolerance K (slot rows a single destination can absorb
    without drops), the flat padded exchange routed across nodes ships
    (R - F)·K padded rows over the slow fabric; hierarchical ships
    (N - 1)·K — exactly an R/N× reduction, since R - F = F·(N - 1).  The
    model must also agree with the lowered slow-axis accounting."""
    from repro.roofline.analysis import per_axis_collective_bytes, slow_axis_bytes_model

    F, N = 4, 2
    item_b = WORDS * 4
    K = 16  # any per-destination burst tolerance
    hier_model = slow_axis_bytes_model(
        "hierarchical", num_ranks=R, fast_size=F, item_bytes=item_b,
        node_capacity=K,
    )
    flat_model = slow_axis_bytes_model(
        "padded", num_ranks=R, fast_size=F, item_bytes=item_b,
        peer_capacity=K,
    )
    assert flat_model / hier_model == pytest.approx(R / N)
    # lowered HLO: stage B is the only slow-axis bulk and matches the model
    hier = ForwardConfig(("node", "device"), R, CAP, exchange="hierarchical", fast_size=F)
    txt = _lower_hier_round(mesh_nodes24, hier)
    per_axis = per_axis_collective_bytes(txt, F)
    assert per_axis["cross"] == 0
    slow_payload = N * hier.node_capacity * WORDS * 4
    assert per_axis["slow"] == slow_payload + N * 4  # stage B + its counts
    # the model counts only rows leaving the node: (N-1)/N of the collective
    assert slow_axis_bytes_model(
        "hierarchical", num_ranks=R, fast_size=F, item_bytes=item_b,
        node_capacity=hier.node_capacity,
    ) == slow_payload * (N - 1) / N


def test_flat_exchange_over_joint_axes_pays_cross_fabric_routing(mesh_nodes24):
    """Contrast guard: the flat padded exchange on the same 2-D mesh lowers
    to ONE all_to_all whose groups span nodes AND lanes — every byte of it is
    exposed to the slow fabric (the motivation for the two-stage route)."""
    cfg = ForwardConfig(("node", "device"), R, CAP, exchange="padded")
    ops = collective_ops(_lower_hier_round(mesh_nodes24, cfg), with_groups=True)
    payload = [
        (b, group_axis(g, 4)) for k, b, g in ops
        if k == "all-to-all" and b >= _payload_threshold(cfg)
    ]
    assert payload == [(R * cfg.peer_capacity * WORDS * 4, "cross")], payload


def _lower_round_with_telemetry(mesh, cfg, axes):
    """Like the other lowerings, but the kernel RETURNS the stats so the
    telemetry computation cannot be DCE'd out of the compared program."""
    from repro import telemetry as TM

    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index(axes)
        q = enqueue(
            q, make_rays(10), ((me + jnp.arange(10)) % R).astype(jnp.int32),
            jnp.ones(10, bool),
        )
        nq, total, stats = forward_work(q, cfg)
        return nq.count[None], total, nq.items.tmin, TM.stack_ring(stats)

    stats_spec = jax.tree.map(
        lambda _: P(axes),
        TM.make_stats(TM.num_tiers(cfg), cfg.telemetry_buckets),
    )
    return jax.jit(
        jax.shard_map(
            kernel, mesh=mesh, in_specs=P(axes),
            out_specs=(P(axes), P(), P(axes), stats_spec),
        )
    ).lower(jnp.arange(8.0)).as_text()


@pytest.mark.telemetry
@pytest.mark.parametrize(
    "fixture,axes,kw",
    [
        ("mesh8", "data", dict(exchange="padded")),
        ("mesh8", "data", dict(exchange="padded", marshal="scatter")),
        (
            "mesh_pods222", ("pod", "node", "device"),
            dict(exchange="hierarchical", level_sizes=(2, 2, 2)),
        ),
    ],
    ids=["padded", "padded-scatter", "hier3"],
)
def test_telemetry_adds_zero_collectives(request, fixture, axes, kw):
    """ISSUE 5 acceptance: stats capture is derived from control-plane values
    the round already computes — the FULL collective inventory (kind, bytes,
    replica groups) of a telemetry-on round is identical to the telemetry-off
    round.  Not just 'no extra payload collective': no extra collective of
    ANY size, so the per-axis budget law is untouched."""
    mesh = request.getfixturevalue(fixture)
    cfg_off = ForwardConfig(axes, R, CAP, **kw)
    cfg_on = ForwardConfig(axes, R, CAP, telemetry=True, **kw)
    lower_off = (
        _lower_one_round(mesh, cfg_off)
        if axes == "data"
        else _lower_hier_round(mesh, cfg_off)
    )
    ops_off = collective_ops(lower_off, with_groups=True)
    ops_on = collective_ops(
        _lower_round_with_telemetry(mesh, cfg_on, axes), with_groups=True
    )
    assert ops_on == ops_off, (ops_on, ops_off)


def _lower_round_any_overflow(mesh, cfg, axes):
    """Overflow-mode-agnostic lowering: a retain round returns the extra
    ``age_out`` (kept live so its computation can't be DCE'd); a drop round
    returns a zero placeholder so both programs have identical output
    signatures and only the round's internals differ."""
    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index(axes)
        q = enqueue(
            q, make_rays(10), ((me + jnp.arange(10)) % R).astype(jnp.int32),
            jnp.ones(10, bool),
        )
        res = forward_work(q, cfg)
        nq, total = res[0], res[1]
        age = res[2] if cfg.overflow == "retain" else jnp.zeros(CAP, jnp.int32)
        return nq.count[None], total, nq.items.tmin, age

    return jax.jit(
        jax.shard_map(
            kernel, mesh=mesh, in_specs=P(axes),
            out_specs=(P(axes), P(), P(axes), P(axes)),
        )
    ).lower(jnp.arange(8.0)).as_text()


@pytest.mark.chaos
@pytest.mark.parametrize(
    "fixture,axes,kw",
    [
        ("mesh8", "data", dict(exchange="padded")),
        ("mesh8", "data", dict(exchange="padded", marshal="scatter")),
        (
            "mesh_pods222", ("pod", "node", "device"),
            dict(exchange="hierarchical", level_sizes=(2, 2, 2)),
        ),
    ],
    ids=["padded", "padded-scatter", "hier3"],
)
def test_retain_adds_zero_collectives(request, fixture, axes, kw):
    """ISSUE 6 acceptance: retention is pure LOCAL compaction — the rows a
    clamp cuts never leave the rank, so the full collective inventory (kind,
    bytes, replica groups) of an ``overflow="retain"`` round is identical to
    the drop-mode round.  The budget, per-axis, and wire-format laws carry
    over to retain mode by construction, not by re-proof."""
    mesh = request.getfixturevalue(fixture)
    cfg_drop = ForwardConfig(axes, R, CAP, **kw)
    cfg_retain = ForwardConfig(axes, R, CAP, overflow="retain", **kw)
    ops_drop = collective_ops(
        _lower_round_any_overflow(mesh, cfg_drop, axes), with_groups=True
    )
    ops_retain = collective_ops(
        _lower_round_any_overflow(mesh, cfg_retain, axes), with_groups=True
    )
    assert ops_retain == ops_drop, (ops_retain, ops_drop)


def _lower_round_with_health(mesh, cfg, axes):
    """A forwarding round with a TRACED rank-health mask (replicated bool
    ``(R,)``) — the ISSUE 7 draining remap in the position the recovery
    drive uses it."""
    def kernel(_x, h):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index(axes)
        q = enqueue(
            q, make_rays(10), ((me + jnp.arange(10)) % R).astype(jnp.int32),
            jnp.ones(10, bool),
        )
        nq, total = forward_work(q, cfg, health=h)
        return nq.count[None], total, nq.items.tmin

    return jax.jit(
        jax.shard_map(
            kernel, mesh=mesh, in_specs=(P(axes), P()),
            out_specs=(P(axes), P(), P(axes)),
        )
    ).lower(jnp.arange(8.0), jnp.ones((R,), bool)).as_text()


@pytest.mark.recovery
@pytest.mark.parametrize(
    "fixture,axes,kw",
    [
        ("mesh8", "data", dict(exchange="padded")),
        ("mesh8", "data", dict(exchange="padded", marshal="scatter")),
        (
            "mesh_pods222", ("pod", "node", "device"),
            dict(exchange="hierarchical", level_sizes=(2, 2, 2)),
        ),
    ],
    ids=["padded", "padded-scatter", "hier3"],
)
def test_health_mask_adds_zero_collectives(request, fixture, axes, kw):
    """ISSUE 7 acceptance: the rank-draining destination remap is a pure
    LOCAL table lookup (``health_table`` + gather) applied before the
    marshal — the full collective inventory (kind, bytes, replica groups) of
    a health-masked round is identical to the plain round.  Draining a rank
    changes WHERE rows go, never what the fabric ships."""
    mesh = request.getfixturevalue(fixture)
    cfg = ForwardConfig(axes, R, CAP, **kw)
    lower_off = (
        _lower_one_round(mesh, cfg)
        if axes == "data"
        else _lower_hier_round(mesh, cfg)
    )
    ops_off = collective_ops(lower_off, with_groups=True)
    ops_health = collective_ops(
        _lower_round_with_health(mesh, cfg, axes), with_groups=True
    )
    assert ops_health == ops_off, (ops_health, ops_off)


@pytest.mark.recovery
def test_segmented_drive_preserves_collective_inventory(mesh8):
    """ISSUE 7 acceptance: splitting the drive into checkpointable start +
    segment programs re-arranges WHERE the while loop pauses, never what the
    fabric does — the combined collective inventory of the two programs
    equals the monolithic ``run_until_done`` drive's exactly (kind, bytes,
    replica groups), accounting counters and health remap included."""
    import numpy as np

    from repro.core import DISCARD, WorkQueue
    from repro.core.context import RafiContext

    ctx = RafiContext(
        mesh8, ray_proto(), capacity=CAP, peer_capacity=8, exchange="padded",
        overflow="retain", telemetry=True, telemetry_window=8,
    )

    def round_fn(q_in, acc, rnd):
        me = jax.lax.axis_index("data")
        out = make_queue(ray_proto(), CAP)
        out = enqueue(
            out, make_rays(4), ((me + rnd) % R) * jnp.ones(4, jnp.int32),
            (jnp.arange(4) >= 0) & (rnd < 2),
        )
        return out, acc + q_in.count

    spec = P("data")
    q0 = WorkQueue(
        items=jax.tree.map(
            lambda a: np.zeros((R * CAP,) + a.shape, a.dtype), ray_proto()
        ),
        dest=np.full((R * CAP,), DISCARD, np.int32),
        count=np.zeros((R,), np.int32),
        drops=np.zeros((R,), np.int32),
    )
    aux0 = np.zeros((R,), np.int32)
    health = np.ones((R,), bool)

    plain = ctx.run_until_done(round_fn, aux_specs=spec, max_rounds=16)
    ops_plain = collective_ops(
        plain.lower(q0, aux0).as_text(), with_groups=True
    )
    start_p, segment_p = ctx.checkpoint_drive_programs(
        round_fn, aux_specs=spec, accounting=True
    )
    ops_start = collective_ops(
        start_p.lower(q0, aux0, health).as_text(), with_groups=True
    )
    carry = start_p(q0, aux0, health)  # a concrete carry to lower against
    ops_segment = collective_ops(
        segment_p.lower(carry, np.int32(4), health).as_text(),
        with_groups=True,
    )
    assert sorted(ops_start + ops_segment) == sorted(ops_plain), (
        ops_start, ops_segment, ops_plain
    )


def test_cycle_hop_ships_one_packed_buffer(mesh8):
    """A ring hop moves items+dest as ONE packed collective_permute (plus the
    scalar count) — the cycling analogue of the forwarding budget."""
    from repro.core.cycling import cycle_step

    cfg = ForwardConfig("data", R, CAP, exchange="padded")

    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index("data")
        q = enqueue(
            q, make_rays(6), ((me + 1) % R) * jnp.ones(6, jnp.int32),
            jnp.ones(6, bool),
        )
        absorbed = make_queue(ray_proto(), CAP)
        nq, na = cycle_step(q, absorbed, cfg)
        return nq.count[None], na.count[None], nq.items.tmin

    txt = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), P("data"), P("data")),
        )
    ).lower(jnp.arange(8.0)).as_text()
    ops = collective_ops(txt)
    perms = [b for k, b in ops if k == "collective-permute"]
    # items (9 words) + dest (1 word) packed together → (CAP, 10) u32
    payload = [b for b in perms if b >= CAP * 4]
    assert payload == [CAP * (WORDS + 1) * 4], ops


# ----------------------------------------------- pipelined budget (ISSUE 8)
@pytest.mark.pipeline
@pytest.mark.parametrize("S", [2, 4])
def test_pipelined_padded_round_budget_S_payload_S_count(mesh8, S):
    """The overlap law's budget: ``pipeline_shards=S`` lowers to exactly S
    payload all_to_alls (one peer-chunk each) + S count all_to_alls — and
    the S chunks sum to the bulk round's wire bytes exactly (pipelining
    re-times the traffic, it never adds any)."""
    cfg = ForwardConfig("data", R, CAP, exchange="padded", pipeline_shards=S)
    ops = collective_ops(_lower_one_round(mesh8, cfg))
    a2a = [b for k, b in ops if k == "all-to-all"]
    chunk = cfg.peer_capacity // S
    payload = [b for b in a2a if b >= chunk * WORDS * 4]
    counts = [b for b in a2a if b < chunk * WORDS * 4]
    assert payload == [R * chunk * WORDS * 4] * S, f"S={S}: {a2a}"
    assert counts == [R * 4] * S, f"S={S}: {a2a}"
    assert sum(payload) == R * cfg.peer_capacity * WORDS * 4  # bytes conserved


@pytest.mark.pipeline
def test_pipelined_3level_budget_S_per_axis(mesh_pods222):
    """Per-axis overlap budget: on the (pod, node, device) route with
    ``pipeline_shards=2``, EVERY tier lowers to 2 chunk-sized payload
    all_to_alls + 2 count all_to_alls — the micro-shards pipeline each
    fabric independently, and no tier escapes its chunking."""
    from repro.roofline.analysis import group_tier

    sizes = (2, 2, 2)
    S = 2
    cfg = ForwardConfig(
        ("pod", "node", "device"), R, CAP, exchange="hierarchical",
        level_sizes=sizes, pipeline_shards=S,
    )
    ops = collective_ops(_lower_hier_round(mesh_pods222, cfg), with_groups=True)
    threshold = min(c // S for c in cfg.level_capacities) * WORDS * 4
    a2a = [(b, group_tier(g, sizes)) for k, b, g in ops if k == "all-to-all"]
    payload = [(b, t) for b, t in a2a if b >= threshold]
    counts = [(b, t) for b, t in a2a if b < threshold]
    assert sorted(t for _b, t in payload) == [0, 0, 1, 1, 2, 2], a2a
    for b, t in payload:
        assert b == sizes[t] * (cfg.level_capacities[t] // S) * WORDS * 4, (
            payload
        )
    assert sorted(t for _b, t in counts) == [0, 0, 1, 1, 2, 2], a2a


# ----------------------------------------------- credit budget (ISSUE 9)
def _lower_round_any_flow(mesh, cfg, axes):
    """Flow-mode-agnostic lowering: a credit round returns ``age_out`` and
    ``credits_out`` (kept live so their computation can't be DCE'd); other
    modes return zero placeholders so every program has the same output
    signature and only the round's internals differ."""
    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index(axes)
        q = enqueue(
            q, make_rays(10), ((me + jnp.arange(10)) % R).astype(jnp.int32),
            jnp.ones(10, bool),
        )
        credits = (
            jnp.full((R,), 4, jnp.int32) if cfg.flow == "credit" else None
        )
        res = forward_work(q, cfg, credits=credits)
        nq, total = res[0], res[1]
        age = res[2] if cfg.overflow == "retain" else jnp.zeros(CAP, jnp.int32)
        creds = res[3] if cfg.flow == "credit" else jnp.zeros(R, jnp.int32)
        return nq.count[None], total, nq.items.tmin, age, creds[None]

    spec = P(axes)
    return jax.jit(
        jax.shard_map(
            kernel, mesh=mesh, in_specs=spec,
            out_specs=(spec, P(), spec, spec, spec),
        )
    ).lower(jnp.arange(8.0)).as_text()


@pytest.mark.backpressure
def test_credit_round_budget_one_payload_one_widened_count(mesh8):
    """ISSUE 9 acceptance, flat: the credit round still lowers to exactly
    ONE payload all_to_all of the SAME size as the open round — the advert
    rides the count collective, widened from (R,) to (R, 2) i32.  Nothing
    payload-sized is added for flow control."""
    cfg = ForwardConfig(
        "data", R, CAP, exchange="padded", overflow="retain", flow="credit"
    )
    ops = collective_ops(_lower_round_any_flow(mesh8, cfg, "data"))
    a2a = [b for k, b in ops if k == "all-to-all"]
    payload = [b for b in a2a if b >= _payload_threshold(cfg)]
    counts = [b for b in a2a if b < _payload_threshold(cfg)]
    assert payload == [R * cfg.peer_capacity * WORDS * 4], a2a
    assert counts == [R * 2 * 4], a2a  # (R, 2) i32: count + advert columns


@pytest.mark.backpressure
@pytest.mark.parametrize(
    "fixture,axes,kw",
    [
        ("mesh8", "data", dict(exchange="padded")),
        ("mesh8", "data", dict(exchange="padded", marshal="scatter")),
        (
            "mesh_pods222", ("pod", "node", "device"),
            dict(exchange="hierarchical", level_sizes=(2, 2, 2),
                 level_capacities=(8, 8, 8)),
        ),
    ],
    ids=["padded", "padded-scatter", "hier3"],
)
def test_credit_adds_only_the_widened_count_column(request, fixture, axes, kw):
    """ISSUE 9 acceptance: the FULL collective inventory of a credit round
    equals the open-retain round's except that each per-tier count
    all_to_all grows by exactly one i32 column (A_l · 4 bytes — the advert
    lane).  Same op kinds, same op count, payload bytes untouched."""
    mesh = request.getfixturevalue(fixture)
    cfg_open = ForwardConfig(axes, R, CAP, overflow="retain", **kw)
    cfg_cred = ForwardConfig(
        axes, R, CAP, overflow="retain", flow="credit", **kw
    )
    ops_open = collective_ops(_lower_round_any_flow(mesh, cfg_open, axes))
    ops_cred = collective_ops(_lower_round_any_flow(mesh, cfg_cred, axes))
    assert len(ops_cred) == len(ops_open), (ops_cred, ops_open)
    sizes = kw.get("level_sizes", (R,))
    threshold = 4 * R * len(sizes) * 4  # any count block is far below this
    widened = 0
    for (ko, bo), (kc, bc) in zip(sorted(ops_open), sorted(ops_cred)):
        assert kc == ko
        if bc == bo:
            continue
        # a widened count exchange: one extra i32 per segment of the block
        assert ko == "all-to-all" and bo < threshold, (ops_open, ops_cred)
        assert (bc - bo) in {4 * a for a in sizes}, (bo, bc)
        widened += 1
    assert widened == len(sizes)  # one widened count collective per tier


# ----------------------------------------------- obs budget (ISSUE 10)
_OBS_CASES = {
    "padded": ("mesh8", dict(exchange="padded")),
    "onehot": ("mesh8", dict(exchange="onehot")),
    "hier3": (
        "mesh_pods222", dict(exchange="hierarchical", level_sizes=(2, 2, 2),
                             level_capacities=(8, 8, 8)),
    ),
    "ragged": ("mesh8", dict(exchange="ragged")),
}


@pytest.mark.obs
@pytest.mark.parametrize("case", sorted(_OBS_CASES))
def test_tracing_leaves_lowering_bit_identical(request, case):
    """ISSUE 10 acceptance: the observation law is HOST-only — with the
    ambient tracer installed (the ``obs`` marker turns it on through the
    ``RAFI_TRACE`` env toggle, so this exercises the real activation path),
    the lowered program of a forwarding round is BYTE-identical to the
    untraced one on every backend, and in particular the full collective
    inventory (kind, bytes, replica groups) is bit-identical.  Tracing can
    never change what the fabric ships — zero collective cost by proof, not
    by promise."""
    from repro.obs import trace as OT

    fixture, kw = _OBS_CASES[case]
    mesh = request.getfixturevalue(fixture)
    axes = "data" if fixture == "mesh8" else ("pod", "node", "device")
    cfg = ForwardConfig(axes, R, CAP, **kw)
    lower = _lower_one_round if fixture == "mesh8" else _lower_hier_round
    assert OT.enabled(), "RAFI_TRACE toggle did not install the tracer"
    on = lower(mesh, cfg)
    OT.uninstall()
    off = lower(mesh, cfg)
    assert on == off, f"{case}: tracing changed the lowered StableHLO"
    assert collective_ops(on, with_groups=True) == collective_ops(
        off, with_groups=True
    )


@pytest.mark.obs
def test_traced_metered_drive_leaves_lowering_bit_identical(mesh8):
    """The full-stack version of the guard: the complete ``run_until_done``
    drive (telemetry on, so the metrics source rides the carry) lowers
    byte-identically with the tracer installed vs not — the span hooks live
    in the host wrapper, never inside the jitted program, and the metrics
    snapshot is derived post-hoc from host-surfaced values."""
    import numpy as np

    from repro.core import DISCARD, WorkQueue
    from repro.core.context import RafiContext
    from repro.obs import trace as OT

    def lower_drive():
        ctx = RafiContext(
            mesh8, ray_proto(), capacity=CAP, peer_capacity=8,
            exchange="padded", telemetry=True, telemetry_window=8,
        )

        def round_fn(q_in, acc, rnd):
            me = jax.lax.axis_index("data")
            out = make_queue(ray_proto(), CAP)
            out = enqueue(
                out, make_rays(4), ((me + rnd) % R) * jnp.ones(4, jnp.int32),
                (jnp.arange(4) >= 0) & (rnd < 2),
            )
            return out, acc + q_in.count

        q0 = WorkQueue(
            items=jax.tree.map(
                lambda a: np.zeros((R * CAP,) + a.shape, a.dtype), ray_proto()
            ),
            dest=np.full((R * CAP,), DISCARD, np.int32),
            count=np.zeros((R,), np.int32),
            drops=np.zeros((R,), np.int32),
        )
        aux0 = np.zeros((R,), np.int32)
        drive = ctx.run_until_done(
            round_fn, aux_specs=P("data"), max_rounds=16
        )
        return drive.lower(q0, aux0).as_text()

    assert OT.enabled()
    on = lower_drive()
    OT.uninstall()
    off = lower_drive()
    assert on == off, "tracing changed the lowered drive program"
    assert collective_ops(on, with_groups=True) == collective_ops(
        off, with_groups=True
    )


# The lowered HLO of one forward round, pinned as SHA-256 digests of the
# StableHLO text this harness's kernel lowers to on JAX 0.9.0.  The PR-8
# stage-graph refactor was proven byte-identical to the monolith it replaced
# against digests taken the same way; these re-pin that snapshot on the
# installed JAX, so any change to what one round lowers to — a refactor that
# was meant to be free, or a new JAX — shows up here first.  The telemetry
# round lowers to the SAME program as the plain one: its stats are unused.
# Re-pinned when the padded receive compaction (``stages.compact_blocks``)
# became an inverse gather; ``onehot`` runs no compaction and kept its digest.
_PRE_REFACTOR_SHA256 = {
    "padded_sort": "9dcdd9362b1dd24ada07d81c513644702130ae75bea5639362d1737e6d91ffd6",
    "padded_scatter": "c3d80335b5e15ad83fd219d24ac7e8a91c19ade298a1e6619509e42cfa8416c1",
    "padded_retain": "746f1e02d19e980946b0ad8e01c7d52110356a2cd9ed23e94697117b9ef884f2",
    "padded_telemetry": "9dcdd9362b1dd24ada07d81c513644702130ae75bea5639362d1737e6d91ffd6",
    "onehot": "d6e4464d803b47854a1df6ec5ee838cfcf91403ec1fddd7a9f7b36ea65f39b9d",
    "hier3_sort": "6f734832be38bb2870de646fcb5a631fc4d2c8b8c02a1c57399dafde268eb14a",
    "hier3_scatter": "74e9e409fdd13c49c6ece65e7e7b77e6ce803a578656f383717248b8b78e5d4b",
    "hier3_retain": "40e828f8294b6ccec5dbc5271f3f8d27fb81bb908ac03b078299af235478da41",
}

_GOLDEN_CASES = {
    "padded_sort": ("mesh8", dict(exchange="padded")),
    "padded_scatter": ("mesh8", dict(exchange="padded", marshal="scatter")),
    "padded_retain": ("mesh8", dict(exchange="padded", overflow="retain")),
    "padded_telemetry": ("mesh8", dict(exchange="padded", telemetry=True)),
    "onehot": ("mesh8", dict(exchange="onehot")),
    "hier3_sort": (
        "mesh_pods222", dict(exchange="hierarchical", level_sizes=(2, 2, 2),
                             level_capacities=(8, 8, 8)),
    ),
    "hier3_scatter": (
        "mesh_pods222", dict(exchange="hierarchical", level_sizes=(2, 2, 2),
                             level_capacities=(8, 8, 8), marshal="scatter"),
    ),
    "hier3_retain": (
        "mesh_pods222", dict(exchange="hierarchical", level_sizes=(2, 2, 2),
                             level_capacities=(8, 8, 8), overflow="retain"),
    ),
}


def _lower_golden(mesh, cfg):
    """The snapshot harness: arity-agnostic (retain/telemetry rounds return
    more, the extras stay unused exactly as in the golden lowering)."""
    axes = cfg.axis_name

    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index(axes)
        q = enqueue(
            q, make_rays(10), ((me + jnp.arange(10)) % R).astype(jnp.int32),
            jnp.ones(10, bool),
        )
        res = forward_work(q, cfg)
        nq, total = res[0], res[1]
        return nq.count[None], total, nq.items.tmin

    spec = P(axes)
    return jax.jit(
        jax.shard_map(
            kernel, mesh=mesh, in_specs=spec, out_specs=(spec, P(), spec)
        )
    ).lower(jnp.arange(8.0)).as_text()


@pytest.mark.pipeline
@pytest.mark.parametrize("case", sorted(_GOLDEN_CASES))
def test_bulk_lowering_bitidentical_to_pre_refactor(request, case):
    """With ``pipeline_shards=1`` the stage-graph exchange lowers
    BYTE-identically to the pinned program — same StableHLO text, so same
    compiled program, no trust required."""
    import hashlib

    fixture, kw = _GOLDEN_CASES[case]
    mesh = request.getfixturevalue(fixture)
    axes = "data" if fixture == "mesh8" else ("pod", "node", "device")
    cfg = ForwardConfig(axes, R, CAP, pipeline_shards=1, **kw)
    got = hashlib.sha256(_lower_golden(mesh, cfg).encode()).hexdigest()
    assert got == _PRE_REFACTOR_SHA256[case], (
        f"{case}: S=1 lowering diverged from the pinned HLO"
    )
