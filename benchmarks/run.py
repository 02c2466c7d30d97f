"""Benchmark harness — one entry per paper table/figure.

  fig8_efficiency_*      Fig. 8 analogue: forwarding bandwidth efficiency vs
                         rays-per-rank (useful payload ÷ total wire bytes,
                         from the lowered production-mesh HLO), for the
                         padded and ragged exchanges.
  sort_cost_*            §6.1 claim "all of [sort/marshal] are trivially
                         cheap": sort-stage FLOPs+bytes vs exchange bytes.
  fwd_walltime_*         forward_work wall time on 8 CPU devices (us/call).
  fwd_walltime_hier_*    flat vs hierarchical two-stage exchange on 2-D
                         (node, device) meshes (2×4, 4×2), with the modeled
                         slow-axis byte volume per route.
  fwd_walltime_hier3_*   flat vs 2-level vs 3-level route on the (2, 2, 2)
                         (pod, node, device) mesh, with modeled per-tier
                         bytes.
  fwd_walltime_marshal_* sort vs scatter marshal (ISSUE 4) on the flat 8-way
                         and the (2, 2, 2) hierarchical mesh, with the
                         modeled marshal plan bytes (the scatter deletes the
                         O(C log C) key-sort traffic; both modes keep the
                         one-payload-pass law).
  fwd_walltime_pipeline_* ISSUE 8: bulk-synchronous vs micro-shard pipelined
                         (``pipeline_shards=4``) padded round on ballasted
                         rounds over growing working sets (quoted ratio =
                         adjacent-pair median; ``gated=1`` marks the
                         cache-exceeding points the compare gate covers),
                         plus an ungated 3-level trend point.
  rebalance_skew_*       skewed-load rebalance (flat / topology-aware /
                         intra scope) with per-tier payload bytes from the
                         lowered HLO — intra must put zero below the
                         fastest tier.
  autotune_drift_*       ISSUE 5: drifting hot-spot scenario (the hot
                         destination rotates mid-run) driven by
                         ``tune.autotune_forward`` — per-burst rows show the
                         capacities/drops trajectory; the final row compares
                         the tuned config's modeled padded wire bytes against
                         the §6.3 worst-case static sizing that achieves the
                         same zero drops.  The section FAILS unless the tuner
                         converges drop-free at ≤ the static wire cost.
  fwd_walltime_telemetry_* only with ``--compare off,telemetry``: forwarding
                         walltime with the flight recorder off vs on
                         (interleaved medians, like the marshal gate).
  fwd_walltime_overflow_* overflow drop vs retain walltime on the happy path
                         (ample capacity, zero spill pressure) — retention
                         must be free when nothing spills.
  chaos_*                ISSUE 6: every deterministic fault-injection
                         scenario (drought / hot-spot / burst / convergecast)
                         run retain vs drop under starved send budgets, with
                         full loss accounting per row.  The section FAILS
                         unless retain loses NOTHING (age within the
                         spill_drain_model bound) while drop loses >20% of
                         the convergecast.
  fwd_walltime_ckpt_*    ISSUE 7: segmented-drive walltime with the
                         checkpoint writer off vs on (checkpoint_every=8)
                         on ballasted convergecast bursts — the recovery
                         law's amortized-overhead measurement.
  chaos_recovery_*       ISSUE 7: the recovery acceptance — preempt at
                         round 5 / resume must be bit-exact with the
                         uninterrupted run at every common checkpoint
                         boundary (SHA-256 of every carry leaf), and a
                         mid-burst two-rank brownout must drain lossless,
                         matching the numpy twin's trajectory.  FAILS on
                         any violation.
  fwd_walltime_flow_*    ISSUE 9: retain-mode forwarding walltime with
                         ``flow`` open vs credit on the fully-credited happy
                         path — the advert column and grant arithmetic must
                         be ~free when nobody is starved.
  chaos_backpressure_*   ISSUE 9: the two overload scenarios (fixed hot-pair
                         saturation, full-width incast) run open vs credit
                         with goodput/waste accounting per row.  The section
                         FAILS unless credit delivers everything with zero
                         receiver drops, bounded occupancy, and an
                         advert-only first round where open flow wastes >30%
                         of its wire rows.
  fwd_walltime_obs_*     ISSUE 10: the same compiled chaos burst with the
                         ambient span tracer + per-burst metrics snapshot
                         off vs on (the lowered HLO is identical — this
                         times the host bookkeeping).
  obs_flight_report_*    ISSUE 10 acceptance: the incast-collapse overload
                         pair captured through the tracer and replayed
                         through the ``repro.obs.report`` flight-data
                         analyzer — the report must reproduce the driver's
                         goodput/waste numbers and flag only the open-flow
                         run as degraded.  FAILS on any mismatch.
  sort_throughput_*      §4.2.1 key pack+sort throughput (keys/s), XLA vs
                         Pallas(interpret) paths.
  app_*                  §5 application throughputs (CPU, small scenes).
  moe_dispatch_*         paper technique on the LM side: RaFI-EP dispatch vs
                         dense-TP baseline wall time (tokens/s).

Output: ``name,us_per_call,derived`` CSV on stdout, and optionally a
machine-readable JSON file (``--json PATH``) so successive PRs can track the
perf trajectory::

    {"meta": {...}, "rows": [{"name": ..., "us_per_call": ...,
                              "derived": {"rays_per_s": 1.6e6, ...}}, ...]}

``--smoke`` runs only the fast forwarding-walltime subset (the regression
canary); ``--only SUBSTR`` filters sections by name; ``--compare
flat,hierarchical`` is the CI gate that fails (exit 1) when the hierarchical
exchange regresses the flat one by >5% walltime on a single-node mesh;
``--compare flat,hierarchical2,hierarchical3`` is the PR-3 gate: the 3-way
(2, 2, 2)-mesh sweep + the skewed rebalance benchmark, failing unless the
3-level route's modeled slowest-tier bytes undercut both alternatives;
``--compare sort,scatter`` is the PR-4 gate: the marshal sweep on the flat
and (2, 2, 2) meshes, failing if the scatter marshal regresses the sort path
by >5% walltime at any point (BENCH_PR4.json is this gate's ``--json`` dump);
``--compare off,telemetry`` is the PR-5 gate: telemetry-on walltime must stay
within a 1.05× geomean of telemetry-off across the sweep, and the
autotune_drift section must converge — BENCH_PR5.json is this gate's dump.
``--compare drop,retain`` is the PR-6 gate: retain-mode walltime must stay
within a 1.05× geomean of drop mode on the happy path, and the
chaos_lossless acceptance must hold — BENCH_PR6.json is this gate's dump.
``--compare nockpt,ckpt`` is the PR-7 gate: the checkpointed drive
(checkpoint_every=8) must stay within a 1.05× walltime geomean of the
save-free segmented drive on ballasted bursts, and the chaos_recovery
acceptance must hold (preempt-resume bit-exact, brownout lossless) —
BENCH_PR7.json is this gate's dump.
``--compare bulk,pipelined`` is the PR-8 gate: the micro-shard pipelined
round must hold a ≤1.0× walltime geomean against the bulk round on the
ballasted flat points whose buffers exceed the cache — where the locality
mechanism applies; pipelining exists only for walltime, so ANY regression
there defeats it.  BENCH_PR8.json is this gate's dump.
``--compare open,credit`` is the PR-9 gate: credit-flow walltime must stay
within a 1.05× geomean of open flow on the fully-credited happy path, and
the chaos_backpressure acceptance must hold (credit lossless with bounded
occupancy on both overload scenarios where open wastes >30% of its wire
rows) — BENCH_PR9.json is this gate's dump.
``--compare off,obs`` is the PR-10 gate: a traced + metered burst must stay
within a 1.05× walltime geomean of the untraced one (the device program is
bit-identical by construction; the gate covers the host span/metrics cost),
and the obs_flight_report acceptance must hold (the flight-data analyzer
reproduces the chaos driver's goodput/waste numbers from the capture alone
and flags only the open-flow overload run as degraded) — BENCH_PR10.json is
this gate's dump.
``--autotune`` runs the autotune_drift section alone; ``--chaos`` runs the
chaos_lossless + chaos_recovery + chaos_backpressure acceptance sections
alone.

Every ``--json`` dump carries provenance: git SHA, jax version, platform,
the command line, and the ``ForwardConfig`` fields + mesh shape of each
benchmarked configuration (``meta.configs``) — enough to re-run any row.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat

# Shared harness (row sink, provenance, timing methodology, Ray44 fixture)
# — split out so new sweeps extend _harness.py instead of this file.
from _harness import (  # noqa: E402
    CONFIGS,
    ROWS,
    Ray44,
    _emit_kernel,
    _git_sha,
    _pair_ratio,
    _mesh8,
    _paired_times,
    _parse_derived,
    _ray_proto,
    _timeit,
    _write_json,
    emit,
    record_cfg,
)


# ------------------------------------------------- Fig. 8: wire efficiency
def fig8_efficiency():
    """Useful payload bytes ÷ total collective bytes, from the lowered HLO of
    the production 256-chip mesh — the structural analogue of Fig. 8's
    bandwidth-utilization curve (no TPU wall clock exists here)."""
    from repro.core import ForwardConfig, item_nbytes
    from repro.roofline.analysis import collective_bytes

    # AbstractMesh: lower for the 256-chip production mesh without devices
    mesh = compat.abstract_mesh((16, 16), ("data", "model"))
    R = 256
    item_b = item_nbytes(_ray_proto())
    for n_emit in (64, 512, 4096, 32768):
        for exchange in ("padded", "ragged"):
            cap = max(n_emit, 256)
            cfg = ForwardConfig(
                ("data", "model"), R, cap, exchange=exchange,
                peer_capacity=max(1, -(-n_emit * 2 // R)),
            )
            kern = _emit_kernel(cfg, n_emit, cap)
            t0 = time.perf_counter()
            low = jax.jit(
                jax.shard_map(kern, mesh=mesh, in_specs=P(("data", "model")),
                              out_specs=P(("data", "model")))
            ).lower(jnp.arange(512.0))
            lower_us = (time.perf_counter() - t0) * 1e6
            coll = collective_bytes(low.as_text())
            useful = n_emit * item_b  # per rank
            if exchange == "ragged":
                # ragged payload bytes are data-dependent == useful; static
                # HLO only bounds the receive buffer.  Wire = payload +
                # control plane (the count collective).
                control = sum(v for k, v in coll.items() if k != "ragged-all-to-all")
                total = useful + control
            else:
                total = sum(coll.values())
            eff = useful / total if total else 0.0
            emit(
                f"fig8_efficiency_{exchange}_n{n_emit}", lower_us,
                f"useful_frac={eff:.3f};useful_B={useful};wire_B={total};item_B={item_b}",
            )


# --------------------------------------------- §6.1: sort stage is ~free
def sort_cost():
    from repro.core import sorting as S

    for n in (4096, 65536):
        dest = jnp.array(np.random.default_rng(0).integers(0, 256, n), jnp.int32)
        rays = jax.tree.map(lambda l: jnp.zeros((n,) + l.shape, l.dtype), _ray_proto())
        f = jax.jit(lambda r, d: S.sort_by_destination(r, d, jnp.int32(n), 256))
        us, _ = _timeit(f, rays, dest)
        cost = f.lower(rays, dest).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = cost.get("flops", 0.0)
        byts = cost.get("bytes accessed", 0.0)
        wire = n * 44  # what the exchange must move anyway
        emit(
            f"sort_cost_n{n}", us,
            f"sort_bytes_over_wire_bytes={byts/max(wire,1):.2f};flops={flops:.2e}",
        )


# ------------------------------------------------ forward_work wall time
def fwd_walltime():
    from repro.core import ForwardConfig

    mesh = _mesh8()
    for n_emit in (256, 2048):
        for exchange in ("padded", "onehot"):
            cap = max(256, n_emit * 2)
            # peer_capacity only exists for padded slots (onehot rejects it)
            kw = {"peer_capacity": cap} if exchange == "padded" else {}
            cfg = ForwardConfig("data", 8, cap, exchange=exchange, **kw)
            record_cfg(f"fwd_walltime_{exchange}_n{n_emit}", cfg, mesh)
            f = jax.jit(
                jax.shard_map(_emit_kernel(cfg, n_emit, cap), mesh=mesh,
                              in_specs=P("data"), out_specs=P("data"))
            )
            us, _ = _timeit(f, jnp.arange(8.0))
            rays_s = 8 * n_emit / (us / 1e6)
            emit(f"fwd_walltime_{exchange}_n{n_emit}", us, f"rays_per_s={rays_s:.2e}")


# ------------------------------------- ISSUE 2: hierarchical vs flat route
def _hier_pair(nodes, devs, n_emit, cap):
    """(flat_cfg, hier_cfg, mesh) for one 2-D (node, device) mesh point."""
    from repro.core import ForwardConfig
    from repro.launch.mesh import make_node_mesh

    mesh = make_node_mesh(nodes, devs)
    axes = ("node", "device")
    flat = ForwardConfig(axes, nodes * devs, cap, exchange="padded")
    hier = ForwardConfig(axes, nodes * devs, cap, exchange="hierarchical", fast_size=devs)
    return flat, hier, mesh


def _time_fwd(cfg, mesh, n_emit, cap, iters=5):
    f = jax.jit(
        jax.shard_map(
            _emit_kernel(cfg, n_emit, cap), mesh=mesh,
            in_specs=P(cfg.axis_name), out_specs=P(cfg.axis_name),
        )
    )
    us, _ = _timeit(f, jnp.arange(8.0), iters=iters)
    return us


def fwd_walltime_hier():
    """Flat-vs-hierarchical forwarding walltime sweep over 2-D (node, device)
    meshes (2×4 and 4×2 on the 8-device CPU platform), plus the modeled bulk
    bytes each route pushes across the slow inter-node fabric — the term the
    two-stage exchange exists to shrink (CPU walltime treats all links as
    equal; the slow-byte model is where multi-node wins show)."""
    from repro.core import item_nbytes
    from repro.roofline.analysis import slow_axis_bytes_model

    item_b = item_nbytes(_ray_proto())
    for nodes, devs in ((2, 4), (4, 2)):
        for n_emit in (256, 2048):
            cap = max(256, n_emit * 2)
            flat, hier, mesh = _hier_pair(nodes, devs, n_emit, cap)
            R = nodes * devs
            for tag, cfg in (("flat", flat), ("hier", hier)):
                record_cfg(f"fwd_walltime_hier_{tag}_{nodes}x{devs}", cfg, mesh)
                us = _time_fwd(cfg, mesh, n_emit, cap)
                slow_b = slow_axis_bytes_model(
                    cfg.exchange if tag == "hier" else "padded",
                    num_ranks=R, fast_size=devs, item_bytes=item_b,
                    peer_capacity=cfg.peer_capacity,
                    node_capacity=getattr(cfg, "node_capacity", 0),
                )
                rays_s = 8 * n_emit / (us / 1e6)
                # burst_rows: the hot-spot burst one destination absorbs
                # without drops at this slow-byte budget.  At the default
                # load-proportional capacities the two routes' total slow
                # bytes coincide, so the discriminating metric is the slow
                # bytes PAID PER ROW of burst tolerance: (R-F)·item_B flat vs
                # (N-1)·item_B hierarchical — per-node padding makes it
                # devs× cheaper (= R/N×, since R-F = F·(N-1)).
                burst = cfg.node_capacity if tag == "hier" else cfg.peer_capacity
                emit(
                    f"fwd_walltime_hier_{tag}_{nodes}x{devs}_n{n_emit}", us,
                    f"rays_per_s={rays_s:.2e};slow_axis_B={slow_b:.0f}"
                    f";burst_rows={burst};slow_B_per_burst_row={slow_b / burst:.1f}",
                )


def _pod_configs(cap):
    """(flat, hier2, hier3, mesh) for the (2, 2, 2) three-tier mesh: flat
    routes one joint all_to_all over everything; hier2 treats (pod, node) as
    one joint slow fabric; hier3 is the full 3-level route."""
    from repro.core import ForwardConfig
    from repro.launch.mesh import make_pod_mesh

    mesh = make_pod_mesh(2, 2, 2)
    axes = ("pod", "node", "device")
    flat = ForwardConfig(axes, 8, cap, exchange="padded")
    hier2 = ForwardConfig(
        (("pod", "node"), "device"), 8, cap, exchange="hierarchical",
        level_sizes=(4, 2),
    )
    hier3 = ForwardConfig(
        axes, 8, cap, exchange="hierarchical", level_sizes=(2, 2, 2)
    )
    return flat, hier2, hier3, mesh


def _stage_crossing_rows(sub_sizes, slot_rows):
    """Rows ONE rank's padded stage pushes across each sub-tier of its
    fabric: the stage fans out prod(sub_sizes) slots of ``slot_rows``; a slot
    whose digit first differs at sub-tier j crosses fabric j (and nothing
    slower).  Returns one entry per sub-tier, slowest first."""
    out, remaining = [], 1
    for a in sub_sizes:
        remaining *= a
    for a in sub_sizes:
        out.append((remaining - remaining // a) * slot_rows)
        remaining //= a
    return out


def _route_tier_rows(tag, cfg, n_tiers=3):
    """Padded rows one rank puts on each physical fabric tier per round,
    attributed by where each slot/segment's destination digit FIRST differs
    (a flat slot to another pod crosses only the DCN hop of the route)."""
    if tag == "flat":
        return _stage_crossing_rows((2, 2, 2), cfg.peer_capacity)
    tiers = [0.0] * n_tiers
    if len(cfg.level_sizes) == 2 and cfg.level_sizes[0] == 4:
        # hier2: the joint (pod, node) slow stage spans two physical fabrics
        t0, t1 = _stage_crossing_rows((2, 2), cfg.level_capacities[0])
        tiers[0], tiers[1] = t0, t1
        tiers[2] = _stage_crossing_rows((2,), cfg.level_capacities[1])[0]
    else:
        for l, (a, s) in enumerate(zip(cfg.level_sizes, cfg.level_capacities)):
            tiers[l] = _stage_crossing_rows((a,), s)[0]
    return tiers


def _time_fwd_axes(cfg, mesh, axes, n_emit, cap, iters=5):
    """Like _time_fwd but with explicit shard_map axes (the config's level
    axes may be nested tuples, which PartitionSpec cannot carry)."""
    f = jax.jit(
        jax.shard_map(
            _emit_kernel(cfg, n_emit, cap), mesh=mesh,
            in_specs=P(axes), out_specs=P(axes),
        )
    )
    us, _ = _timeit(f, jnp.arange(8.0), iters=iters)
    return us


def fwd_walltime_hier3():
    """ISSUE 3 sweep: flat vs 2-level vs 3-level route on the (2, 2, 2)
    (pod, node, device) mesh, with the modeled bytes each route pushes across
    every fabric tier (CPU walltime treats all links as equal; the byte model
    is where the N-level win shows).  At the default load-proportional
    capacities the routes' total slowest-tier bytes can coincide, so the
    discriminating metric — as in the PR-2 2-level sweep — is the slowest-
    tier bytes PAID PER ROW of burst tolerance: 4·item_B flat (4 of 7 slots
    cross the pod fabric) vs 2·item_B hier2 (2 of 3 joint-tier segments) vs
    1·item_B hier3 (exactly the one off-pod segment)."""
    from repro.core import item_nbytes

    item_b = item_nbytes(_ray_proto())
    axes = ("pod", "node", "device")
    for n_emit in (256, 2048):
        cap = max(256, n_emit * 2)
        flat, hier2, hier3, mesh = _pod_configs(cap)
        for tag, cfg in (("flat", flat), ("hier2", hier2), ("hier3", hier3)):
            record_cfg(f"fwd_walltime_hier3_{tag}", cfg, mesh)
            us = _time_fwd_axes(cfg, mesh, axes, n_emit, cap)
            tiers = [r * item_b for r in _route_tier_rows(tag, cfg)]
            # burst_rows: the hot-spot burst one destination absorbs without
            # drops at this budget (per-slot flat, per slowest-segment hier)
            burst = (
                cfg.peer_capacity if tag == "flat" else cfg.level_capacities[0]
            )
            rays_s = 8 * n_emit / (us / 1e6)
            emit(
                f"fwd_walltime_hier3_{tag}_2x2x2_n{n_emit}", us,
                f"rays_per_s={rays_s:.2e};tier0_B={tiers[0]:.0f}"
                f";tier1_B={tiers[1]:.0f};tier2_B={tiers[2]:.0f}"
                f";burst_rows={burst}"
                f";tier0_B_per_burst_row={tiers[0] / burst:.1f}",
            )


def rebalance_skew():
    """ISSUE 3: skewed-load rebalance on the (2, 2, 2) mesh — flat global
    plan vs topology-aware plan vs intra-tier scope, with the payload bytes
    the lowered program puts on each fabric tier (from the HLO replica
    groups).  The intra route must show ZERO bytes below the fastest tier."""
    from repro.core import DISCARD, ForwardConfig, WorkQueue, rebalance
    from repro.core import types as T
    from repro.launch.mesh import make_pod_mesh
    from repro.roofline.analysis import per_tier_collective_bytes

    sizes = (2, 2, 2)
    axes = ("pod", "node", "device")
    mesh = make_pod_mesh(*sizes)
    cap = 512
    words = T.pack_spec(_ray_proto()).total_words
    flat_cfg = ForwardConfig(axes, 8, cap, exchange="padded")
    hier_cfg = ForwardConfig(
        axes, 8, cap, exchange="hierarchical", level_sizes=sizes
    )

    def bench(tag, cfg, scope):
        def bal(_x):
            me = jax.lax.axis_index(axes)
            n = jnp.where(me % 2 == 0, 300, 4)  # node-local hoarders
            rays = jax.tree.map(
                lambda l: jnp.zeros((cap,) + l.shape, l.dtype), _ray_proto()
            )
            q = WorkQueue(
                items=rays, dest=jnp.full((cap,), DISCARD, jnp.int32),
                count=n.astype(jnp.int32), drops=jnp.zeros((), jnp.int32),
            )
            nq, total = rebalance(q, cfg, scope=scope)
            checksum = jnp.sum(nq.items.tmin) * 0
            return nq.count[None] + checksum.astype(jnp.int32)

        f = jax.jit(
            jax.shard_map(bal, mesh=mesh, in_specs=P(axes), out_specs=P(axes))
        )
        us, _ = _timeit(f, jnp.arange(8.0))
        per_tier = per_tier_collective_bytes(
            f.lower(jnp.arange(8.0)).as_text(), sizes, min_bytes=words * 4 * 8
        )
        emit(
            f"rebalance_skew_{tag}_2x2x2", us,
            f"tier0_B={per_tier[0]};tier1_B={per_tier[1]}"
            f";tier2_B={per_tier[2]};cross_B={per_tier['cross']}",
        )
        return per_tier

    bench("flat", flat_cfg, "global")
    bench("hier", hier_cfg, "global")
    intra = bench("intra", hier_cfg, "intra")
    if intra[0] or intra[1] or intra["cross"]:
        raise RuntimeError(
            f"intra-scope rebalance leaked payload bytes off the fastest "
            f"tier: {intra}"
        )


# ------------------------------------- ISSUE 5: drifting hot-spot autotune
def _drift_run_burst(mesh, axes, num_ranks, cap, n_emit, rounds, times):
    """``tune.autotune_forward`` burst driver for the drifting hot-spot
    scenario: every round, half of each rank's emits chase a hot destination
    that ROTATES every 2 rounds — a workload no single static observation
    sizes correctly, which is exactly what the flight recorder's windowed
    max is for.  Each distinct config re-jits (configs are static);
    per-burst walltimes are appended to ``times``."""
    from repro import telemetry as TM
    from repro.core import DISCARD, enqueue, make_queue, run_until_done

    def emits(me, rnd):
        lane = jnp.arange(n_emit)
        hot = (rnd // 2) % num_ranks
        dest = jnp.where(lane % 2 == 0, hot, (me + lane) % num_ranks)
        rays = Ray44(
            origin=jnp.ones((n_emit, 3)), direction=jnp.ones((n_emit, 3)),
            tmin=lane.astype(jnp.float32), pixel=lane.astype(jnp.int32),
            integral=jnp.zeros(n_emit), extra=jnp.zeros((n_emit, 2)),
        )
        return rays, dest.astype(jnp.int32)

    compiled = {}

    def run_burst(cfg):
        if cfg not in compiled:
            def round_fn(q_in, acc, rnd):
                me = jax.lax.axis_index(axes)
                rays, dest = emits(me, rnd + 1)
                out = make_queue(_ray_proto(), cap)
                out = enqueue(
                    out, rays, jnp.where(rnd + 1 < rounds, dest, DISCARD),
                    jnp.ones(n_emit, bool),
                )
                return out, acc

            def drive(_x):
                me = jax.lax.axis_index(axes)
                rays, dest = emits(me, 0)
                q0 = enqueue(
                    make_queue(_ray_proto(), cap), rays, dest,
                    jnp.ones(n_emit, bool),
                )
                q, _acc, _r, _done, ring = run_until_done(
                    round_fn, q0, jnp.zeros((), jnp.int32), cfg,
                    max_rounds=rounds + 2,
                )
                return q.drops[None], TM.stack_ring(ring)

            ring_spec = jax.tree.map(
                lambda _: P(axes),
                TM.make_ring(
                    TM.num_tiers(cfg), window=cfg.telemetry_window,
                    buckets=cfg.telemetry_buckets,
                ),
            )
            compiled[cfg] = jax.jit(
                jax.shard_map(
                    drive, mesh=mesh, in_specs=P(axes),
                    out_specs=(P(axes), ring_spec),
                )
            )
        t0 = time.perf_counter()
        drops, ring = jax.block_until_ready(compiled[cfg](jnp.arange(8.0)))
        times.append((time.perf_counter() - t0) * 1e6)
        return int(np.asarray(drops).sum()), ring

    return run_burst


def autotune_drift():
    """ISSUE 5 acceptance: on the drifting hot-spot, ``autotune_forward``
    must converge from a deliberately undersized config to VERIFIED zero
    clamp drops, with modeled padded wire bytes ≤ the §6.3 worst-case static
    sizing that achieves the same (per tier, a slot concatenates the emits
    of every source sub-segment feeding it — n_emit × that fan-in is the
    provable bound and the tuner's ceiling)."""
    from repro import telemetry as TM
    from repro.core import ForwardConfig, item_nbytes
    from repro.launch.mesh import make_pod_mesh
    from repro.roofline.analysis import occupancy_waste_model
    from repro.tune import TunePolicy, autotune_forward

    item_b = item_nbytes(_ray_proto())
    cap, n_emit, rounds = 1024, 96, 8
    axes3 = ("pod", "node", "device")
    scenarios = (
        (
            "flat", _mesh8(), "data", (8,), (n_emit,),
            dict(exchange="padded", peer_capacity=8),
        ),
        (
            "hier3", make_pod_mesh(2, 2, 2), axes3, (2, 2, 2),
            (4 * n_emit, 2 * n_emit, n_emit),
            dict(
                exchange="hierarchical", level_sizes=(2, 2, 2),
                level_capacities=(8, 8, 8),
            ),
        ),
    )
    for tag, mesh, axes, sizes, bounds, kw in scenarios:
        times = []
        run_burst = _drift_run_burst(mesh, axes, 8, cap, n_emit, rounds, times)
        cfg0 = ForwardConfig(
            axes, 8, cap, telemetry=True, telemetry_window=rounds + 2, **kw
        )
        final, report = autotune_forward(
            run_burst, cfg0,
            policy=TunePolicy(headroom=1.25, granularity=8),
            bounds=bounds, max_bursts=6,
        )
        for s, us in zip(report.steps, times):
            emit(
                f"autotune_drift_{tag}_burst{s.burst}", us,
                f"drops={s.drops}"
                f";caps={'/'.join(map(str, s.capacities))}"
                f";planned={'/'.join(map(str, s.planned))}"
                f";demand_max={'/'.join(map(str, s.demand_max))}",
            )
        tuned = occupancy_waste_model(
            sizes, TM.tier_capacities(final), item_b
        )
        static = occupancy_waste_model(sizes, bounds, item_b)
        record_cfg(f"autotune_drift_{tag}_final", final, mesh)
        emit(
            f"autotune_drift_{tag}_final", float(np.mean(times)),
            f"converged={int(report.converged)};final_drops={report.final_drops}"
            f";bursts={report.bursts}"
            f";tuned_wire_B={tuned['wire_B']:.0f}"
            f";static_wire_B={static['wire_B']:.0f}"
            f";caps={'/'.join(map(str, TM.tier_capacities(final)))}",
        )
        if (
            not report.converged
            or report.final_drops != 0
            or tuned["wire_B"] > static["wire_B"]
        ):
            raise RuntimeError(
                f"autotune_drift_{tag} failed: converged={report.converged} "
                f"final_drops={report.final_drops} tuned_wire_B="
                f"{tuned['wire_B']:.0f} static_wire_B={static['wire_B']:.0f}"
            )


# ------------------------------------- ISSUE 5: telemetry overhead gate
def fwd_walltime_telemetry(samples=8):
    """Flight-recorder overhead sweep: the same forwarding round with
    ``telemetry`` off vs on (flat padded + 3-level hierarchical), timed
    interleaved per point (see :func:`_paired_times`).  Returns
    ``{(tag, variant, n_emit): us}`` for the ``--compare off,telemetry``
    gate (on/off walltime geomean must stay ≤ 1.05)."""
    from repro.core import ForwardConfig
    from repro.launch.mesh import make_pod_mesh

    mesh_flat = _mesh8()
    mesh_pod = make_pod_mesh(2, 2, 2)
    axes3 = ("pod", "node", "device")
    times = {}
    for n_emit in (256, 2048):
        cap = max(256, n_emit * 2)
        points = (
            (
                "flat", mesh_flat, "data",
                lambda t: ForwardConfig(
                    "data", 8, cap, exchange="padded", telemetry=t
                ),
            ),
            (
                "hier3", mesh_pod, axes3,
                lambda t: ForwardConfig(
                    axes3, 8, cap, exchange="hierarchical",
                    level_sizes=(2, 2, 2), telemetry=t,
                ),
            ),
        )
        for tag, mesh, axes, mk_cfg in points:
            best = _paired_times(
                {"off": mk_cfg(False), "telemetry": mk_cfg(True)},
                mesh, axes, n_emit, cap, samples,
            )
            record_cfg(f"telemetry_{tag}_n{n_emit}", mk_cfg(True), mesh)
            for variant, us in best.items():
                times[(tag, variant, n_emit)] = us
                rays_s = 8 * n_emit / (us / 1e6)
                emit(
                    f"fwd_walltime_telemetry_{tag}_{variant}_n{n_emit}", us,
                    f"rays_per_s={rays_s:.2e}",
                )
    return times


# --------------------------------- ISSUE 6: lossless forwarding (chaos)
def fwd_walltime_overflow(samples=8):
    """Retain-mode overhead sweep on the HAPPY PATH (capacity ample, zero
    spill pressure): the same forwarding round with ``overflow`` drop vs
    retain (flat padded + 3-level hierarchical), timed interleaved per point.
    Returns ``{(tag, variant, n_emit): us}`` for the ``--compare drop,retain``
    gate (retain/drop walltime geomean must stay ≤ 1.05 — retention must be
    free when nothing spills)."""
    from repro.core import ForwardConfig
    from repro.launch.mesh import make_pod_mesh

    mesh_flat = _mesh8()
    mesh_pod = make_pod_mesh(2, 2, 2)
    axes3 = ("pod", "node", "device")
    times = {}
    for n_emit in (256, 2048):
        cap = max(256, n_emit * 2)
        points = (
            (
                "flat", mesh_flat, "data",
                lambda o: ForwardConfig(
                    "data", 8, cap, exchange="padded", overflow=o
                ),
            ),
            (
                "hier3", mesh_pod, axes3,
                lambda o: ForwardConfig(
                    axes3, 8, cap, exchange="hierarchical",
                    level_sizes=(2, 2, 2), overflow=o,
                ),
            ),
        )
        for tag, mesh, axes, mk_cfg in points:
            best = _paired_times(
                {"drop": mk_cfg("drop"), "retain": mk_cfg("retain")},
                mesh, axes, n_emit, cap, samples,
            )
            record_cfg(f"overflow_{tag}_n{n_emit}", mk_cfg("retain"), mesh)
            for variant, us in best.items():
                times[(tag, variant, n_emit)] = us
                rays_s = 8 * n_emit / (us / 1e6)
                emit(
                    f"fwd_walltime_overflow_{tag}_{variant}_n{n_emit}", us,
                    f"rays_per_s={rays_s:.2e}",
                )
    return times


def chaos_lossless():
    """The ISSUE-6 acceptance run: every chaos scenario, retain vs drop,
    under deliberately starved send budgets (peer slots of 2 rows where the
    convergecast backlogs 48 per sender).  Records per-scenario loss
    accounting and RAISES unless (a) retain mode loses NOTHING anywhere
    (drops == lost == 0, clean drain, age within the spill_drain_model
    bound) while (b) drop mode — the same traffic, same capacities — loses
    >20%% of the convergecast.  That contrast is the subsystem's reason to
    exist; a silent regression here must trip CI, not trend a row."""
    from repro.chaos import all_scenarios, run_scenario
    from repro.roofline.analysis import spill_drain_model

    mesh = _mesh8()
    S, C = 2, 128
    problems = []
    for sc in all_scenarios(8):
        rows = {}
        for mode in ("drop", "retain"):
            t0 = time.perf_counter()
            res = run_scenario(
                mesh, sc, capacity=C, peer_capacity=S, overflow=mode,
                max_rounds=64,
            )
            dt = time.perf_counter() - t0
            rows[mode] = res
            loss_frac = (res["drops"] + res["lost"]) / res["emitted"]
            emit(
                f"chaos_{sc.name}_{mode}", dt * 1e6,
                f"emitted={res['emitted']};delivered={res['delivered_total']}"
                f";drops={res['drops']};lost={res['lost']}"
                f";loss_frac={loss_frac:.3f};rounds={res['rounds']}"
                f";age_max={res.get('age_max', 0)}",
            )
            if res["lost"] != 0:  # conservation broken in EITHER mode
                problems.append(f"{sc.name}/{mode}: lost={res['lost']}")
        ret = rows["retain"]
        if ret["drops"] != 0 or not ret["done"]:
            problems.append(
                f"{sc.name}/retain: drops={ret['drops']} done={ret['done']}"
            )
        bound = (
            spill_drain_model(sc.rounds * sc.emits_per_round, S)["age_bound"]
            + sc.rounds
        )
        if ret["age_max"] > bound:
            problems.append(
                f"{sc.name}/retain: age_max={ret['age_max']} > bound={bound}"
            )
        if sc.name == "convergecast":
            frac = rows["drop"]["drops"] / sc.emitted
            if frac <= 0.2:
                problems.append(
                    f"convergecast/drop: loses only {frac:.1%} — the starved "
                    "budgets no longer demonstrate the retain win"
                )
    if problems:
        raise RuntimeError("chaos gate failed: " + "; ".join(problems))
    print("# chaos ok: retain lossless on all scenarios, drop >20% loss "
          "on convergecast, ages within drain bound")


# --------------------------------- ISSUE 7: recovery law (ckpt / brownout)
def _ballast_round_fn(base, width=48, iters=512):
    """Wrap a chaos ``round_fn`` with app-realistic per-round compute (a
    ray-march-shaped ``fori_loop`` over a per-lane scratch).  The overhead
    gate must amortize the checkpoint writer against rounds that DO WORK —
    the bare chaos probe rounds are ~1 ms microbenchmarks, an order of
    magnitude under any real per-round app kernel (trace, integrate, shade),
    and would overstate the writer's relative cost by that same factor.  The
    ballast folds into the aux through a branch XLA cannot constant-fold
    (``isnan`` of a finite sum is 0 only at runtime) without perturbing any
    checksum."""

    def round_fn(q_in, aux, rnd):
        x = q_in.items.val[:, :1] * jnp.ones((1, width)) + 1.0
        x = jax.lax.fori_loop(
            0, iters, lambda i, v: v * 0.999 + jnp.sin(v) * 1e-3, x
        )
        out, (cnt, s, s2) = base(q_in, aux, rnd)
        cnt = cnt + jnp.where(
            jnp.isnan(jnp.sum(x)), jnp.uint32(1), jnp.uint32(0)
        )
        return out, (cnt, s, s2)

    return round_fn


def fwd_walltime_ckpt(samples=3):
    """Segmented-drive walltime with the checkpoint writer OFF vs ON
    (``ckpt_dir=None`` vs a real directory) at the ISSUE-7 amortization
    point ``checkpoint_every=8``, on two convergecast burst lengths with
    ballasted rounds (:func:`_ballast_round_fn`).  Both variants run the
    SAME compiled start/segment programs and the same host boundary loop —
    the delta is exactly what the writer adds per boundary (serialize +
    fsync + retention sweep), amortized over the W rounds between saves.
    Timed interleaved with per-variant medians (the runs are seconds long;
    interleaving cancels the host's slow load drift).  Returns
    ``{(tag, variant): us}`` for the ``--compare nockpt,ckpt`` gate."""
    import tempfile

    from repro.chaos import convergecast
    from repro.chaos.driver import _aux0, _make_ctx, _make_round_fn, _seed_queue
    from repro.core import recovery

    mesh = _mesh8()
    S, C, W, max_rounds = 2, 128, 8, 64
    times = {}
    for tag, sc in (
        ("short", convergecast(8)),
        ("long", convergecast(8, rounds=8)),
    ):
        ctx = _make_ctx(
            mesh, capacity=C, peer_capacity=S, overflow="retain",
            max_rounds=max_rounds,
        )
        spec = ctx._spec
        start_p, segment_p = ctx.checkpoint_drive_programs(
            _ballast_round_fn(_make_round_fn(ctx, sc)),
            aux_specs=(spec, spec, spec), accounting=True,
        )
        carry0 = start_p(_seed_queue(sc, C), _aux0(8), np.ones((8,), bool))
        jax.block_until_ready(jax.tree.leaves(carry0))
        ckpt_root = tempfile.mkdtemp(prefix=f"rafi_bench_ckpt_{tag}_")
        record_cfg(f"ckpt_{tag}", ctx.cfg, mesh)

        def run(ckpt_dir):
            # reuse the REAL boundary loop (not a replica) against the one
            # pair of compiled programs, so the variants differ only in the
            # writer work — recompiling per call would drown the delta
            res = recovery._drive_loop(
                ctx, segment_p, carry0, ckpt_dir=ckpt_dir,
                checkpoint_every=W, max_rounds=max_rounds,
                health=None, keep=3, halt_after_round=None,
            )
            assert res["done"]
            return res

        res = run(None)
        run(ckpt_root)  # publish once: later samples measure the overwrite
        rounds = res["rounds"]  # steady state (replace + retention sweep)
        saves = rounds // W + 1 + (1 if rounds % W else 0)
        ts = {"nockpt": [], "ckpt": []}
        for _ in range(samples):
            for variant, d in (("nockpt", None), ("ckpt", ckpt_root)):
                t0 = time.perf_counter()
                run(d)
                ts[variant].append((time.perf_counter() - t0) * 1e6)
        for variant, v in ts.items():
            us = float(np.median(v))
            times[(tag, variant)] = us
            emit(
                f"fwd_walltime_ckpt_{tag}_{variant}", us,
                f"rounds={rounds};boundaries={saves};W={W}"
                f";rounds_per_s={rounds / (us / 1e6):.1f}",
            )
    return times


def chaos_recovery():
    """The ISSUE-7 acceptance run: the recovery law, end to end, RAISING on
    any violation (like :func:`chaos_lossless`, this must trip CI, not trend
    a row).

    * **Preempt/resume bit-exactness** — the capacity-drought burst driven
      through the checkpointed drive uninterrupted vs killed at round 5 and
      resumed from disk: both runs must publish the SAME boundary rounds
      with IDENTICAL per-leaf SHA-256 digests at every common boundary
      (``boundary_digests`` — byte equality of the full forwarding state,
      no tolerance), and both must drain lossless to the schedule's
      checksums.
    * **Brownout losslessness** — the rank-brownout burst with two ranks
      going dark at round 3 (health re-read each segment boundary): zero
      drops, zero lost, clean drain, and the whole trajectory — deliveries
      AND round count — equal to the numpy twin evaluated under the
      device's segment-boundary health timing."""
    import tempfile

    from repro.chaos import (
        boundary_digests,
        brownout_mask,
        capacity_drought,
        expected_by_rank,
        rank_brownout,
        run_scenario_checkpointed,
        simulate_flat_retain,
    )

    mesh = _mesh8()
    S, C, W = 2, 128, 3
    problems = []

    # --- preempt at round 5, resume, compare boundary digests
    sc = capacity_drought(8)
    kw = dict(
        capacity=C, peer_capacity=S, overflow="retain", max_rounds=64,
        checkpoint_every=W, keep=99,
    )
    with tempfile.TemporaryDirectory() as da, tempfile.TemporaryDirectory() as db:
        t0 = time.perf_counter()
        a = run_scenario_checkpointed(mesh, sc, ckpt_dir=da, **kw)
        b = run_scenario_checkpointed(
            mesh, sc, ckpt_dir=db, preempt_at=5, **kw
        )
        dt = time.perf_counter() - t0
        dga, dgb = boundary_digests(da), boundary_digests(db)
        common = sorted(set(dga) & set(dgb))
        emit(
            f"chaos_recovery_preempt_{sc.name}", dt * 1e6,
            f"rounds={a['rounds']};boundaries={len(dga)}"
            f";common={len(common)};preempted={b['preempted']}",
        )
        if not b["preempted"]:
            problems.append("preempt: halt_after_round=5 did not preempt")
        if a["steps"] != b["steps"]:
            problems.append(
                f"preempt: boundary rounds diverge {a['steps']} vs {b['steps']}"
            )
        if len(common) < 3:
            problems.append(f"preempt: only {len(common)} common boundaries")
        for s in common:
            if dga[s] != dgb[s]:
                problems.append(f"preempt: digest mismatch at boundary {s}")
        for tag, r in (("uninterrupted", a), ("resumed", b)):
            if r["drops"] or r["lost"] or not r["done"]:
                problems.append(
                    f"preempt/{tag}: drops={r['drops']} lost={r['lost']} "
                    f"done={r['done']}"
                )
        if not np.array_equal(a["delivered"], expected_by_rank(sc)):
            problems.append("preempt: delivered checksums != schedule oracle")

    # --- brownout: ranks 2 and 5 go dark at round 3, nothing is lost
    sc = rank_brownout(8)
    health = brownout_mask(8, down=(2, 5), down_from=3)

    def twin_health(f):
        # the device re-reads health at segment boundaries: forward 0 routes
        # under health(0); forward f >= 1 (body round f-1) under the mask of
        # the boundary that launched its segment
        return health(0) if f == 0 else health(W * ((f - 1) // W))

    sim = simulate_flat_retain(
        sc, peer_capacity=S, capacity=C, health=twin_health
    )
    with tempfile.TemporaryDirectory() as dc:
        t0 = time.perf_counter()
        res = run_scenario_checkpointed(
            mesh, sc, ckpt_dir=dc, capacity=C, peer_capacity=S,
            overflow="retain", max_rounds=64, checkpoint_every=W,
            keep=99, health=health,
        )
        dt = time.perf_counter() - t0
        emit(
            f"chaos_recovery_brownout_{sc.name}", dt * 1e6,
            f"emitted={res['emitted']};delivered={res['delivered_total']}"
            f";drops={res['drops']};lost={res['lost']}"
            f";rounds={res['rounds']}",
        )
        if res["drops"] or res["lost"] or not res["done"]:
            problems.append(
                f"brownout: drops={res['drops']} lost={res['lost']} "
                f"done={res['done']}"
            )
        if res["delivered_total"] != sc.emitted:
            problems.append(
                f"brownout: delivered {res['delivered_total']} != emitted "
                f"{sc.emitted}"
            )
        if not np.array_equal(res["delivered"], sim["delivered"]):
            problems.append("brownout: device checksums != numpy twin")
        if res["rounds"] != sim["rounds"]:
            problems.append(
                f"brownout: rounds {res['rounds']} != twin {sim['rounds']}"
            )
    if problems:
        raise RuntimeError("recovery gate failed: " + "; ".join(problems))
    print(
        "# recovery ok: preempt-resume bit-exact at every common boundary, "
        "brownout lossless and twin-exact"
    )


# --------------------------------- ISSUE 9: backpressure (credit flow)
def fwd_walltime_flow(samples=8):
    """Credit-flow overhead sweep on the HAPPY PATH (every receiver fully
    credited, nothing gated): the same retain-mode forwarding round with
    ``flow`` open vs credit (flat padded + 3-level hierarchical), timed
    interleaved per point.  Returns ``{(tag, variant, n_emit): us}`` for the
    ``--compare open,credit`` gate (credit/open walltime geomean must stay
    ≤ 1.05 — the advert column and the grant arithmetic must be ~free when
    nobody is starved)."""
    from repro.core import ForwardConfig
    from repro.launch.mesh import make_pod_mesh

    mesh_flat = _mesh8()
    mesh_pod = make_pod_mesh(2, 2, 2)
    axes3 = ("pod", "node", "device")
    times = {}
    for n_emit in (256, 2048):
        cap = max(256, n_emit * 2)
        points = (
            (
                "flat", mesh_flat, "data",
                lambda f: ForwardConfig(
                    "data", 8, cap, exchange="padded", overflow="retain",
                    flow=f,
                ),
            ),
            (
                "hier3", mesh_pod, axes3,
                lambda f: ForwardConfig(
                    axes3, 8, cap, exchange="hierarchical",
                    level_sizes=(2, 2, 2), overflow="retain", flow=f,
                ),
            ),
        )
        for tag, mesh, axes, mk_cfg in points:
            best = _paired_times(
                {"open": mk_cfg("open"), "credit": mk_cfg("credit")},
                mesh, axes, n_emit, cap, samples,
            )
            record_cfg(f"flow_{tag}_n{n_emit}", mk_cfg("credit"), mesh)
            for variant, us in best.items():
                times[(tag, variant, n_emit)] = us
                rays_s = 8 * n_emit / (us / 1e6)
                emit(
                    f"fwd_walltime_flow_{tag}_{variant}_n{n_emit}", us,
                    f"rays_per_s={rays_s:.2e}",
                )
    return times


def chaos_backpressure():
    """The ISSUE-9 acceptance run: the two overload scenarios (fixed
    hot-pair saturation, full-width incast) under queue capacities their
    offered load overwhelms, open vs credit flow.  Records per-scenario
    goodput/waste accounting and RAISES unless (a) OPEN flow wastes >30%%
    of its wire rows on receiver drops — the configs must keep demonstrating
    the collapse — while (b) CREDIT flow on the IDENTICAL schedule delivers
    every row with zero receiver drops, zero emission overflow, a
    payload-free first round (the zero-credit cold start), occupancy
    bounded by the configured queues, and a clean drain.  Graceful
    degradation must trip CI when it regresses, not trend a row."""
    from repro.chaos import overload_scenarios, run_scenario

    mesh = _mesh8()
    # per-scenario (capacity, slot): each pins open-flow waste >30% while
    # staying large enough that the gated emitter never clips a seed row
    caps = {"sustained_overload": (16, 4), "incast_collapse": (32, 8)}
    problems = []
    for sc in overload_scenarios(8):
        C, S = caps[sc.name]
        rows = {}
        for flow in ("open", "credit"):
            t0 = time.perf_counter()
            res = run_scenario(
                mesh, sc, capacity=C, peer_capacity=S, overflow="retain",
                flow=flow, max_rounds=256,
            )
            dt = time.perf_counter() - t0
            rows[flow] = res
            waste = res["wasted_wire_rows"] / max(res["wire_rows"], 1)
            emit(
                f"chaos_backpressure_{sc.name}_{flow}", dt * 1e6,
                f"emitted={res['emitted']};delivered={res['delivered_total']}"
                f";drops={res['drops']};lost={res['lost']}"
                f";goodput={res['goodput']:.3f};waste_frac={waste:.3f}"
                f";emit_overflow={res['emit_overflow']}"
                f";rounds={res['rounds']};age_max={res.get('age_max', 0)}",
            )
            if res["lost"] != 0:  # conservation broken in EITHER mode
                problems.append(f"{sc.name}/{flow}: lost={res['lost']}")
        op, cr = rows["open"], rows["credit"]
        waste = op["wasted_wire_rows"] / max(op["wire_rows"], 1)
        if waste <= 0.30:
            problems.append(
                f"{sc.name}/open: wastes only {waste:.1%} of wire rows — the "
                "overload no longer demonstrates the credit win"
            )
        if cr["drops"] != 0 or cr["emit_overflow"] != 0 or not cr["done"]:
            problems.append(
                f"{sc.name}/credit: drops={cr['drops']} "
                f"emit_overflow={cr['emit_overflow']} done={cr['done']}"
            )
        if cr["delivered_total"] != sc.emitted:
            problems.append(
                f"{sc.name}/credit: delivered {cr['delivered_total']} != "
                f"emitted {sc.emitted}"
            )
        if cr["goodput"] < op["goodput"] or cr["goodput"] != 1.0:
            problems.append(
                f"{sc.name}: credit goodput {cr['goodput']:.3f} must be 1.0 "
                f"(open: {op['goodput']:.3f})"
            )
        if int(np.asarray(cr["recv_trace"])[0]) != 0:
            problems.append(
                f"{sc.name}/credit: first round shipped payload before any "
                "receiver advertised"
            )
        if int(np.asarray(cr["retained_trace"]).max()) > 8 * C:
            problems.append(
                f"{sc.name}/credit: retained rows exceed the configured "
                f"queues ({int(np.asarray(cr['retained_trace']).max())} > "
                f"{8 * C}) — occupancy unbounded"
            )
    if problems:
        raise RuntimeError("backpressure gate failed: " + "; ".join(problems))
    print(
        "# backpressure ok: open flow wastes >30% wire rows on both overload "
        "scenarios, credit flow drains both lossless with goodput 1.0, "
        "bounded occupancy, and an advert-only first round"
    )


# --------------------------------- ISSUE 10: the observation law (obs)
def fwd_walltime_obs(samples=8):
    """Observation-law overhead sweep: the SAME compiled chaos burst timed
    with the ambient tracer OFF vs ON — the ON arm pays the ambient cost of
    the toggle (the drive-entry span hooks recording into the ring buffer).
    The lowered device program is shared by construction (obs is host-only;
    HLO bit-identity is guarded in ``tests/test_collective_budget.py``), so
    the delta is exactly the host bookkeeping.  Interleaved samples,
    per-variant medians (see :func:`_paired_times` for why).

    The metrics EXPORT (``obs.metrics.from_summary`` + the Prometheus
    render on the burst's flight-recorder summary) is an explicit user
    call, not part of the toggle — its cost is emitted as an informational
    ``_metrics`` row per scenario, outside the overhead gate.  Returns
    ``{(tag, variant): us}`` for the ``--compare off,obs`` gate."""
    from repro.chaos.driver import _aux0, _make_ctx, _make_round_fn, _seed_queue
    from repro.chaos.scenarios import burst_storm, rotating_hotspot
    from repro.obs import metrics as OM
    from repro.obs import trace as OT
    from repro.telemetry import stats as TS

    mesh = _mesh8()
    times = {}
    for tag, sc in (("hotspot", rotating_hotspot(8)), ("burst", burst_storm(8))):
        ctx = _make_ctx(mesh, capacity=256, peer_capacity=64, max_rounds=32)
        rfn = _make_round_fn(ctx, sc)
        spec = ctx._spec
        drive = ctx.run_until_done(rfn, aux_specs=(spec,) * 3, max_rounds=32)
        q0 = _seed_queue(sc, 256)
        aux0 = _aux0(8)
        caps = TS.tier_capacities(ctx.cfg)

        def burst():
            out = drive(q0, aux0)
            jax.block_until_ready(jax.tree.leaves(out))
            return out

        burst()
        out = burst()  # compile + warm
        ts = {"off": [], "obs": []}
        for _ in range(samples):
            t0 = time.perf_counter()
            burst()
            ts["off"].append((time.perf_counter() - t0) * 1e6)
            with OT.capture():
                t0 = time.perf_counter()
                burst()
                ts["obs"].append((time.perf_counter() - t0) * 1e6)
        record_cfg(f"obs_{tag}", ctx.cfg, mesh)
        for variant, v in ts.items():
            us = float(np.median(v))
            times[(tag, variant)] = us
            emit(
                f"fwd_walltime_obs_{tag}_{variant}", us,
                f"scenario={sc.name};rounds_max=32",
            )
        # metrics export cost — explicit user call, informational (ungated)
        mts = []
        for _ in range(max(samples, 5)):
            t0 = time.perf_counter()
            summary = TS.summarize(out[-1], tier_capacities=caps)
            OM.to_prometheus(OM.from_summary(summary))
            mts.append((time.perf_counter() - t0) * 1e6)
        emit(
            f"fwd_walltime_obs_{tag}_metrics", float(np.median(mts)),
            f"scenario={sc.name};rounds_max=32;gated=no",
        )
    return times


def obs_flight_report():
    """The ISSUE-10 acceptance run: capture the incast-collapse overload
    pair (open vs credit, the PR-9 gauntlet point) with the ambient tracer
    on, build the flight capture, and run the ``repro.obs.report`` analyzer
    over it.  RAISES unless the report (a) reproduces the chaos driver's
    goodput and wasted-wire-row numbers exactly from the capture alone and
    (b) flags the open-flow run — and ONLY it — as degraded.  Like the other
    acceptance sections this must trip CI, not trend a row."""
    import tempfile
    from pathlib import Path

    from repro.chaos import run_scenario
    from repro.chaos.scenarios import incast_collapse
    from repro.obs import report as OR
    from repro.obs import trace as OT

    mesh = _mesh8()
    sc = incast_collapse(8)
    C, S = 32, 8  # the chaos_backpressure gauntlet's incast point
    runs, events, driver = [], [], {}
    for flow in ("open", "credit"):
        t0 = time.perf_counter()
        with OT.capture() as tr:
            res = run_scenario(
                mesh, sc, capacity=C, peer_capacity=S, overflow="retain",
                flow=flow, max_rounds=256,
            )
        dt = time.perf_counter() - t0
        driver[flow] = res
        runs.append(OR.chaos_capture(
            f"{sc.name}_{flow}", res, flow=flow, tier_capacities=(S,),
            capacity=C,
        ))
        events.extend(tr.events)
        emit(
            f"obs_flight_{sc.name}_{flow}", dt * 1e6,
            f"goodput={res['goodput']:.3f};wasted={res['wasted_wire_rows']}"
            f";wire={res['wire_rows']};rounds={res['rounds']}",
        )
    problems = []
    with tempfile.TemporaryDirectory() as d:
        path = OR.save_capture(
            Path(d) / "capture.json", runs, events=events,
            meta={"source": "benchmarks.obs_flight_report"},
        )
        report = OR.analyze(OR.load_capture(path))
    for rr in report["runs"]:
        res = driver[rr["flow"]]
        if abs(rr["goodput"] - res["goodput"]) > 1e-9:
            problems.append(
                f"{rr['name']}: report goodput {rr['goodput']:.6f} != driver "
                f"{res['goodput']:.6f}"
            )
        if rr["wasted_wire_rows"] != res["wasted_wire_rows"]:
            problems.append(
                f"{rr['name']}: report wasted {rr['wasted_wire_rows']} != "
                f"driver {res['wasted_wire_rows']}"
            )
        bad = [c["check"] for c in rr["checks"] if not c["ok"]]
        if bad:
            problems.append(f"{rr['name']}: failed checks {bad}")
    deg = set(report["degraded_runs"])
    if deg != {f"{sc.name}_open"}:
        problems.append(
            f"degraded set {sorted(deg)} != exactly the open run"
        )
    if problems:
        raise RuntimeError("obs flight gate failed: " + "; ".join(problems))
    print(
        "# obs flight ok: report reproduces driver goodput/waste on both "
        "incast runs and flags only the open run as degraded"
    )


# ------------------------------------- ISSUE 4: sort vs scatter marshal
def _paired_marshal_times(mk_cfg, mesh, axes, n_emit, cap, samples):
    return _paired_times(
        {m: mk_cfg(m) for m in ("sort", "scatter")},
        mesh, axes, n_emit, cap, samples,
    )


def fwd_walltime_marshal(samples=8):
    """Sort vs scatter marshal sweep: the flat padded exchange on the 8-way
    mesh and the 3-level hierarchical route on the (2, 2, 2) pod mesh, both
    marshal modes, with the modeled marshal plan bytes alongside (the scatter
    deletes the key pack + O(C log C) sort traffic; payload passes stay at
    the one-pass law in both modes).  Per point the two modes are timed
    interleaved and the per-mode MEDIAN over ``samples`` is recorded (see
    :func:`_paired_marshal_times`).  Returns ``{(tag, marshal, n_emit): us}``
    for the ``--compare sort,scatter`` gate."""
    from repro.core import ForwardConfig, item_nbytes
    from repro.launch.mesh import make_pod_mesh
    from repro.roofline.analysis import marshal_cost_model

    item_b = item_nbytes(_ray_proto())
    mesh_flat = _mesh8()
    mesh_pod = make_pod_mesh(2, 2, 2)
    axes3 = ("pod", "node", "device")
    times = {}
    for n_emit in (256, 2048):
        cap = max(256, n_emit * 2)
        points = (
            (
                "flat", mesh_flat, "data",
                lambda m: ForwardConfig("data", 8, cap, exchange="padded", marshal=m),
            ),
            (
                "hier3", mesh_pod, axes3,
                lambda m: ForwardConfig(
                    axes3, 8, cap, exchange="hierarchical",
                    level_sizes=(2, 2, 2), marshal=m,
                ),
            ),
        )
        for tag, mesh, axes, mk_cfg in points:
            best = _paired_marshal_times(mk_cfg, mesh, axes, n_emit, cap, samples)
            for marshal, us in best.items():
                times[(tag, marshal, n_emit)] = us
                cfg = mk_cfg(marshal)
                record_cfg(f"fwd_walltime_marshal_{tag}_{marshal}_n{n_emit}", cfg, mesh)
                send_rows = (
                    8 * cfg.peer_capacity if tag == "flat"
                    else 2 * cfg.level_capacities[-1]
                )
                model = marshal_cost_model(
                    marshal, capacity=cap, item_bytes=item_b,
                    send_rows=send_rows, num_ranks=8,
                )
                rays_s = 8 * n_emit / (us / 1e6)
                emit(
                    f"fwd_walltime_marshal_{tag}_{marshal}_n{n_emit}", us,
                    f"rays_per_s={rays_s:.2e}"
                    f";marshal_plan_B={model['plan_bytes']:.0f}"
                    f";marshal_total_B={model['total_bytes']:.0f}"
                    f";payload_passes={model['payload_passes']:.0f}",
                )
    return times


PIPELINE_GATE_MIN_EMIT = 16384  # flat points at/above this gate the geomean


def fwd_walltime_pipeline(samples=8):
    """Bulk-synchronous vs micro-shard pipelined forwarding (ISSUE 8): the
    flat padded round at ``pipeline_shards=4`` on compute-ballasted rounds
    (``ballast_iters=128`` — the exchange must amortize against rounds that
    DO WORK, same reasoning as the ckpt gate's ``_ballast_round_fn``), swept
    over growing working sets, timed interleaved with the quoted ratio
    being the ADJACENT-PAIR median (``_pair_ratio``) — the only estimator
    stable enough for a ≤1.0× gate on a drifting host.

    On this CPU backend collectives are synchronous memcpys, so no wire time
    hides behind compute and the measured pipelined win is the locality
    corollary: each 1/S chunk is marshalled, shipped and compacted while
    still cache-resident, which starts paying once the round's buffers
    outgrow the cache.  The gate therefore covers only the flat points at
    ``n_emit >= PIPELINE_GATE_MIN_EMIT`` — where the per-device buffers
    exceed the cache and the mechanism applies; the smaller flat point and
    a 3-level trend point ride along UNGATED (sub-cache rounds are
    launch-overhead-bound on this fabric, and the hier route's per-tier
    chunks are S× smaller still — both rows document the CPU limitation
    an async fabric such as TPU ICI removes).  Returns ``(times, ratios)`` —
    ``{(tag, variant, n_emit): median_us}`` and
    ``{(tag, n_emit): pair_ratio}`` — for the ``--compare bulk,pipelined``
    gate."""
    from repro.core import ForwardConfig
    from repro.launch.mesh import make_pod_mesh

    S, ballast = 4, 128
    mesh = _mesh8()
    times, ratios = {}, {}
    for n_emit in (8192, 16384, 32768):
        cap = n_emit * 2
        cfgs = {
            "bulk": ForwardConfig("data", 8, cap, exchange="padded"),
            "pipelined": ForwardConfig(
                "data", 8, cap, exchange="padded", pipeline_shards=S
            ),
        }
        med, raw = _paired_times(
            cfgs, mesh, "data", n_emit, cap, samples, ballast_iters=ballast,
            raw=True,
        )
        ratio = _pair_ratio(raw, "pipelined", "bulk")
        ratios[("flat", n_emit)] = ratio
        for variant, us in med.items():
            times[("flat", variant, n_emit)] = us
            record_cfg(
                f"fwd_walltime_pipeline_flat_{variant}_n{n_emit}",
                cfgs[variant], mesh,
            )
            emit(
                f"fwd_walltime_pipeline_flat_{variant}_n{n_emit}", us,
                f"rays_per_s={8 * n_emit / (us / 1e6):.2e}"
                f";shards={cfgs[variant].pipeline_shards}"
                f";ballast_iters={ballast}"
                f";ratio={ratio if variant == 'pipelined' else 1.0:.3f}"
                f";gated={int(n_emit >= PIPELINE_GATE_MIN_EMIT)}",
            )
    # hier3 trend point (ungated — see docstring)
    mesh_pod = make_pod_mesh(2, 2, 2)
    axes3 = ("pod", "node", "device")
    n_emit = 8192
    cap = n_emit * 2
    cfgs = {
        "bulk": ForwardConfig(
            axes3, 8, cap, exchange="hierarchical", level_sizes=(2, 2, 2)
        ),
        "pipelined": ForwardConfig(
            axes3, 8, cap, exchange="hierarchical", level_sizes=(2, 2, 2),
            pipeline_shards=2,
        ),
    }
    med, raw = _paired_times(
        cfgs, mesh_pod, axes3, n_emit, cap, max(4, samples // 2),
        ballast_iters=ballast, raw=True,
    )
    ratio = _pair_ratio(raw, "pipelined", "bulk")
    ratios[("hier3", n_emit)] = ratio
    for variant, us in med.items():
        times[("hier3", variant, n_emit)] = us
        record_cfg(
            f"fwd_walltime_pipeline_hier3_{variant}_n{n_emit}",
            cfgs[variant], mesh_pod,
        )
        emit(
            f"fwd_walltime_pipeline_hier3_{variant}_n{n_emit}", us,
            f"rays_per_s={8 * n_emit / (us / 1e6):.2e}"
            f";shards={cfgs[variant].pipeline_shards}"
            f";ballast_iters={ballast}"
            f";ratio={ratio if variant == 'pipelined' else 1.0:.3f}"
            f";gated=0",
        )
    return times, ratios


def compare_backends(spec: str) -> int:
    """The CI gates for the hierarchical routes.

    ``--compare flat,hierarchical`` (PR-2 gate): on a SINGLE-NODE mesh (slow
    axis of extent 1 — the slow stage degenerates to a local copy) the
    hierarchical exchange must not regress the flat padded exchange by more
    than 5% walltime; a regression there means pure multi-stage overhead, not
    topology routing.

    ``--compare flat,hierarchical2,hierarchical3`` (PR-3 gate): runs the
    (2, 2, 2)-mesh sweep plus the skewed-load rebalance benchmark, and fails
    unless the 3-level route's modeled slowest-tier bytes PER ROW OF BURST
    TOLERANCE strictly undercut both the flat route's and the 2-level
    route's.  (At load-proportional default capacities the routes' absolute
    slowest-tier bytes coincide — the structural win, as in the PR-2 2-level
    sweep, is how few DCN-crossing padded rows a unit of per-destination
    burst absorption costs: 4 flat, 2 hier2, 1 hier3.)  Returns a nonzero
    exit code on gate failure."""
    names = tuple(s.strip() for s in spec.split(","))
    if names == ("off", "telemetry"):
        # PR-5 gate: the flight recorder must be ~free — telemetry-on
        # walltime within a 1.05× GEOMEAN of telemetry-off across the sweep
        # (same per-point interleaved-median methodology as the marshal
        # gate) — and the autotune_drift section must converge drop-free at
        # ≤ the static worst-case wire cost (it raises otherwise).
        times = fwd_walltime_telemetry(samples=40)
        ratios = []
        for (tag, variant, n_emit), us in sorted(times.items()):
            if variant != "telemetry":
                continue
            ratio = us / times[(tag, "off", n_emit)]
            ratios.append(ratio)
            emit(f"compare_telemetry_{tag}_n{n_emit}", us, f"ratio={ratio:.3f}")
        geomean = float(np.exp(np.mean(np.log(ratios))))
        emit("compare_telemetry_geomean", 0.0, f"ratio={geomean:.3f}")
        if geomean > 1.05:
            print(
                f"# COMPARE FAILED: telemetry-on regresses telemetry-off by "
                f"{geomean:.2f}x > 1.05x (geomean over the sweep)"
            )
            return 1
        print(
            f"# compare ok: telemetry/off walltime geomean {geomean:.3f} "
            f"(per-point: {', '.join(f'{r:.3f}' for r in ratios)})"
        )
        try:
            autotune_drift()
        except RuntimeError as e:
            # gate contract: nonzero exit + the JSON dump still written
            # (with compare_failed=true), like every other compare mode —
            # never a traceback that loses the collected rows
            print(f"# COMPARE FAILED: {e}")
            return 1
        return 0
    if names == ("drop", "retain"):
        # PR-6 gate: spill-and-retry must be free when nothing spills —
        # retain-mode walltime within a 1.05× GEOMEAN of drop mode across
        # the happy-path sweep — and the chaos_lossless acceptance must hold
        # (retain loses nothing where drop loses >20%; it raises otherwise).
        times = fwd_walltime_overflow(samples=40)
        ratios = []
        for (tag, variant, n_emit), us in sorted(times.items()):
            if variant != "retain":
                continue
            ratio = us / times[(tag, "drop", n_emit)]
            ratios.append(ratio)
            emit(f"compare_overflow_{tag}_n{n_emit}", us, f"ratio={ratio:.3f}")
        geomean = float(np.exp(np.mean(np.log(ratios))))
        emit("compare_overflow_geomean", 0.0, f"ratio={geomean:.3f}")
        if geomean > 1.05:
            print(
                f"# COMPARE FAILED: retain mode regresses drop mode by "
                f"{geomean:.2f}x > 1.05x on the happy path (geomean)"
            )
            return 1
        print(
            f"# compare ok: retain/drop walltime geomean {geomean:.3f} "
            f"(per-point: {', '.join(f'{r:.3f}' for r in ratios)})"
        )
        try:
            chaos_lossless()
        except RuntimeError as e:
            print(f"# COMPARE FAILED: {e}")
            return 1
        return 0
    if names == ("open", "credit"):
        # PR-9 gate: credit flow must be ~free when nobody is starved —
        # credit-mode walltime within a 1.05× GEOMEAN of open flow across
        # the fully-credited happy-path sweep — and the chaos_backpressure
        # acceptance must hold (credit lossless with bounded occupancy on
        # both overload scenarios where open wastes >30% of its wire rows;
        # it raises otherwise).
        times = fwd_walltime_flow(samples=40)
        ratios = []
        for (tag, variant, n_emit), us in sorted(times.items()):
            if variant != "credit":
                continue
            ratio = us / times[(tag, "open", n_emit)]
            ratios.append(ratio)
            emit(f"compare_flow_{tag}_n{n_emit}", us, f"ratio={ratio:.3f}")
        geomean = float(np.exp(np.mean(np.log(ratios))))
        emit("compare_flow_geomean", 0.0, f"ratio={geomean:.3f}")
        if geomean > 1.05:
            print(
                f"# COMPARE FAILED: credit flow regresses open flow by "
                f"{geomean:.2f}x > 1.05x on the fully-credited happy path "
                f"(geomean)"
            )
            return 1
        print(
            f"# compare ok: credit/open walltime geomean {geomean:.3f} "
            f"(per-point: {', '.join(f'{r:.3f}' for r in ratios)})"
        )
        try:
            chaos_backpressure()
        except RuntimeError as e:
            print(f"# COMPARE FAILED: {e}")
            return 1
        return 0
    if names == ("off", "obs"):
        # PR-10 gate: observation must be ~free — a traced + metered burst
        # within a 1.05× walltime GEOMEAN of the untraced one (the lowered
        # HLO is bit-identical by construction; this gates the host
        # bookkeeping) — and the flight-data analyzer acceptance must hold
        # (the report reproduces the chaos driver's goodput/waste numbers
        # from the capture alone and flags only the open-flow overload run
        # as degraded; it raises otherwise).
        times = fwd_walltime_obs(samples=40)
        ratios = []
        for (tag, variant), us in sorted(times.items()):
            if variant != "obs":
                continue
            ratio = us / times[(tag, "off")]
            ratios.append(ratio)
            emit(f"compare_obs_{tag}", us, f"ratio={ratio:.3f}")
        geomean = float(np.exp(np.mean(np.log(ratios))))
        emit("compare_obs_geomean", 0.0, f"ratio={geomean:.3f}")
        if geomean > 1.05:
            print(
                f"# COMPARE FAILED: tracing+metrics regresses the untraced "
                f"burst by {geomean:.2f}x > 1.05x (geomean)"
            )
            return 1
        print(
            f"# compare ok: obs/off walltime geomean {geomean:.3f} "
            f"(per-point: {', '.join(f'{r:.3f}' for r in ratios)})"
        )
        try:
            obs_flight_report()
        except RuntimeError as e:
            print(f"# COMPARE FAILED: {e}")
            return 1
        return 0
    if names == ("nockpt", "ckpt"):
        # PR-7 gate: recovery must be amortized — the segmented drive WITH
        # the checkpoint writer (W=8 rounds between saves) within a 1.05×
        # walltime GEOMEAN of the save-free segmented drive on ballasted
        # bursts — and the chaos_recovery acceptance must hold
        # (preempt-resume bit-exact, brownout lossless; it raises otherwise).
        times = fwd_walltime_ckpt(samples=5)
        ratios = []
        for (tag, variant), us in sorted(times.items()):
            if variant != "ckpt":
                continue
            ratio = us / times[(tag, "nockpt")]
            ratios.append(ratio)
            emit(f"compare_ckpt_{tag}", us, f"ratio={ratio:.3f}")
        geomean = float(np.exp(np.mean(np.log(ratios))))
        emit("compare_ckpt_geomean", 0.0, f"ratio={geomean:.3f}")
        if geomean > 1.05:
            print(
                f"# COMPARE FAILED: checkpointing every 8 rounds regresses "
                f"the save-free drive by {geomean:.2f}x > 1.05x (geomean)"
            )
            return 1
        print(
            f"# compare ok: ckpt/nockpt walltime geomean {geomean:.3f} "
            f"(per-point: {', '.join(f'{r:.3f}' for r in ratios)})"
        )
        try:
            chaos_recovery()
        except RuntimeError as e:
            print(f"# COMPARE FAILED: {e}")
            return 1
        return 0
    if names == ("bulk", "pipelined"):
        # PR-8 gate: micro-shard pipelining must never cost walltime where
        # its mechanism applies — pipelined (S=4) within a 1.0× GEOMEAN of
        # the bulk round over the ballasted flat points whose buffers exceed
        # the cache (n_emit >= PIPELINE_GATE_MIN_EMIT; the gate is ≤ 1.0,
        # not 1.05: unlike the feature gates, pipelining exists ONLY for
        # walltime, so any regression defeats it).  Ratios are adjacent-pair
        # medians (see _pair_ratio) — per-variant medians drift by more than
        # the gate margin on this host.  The sub-cache flat point and the
        # hier3 rows are reported but not gated (see fwd_walltime_pipeline).
        times, pair_ratios = fwd_walltime_pipeline(samples=40)
        ratios = []
        for (tag, n_emit), ratio in sorted(pair_ratios.items()):
            us = times[(tag, "pipelined", n_emit)]
            in_gate = tag == "flat" and n_emit >= PIPELINE_GATE_MIN_EMIT
            emit(
                f"compare_pipeline_{tag}_n{n_emit}", us,
                f"ratio={ratio:.3f};gated={int(in_gate)}",
            )
            if in_gate:
                ratios.append(ratio)
        geomean = float(np.exp(np.mean(np.log(ratios))))
        emit("compare_pipeline_geomean", 0.0, f"ratio={geomean:.3f}")
        if geomean > 1.0:
            print(
                f"# COMPARE FAILED: pipelined regresses bulk by "
                f"{geomean:.3f}x > 1.0x (pair-ratio geomean over the "
                f"ballasted flat points with n_emit >= "
                f"{PIPELINE_GATE_MIN_EMIT})"
            )
            return 1
        print(
            f"# compare ok: pipelined/bulk walltime geomean {geomean:.3f} "
            f"(per-point: {', '.join(f'{r:.3f}' for r in ratios)})"
        )
        return 0
    if names == ("sort", "scatter"):
        # PR-4 gate: across the sweep the scatter marshal must be no more
        # than 5% slower than the sort path — a regression there means the
        # "one payload pass, no sort" pipeline lost to the thing it
        # replaces.  Gated on the GEOMEAN of the per-point interleaved-median
        # ratios: a single ~2 ms CPU point still wobbles a few percent
        # run-to-run from scheduler noise, but the sweep-level geomean is
        # stable to <1% (per-point ratios are all emitted as rows).  On TPU
        # the deleted lax.sort is worth strictly more.
        times = fwd_walltime_marshal(samples=40)
        ratios = []
        for (tag, marshal, n_emit), us in sorted(times.items()):
            if marshal != "scatter":
                continue
            ratio = us / times[(tag, "sort", n_emit)]
            ratios.append(ratio)
            emit(
                f"compare_marshal_{tag}_n{n_emit}", us, f"ratio={ratio:.3f}"
            )
        geomean = float(np.exp(np.mean(np.log(ratios))))
        emit("compare_marshal_geomean", 0.0, f"ratio={geomean:.3f}")
        if geomean > 1.05:
            print(
                f"# COMPARE FAILED: scatter marshal regresses sort by "
                f"{geomean:.2f}x > 1.05x (geomean over the sweep)"
            )
            return 1
        print(
            f"# compare ok: scatter/sort walltime geomean {geomean:.3f} "
            f"(per-point: {', '.join(f'{r:.3f}' for r in ratios)})"
        )
        return 0
    if names == ("flat", "hierarchical2", "hierarchical3"):
        from repro.core import item_nbytes

        fwd_walltime_hier3()
        rebalance_skew()
        item_b = item_nbytes(_ray_proto())
        flat, hier2, hier3, _mesh = _pod_configs(4096)
        per_burst = {}
        for tag, cfg in (("flat", flat), ("hier2", hier2), ("hier3", hier3)):
            burst = (
                cfg.peer_capacity if tag == "flat" else cfg.level_capacities[0]
            )
            per_burst[tag] = _route_tier_rows(tag, cfg)[0] * item_b / burst
        emit(
            "compare3_slowest_tier_bytes_per_burst_row", 0.0,
            f"flat_B={per_burst['flat']:.1f};hier2_B={per_burst['hier2']:.1f}"
            f";hier3_B={per_burst['hier3']:.1f}",
        )
        if not (
            per_burst["hier3"] < per_burst["hier2"] < per_burst["flat"]
        ):
            print(
                "# COMPARE FAILED: slowest-tier bytes per burst row not "
                f"strictly decreasing flat > hier2 > hier3: {per_burst}"
            )
            return 1
        print(
            "# compare ok: slowest-tier bytes per burst row "
            f"flat {per_burst['flat']:.1f} > hier2 {per_burst['hier2']:.1f} "
            f"> hier3 {per_burst['hier3']:.1f} on 2x2x2"
        )
        return 0
    if names != ("flat", "hierarchical"):
        raise SystemExit(
            "error: --compare supports 'flat,hierarchical', "
            "'flat,hierarchical2,hierarchical3', 'sort,scatter', "
            "'off,telemetry', 'drop,retain', 'nockpt,ckpt', "
            f"'bulk,pipelined', 'open,credit', or 'off,obs', got {spec!r}"
        )
    n_emit, cap = 2048, 4096
    flat, hier, mesh = _hier_pair(1, 8, n_emit, cap)
    flat_us = _time_fwd(flat, mesh, n_emit, cap, iters=10)
    hier_us = _time_fwd(hier, mesh, n_emit, cap, iters=10)
    ratio = hier_us / flat_us
    emit(f"compare_flat_1x8_n{n_emit}", flat_us, f"ratio=1.0")
    emit(f"compare_hierarchical_1x8_n{n_emit}", hier_us, f"ratio={ratio:.3f}")
    if ratio > 1.05:
        print(
            f"# COMPARE FAILED: hierarchical {hier_us:.0f}us vs flat "
            f"{flat_us:.0f}us on single-node 1x8 mesh ({ratio:.2f}x > 1.05x)"
        )
        return 1
    print(f"# compare ok: hierarchical/flat = {ratio:.3f} on single-node 1x8 mesh")
    return 0


# ------------------------------------------------- §4.2.1 sort throughput
def sort_throughput():
    from repro.core import sorting as S
    from repro.kernels.sort_keys import ops as sk

    n = 65536
    dest = jnp.array(np.random.default_rng(1).integers(0, 256, n), jnp.int32)
    items = {"x": jnp.zeros((n, 4))}
    for name, fn in (
        ("xla_pack", jax.jit(lambda d: S.sort_by_destination(items, d, jnp.int32(n), 256, method="pack"))),
        ("xla_argsort", jax.jit(lambda d: S.sort_by_destination(items, d, jnp.int32(n), 256, method="argsort"))),
        ("pallas_interp", jax.jit(lambda d: sk.sort_by_destination(items, d, jnp.int32(n), 256))),
    ):
        us, _ = _timeit(fn, dest)
        emit(f"sort_throughput_{name}", us, f"keys_per_s={n/(us/1e6):.2e}")


# ----------------------------------------------------------- §5 app rates
def app_rates():
    from repro.apps import vopat
    from repro.apps import streamlines as sl
    from repro.apps import nbody

    mesh = _mesh8()
    scene = vopat.VopatScene(width=32, height=32, spp=1)
    t0 = time.perf_counter()
    img, stats = vopat.render(mesh, scene)
    dt = time.perf_counter() - t0
    emit("app_vopat_32x32", dt * 1e6,
         f"rays={scene.width*scene.height};rounds={stats['rounds']}")

    cfg = sl.StreamlineConfig(num_particles=64, max_steps=64, dt=0.1)
    t0 = time.perf_counter()
    tr, lens, st = sl.run(mesh, cfg)
    dt = time.perf_counter() - t0
    emit("app_streamlines_64p", dt * 1e6,
         f"particle_steps={int(lens.sum())};steps_per_s={lens.sum()/dt:.2e}")

    ncfg = nbody.NBodyConfig(num_particles=128, steps=4)
    t0 = time.perf_counter()
    nbody.run(mesh, ncfg)
    dt = time.perf_counter() - t0
    inter = ncfg.num_particles * (ncfg.num_particles + 9 * 8) * ncfg.steps
    emit("app_nbody_128p", dt * 1e6, f"interactions_per_s={inter/dt:.2e}")


# --------------------------------- paper technique on the LM side: MoE
def moe_dispatch():
    import dataclasses as dc

    from repro.configs import get_smoke_config
    from repro.models import moe
    from repro.models.common import init_params
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh()
    cfg = get_smoke_config("dbrx-132b")
    n_tok = 2048
    x = jax.random.normal(jax.random.PRNGKey(0), (8, n_tok // 8, cfg.d_model), jnp.float32)
    params = init_params(moe.moe_defs(cfg), jax.random.PRNGKey(1), jnp.float32)
    for plane in ("rafi_ep", "dense_tp"):
        c = dc.replace(cfg, moe_dispatch=plane, capacity_factor=2.0)
        f = jax.jit(lambda p, x: moe.moe_block(p, x, c, mesh=mesh))
        us, _ = _timeit(f, params, x)
        emit(f"moe_dispatch_{plane}", us, f"tokens_per_s={n_tok/(us/1e6):.2e}")


SECTIONS = [
    ("fig8_efficiency", fig8_efficiency),
    ("sort_cost", sort_cost),
    ("fwd_walltime", fwd_walltime),
    ("fwd_walltime_hier", fwd_walltime_hier),
    ("fwd_walltime_hier3", fwd_walltime_hier3),
    ("fwd_walltime_marshal", fwd_walltime_marshal),
    ("fwd_walltime_pipeline", fwd_walltime_pipeline),
    ("fwd_walltime_telemetry", fwd_walltime_telemetry),
    ("fwd_walltime_overflow", fwd_walltime_overflow),
    ("fwd_walltime_ckpt", fwd_walltime_ckpt),
    ("fwd_walltime_flow", fwd_walltime_flow),
    ("chaos_lossless", chaos_lossless),
    ("chaos_recovery", chaos_recovery),
    ("chaos_backpressure", chaos_backpressure),
    ("fwd_walltime_obs", fwd_walltime_obs),
    ("obs_flight_report", obs_flight_report),
    ("rebalance_skew", rebalance_skew),
    ("autotune_drift", autotune_drift),
    ("sort_throughput", sort_throughput),
    ("app_rates", app_rates),
    ("moe_dispatch", moe_dispatch),
]

SMOKE_SECTIONS = (
    "fwd_walltime", "fwd_walltime_hier", "fwd_walltime_marshal", "sort_throughput"
)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write results as machine-readable JSON")
    ap.add_argument("--smoke", action="store_true",
                    help=f"fast subset only: {', '.join(SMOKE_SECTIONS)}")
    ap.add_argument("--only", metavar="SUBSTR", default=None,
                    help="run only sections whose name contains SUBSTR")
    ap.add_argument("--autotune", action="store_true",
                    help="run only the ISSUE-5 autotune_drift section "
                         "(drifting hot-spot + adaptive capacity controller)")
    ap.add_argument("--chaos", action="store_true",
                    help="run only the chaos acceptance sections: the ISSUE-6 "
                         "chaos_lossless gauntlet (retain mode must lose "
                         "nothing where drop mode loses >20%%), the ISSUE-7 "
                         "chaos_recovery run (preempt-resume bit-exact, rank "
                         "brownout lossless), and the ISSUE-9 "
                         "chaos_backpressure overload pair (credit flow "
                         "lossless with bounded occupancy where open flow "
                         "wastes >30%% of its wire rows)")
    ap.add_argument("--compare", metavar="A,B[,C]", default=None,
                    help="regression gate: 'flat,hierarchical' times both "
                         "exchanges on a single-node mesh and exits nonzero "
                         "if hierarchical regresses flat by >5%%; "
                         "'flat,hierarchical2,hierarchical3' runs the "
                         "(2,2,2)-mesh sweep + rebalance_skew and gates on "
                         "the modeled slowest-tier bytes; 'sort,scatter' "
                         "runs the marshal sweep and gates on scatter "
                         "regressing sort by >5%% walltime; 'off,telemetry' "
                         "gates the flight recorder at a 1.05x walltime "
                         "geomean and runs the autotune_drift acceptance; "
                         "'drop,retain' gates spill-and-retry at a 1.05x "
                         "happy-path geomean and runs the chaos_lossless "
                         "acceptance; 'nockpt,ckpt' gates the checkpointed "
                         "drive (W=8) at a 1.05x walltime geomean over the "
                         "save-free segmented drive and runs the "
                         "chaos_recovery acceptance; 'bulk,pipelined' gates "
                         "micro-shard pipelining at a 1.0x geomean over the "
                         "bulk round on ballasted cache-exceeding rounds; "
                         "'open,credit' gates credit flow "
                         "at a 1.05x walltime geomean over open flow on the "
                         "fully-credited happy path and runs the "
                         "chaos_backpressure acceptance; 'off,obs' gates "
                         "the observation law (tracer + metrics snapshot) "
                         "at a 1.05x walltime geomean over the untraced "
                         "burst and runs the obs_flight_report acceptance "
                         "(the analyzer must reproduce the chaos driver's "
                         "goodput/waste numbers and flag only the open-flow "
                         "overload run as degraded)")
    args = ap.parse_args(argv)

    if args.autotune:
        args.only = "autotune_drift"
    if args.chaos:
        args.only = "chaos"  # chaos_lossless + chaos_recovery + chaos_backpressure

    print("name,us_per_call,derived")
    if args.compare:
        t0 = time.perf_counter()
        rc = compare_backends(args.compare)
        if args.json:
            _write_json(
                args.json, compare=args.compare, compare_failed=bool(rc),
                compare_walltime_s=round(time.perf_counter() - t0, 3),
            )
        raise SystemExit(rc)
    failures = []
    selected = [
        (name, fn)
        for name, fn in SECTIONS
        if (not args.smoke or name in SMOKE_SECTIONS)
        and (not args.only or args.only in name)
    ]
    if not selected:  # a typo'd --only must not record an empty "green" run
        only_hits = [n for n, _ in SECTIONS if not args.only or args.only in n]
        if args.smoke and only_hits:
            raise SystemExit(
                f"error: --only {args.only!r} matches only non-smoke sections "
                f"{only_hits}; drop --smoke to run them"
            )
        raise SystemExit(f"error: no benchmark section matches --only {args.only!r}")
    section_walltime_s = {}
    for name, fn in selected:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:  # a broken section must not hide the others' rows
            failures.append(name)
            print(f"# section {name} failed: {type(e).__name__}: {e}", flush=True)
        finally:
            # per-section wall time rides the JSON dump (the trajectory files
            # show WHERE a slow bench run spent its minutes, not just rows)
            section_walltime_s[name] = round(time.perf_counter() - t0, 3)
    print(f"# {len(ROWS)} benchmarks complete" + (f"; failed sections: {failures}" if failures else ""))

    if args.json:
        _write_json(
            args.json, smoke=bool(args.smoke), failed_sections=failures,
            section_walltime_s=section_walltime_s,
        )

    if failures:  # the canary must trip CI, not just leave a comment
        raise SystemExit(1)


if __name__ == "__main__":
    main()
