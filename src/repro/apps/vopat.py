"""VoPaT — data-parallel volume path tracer on the forwarding core (§5.1).

Faithful wavefront structure (paper Fig. 1):

  1. every rank holds the same slab partition ("proxies") and generates its
     share of primary rays (the paper generates all rays everywhere and
     discards foreign ones — generating disjoint subsets is the equivalent,
     cheaper formulation);
  2. per round, a render kernel advances each ray by ONE Woodcock event:
     * no pending flight → draw a tentative free-flight from the *global*
       majorant (one RNG event, keyed by (pixel, events) so the walk is
       bit-identical at any rank count);
     * flight ends inside the slab → acceptance test: real collision scatters
       isotropically (with albedo Russian roulette) and re-emits TO ITSELF
       (Fig. 1: "scattered, then passed to RaFI for forwarding to itself");
       null collision re-arms from the new position;
     * flight crosses the slab face → the ray moves to the boundary and is
       forwarded to the neighbour rank *carrying its remaining flight*
       (exponential flights are memoryless, and carrying the pending target
       keeps the multi-rank walk bitwise equal to the single-rank walk);
     * leaving [0,1]³ → deposit throughput·sky into the distributed
       framebuffer and terminate;
  3. ``forward_work`` moves rays; the on-device while_loop repeats until the
     global in-flight count is zero (§4.2.3 distributed termination);
  4. the per-rank framebuffers are reduced with a psum — the "distributed
     frame buffer" of BriX/VoPaT.

Because the RNG is keyed by (pixel, event) and boundary crossings consume no
events, rendering with R ranks reproduces the R=1 image exactly — the
paper's "the rendered images will not differ in any way" claim, promoted to
a bitwise test (spp=1) in tests/test_apps_vopat.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.apps import fields as F
from repro.core import (
    DISCARD,
    ForwardConfig,
    enqueue,
    make_queue,
    run_until_done,
    work_item,
)

AXIS = "data"


@work_item
@dataclasses.dataclass
class PathRay:
    """44-byte forwardable path state (cf. the paper's 44-byte rays, Fig. 8)."""

    origin: jax.Array      # (3,) f32 current path-segment origin
    dir: jax.Array         # (3,) f32
    t: jax.Array           # () f32 current param along segment
    t_tgt: jax.Array       # () f32 pending tentative-collision param
    u2: jax.Array          # () f32 carried acceptance uniform
    throughput: jax.Array  # () f32
    pixel: jax.Array       # () i32
    events: jax.Array      # () i32 RNG event counter
    bounces: jax.Array     # () i32
    slab: jax.Array        # () i32 current slab index
    in_flight: jax.Array   # () i32 pending flight valid?


def _proto():
    z, zi = jnp.zeros(()), jnp.zeros((), jnp.int32)
    return PathRay(jnp.zeros(3), jnp.zeros(3), z, z, z, z, zi, zi, zi, zi, zi)


@dataclasses.dataclass(frozen=True)
class VopatScene:
    width: int = 64
    height: int = 64
    spp: int = 1
    albedo: float = 0.8
    max_bounces: int = 3
    seed: int = 0
    num_blobs: int = 6


def _event_uniforms(key, pixel, events, n):
    """(lanes, n) uniforms keyed by (pixel, events) — rank-count invariant."""

    def one(px, ev):
        return jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(key, px), ev), (n,)
        )

    return jax.vmap(one)(pixel, events)


def _round_fn(q_in, fb, rnd, *, part: F.SlabPartition, blobs, mu, key, scene, cap):
    r = q_in.items
    lane = jnp.arange(cap)
    valid = lane < q_in.count

    # --- arm pending flights (one RNG event) -------------------------------
    draw = valid & (r.in_flight == 0)
    u = _event_uniforms(key, r.pixel, r.events, 2)
    t_tgt = jnp.where(draw, r.t - jnp.log1p(-u[:, 0]) / mu, r.t_tgt)
    u2 = jnp.where(draw, u[:, 1], r.u2)
    events = r.events + draw.astype(jnp.int32)

    # --- slab geometry ------------------------------------------------------
    lo, hi = part.bounds(r.slab)
    t_exit, axis, pos_side = F.ray_box_exit(r.origin, r.dir, r.t, lo, hi)
    arrives = valid & (t_tgt <= t_exit)
    crosses = valid & ~arrives

    # --- arrivals: acceptance test ------------------------------------------
    p_tgt = r.origin + t_tgt[:, None] * r.dir
    dens = F.density(p_tgt, blobs)
    hit = arrives & (u2 * mu < dens)
    null = arrives & ~hit

    # --- real collisions: Russian-roulette scatter (one RNG event) ----------
    su = _event_uniforms(key, r.pixel, events, 3)
    events = events + hit.astype(jnp.int32)
    absorbed = hit & (su[:, 2] >= scene.albedo)
    exhausted = hit & ~absorbed & (r.bounces + 1 > scene.max_bounces)
    scattered = hit & ~absorbed & ~exhausted
    z = 1.0 - 2.0 * su[:, 0]
    phi = 2.0 * jnp.pi * su[:, 1]
    s = jnp.sqrt(jnp.maximum(0.0, 1.0 - z * z))
    new_dir = jnp.stack([s * jnp.cos(phi), s * jnp.sin(phi), z], axis=-1)

    # --- boundary crossings --------------------------------------------------
    next_slab = r.slab + jnp.where(pos_side, 1, -1)
    stays_in = (next_slab >= 0) & (next_slab < part.num_slabs)
    to_neighbor = crosses & (axis == 0) & stays_in
    escapes = crosses & ~((axis == 0) & stays_in)

    # --- terminal deposits ----------------------------------------------------
    deposit = jnp.where(escapes, r.throughput * F.sky(r.dir), 0.0)
    fb = fb.at[r.pixel].add(jnp.where(valid, deposit, 0.0), mode="drop")

    # --- assemble next-round rays ---------------------------------------------
    alive = null | scattered | to_neighbor
    new = PathRay(
        origin=jnp.where(scattered[:, None], p_tgt, r.origin),
        dir=jnp.where(scattered[:, None], new_dir, r.dir),
        t=jnp.where(scattered, 0.0, jnp.where(null, t_tgt, t_exit)),
        t_tgt=t_tgt,
        u2=u2,
        throughput=r.throughput,
        pixel=r.pixel,
        events=events,
        bounces=r.bounces + scattered.astype(jnp.int32),
        slab=jnp.where(to_neighbor, next_slab, r.slab),
        in_flight=to_neighbor.astype(jnp.int32),
    )
    dest = jnp.where(
        to_neighbor,
        part.owner_of_slab(next_slab),
        jnp.where(alive, jax.lax.axis_index(AXIS), DISCARD),
    ).astype(jnp.int32)

    out = make_queue(_proto(), cap)
    out = enqueue(out, new, dest, alive)
    return out, fb


def _raygen(me, *, part, blobs, key, scene, cap, num_ranks):
    """Per-rank primary rays (disjoint pixel range) + direct sky for misses."""
    hw = scene.width * scene.height * scene.spp
    ppr = hw // num_ranks
    pix = me * ppr + jnp.arange(ppr)
    o_all, d_all = F.camera_rays(scene.width, scene.height)
    o = o_all[(pix // scene.spp) % (scene.width * scene.height)]
    d = d_all[(pix // scene.spp) % (scene.width * scene.height)]
    t_entry, hits = F.ray_domain_entry(o, d)

    fb = jnp.zeros((scene.width * scene.height,), jnp.float32)
    fb = fb.at[pix // scene.spp].add(jnp.where(hits, 0.0, F.sky(d)), mode="drop")

    p_in = o + (t_entry[:, None] + 1e-4) * d
    slab = part.slab_of(jnp.clip(p_in[:, 0], 0.0, 1.0 - 1e-6))
    z, zi = jnp.zeros(ppr), jnp.zeros(ppr, jnp.int32)
    rays = PathRay(
        origin=o,
        dir=d,
        t=t_entry,
        t_tgt=z,
        u2=z,
        throughput=jnp.ones(ppr),
        pixel=(pix // scene.spp).astype(jnp.int32),
        events=(pix % scene.spp) * jnp.int32(1 << 20) + zi,
        bounces=zi,
        slab=slab,
        in_flight=zi,
    )
    dest = jnp.where(hits, part.owner_of_slab(slab), DISCARD).astype(jnp.int32)
    q0 = make_queue(_proto(), cap)
    q0 = enqueue(q0, rays, dest, jnp.ones(ppr, bool))
    return q0, fb


def renderer(
    mesh,
    scene: VopatScene = VopatScene(),
    *,
    blobs=None,
    max_rounds: int = 512,
    exchange: str = "padded",
    marshal: str = "sort",
    use_pallas: bool = False,
    telemetry: bool = False,
    telemetry_window: int = 32,
) -> Callable[[], Tuple[np.ndarray, dict]]:
    """Build the distributed render of ``scene`` on ``mesh`` once.

    Returns a function of no arguments that renders one frame and returns
    ``(image (H,W) float, stats dict)``; its first call compiles, later calls
    reuse the compiled program.  See :func:`render` for the stats."""
    R = mesh.shape[AXIS]
    if blobs is None:
        blobs = F.default_blobs(scene.num_blobs, scene.seed)
    mu = F.majorant(blobs)
    part = F.SlabPartition(num_slabs=R, num_ranks=R)
    hw = scene.width * scene.height * scene.spp
    # Worst-case wavefront: the whole camera frustum can enter one slab, so a
    # single rank may momentarily own every ray.  The paper's §6.3 guidance —
    # "it was always possible to compute an upper bound ... so queues could be
    # sized accordingly" — for a pinhole camera that bound is all rays.
    cap = max(256, hw)
    # peer slots only exist for the padded exchange (ragged/onehot reject it)
    slots = {"peer_capacity": cap} if exchange == "padded" else {}
    cfg = ForwardConfig(
        AXIS, R, cap, exchange=exchange, marshal=marshal,
        use_pallas=use_pallas, telemetry=telemetry,
        telemetry_window=telemetry_window, **slots
    )
    key = jax.random.PRNGKey(scene.seed)

    round_fn = partial(
        _round_fn, part=part, blobs=blobs, mu=mu, key=key, scene=scene, cap=cap
    )

    def drive(_x):
        me = jax.lax.axis_index(AXIS)
        q0, fb = _raygen(
            me, part=part, blobs=blobs, key=key, scene=scene, cap=cap, num_ranks=R
        )
        if telemetry:
            from repro.telemetry import stats as TS

            q, fb, rounds, done, ring = run_until_done(
                round_fn, q0, fb, cfg, max_rounds=max_rounds
            )
            img = jax.lax.psum(fb, AXIS)
            return img, done, rounds[None], q.drops[None], TS.stack_ring(ring)
        q, fb, rounds, done = run_until_done(round_fn, q0, fb, cfg, max_rounds=max_rounds)
        img = jax.lax.psum(fb, AXIS)
        return img, done, rounds[None], q.drops[None]

    out_specs = (P(), P(), P(AXIS), P(AXIS))
    if telemetry:
        from repro.telemetry import stats as TS

        ring_proto = TS.make_ring(
            TS.num_tiers(cfg), window=cfg.telemetry_window,
            buckets=cfg.telemetry_buckets,
        )
        out_specs = out_specs + (jax.tree.map(lambda _: P(AXIS), ring_proto),)
    f = jax.jit(
        jax.shard_map(drive, mesh=mesh, in_specs=P(AXIS), out_specs=out_specs)
    )
    x = jax.device_put(np.arange(R, dtype=np.float32), NamedSharding(mesh, P(AXIS)))

    def run() -> Tuple[np.ndarray, dict]:
        out = f(x)
        img, done, rounds, drops = out[:4]
        img = np.asarray(img).reshape(scene.height, scene.width) / scene.spp
        stats = {
            "done": bool(done),
            "rounds": int(np.max(np.asarray(rounds))),
            "drops": int(np.sum(np.asarray(drops))),
            "majorant": mu,
            "capacity": cap,
            # the device holding each rank's shard, in rank order
            "devices": [s.device for s in sorted(
                rounds.addressable_shards, key=lambda s: s.index[0].start or 0
            )],
        }
        if telemetry:
            from repro import telemetry as TM

            stats["telemetry"] = TM.summarize(
                out[4], tier_capacities=TM.tier_capacities(cfg)
            )
        return img, stats

    return run


def render(
    mesh,
    scene: VopatScene = VopatScene(),
    **kwargs,
) -> Tuple[np.ndarray, dict]:
    """Distributed render. Returns (image (H,W) float, stats dict).

    Keyword arguments are those of :func:`renderer`.  ``stats["done"]`` is
    the termination verdict: False when ``max_rounds`` cut the frame with
    rays still in flight, so the image is incomplete.  ``stats["devices"]``
    lists the device that ran each rank.

    With ``telemetry`` the drive loop carries the flight-recorder ring and
    the stats dict gains a ``"telemetry"`` summary (per-tier demand
    histogram/max, clamp drops — see ``repro.telemetry.summarize``): the
    measured basis for replacing this module's worst-case §6.3 queue sizing
    with ``repro.tune``-planned capacities."""
    return renderer(mesh, scene, **kwargs)()
