"""rafi/NBody — distributed Barnes-Hut-style N-body (§5.5).

The paper's defining demonstration: a *multi-phase* distributed algorithm
where THREE different work-item types travel through three simultaneous
forwarding contexts (its Listing 2, reproduced here field-for-field):

  Particle       migration after integration (pos, vel, force, mass [+uid])
  VirtualParticle adaptive essential-tree exchange (com, mass, size, sourceRank)
  RefinementReq  requests for finer remote data (senderRank)

Per timestep (all inside one jitted, shard_mapped program — fixed number of
forwarding rounds, no host round-trips):

  1. every rank aggregates its region's monopole (center-of-mass, mass,
     node size) and its 8 octant monopoles — the two-level essential tree;
  2. roots are broadcast to all peers via the VirtualParticle context;
  3. peers apply the multipole-acceptance criterion (size/dist > θ) and send
     a RefinementReq back to owners that are too close;
  4. owners answer each request with their 8 octant VirtualParticles;
  5. forces: the Pallas ``pairwise_accel`` kernel sums gravity from local
     particles ∪ accepted roots ∪ received octants (zero-mass padding lanes
     are inert);
  6. leapfrog kick-drift with reflective walls;
  7. particles migrate to ``owner(new_pos)`` via the Particle context — the
     owner is computed directly on device from the position (the property
     the paper gets from its Morton decomposition; our grid decomposition
     keeps it).

Domain: [0,1]³ split into a (gx, gy, gz) rank grid (R = gx·gy·gz).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import (
    DISCARD,
    ForwardConfig,
    enqueue,
    forward_work,
    make_queue,
    work_item,
)
from repro.kernels.nbody_forces import ops as nb

AXIS = "data"


@work_item
@dataclasses.dataclass
class Particle:
    """Paper Listing 2: pos, vel, force, mass (+uid for cross-rank tracking)."""

    pos: jax.Array    # (3,)
    vel: jax.Array    # (3,)
    force: jax.Array  # (3,)
    mass: jax.Array   # ()
    uid: jax.Array    # () i32


@work_item
@dataclasses.dataclass
class VirtualParticle:
    """Paper Listing 2: center of mass, mass, node size (0 = leaf), source."""

    pos: jax.Array         # (3,)
    mass: jax.Array        # ()
    size: jax.Array        # ()
    source_rank: jax.Array # () i32


@work_item
@dataclasses.dataclass
class RefinementReq:
    """Paper Listing 2: the rank requesting refinement."""

    sender_rank: jax.Array  # () i32


def _p_proto():
    z, zi = jnp.zeros(()), jnp.zeros((), jnp.int32)
    return Particle(jnp.zeros(3), jnp.zeros(3), jnp.zeros(3), z, zi)


def _vp_proto():
    z, zi = jnp.zeros(()), jnp.zeros((), jnp.int32)
    return VirtualParticle(jnp.zeros(3), z, z, zi)


def _rq_proto():
    return RefinementReq(jnp.zeros((), jnp.int32))


@dataclasses.dataclass(frozen=True)
class NBodyConfig:
    num_particles: int = 128
    steps: int = 4
    dt: float = 1e-3
    theta: float = 0.6     # MAC opening angle; larger ⇒ more refinement
    g: float = 1.0
    eps2: float = 1e-3
    seed: int = 0
    use_pallas: bool = True


def _grid_dims(R: int) -> Tuple[int, int, int]:
    dims = [1, 1, 1]
    i = 0
    while R > 1:
        assert R % 2 == 0, "rank count must be a power of two"
        dims[i % 3] *= 2
        R //= 2
        i += 1
    return tuple(dims)


def _owner(pos, dims):
    gx, gy, gz = dims
    ix = jnp.clip((pos[..., 0] * gx).astype(jnp.int32), 0, gx - 1)
    iy = jnp.clip((pos[..., 1] * gy).astype(jnp.int32), 0, gy - 1)
    iz = jnp.clip((pos[..., 2] * gz).astype(jnp.int32), 0, gz - 1)
    return ix + gx * (iy + gy * iz)


def _region_center(me, dims):
    gx, gy, gz = dims
    ix = me % gx
    iy = (me // gx) % gy
    iz = me // (gx * gy)
    return (
        jnp.stack(
            [
                (ix.astype(jnp.float32) + 0.5) / gx,
                (iy.astype(jnp.float32) + 0.5) / gy,
                (iz.astype(jnp.float32) + 0.5) / gz,
            ]
        ),
        jnp.asarray([1.0 / gx, 1.0 / gy, 1.0 / gz], jnp.float32),
    )


def _octant_monopoles(pos, mass, center):
    """8 octant (com, mass) pairs of the local region, by position-bit index."""
    bits = (pos >= center[None, :]).astype(jnp.int32)  # (n, 3)
    oct_id = bits[:, 0] + 2 * bits[:, 1] + 4 * bits[:, 2]
    m_oct = jnp.zeros(8).at[oct_id].add(mass)
    wx = jnp.zeros((8, 3)).at[oct_id].add(mass[:, None] * pos)
    com = wx / jnp.maximum(m_oct[:, None], 1e-20)
    return com, m_oct


def run(mesh, cfg: NBodyConfig = NBodyConfig()) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Simulate. Returns (final positions (N,3), final velocities (N,3), stats).

    Positions/velocities are returned in uid order (globally merged).
    """
    R = mesh.shape[AXIS]
    dims = _grid_dims(R)
    n = cfg.num_particles
    cap_p = max(64, n)                      # all particles may cluster on one rank
    cap_vp = max(16, 9 * R)                 # R roots + 8·R octants worst case
    cap_rq = max(8, R)
    pcfg = ForwardConfig(AXIS, R, cap_p, peer_capacity=cap_p, exchange="padded")
    vcfg = ForwardConfig(AXIS, R, cap_vp, peer_capacity=cap_vp, exchange="padded")
    rcfg = ForwardConfig(AXIS, R, cap_rq, peer_capacity=cap_rq, exchange="padded")

    def accel(xi, xj, mj):
        if cfg.use_pallas:
            return cfg.g * nb.pairwise_accel(xi, xj, mj, eps2=cfg.eps2)
        from repro.kernels.nbody_forces import ref

        return cfg.g * ref.pairwise_accel(xi, xj, mj, eps2=cfg.eps2)

    def timestep(pq, _):
        me = jax.lax.axis_index(AXIS)
        lane_p = jnp.arange(cap_p)
        pvalid = lane_p < pq.count
        p = pq.items
        mass = jnp.where(pvalid, p.mass, 0.0)

        # ---- 1. local essential tree (root + 8 octants) --------------------
        center, ext = _region_center(me, dims)
        m_tot = jnp.sum(mass)
        com = jnp.sum(mass[:, None] * p.pos, axis=0) / jnp.maximum(m_tot, 1e-20)
        node_size = jnp.linalg.norm(ext)
        oct_com, oct_m = _octant_monopoles(p.pos, mass, center)

        # ---- 2. broadcast roots (VirtualParticle context) -------------------
        vq = make_queue(_vp_proto(), cap_vp)
        peers = jnp.arange(R, dtype=jnp.int32)
        roots = VirtualParticle(
            pos=jnp.broadcast_to(com, (R, 3)),
            mass=jnp.full((R,), m_tot),
            size=jnp.full((R,), node_size),
            source_rank=jnp.full((R,), me, jnp.int32),
        )
        vq = enqueue(vq, roots, peers, peers != me)
        vq, _ = forward_work(vq, vcfg)

        # ---- 3. MAC test → refinement requests ------------------------------
        lane_v = jnp.arange(cap_vp)
        vvalid = lane_v < vq.count
        vp = vq.items
        dist = jnp.linalg.norm(vp.pos - center[None, :], axis=-1)
        too_close = vvalid & (vp.size > cfg.theta * dist) & (vp.mass > 0)
        rq = make_queue(_rq_proto(), cap_rq)
        rq = enqueue(
            rq,
            RefinementReq(sender_rank=jnp.full((cap_vp,), me, jnp.int32)),
            jnp.where(too_close, vp.source_rank, DISCARD).astype(jnp.int32),
            vvalid,
        )
        rq, _ = forward_work(rq, rcfg)

        # roots we asked to refine are replaced by their octants when they come
        refined_src = jnp.zeros((R,), bool).at[
            jnp.where(too_close, vp.source_rank, R)
        ].set(True, mode="drop")
        keep_root = vvalid & ~refined_src[jnp.clip(vp.source_rank, 0, R - 1)]

        # ---- 4. answer requests with octants ---------------------------------
        lane_r = jnp.arange(cap_rq)
        rvalid = lane_r < rq.count
        req = rq.items
        vq2 = make_queue(_vp_proto(), cap_vp)
        # emit 8 octants per request: flatten (cap_rq, 8)
        reps = jnp.repeat(req.sender_rank, 8)
        rmask = jnp.repeat(rvalid, 8)
        oct_items = VirtualParticle(
            pos=jnp.tile(oct_com, (cap_rq, 1)),
            mass=jnp.tile(oct_m, cap_rq),
            size=jnp.full((cap_rq * 8,), node_size * 0.5),
            source_rank=jnp.full((cap_rq * 8,), me, jnp.int32),
        )
        vq2 = enqueue(vq2, oct_items, reps.astype(jnp.int32), rmask)
        vq2, _ = forward_work(vq2, vcfg)

        lane_v2 = jnp.arange(cap_vp)
        v2valid = lane_v2 < vq2.count

        # ---- 5. forces: local ∪ kept roots ∪ octants -------------------------
        src_pos = jnp.concatenate(
            [p.pos, vp.pos, vq2.items.pos], axis=0
        )
        src_m = jnp.concatenate(
            [
                mass,
                jnp.where(keep_root, vp.mass, 0.0),
                jnp.where(v2valid, vq2.items.mass, 0.0),
            ]
        )
        a = accel(p.pos, src_pos, src_m)

        # ---- 6. leapfrog + reflective walls ----------------------------------
        vel = p.vel + cfg.dt * a
        pos = p.pos + cfg.dt * vel
        vel = jnp.where((pos < 0) | (pos > 1), -vel, vel)
        pos = jnp.abs(pos)
        pos = 1.0 - jnp.abs(1.0 - pos)

        # ---- 7. migration (Particle context) ---------------------------------
        out = make_queue(_p_proto(), cap_p)
        moved = Particle(pos=pos, vel=vel, force=a, mass=p.mass, uid=p.uid)
        dest = jnp.where(pvalid, _owner(pos, dims), DISCARD).astype(jnp.int32)
        out = enqueue(out, moved, dest, pvalid)
        new_pq, total = forward_work(out, pcfg)
        return new_pq, total

    def drive(_x):
        me = jax.lax.axis_index(AXIS)
        key = jax.random.PRNGKey(cfg.seed)
        pos0 = 0.5 + 0.15 * jax.random.normal(key, (n, 3))
        pos0 = jnp.clip(pos0, 0.05, 0.95)
        vel0 = 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (n, 3))
        mass0 = jax.random.uniform(jax.random.fold_in(key, 2), (n,), minval=0.5, maxval=1.5)
        uid = jnp.arange(n, dtype=jnp.int32)
        mine = _owner(pos0, dims) == me
        q0 = make_queue(_p_proto(), cap_p)
        q0 = enqueue(
            q0,
            Particle(pos=pos0, vel=vel0, force=jnp.zeros((n, 3)), mass=mass0, uid=uid),
            jnp.where(mine, me, DISCARD).astype(jnp.int32),
            jnp.ones(n, bool),
        )

        def varying(q):  # one carry type whatever each step's queue holds
            return jax.tree.map(lambda x: compat.pcast_varying(x, (AXIS,)), q)

        def body(pq, _):
            new_pq, total = timestep(pq, None)
            return varying(new_pq), total

        pq, totals = jax.lax.scan(body, varying(q0), None, length=cfg.steps)

        # merge final state by uid (disjoint ownership — pmin over +inf pad)
        lane = jnp.arange(cap_p)
        pvalid = lane < pq.count
        big = jnp.float32(jnp.inf)
        posb = jnp.full((n, 3), big)
        velb = jnp.full((n, 3), big)
        uid_idx = jnp.where(pvalid, pq.items.uid, n)
        posb = posb.at[uid_idx].min(
            jnp.where(pvalid[:, None], pq.items.pos, big), mode="drop"
        )
        velb = velb.at[uid_idx].min(
            jnp.where(pvalid[:, None], pq.items.vel, big), mode="drop"
        )
        pos = jax.lax.pmin(posb, AXIS)
        vel = jax.lax.pmin(velb, AXIS)
        return pos, vel, totals, pq.drops[None]

    f = jax.jit(
        jax.shard_map(
            drive, mesh=mesh, in_specs=P(AXIS),
            out_specs=(P(), P(), P(), P(AXIS)),
        )
    )
    pos, vel, totals, drops = f(jnp.arange(R, dtype=jnp.float32))
    return (
        np.asarray(pos),
        np.asarray(vel),
        {
            "totals": np.asarray(totals).tolist(),
            "drops": int(np.sum(np.asarray(drops))),
            "dims": dims,
        },
    )


def oracle(cfg: NBodyConfig = NBodyConfig()) -> Tuple[np.ndarray, np.ndarray]:
    """Single-device direct-sum leapfrog — ground truth for force accuracy."""
    from repro.kernels.nbody_forces import ref

    key = jax.random.PRNGKey(cfg.seed)
    n = cfg.num_particles
    pos = jnp.clip(0.5 + 0.15 * jax.random.normal(key, (n, 3)), 0.05, 0.95)
    vel = 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (n, 3))
    mass = jax.random.uniform(jax.random.fold_in(key, 2), (n,), minval=0.5, maxval=1.5)
    for _ in range(cfg.steps):
        a = cfg.g * ref.pairwise_accel(pos, pos, mass, eps2=cfg.eps2)
        vel = vel + cfg.dt * a
        pos = pos + cfg.dt * vel
        vel = jnp.where((pos < 0) | (pos > 1), -vel, vel)
        pos = jnp.abs(pos)
        pos = 1.0 - jnp.abs(1.0 - pos)
    return np.asarray(pos), np.asarray(vel)
