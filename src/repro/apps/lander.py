"""rafi/Lander — volume rendering of NON-CONVEX partitions (§5.2).

The Mars-Lander problem: with the solver's native partitioning, one rank's
domain is not convex, so a ray enters and leaves the same rank many times.
We reproduce the structure with interleaved slab ownership: ``num_slabs =
k·R`` x-slabs, rank r owning slabs {r, r+R, r+2R, ...} — every ray crosses
every rank up to k times.

Two renderers over the same partition and the same globally-aligned sample
grid (samples at t_entry + (k+½)·Δs, so partitioning cannot change *where*
the field is sampled):

* ``render_forwarding`` — the RaFI realization: each ray carries its
  accumulated (L, T) emission-absorption state slab-to-slab via
  ``forward_work``; segments per ray are unlimited; non-straight extensions
  (shadow/scatter) would be possible (not exercised here — VoPaT covers
  scattering).
* ``render_deep_compositing`` — the baseline it replaced (Sahistan et al.):
  every rank integrates each of its *owned segments* independently into a
  fixed-depth fragment list (max ``max_fragments`` per pixel per rank —
  fragments past that are DROPPED, the paper's artifact mechanism), then a
  depth-sorted composite merges all ranks' fragments.

With ``max_fragments >= slabs_per_rank`` the two agree to float tolerance;
with fewer fragments the compositor mis-renders exactly as §5.2 describes
while the forwarding renderer stays correct.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

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
MARCH_PER_ROUND = 32


@work_item
@dataclasses.dataclass
class EARay:
    """Emission-absorption ray state forwarded between partitions."""

    origin: jax.Array   # (3,)
    dir: jax.Array      # (3,)
    t_entry: jax.Array  # () domain entry (sample-grid anchor)
    k: jax.Array        # () i32 next sample index
    pixel: jax.Array    # () i32
    slab: jax.Array     # () i32
    radiance: jax.Array # () f32 accumulated L
    trans: jax.Array    # () f32 accumulated transmittance T


def _proto():
    z, zi = jnp.zeros(()), jnp.zeros((), jnp.int32)
    return EARay(jnp.zeros(3), jnp.zeros(3), z, zi, zi, zi, z, z)


@dataclasses.dataclass(frozen=True)
class LanderScene:
    width: int = 32
    height: int = 32
    num_slabs: int = 32        # total slabs — independent of R so the sample
    samples_per_slab: int = 8  # grid (and hence the image) is R-invariant
    seed: int = 1
    num_blobs: int = 6


def _delta_s(part: F.SlabPartition, scene: LanderScene) -> float:
    return part.width / scene.samples_per_slab


def _march_segment(ray: EARay, t_hi, blobs, ds, steps: int):
    """Advance ≤ ``steps`` samples while t_k < t_hi; returns updated (k, L, T)."""
    k, L, T = ray.k, ray.radiance, ray.trans
    for _ in range(steps):
        t_k = ray.t_entry + (k.astype(jnp.float32) + 0.5) * ds
        inside = t_k < t_hi
        p = ray.origin + t_k[:, None] * ray.dir
        sigma = F.density(p, blobs)
        a = 1.0 - jnp.exp(-sigma * ds)
        L = jnp.where(inside, L + T * a, L)
        T = jnp.where(inside, T * (1.0 - a), T)
        k = k + inside.astype(jnp.int32)
    return k, L, T


def _round_fn(q_in, fb, rnd, *, part, blobs, ds, cap):
    r = q_in.items
    lane = jnp.arange(cap)
    valid = lane < q_in.count

    lo, hi = part.bounds(r.slab)
    t_cur = r.t_entry + r.k.astype(jnp.float32) * ds  # lower bound on position
    t_exit, axis, pos_side = F.ray_box_exit(r.origin, r.dir, t_cur, lo, hi)

    k, L, T = _march_segment(r, t_exit, blobs, ds, MARCH_PER_ROUND)
    t_next = r.t_entry + (k.astype(jnp.float32) + 0.5) * ds
    done_seg = t_next >= t_exit  # consumed the whole in-slab segment

    next_slab = r.slab + jnp.where(pos_side, 1, -1)
    stays = (next_slab >= 0) & (next_slab < part.num_slabs) & (axis == 0)
    finish = valid & done_seg & ~stays
    cross = valid & done_seg & stays
    again = valid & ~done_seg  # more samples needed in this slab

    deposit = jnp.where(finish, L + T * F.sky(r.dir), 0.0)
    fb = fb.at[r.pixel].add(jnp.where(valid, deposit, 0.0), mode="drop")

    new = EARay(
        origin=r.origin, dir=r.dir, t_entry=r.t_entry, k=k, pixel=r.pixel,
        slab=jnp.where(cross, next_slab, r.slab), radiance=L, trans=T,
    )
    alive = cross | again
    dest = jnp.where(
        cross,
        part.owner_of_slab(next_slab),
        jnp.where(again, jax.lax.axis_index(AXIS), DISCARD),
    ).astype(jnp.int32)
    out = make_queue(_proto(), cap)
    out = enqueue(out, new, dest, alive)
    return out, fb


def _primary_rays(scene: LanderScene):
    o, d = F.camera_rays(scene.width, scene.height)
    t_entry, hits = F.ray_domain_entry(o, d)
    return o, d, t_entry, hits


def render_forwarding(
    mesh, scene: LanderScene = LanderScene(), *, blobs=None, max_rounds: int = 4096,
    exchange: str = "padded",
) -> Tuple[np.ndarray, dict]:
    """RaFI-style renderer. Returns (image (H,W), stats)."""
    R = mesh.shape[AXIS]
    if blobs is None:
        blobs = F.default_blobs(scene.num_blobs, scene.seed)
    part = F.SlabPartition(num_slabs=scene.num_slabs, num_ranks=R)
    ds = _delta_s(part, scene)
    hw = scene.width * scene.height
    cap = max(256, hw)
    # peer slots only exist for the padded exchange (ragged/onehot reject it)
    slots = {"peer_capacity": cap} if exchange == "padded" else {}
    cfg = ForwardConfig(AXIS, R, cap, exchange=exchange, **slots)

    round_fn = partial(_round_fn, part=part, blobs=blobs, ds=ds, cap=cap)

    def drive(_x):
        me = jax.lax.axis_index(AXIS)
        ppr = hw // R
        pix = me * ppr + jnp.arange(ppr)
        o, d, t_entry, hits = _primary_rays(scene)
        o, d, t_entry, hits = o[pix], d[pix], t_entry[pix], hits[pix]
        fb = jnp.zeros((hw,), jnp.float32)
        fb = fb.at[pix].add(jnp.where(hits, 0.0, F.sky(d)), mode="drop")
        p_in = o + (t_entry[:, None] + 1e-4) * d
        slab = part.slab_of(jnp.clip(p_in[:, 0], 0.0, 1.0 - 1e-6))
        n = pix.shape[0]
        rays = EARay(
            origin=o, dir=d, t_entry=t_entry, k=jnp.zeros(n, jnp.int32),
            pixel=pix.astype(jnp.int32), slab=slab,
            radiance=jnp.zeros(n), trans=jnp.ones(n),
        )
        dest = jnp.where(hits, part.owner_of_slab(slab), DISCARD).astype(jnp.int32)
        q0 = make_queue(_proto(), cap)
        q0 = enqueue(q0, rays, dest, jnp.ones(n, bool))
        q, fb, rounds, _done = run_until_done(round_fn, q0, fb, cfg, max_rounds=max_rounds)
        return jax.lax.psum(fb, AXIS), rounds[None], q.drops[None]

    f = jax.jit(jax.shard_map(drive, mesh=mesh, in_specs=P(AXIS),
                              out_specs=(P(), P(AXIS), P(AXIS))))
    img, rounds, drops = f(jnp.arange(R, dtype=jnp.float32))
    return (
        np.asarray(img).reshape(scene.height, scene.width),
        {"rounds": int(np.max(np.asarray(rounds))), "drops": int(np.sum(np.asarray(drops)))},
    )


def render_deep_compositing(
    mesh, scene: LanderScene = LanderScene(), *, blobs=None, max_fragments: int = 4,
) -> Tuple[np.ndarray, dict]:
    """The §5.2 baseline: per-rank fragment lists + depth-sorted compositing.

    Every rank integrates each of its owned segments of every ray locally
    (no forwarding), keeping at most ``max_fragments`` (L, T, depth) triples
    per pixel — excess fragments are dropped, which is the artifact mechanism
    the paper describes.  An all-gather + depth sort then composites.
    """
    R = mesh.shape[AXIS]
    if blobs is None:
        blobs = F.default_blobs(scene.num_blobs, scene.seed)
    part = F.SlabPartition(num_slabs=scene.num_slabs, num_ranks=R)
    ds = _delta_s(part, scene)
    hw = scene.width * scene.height
    FMAX = max_fragments

    def rank_fragments(_x):
        me = jax.lax.axis_index(AXIS)
        o, d, t_entry, hits = _primary_rays(scene)
        # integrate every owned slab for every ray (sort-last: no forwarding)
        fragL = jnp.zeros((hw, FMAX))
        fragT = jnp.ones((hw, FMAX))
        fragD = jnp.full((hw, FMAX), jnp.inf)
        nfrag = jnp.zeros((hw,), jnp.int32)
        dropped = jnp.zeros((), jnp.int32)
        for j in range(-(-scene.num_slabs // R)):  # owned slabs: me, me+R, ...
            # dynamic slab id: me + j*R (owned, in paper's round-robin layout)
            sid = me + j * R
            slab = sid * jnp.ones((hw,), jnp.int32)
            lo, hi = part.bounds(slab)
            # in-slab param range along each ray (x is monotone for d_x ≠ 0)
            eps = 1e-12
            dx = jnp.where(jnp.abs(d[:, 0]) < eps, eps, d[:, 0])
            ta = (lo - o[:, 0]) / dx
            tb = (hi - o[:, 0]) / dx
            t0s = jnp.maximum(jnp.minimum(ta, tb), t_entry)
            # clip by domain y/z exit
            _, far = F.ray_domain_entry(o, d)
            inv = 1.0 / jnp.where(jnp.abs(d) < eps, jnp.where(d >= 0, eps, -eps), d)
            tfar = jnp.min(
                jnp.where(d >= 0, (1.0 - o) * inv, (0.0 - o) * inv), axis=-1
            )
            t1s = jnp.minimum(jnp.maximum(ta, tb), tfar)
            seg_ok = hits & (t1s > t0s)
            # globally aligned samples: k in [ceil((t0-te)/ds - .5), …)
            k0 = jnp.ceil((t0s - t_entry) / ds - 0.5).astype(jnp.int32)
            k0 = jnp.maximum(k0, 0)
            L = jnp.zeros((hw,))
            T = jnp.ones((hw,))
            k = k0
            for _ in range(scene.samples_per_slab + 2):
                t_k = t_entry + (k.astype(jnp.float32) + 0.5) * ds
                inside = seg_ok & (t_k < t1s)
                p = o + t_k[:, None] * d
                sigma = F.density(p, blobs)
                a = 1.0 - jnp.exp(-sigma * ds)
                L = jnp.where(inside, L + T * a, L)
                T = jnp.where(inside, T * (1.0 - a), T)
                k = k + inside.astype(jnp.int32)
            has = seg_ok & (k > k0)
            slot = jnp.minimum(nfrag, FMAX - 1)
            fits = has & (nfrag < FMAX)
            dropped = dropped + jnp.sum(has & ~fits)
            fragL = fragL.at[jnp.arange(hw), slot].set(
                jnp.where(fits, L, fragL[jnp.arange(hw), slot])
            )
            fragT = fragT.at[jnp.arange(hw), slot].set(
                jnp.where(fits, T, fragT[jnp.arange(hw), slot])
            )
            fragD = fragD.at[jnp.arange(hw), slot].set(
                jnp.where(fits, t0s, fragD[jnp.arange(hw), slot])
            )
            nfrag = nfrag + fits.astype(jnp.int32)
        return fragL, fragT, fragD, dropped[None]

    f = jax.jit(jax.shard_map(rank_fragments, mesh=mesh, in_specs=P(AXIS),
                              out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS))))
    allL, allT, allD, dropped = f(jnp.arange(R, dtype=jnp.float32))
    # host-side composite (the "sort-last" stage): depth-sort, front-to-back
    allL = np.asarray(allL).reshape(R, hw, -1).transpose(1, 0, 2).reshape(hw, -1)
    allT = np.asarray(allT).reshape(R, hw, -1).transpose(1, 0, 2).reshape(hw, -1)
    allD = np.asarray(allD).reshape(R, hw, -1).transpose(1, 0, 2).reshape(hw, -1)
    order = np.argsort(allD, axis=1)
    L = np.take_along_axis(allL, order, 1)
    T = np.take_along_axis(allT, order, 1)
    img = np.zeros(hw)
    Tacc = np.ones(hw)
    for i in range(L.shape[1]):
        img += Tacc * L[:, i]
        Tacc *= T[:, i]
    # background through remaining transmittance (+ pure misses)
    o, d = F.camera_rays(scene.width, scene.height)
    _, hits = F.ray_domain_entry(o, d)
    sky = np.asarray(F.sky(d))
    img = np.where(np.asarray(hits), img + Tacc * sky, sky)
    return (
        img.reshape(scene.height, scene.width),
        {"dropped_fragments": int(np.sum(np.asarray(dropped)))},
    )
