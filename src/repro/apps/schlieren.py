"""SchlieRaFI — data-parallel Schlieren renderer (§5.3).

Straight-ray Schlieren (Yates' formulation): each ray integrates the
projected density gradient along its path,

    I_u = ∫ (∇σ(p) · u) ds      I_v = ∫ (∇σ(p) · v) ds

where (u, v) are the camera's right/up axes.  A *knife edge* then filters
the integral into an image — a "U" knife edge emphasizes horizontal
gradients, "V" vertical ones (paper Fig. 5).

The forwarded state mirrors the paper's Listing 1 (FWDRay: origin,
direction, restart parameter, pixelID, partial integral): rays march a
globally-aligned sample grid through the slab partition and forward
themselves at partition boundaries carrying their partial integrals.
Schlieren *adds* contributions (no compositing order), so — as §6.1 notes —
a sort-last implementation is also correct; the forwarding version exists
for generality (refracted rays) and is validated to be R-invariant.
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
class SchlierenRay:
    """Paper Listing 1's FWDRay, adapted: two knife-edge partial integrals."""

    origin: jax.Array   # (3,)
    dir: jax.Array      # (3,)
    t_entry: jax.Array  # () f32 "restart parameter" analogue (grid anchor)
    k: jax.Array        # () i32 next sample index
    pixel: jax.Array    # () i32 framebuffer index
    slab: jax.Array     # () i32
    iu: jax.Array       # () f32 accumulated u-gradient integral
    iv: jax.Array       # () f32 accumulated v-gradient integral


def _proto():
    z, zi = jnp.zeros(()), jnp.zeros((), jnp.int32)
    return SchlierenRay(jnp.zeros(3), jnp.zeros(3), z, zi, zi, zi, z, z)


@dataclasses.dataclass(frozen=True)
class SchlierenScene:
    width: int = 32
    height: int = 32
    num_slabs: int = 32
    samples_per_slab: int = 8
    gain: float = 0.15
    seed: int = 2
    num_blobs: int = 6


def _camera_axes():
    fwd = jnp.asarray([1.0, 0.0, 0.0])
    up0 = jnp.asarray([0.0, 0.0, 1.0])
    right = jnp.cross(fwd, up0)
    right = right / jnp.linalg.norm(right)
    up = jnp.cross(right, fwd)
    return right, up


def _round_fn(q_in, fb2, rnd, *, part, blobs, ds, cap, right, up):
    r = q_in.items
    lane = jnp.arange(cap)
    valid = lane < q_in.count

    lo, hi = part.bounds(r.slab)
    t_cur = r.t_entry + r.k.astype(jnp.float32) * ds
    t_exit, axis, pos_side = F.ray_box_exit(r.origin, r.dir, t_cur, lo, hi)

    k, iu, iv = r.k, r.iu, r.iv
    for _ in range(MARCH_PER_ROUND):
        t_k = r.t_entry + (k.astype(jnp.float32) + 0.5) * ds
        inside = t_k < t_exit
        p = r.origin + t_k[:, None] * r.dir
        g = F.density_gradient(p, blobs)
        iu = jnp.where(inside, iu + jnp.dot(g, right) * ds, iu)
        iv = jnp.where(inside, iv + jnp.dot(g, up) * ds, iv)
        k = k + inside.astype(jnp.int32)
    t_next = r.t_entry + (k.astype(jnp.float32) + 0.5) * ds
    done_seg = t_next >= t_exit

    next_slab = r.slab + jnp.where(pos_side, 1, -1)
    stays = (next_slab >= 0) & (next_slab < part.num_slabs) & (axis == 0)
    finish = valid & done_seg & ~stays
    cross = valid & done_seg & stays
    again = valid & ~done_seg

    dep = jnp.stack([jnp.where(finish, iu, 0.0), jnp.where(finish, iv, 0.0)], -1)
    fb2 = fb2.at[r.pixel].add(jnp.where(valid[:, None], dep, 0.0), mode="drop")

    new = SchlierenRay(
        origin=r.origin, dir=r.dir, t_entry=r.t_entry, k=k, pixel=r.pixel,
        slab=jnp.where(cross, next_slab, r.slab), iu=iu, iv=iv,
    )
    alive = cross | again
    dest = jnp.where(
        cross,
        part.owner_of_slab(next_slab),
        jnp.where(again, jax.lax.axis_index(AXIS), DISCARD),
    ).astype(jnp.int32)
    out = make_queue(_proto(), cap)
    out = enqueue(out, new, dest, alive)
    return out, fb2


def render(
    mesh, scene: SchlierenScene = SchlierenScene(), *, blobs=None,
    max_rounds: int = 4096, exchange: str = "padded",
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Returns (knife_u image, knife_v image, stats) — paper Fig. 5's pair."""
    R = mesh.shape[AXIS]
    if blobs is None:
        blobs = F.default_blobs(scene.num_blobs, scene.seed)
    part = F.SlabPartition(num_slabs=scene.num_slabs, num_ranks=R)
    ds = part.width / scene.samples_per_slab
    hw = scene.width * scene.height
    cap = max(256, hw)
    # peer slots only exist for the padded exchange (ragged/onehot reject it)
    slots = {"peer_capacity": cap} if exchange == "padded" else {}
    cfg = ForwardConfig(AXIS, R, cap, exchange=exchange, **slots)
    right, up = _camera_axes()

    round_fn = partial(
        _round_fn, part=part, blobs=blobs, ds=ds, cap=cap, right=right, up=up
    )

    def drive(_x):
        me = jax.lax.axis_index(AXIS)
        ppr = hw // R
        pix = me * ppr + jnp.arange(ppr)
        o, d = F.camera_rays(scene.width, scene.height)
        o, d = o[pix], d[pix]
        t_entry, hits = F.ray_domain_entry(o, d)
        fb2 = jnp.zeros((hw, 2), jnp.float32)
        p_in = o + (t_entry[:, None] + 1e-4) * d
        slab = part.slab_of(jnp.clip(p_in[:, 0], 0.0, 1.0 - 1e-6))
        n = pix.shape[0]
        rays = SchlierenRay(
            origin=o, dir=d, t_entry=t_entry, k=jnp.zeros(n, jnp.int32),
            pixel=pix.astype(jnp.int32), slab=slab,
            iu=jnp.zeros(n), iv=jnp.zeros(n),
        )
        dest = jnp.where(hits, part.owner_of_slab(slab), DISCARD).astype(jnp.int32)
        q0 = make_queue(_proto(), cap)
        q0 = enqueue(q0, rays, dest, jnp.ones(n, bool))
        q, fb2, rounds, _done = run_until_done(round_fn, q0, fb2, cfg, max_rounds=max_rounds)
        return jax.lax.psum(fb2, AXIS), rounds[None], q.drops[None]

    f = jax.jit(jax.shard_map(drive, mesh=mesh, in_specs=P(AXIS),
                              out_specs=(P(), P(AXIS), P(AXIS))))
    fb2, rounds, drops = f(jnp.arange(R, dtype=jnp.float32))
    fb2 = np.asarray(fb2)
    # knife-edge filter: mid-gray plus the (signed) projected gradient integral
    img_u = np.clip(0.5 + scene.gain * fb2[:, 0], 0, 1).reshape(scene.height, scene.width)
    img_v = np.clip(0.5 + scene.gain * fb2[:, 1], 0, 1).reshape(scene.height, scene.width)
    return img_u, img_v, {
        "rounds": int(np.max(np.asarray(rounds))),
        "drops": int(np.sum(np.asarray(drops))),
        "raw": fb2,
    }
