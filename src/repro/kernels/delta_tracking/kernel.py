"""Pallas kernel: Woodcock (delta) tracking for the VoPaT app (§5.1).

Performs K tentative-collision steps of Woodcock tracking per call for a
(TILE,) block of rays against a *procedural* density field (a mixture of
Gaussian blobs whose parameters sit in VMEM).  Procedural fields keep the
kernel gather-free — the TPU-friendly formulation (see DESIGN.md; the app's
grid-data path uses XLA gather in ops.py instead).

Per step k each active ray:
  t   ← t − ln(1−u₁)/μ̄           (tentative free flight, majorant μ̄)
  p   ← o + t·d
  hit ← u₂ < σ(p)/μ̄              (real collision w.p. density/majorant)
rays that leave the rank's slab ([t_exit]) terminate as "passed through".

Outputs per ray: collision t (or +inf), status ∈ {0: still tracking,
1: real collision, 2: exited slab}.  The uniforms are supplied (TILE, K, 2)
so the kernel is RNG-free and bit-reproducible (counter-based keys are
generated by the app).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import call, sds

STILL, HIT, EXITED = 0, 1, 2


def _density(p, blobs):
    """σ(p) = Σ_g amp_g · exp(−|p−c_g|²/(2s_g²));  blobs (G,5) = (cx,cy,cz,s,amp)."""
    d = p[:, None, :] - blobs[None, :, :3]  # (T, G, 3)
    r2 = jnp.sum(d * d, axis=-1)  # (T, G)
    s2 = blobs[None, :, 3] ** 2
    return jnp.sum(blobs[None, :, 4] * jnp.exp(-0.5 * r2 / s2), axis=-1)


def _track_kernel(o_ref, d_ref, t_ref, texit_ref, u_ref, blobs_ref,
                  tout_ref, status_ref, *, majorant, steps):
    o = o_ref[...]
    dirs = d_ref[...]
    t = t_ref[...]
    texit = texit_ref[...]
    u = u_ref[...]
    blobs = blobs_ref[...]
    status = jnp.zeros(t.shape, jnp.int32)
    for k in range(steps):
        active = status == STILL
        t_new = t - jnp.log1p(-u[:, k, 0]) / majorant
        p = o + t_new[:, None] * dirs
        dens = _density(p, blobs)
        exited = active & (t_new >= texit)
        hit = active & ~exited & (u[:, k, 1] * majorant < dens)
        t = jnp.where(active, t_new, t)
        status = jnp.where(exited, EXITED, jnp.where(hit, HIT, status))
    tout_ref[...] = t
    status_ref[...] = status


@functools.partial(jax.jit, static_argnames=("majorant", "steps", "tile", "interpret"))
def track(
    origins: jax.Array,   # (N, 3)
    dirs: jax.Array,      # (N, 3) normalized
    t0: jax.Array,        # (N,) current parametric position
    t_exit: jax.Array,    # (N,) slab exit parameter
    uniforms: jax.Array,  # (N, K, 2) in [0,1)
    blobs: jax.Array,     # (G, 5) procedural field
    *,
    majorant: float,
    steps: int = 8,
    tile: int = 512,
    interpret: bool = False,
):
    """K Woodcock steps. Returns (t (N,), status (N,) int32)."""
    n = origins.shape[0]
    g = blobs.shape[0]
    tile = min(tile, n)
    while n % tile:
        tile //= 2

    def kernel(origins, dirs, t0, t_exit, uniforms, blobs):
        return pl.pallas_call(
            functools.partial(_track_kernel, majorant=majorant, steps=steps),
            grid=(n // tile,),
            in_specs=[
                pl.BlockSpec((tile, 3), lambda i: (i, 0)),
                pl.BlockSpec((tile, 3), lambda i: (i, 0)),
                pl.BlockSpec((tile,), lambda i: (i,)),
                pl.BlockSpec((tile,), lambda i: (i,)),
                pl.BlockSpec((tile, uniforms.shape[1], 2), lambda i: (i, 0, 0)),
                pl.BlockSpec((g, 5), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((tile,), lambda i: (i,)),
                pl.BlockSpec((tile,), lambda i: (i,)),
            ],
            out_shape=[
                sds((n,), jnp.float32, origins, dirs, t0, uniforms),
                sds((n,), jnp.int32, origins, dirs, t0, uniforms),
            ],
            interpret=interpret,
        )(origins, dirs, t0, t_exit, uniforms, blobs)

    return call(kernel, origins, dirs, t0, t_exit, uniforms, blobs, interpret=interpret)
