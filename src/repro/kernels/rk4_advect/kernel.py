"""Pallas kernel: RK4 particle advection for the streamlines app (§5.4).

One Runge-Kutta-4 update per particle per round ("each rank/GPU
independently performs an update step on each particle — one GPU thread per
particle").  The TPU mapping is one *lane* per particle: a (TILE, 3) block of
positions is advanced through the four stages entirely in registers/VMEM.

The velocity field is *procedural* (gather-free — the TPU-friendly choice):
  field 0: ABC (Arnold–Beltrami–Childress) flow — the classic streamline demo
  field 1: a swirling "tornado" column around the z axis
  field 2: Taylor–Green-like cellular vortex
Grid-sampled fields go through the XLA-gather path in the app instead; the
kernel covers the compute-bound analytic case (cf. DESIGN.md on TPU gather).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import call, sds

ABC, TORNADO, TAYLOR_GREEN = 0, 1, 2


def _velocity(p, field_id: int, params):
    x, y, z = p[:, 0], p[:, 1], p[:, 2]
    a, b, c = params
    if field_id == ABC:
        return jnp.stack(
            [a * jnp.sin(z) + c * jnp.cos(y),
             b * jnp.sin(x) + a * jnp.cos(z),
             c * jnp.sin(y) + b * jnp.cos(x)],
            axis=-1,
        )
    if field_id == TORNADO:
        r2 = x * x + y * y + 1e-3
        swirl = a / r2
        return jnp.stack([-y * swirl, x * swirl, b + c * jnp.sqrt(r2)], axis=-1)
    if field_id == TAYLOR_GREEN:
        return jnp.stack(
            [a * jnp.cos(x) * jnp.sin(y) * jnp.sin(z),
             -a * jnp.sin(x) * jnp.cos(y) * jnp.sin(z),
             c * jnp.sin(x) * jnp.sin(y) * jnp.cos(z)],
            axis=-1,
        )
    raise ValueError(f"unknown field {field_id}")


def _rk4_kernel(pos_ref, out_ref, vel_ref, *, dt, field_id, params):
    p = pos_ref[...]
    k1 = _velocity(p, field_id, params)
    k2 = _velocity(p + 0.5 * dt * k1, field_id, params)
    k3 = _velocity(p + 0.5 * dt * k2, field_id, params)
    k4 = _velocity(p + dt * k3, field_id, params)
    out_ref[...] = p + (dt / 6.0) * (k1 + 2.0 * k2 + 2.0 * k3 + k4)
    vel_ref[...] = k1


@functools.partial(jax.jit, static_argnames=("dt", "field_id", "params", "tile", "interpret"))
def rk4_step(
    pos: jax.Array,  # (N, 3)
    *,
    dt: float,
    field_id: int = ABC,
    params: tuple = (1.0, 0.8, 0.6),
    tile: int = 1024,
    interpret: bool = False,
):
    """One RK4 step. Returns (new_pos (N,3), velocity-at-pos (N,3))."""
    n = pos.shape[0]
    tile = min(tile, n)
    while n % tile:
        tile //= 2

    def kernel(pos):
        return pl.pallas_call(
            functools.partial(_rk4_kernel, dt=dt, field_id=field_id, params=params),
            grid=(n // tile,),
            in_specs=[pl.BlockSpec((tile, 3), lambda i: (i, 0))],
            out_specs=[
                pl.BlockSpec((tile, 3), lambda i: (i, 0)),
                pl.BlockSpec((tile, 3), lambda i: (i, 0)),
            ],
            out_shape=[
                sds((n, 3), jnp.float32, pos),
                sds((n, 3), jnp.float32, pos),
            ],
            interpret=interpret,
        )(pos)

    return call(kernel, pos, interpret=interpret)
