"""Pallas kernel: tiled O(N·M) pairwise gravity for the N-body app (§5.5).

Computes softened monopole accelerations of N target particles due to M
sources (sources = local particles ∪ received VirtualParticles).  Classic
two-level tiling: grid (N/TI, M/TJ) with the source loop innermost; the
accumulator lives in the revisited output block (sequential TPU grid ⇒
safe).  Targets run along lanes and sources along sublanes: the kernel sees
target coordinates as a ``(3, TI)`` block, sources as ``(TJ, 3)`` and masses
as ``(TJ, 1)``, so every pairwise quantity is one ``(TJ, TI)`` tile per
coordinate and the source sum is a sublane reduction.  The wrapper
transposes targets in and accelerations out.

VMEM per step: about six (TJ, TI) f32 tiles ≈ 1.5 MB at TI=TJ=256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import call, sds


def _forces_kernel(xi_ref, xj_ref, mj_ref, out_ref, *, eps2):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xi = xi_ref[...]  # (3, TI) targets, one coordinate per row
    xj = xj_ref[...]  # (TJ, 3) sources
    dx = [xj[:, k:k + 1] - xi[k:k + 1, :] for k in range(3)]  # (TJ, TI) each
    r2 = dx[0] * dx[0] + dx[1] * dx[1] + dx[2] * dx[2] + eps2
    inv = jax.lax.rsqrt(r2)
    w = mj_ref[...] * inv * inv * inv  # G·m_j / r³ (G folded in by caller)
    for k in range(3):
        out_ref[k:k + 1, :] += jnp.sum(w * dx[k], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("eps2", "ti", "tj", "interpret"))
def pairwise_accel(
    xi: jax.Array,  # (N, 3) targets
    xj: jax.Array,  # (M, 3) sources
    mj: jax.Array,  # (M,) source masses (zero mass ⇒ inert padding lane)
    *,
    eps2: float = 1e-4,
    ti: int = 256,
    tj: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """(N, 3) accelerations: a_i = Σ_j m_j (x_j − x_i) / (|x_j − x_i|² + ε²)^{3/2}."""
    n, m = xi.shape[0], xj.shape[0]
    ti = min(ti, n)
    while n % ti:
        ti //= 2
    tj = min(tj, m)
    while m % tj:
        tj //= 2
    grid = (n // ti, m // tj)
    xi_t = xi.T
    mj = mj.reshape(m, 1)

    def kernel(xi_t, xj, mj):
        return pl.pallas_call(
            functools.partial(_forces_kernel, eps2=eps2),
            grid=grid,
            in_specs=[
                pl.BlockSpec((3, ti), lambda i, j: (0, i)),
                pl.BlockSpec((tj, 3), lambda i, j: (j, 0)),
                pl.BlockSpec((tj, 1), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((3, ti), lambda i, j: (0, i)),
            out_shape=sds((3, n), jnp.float32, xi_t, xj, mj),
            interpret=interpret,
        )(xi_t, xj, mj)

    (out,) = call(kernel, xi_t, xj, mj, interpret=interpret)
    return out.T
