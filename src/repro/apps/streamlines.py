"""rafi/StreamLines — data-parallel particle advection (§5.4).

Round-based structure exactly as the paper describes: each rank advances the
particles that currently overlap its spatial domain by one RK4 step (the
Pallas ``rk4_advect`` kernel — "one GPU thread per particle" becomes one
vector lane per particle), records the new position into the particle's
trace, then determines the destination rank by projecting the position onto
the partition ("if the space partitioning uses a grid, the neighboring rank
is found by projecting the position onto the grid") and calls
``emitOutgoing(P, destination)``.  ``forward_work`` plays ``forwardRays()``;
termination is the paper's distributed criterion (no particles anywhere, or
per-particle step budget exhausted).

The "ray type" is the paper's particle verbatim: a unique ID (so we can
track them across ranks) plus position — we add the per-particle step count.

Domain: [0, 2π]³ with an ABC / tornado / Taylor-Green analytic field; slab
partition along x.  Because a particle's trajectory depends only on its own
position, an R-rank run reproduces the R=1 trajectories bitwise.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import (
    DISCARD,
    ForwardConfig,
    enqueue,
    make_queue,
    run_until_done,
    work_item,
)
from repro.kernels.rk4_advect import ops as rk4

AXIS = "data"
TWO_PI = 2.0 * np.pi


@work_item
@dataclasses.dataclass
class Particle:
    """§5.4: 'a unique ID … and a 3D position (float3)' (+ step counter)."""

    uid: jax.Array    # () i32
    pos: jax.Array    # (3,) f32
    steps: jax.Array  # () i32


def _proto():
    return Particle(jnp.zeros((), jnp.int32), jnp.zeros(3), jnp.zeros((), jnp.int32))


@dataclasses.dataclass(frozen=True)
class StreamlineConfig:
    num_particles: int = 64
    max_steps: int = 128
    dt: float = 0.1
    field_id: int = rk4.ABC
    params: tuple = (1.0, 0.8, 0.6)
    seed: int = 0


def _owner(x, num_ranks):
    return jnp.clip(
        (x / (TWO_PI / num_ranks)).astype(jnp.int32), 0, num_ranks - 1
    )


def _inside(p):
    return jnp.all((p >= 0.0) & (p <= TWO_PI), axis=-1)


def run(
    mesh, cfg: StreamlineConfig = StreamlineConfig(), *, exchange: str = "padded",
    use_pallas_rk4: bool = True,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Advect. Returns (traces (N, max_steps+1, 3) with NaN padding,
    lengths (N,), stats)."""
    R = mesh.shape[AXIS]
    n = cfg.num_particles
    cap = max(64, n)
    # peer slots only exist for the padded exchange (ragged/onehot reject it)
    slots = {"peer_capacity": cap} if exchange == "padded" else {}
    fcfg = ForwardConfig(AXIS, R, cap, exchange=exchange, **slots)

    def step_kernel(pos):
        if use_pallas_rk4:
            new_pos, _ = rk4.rk4_step(
                pos, dt=cfg.dt, field_id=cfg.field_id, params=cfg.params
            )
            return new_pos
        from repro.kernels.rk4_advect import ref

        new_pos, _ = ref.rk4_step(pos, dt=cfg.dt, field_id=cfg.field_id, params=cfg.params)
        return new_pos

    def round_fn(q_in, traces, rnd):
        p = q_in.items
        lane = jnp.arange(cap)
        valid = lane < q_in.count
        new_pos = step_kernel(p.pos)
        steps = p.steps + 1
        # record: traces[uid, steps] = new_pos  (uids are globally unique;
        # invalid lanes scatter to index n which mode="drop" discards)
        uid_idx = jnp.where(valid, p.uid, traces.shape[0])
        traces = traces.at[uid_idx, steps].set(new_pos, mode="drop")
        alive = valid & _inside(new_pos) & (steps < cfg.max_steps)
        dest = jnp.where(alive, _owner(new_pos[:, 0], R), DISCARD).astype(jnp.int32)
        out = make_queue(_proto(), cap)
        out = enqueue(out, Particle(uid=p.uid, pos=new_pos, steps=steps), dest, valid)
        return out, traces

    def drive(_x):
        me = jax.lax.axis_index(AXIS)
        key = jax.random.PRNGKey(cfg.seed)
        seeds = jax.random.uniform(key, (n, 3), minval=0.5, maxval=TWO_PI - 0.5)
        uid = jnp.arange(n, dtype=jnp.int32)
        traces = jnp.full((n, cfg.max_steps + 1, 3), jnp.nan)
        # every rank computes all seeds (cheap, deterministic) but only emits
        # the ones it owns — the §5.1 ray-gen pattern applied to particles.
        mine = _owner(seeds[:, 0], R) == me
        traces = jnp.where(mine[:, None, None] & (jnp.arange(cfg.max_steps + 1) == 0)[None, :, None],
                           seeds[:, None, :], traces)
        q0 = make_queue(_proto(), cap)
        q0 = enqueue(
            q0,
            Particle(uid=uid, pos=seeds, steps=jnp.zeros(n, jnp.int32)),
            jnp.where(mine, me, DISCARD).astype(jnp.int32),
            jnp.ones(n, bool),
        )
        q, traces, rounds, _done = run_until_done(
            round_fn, q0, traces, fcfg, max_rounds=cfg.max_steps + 2
        )
        # traces are disjoint across ranks (NaN elsewhere) — merge via min
        merged = jax.lax.pmin(jnp.where(jnp.isnan(traces), jnp.inf, traces), AXIS)
        return merged, rounds[None], q.drops[None]

    f = jax.jit(jax.shard_map(drive, mesh=mesh, in_specs=P(AXIS),
                              out_specs=(P(), P(AXIS), P(AXIS))))
    merged, rounds, drops = f(jnp.arange(R, dtype=jnp.float32))
    traces = np.array(merged)
    traces[~np.isfinite(traces)] = np.nan
    lengths = np.sum(np.isfinite(traces[:, :, 0]), axis=1)
    return traces, lengths, {
        "rounds": int(np.max(np.asarray(rounds))),
        "drops": int(np.sum(np.asarray(drops))),
    }


def oracle(cfg: StreamlineConfig = StreamlineConfig()) -> np.ndarray:
    """Single-device direct integration (no forwarding) — the ground truth.

    Positions are padded to the distributed run's queue capacity so the RK4
    op sees the same lane shape (XLA's vectorized libm can differ by an ulp
    across shapes, which 60 RK4 steps would amplify) — bitwise comparability
    is part of the contract under test."""
    key = jax.random.PRNGKey(cfg.seed)
    n = cfg.num_particles
    cap = max(64, n)
    seeds = jax.random.uniform(key, (n, 3), minval=0.5, maxval=TWO_PI - 0.5)
    traces = np.full((n, cfg.max_steps + 1, 3), np.nan, np.float32)
    traces[:, 0] = np.asarray(seeds)
    pos = jnp.zeros((cap, 3)).at[:n].set(seeds)
    alive = np.ones(n, bool)
    for s in range(1, cfg.max_steps + 1):
        new_pos, _ = rk4.rk4_step(pos, dt=cfg.dt, field_id=cfg.field_id, params=cfg.params)
        npos = np.asarray(new_pos[:n])
        traces[alive, s] = npos[alive]
        inside = np.all((npos >= 0) & (npos <= TWO_PI), axis=-1)
        alive = alive & inside
        pos = new_pos
    return traces
