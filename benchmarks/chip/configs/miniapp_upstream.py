"""The upstream miniApp (``ingowald/rafi`` ``miniApp/miniApp.cu``) on R ranks.

One job is one burst: every rank seeds ``sizes[rank]`` rays ``{srcRank,
srcID}`` of 8 bytes, upstream's two hashes pick each ray's next rank, and
``run_until_done`` → ``forward_work`` drains the burst to global
termination.  The sizes of every burst a window may run are put on the
device in set-up, in one table; each burst reads its row by an index the
previous burst returned, so no transfer to the device sits between two
bursts (as upstream passes a burst's size to its seeding kernel).  The queue holds ``1000·128·R`` rows per rank, the exchange is
padded with one queue of slots per peer, and the library's defaults apply
otherwise.  Each burst's per-rank sizes come from ``--seed``
(:func:`jobs`, the traffic kind ``bursts``).

The process step also folds a hash of every delivery (source rank and id,
round, rank, position) into a per-rank uint32 sum, so a burst's answer,
``(rounds, deliveries, digest)``, is compared with the plain replay in
``miniapp_upstream_reference.py`` once the window has closed.
"""
from __future__ import annotations

import dataclasses
import gc
import itertools
from pathlib import Path
from typing import Any, Dict, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from harness import load_module

REFERENCE = Path(__file__).with_name("miniapp_upstream_reference.py")
AXIS = "data"
_MIX = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1)

# the sizes a CPU test replaces in configs/miniapp_upstream.json
SMALL = {"queue_rows_per_mesh_rank": 1024}

# name -> limit; every comparison is exact (PERF.md has the readings)
LIMITS = {
    "digest_off": 0,      # bursts whose delivery digest differs
    "deliveries_off": 0,  # sum over bursts of |deliveries - replay|
    "rounds_off": 0,      # bursts whose rounds to termination differ
    "drops": 0,           # rays the queues dropped
    "not_done": 0,        # bursts cut by max_rounds
}


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # any whole number, negative or past 64 bits, maps to one entropy word
    return np.random.default_rng([seed % (1 << 64), *stream])


def burst_sizes(mix: Dict[str, Any], seed: int, ranks: int) -> Iterator[np.ndarray]:
    """Per-rank seed counts of successive bursts: ``(lo + v) · rows`` with
    ``v`` in ``[0, spread)``, upstream's ``int(10 + 128·drand48()) · 128``.

    ``v`` is drawn stratified, so that every seed offers the same work in
    another order: each block of ``spread`` bursts of a rank holds every
    value once, in antithetic pairs ``(v, spread−1−v)`` whose order the seed
    shuffles, and ranks ``2k`` and ``2k+1`` take complementary values in
    every burst.  Any whole number of pairs of bursts then seeds the same
    rays on every seed."""
    lo, spread, rows = mix["seed_blocks_min"], mix["seed_blocks_spread"], mix["block_rows"]
    if spread % 2:
        raise ValueError("seed_blocks_spread must be even (antithetic pairs)")
    rngs = [_rng(seed, r) for r in range(0, ranks, 2)]

    def block(rng):
        pairs = rng.permutation(spread // 2)
        flip = rng.integers(0, 2, size=spread // 2)
        first = np.where(flip == 1, spread - 1 - pairs, pairs)
        return np.stack([first, spread - 1 - first], axis=1).reshape(-1)

    while True:
        lead = np.stack([block(g) for g in rngs], axis=1)  # (spread, ceil(R/2))
        vals = np.stack([lead, spread - 1 - lead], axis=2).reshape(spread, -1)[:, :ranks]
        for v in vals:
            yield ((lo + v) * rows).astype(np.int32)


def jobs(mix: Dict[str, Any], seed: int, ranks: int) -> List[np.ndarray]:
    """The window's bursts: the first ``staged_jobs`` of :func:`burst_sizes`.
    Raises ``ValueError`` for a traffic kind other than ``bursts``."""
    if mix["kind"] != "bursts":
        raise ValueError(f"traffic kind {mix['kind']!r}: the miniApp draws only 'bursts'")
    return list(itertools.islice(burst_sizes(mix, seed, ranks), mix["staged_jobs"]))


def _mix(src_rank, src_id, rnd, rank, tid):
    """The replay's per-delivery hash, in uint32 on the device."""
    u = lambda x: jnp.asarray(x).astype(jnp.uint32)  # noqa: E731
    h = jnp.zeros(jnp.shape(src_id), jnp.uint32)
    for w, m in zip((src_rank, src_id, rnd, rank, tid), _MIX):
        h = h + u(w) * jnp.uint32(m)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> 12)
    h = h * jnp.uint32(0x297A2D39)
    return h ^ (h >> 15)


def _program(spec, R):
    from repro.core import ForwardConfig, enqueue, make_queue, run_until_done, work_item

    @work_item
    @dataclasses.dataclass
    class Ray:
        src_rank: jax.Array  # () int32
        src_id: jax.Array    # () int32

    C = spec["queue_rows_per_mesh_rank"] * R
    cfg = ForwardConfig(AXIS, R, C, exchange=spec["exchange"], peer_capacity=C)
    proto = Ray(jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))

    def burst(sizes, i):
        n = sizes[i, 0]
        me = jax.lax.axis_index(AXIS)
        tid = jnp.arange(C, dtype=jnp.int32)
        dst = (123 + 13 * 17 * 23 * tid) % (2 * R)
        seeds = Ray(jnp.full((C,), me, jnp.int32), tid)
        q0 = enqueue(make_queue(proto, C), seeds, dst, (tid < n) & (dst < R))

        def process(q, aux, rnd):
            lane = jnp.arange(C, dtype=jnp.int32)
            valid = lane < q.count
            ray = q.items
            h = jnp.where(valid, _mix(ray.src_rank, ray.src_id, rnd, me, lane), 0)
            aux = (aux[0] + jnp.sum(h, dtype=jnp.uint32), aux[1] + q.count)
            dst = (1234 + (rnd + 1) * (13 + 17 * (ray.src_id + 23 * lane))) % (2 * R)
            out = enqueue(make_queue(proto, C), ray, dst, valid & (lane > 1) & (dst < R))
            return out, aux

        aux0 = (jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.int32))
        q, aux, rounds, done = run_until_done(
            process, q0, aux0, cfg, max_rounds=spec["max_rounds"]
        )
        digest = jax.lax.bitcast_convert_type(aux[0], jnp.int32)
        row = jnp.stack([rounds, done.astype(jnp.int32), q.drops, aux[1], digest])
        return row[None].astype(jnp.int32), i + 1

    return burst


class Deployment:
    def __init__(self, spec, mix, devices):
        from repro import compat

        self.spec = spec
        self.ranks = R = len(devices)
        if mix.get("ranks", R) != R:
            raise ValueError(f"traffic is offered by {mix['ranks']} ranks, the cell has {R}")
        mesh = compat.make_mesh((R,), (AXIS,), devices=devices)
        self._table = NamedSharding(mesh, P(None, AXIS))
        self._index = NamedSharding(mesh, P())
        self._fn = jax.jit(jax.shard_map(
            _program(spec, R), mesh=mesh, in_specs=(P(None, AXIS), P()),
            out_specs=(P(AXIS), P()),
        ))
        self._burst = None

    def warm(self, jobs):
        """Put the sizes of ``jobs``, the bursts in the order they will run,
        on the device, and compile (or load from the cache) the one burst
        program."""
        with TraceAnnotation("bench.seed"):
            self._sizes = jax.device_put(np.asarray(jobs, np.int32), self._table)
            self._next = jax.device_put(np.int32(0), self._index)
        self._burst = self._fn.lower(self._sizes, self._next).compile()

    def run(self, sizes):
        """The next burst of the staged ones, whose sizes are ``sizes``."""
        with TraceAnnotation("bench.dispatch"):
            out, self._next = self._burst(self._sizes, self._next)
        with TraceAnnotation("bench.readback"):
            rows = np.asarray(out)
        return {
            "sizes": np.asarray(sizes),
            "rounds": int(rows[:, 0].max()),
            "done": bool(rows[:, 1].all()),
            "drops": int(rows[:, 2].sum()),
            "deliveries": int(rows[:, 3].sum()),
            "digest": int(rows[:, 4].astype(np.uint32).astype(np.int64).sum() % (1 << 32)),
        }

    def release(self):
        self._burst = self._fn = self._sizes = self._next = None
        gc.collect()

    def _replay(self, records, stable=True):
        ref = load_module(REFERENCE)
        return [ref.burst(rec["sizes"], stable=stable) for rec in records]

    def check(self, records):
        """``(checks, failed)`` of every burst of the window against the
        replay; each check is ``(name, value, limit)``."""
        answers = [(r["rounds"], r["deliveries"], r["digest"]) for r in records]
        readings, bad = _compare(answers, self._replay(records))
        readings["drops"] = sum(r["drops"] for r in records)
        readings["not_done"] = sum(not r["done"] for r in records)
        bad |= {i for i, r in enumerate(records) if r["drops"] or not r["done"]}
        return [(k, readings[k], LIMITS[k]) for k in LIMITS], len(bad)

    def control(self, records):
        """The control's checks: the replay with each source's arrivals in
        reverse (the order guarantee broken) in the program's place."""
        readings, _ = _compare(self._replay(records, stable=False), self._replay(records))
        readings.update(drops=0, not_done=0)
        return [(k, readings[k], LIMITS[k]) for k in LIMITS]


def _compare(answers, replay):
    bad = set()
    readings = {"digest_off": 0, "deliveries_off": 0, "rounds_off": 0}
    for i, ((rounds, deliv, digest), (r_rounds, r_deliv, r_digest)) in enumerate(zip(answers, replay)):
        readings["digest_off"] += digest != r_digest
        readings["deliveries_off"] += abs(deliv - r_deliv)
        readings["rounds_off"] += rounds != r_rounds
        if (rounds, deliv, digest) != (r_rounds, r_deliv, r_digest):
            bad.add(i)
    return readings, bad
