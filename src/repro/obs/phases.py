"""Per-phase device timing of one forwarding round — the stage-graph round
as a measurable timeline, for ANY backend.

Promoted from the padded-only ``benchmarks/run.py::_profile_phases`` (PR 8)
into the observation law's library half: each stage of the exchange
(``stages.Marshal`` / ``CountExchange`` / ``PayloadExchange`` /
``SpillExtract``+``Unmarshal``) is rebuilt as a STANDALONE jitted
``shard_map`` program over the same production primitives
(``exchange.padded_send_buffer``, ``exchange.exchange_counts``,
``exchange._a2a``, ``exchange._compact_blocks``, ``stages.padded_send_shard``,
``stages.compact_shard``, ``stages.ragged_control_plane``) and timed on its
own — the sum can exceed the fused round, which runs all phases in one XLA
program; the split shows WHERE the time goes.

Supported backends and the phase keys they produce (the
``fwd_profile_{tag}_{key}`` bench row names — STABLE since PR 8 for the flat
padded case):

* flat padded, ``pipeline_shards=1``:
  ``marshal`` / ``count_collective`` / ``payload_collective`` / ``unmarshal``
* flat padded, ``pipeline_shards=S>1``: the bulk four plus per-shard
  ``shard{k}_marshal`` / ``shard{k}_payload_collective`` /
  ``shard{k}_unmarshal`` (each shard's count collective ships the full
  vector, so there is exactly one ``count_collective`` key).
* hierarchical: per-tier ``tier{l}_marshal`` / ``tier{l}_count_collective``
  / ``tier{l}_payload_collective`` for every extent>1 tier ``l`` (slowest
  first, fastest runs first), plus the final ``unmarshal``.
* ragged: ``marshal`` / ``count_collective`` (the one-all_gather control
  plane) / ``payload_collective`` (requires ``lax.ragged_all_to_all`` —
  absent on this container's JAX, the key is skipped).

:func:`to_perfetto` lays the measured phase durations out as a merged
multi-rank timeline in Chrome/Perfetto ``trace_event`` JSON — one process
track per rank, one thread track per tier — composable with the host-side
``obs.trace`` span timeline (same track convention).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat

__all__ = ["profile_phases", "to_perfetto", "tier_of_phase"]


def _default_timeit(fn: Callable, *args, warmup: int = 2, iters: int = 5):
    """Median-of-iters wall time in us (the benchmarks harness passes its
    own ``_timeit`` so bench rows keep the established methodology)."""
    out = None
    for _ in range(warmup):
        out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2], out


def _fill_items(proto: Any, n_emit: int):
    """Generic work-item filler: lane-varying leaves of the proto's shapes
    (values don't matter for timing; lane-derived so nothing folds away)."""
    lane = jnp.arange(n_emit)

    def leaf(a):
        x = lane.astype(a.dtype if jnp.issubdtype(a.dtype, jnp.floating)
                        else jnp.int32).astype(a.dtype)
        return jnp.broadcast_to(
            x.reshape((n_emit,) + (1,) * a.ndim), (n_emit,) + a.shape
        )

    return jax.tree.map(leaf, proto)


def profile_phases(
    cfg: Any,
    mesh,
    *,
    n_emit: int,
    cap: int,
    proto: Any,
    timeit: Optional[Callable] = None,
) -> Dict[str, float]:
    """Time each stage of one ``cfg`` forwarding round standalone; returns
    ``{phase_key: us}`` (see module docstring for the key vocabulary)."""
    if timeit is None:
        timeit = _default_timeit
    if cfg.exchange == "padded":
        phases = _padded_phases(cfg, n_emit, cap, proto)
        if cfg.pipeline_shards > 1:
            phases += _pipelined_phases(cfg, n_emit, cap, proto)
    elif cfg.exchange == "hierarchical":
        phases = _hierarchical_phases(cfg, n_emit, cap, proto)
    elif cfg.exchange == "ragged":
        phases = _ragged_phases(cfg, n_emit, cap, proto)
    else:
        raise ValueError(
            f"profile_phases supports padded/hierarchical/ragged rounds, "
            f"got exchange={cfg.exchange!r}"
        )
    from repro.core.forwarding import flatten_axis_names

    axes = flatten_axis_names(cfg.axis_name)
    phase_us: Dict[str, float] = {}
    for key, kernel in phases:
        f = jax.jit(
            jax.shard_map(
                kernel, mesh=mesh, in_specs=P(axes), out_specs=P(axes)
            )
        )
        us, _ = timeit(f, jnp.arange(float(cfg.num_ranks)))
        phase_us[key] = us
    return phase_us


def _setup(cfg, n_emit, cap, proto):
    """Shared emission: a filled queue with a deterministic scattered
    destination pattern (same law as the PR-8 bench profiler)."""
    from repro.core import enqueue, make_queue

    R = cfg.num_ranks

    def setup(me):
        q = make_queue(proto, cap)
        lane = jnp.arange(n_emit)
        dest = ((me * 7 + lane * 131) % R).astype(jnp.int32)
        return enqueue(q, _fill_items(proto, n_emit), dest, jnp.ones(n_emit, bool))

    return setup


def _marshal_plan(cfg, q):
    """The send-side plan (sort or scatter), shared by every marshal phase."""
    from repro.core import sorting as S

    R = cfg.num_ranks
    if cfg.marshal == "scatter":
        d_clean, rank, hist = S.destination_rank(q.dest, q.count, R)
        return dict(perm=None, counts=hist[:R], dest_clean=d_clean,
                    dest_rank=rank)
    perm, _d, counts = S.sort_permutation(
        q.dest, q.count, R, method=cfg.sort_method
    )
    return dict(perm=perm, counts=counts[:R], dest_clean=None, dest_rank=None)


def _padded_phases(cfg, n_emit, cap, proto) -> Tuple:
    from repro.core import exchange as X
    from repro.core import types as T
    from repro.core.forwarding import flatten_axis_names

    R, slot = cfg.num_ranks, cfg.peer_capacity
    words = T.pack_spec(proto).total_words
    axes = flatten_axis_names(cfg.axis_name)
    setup = _setup(cfg, n_emit, cap, proto)

    def marshal_kernel(x):
        me = jax.lax.axis_index(axes)
        q = setup(me)
        packed, _spec = T.pack_payload(q.items)
        plan = _marshal_plan(cfg, q)
        send = X.padded_send_buffer(
            packed, plan["perm"], plan["counts"], num_ranks=R,
            peer_capacity=slot, marshal=cfg.marshal,
            dest_clean=plan["dest_clean"], dest_rank=plan["dest_rank"],
            use_pallas=cfg.use_pallas,
        )
        return jnp.sum(send, dtype=jnp.uint32)[None] + x[:1].astype(jnp.uint32) * 0

    def count_collective_kernel(x):
        me = jax.lax.axis_index(axes)
        counts = ((me + jnp.arange(R)) % jnp.int32(slot)).astype(jnp.int32)
        recv = X.exchange_counts(counts, cfg.axis_name)
        return jnp.sum(recv)[None] + x[:1].astype(jnp.int32) * 0

    def payload_collective_kernel(x):
        me = jax.lax.axis_index(axes)
        buf = (
            me.astype(jnp.uint32) + jnp.arange(R * slot * words, dtype=jnp.uint32)
        ).reshape(R, slot, words)
        recv = X._a2a(buf, cfg.axis_name)
        return jnp.sum(recv, dtype=jnp.uint32)[None] + x[:1].astype(jnp.uint32) * 0

    def unmarshal_kernel(x):
        me = jax.lax.axis_index(axes)
        buf = (
            me.astype(jnp.uint32) + jnp.arange(R * slot * words, dtype=jnp.uint32)
        ).reshape(R, slot, words)
        counts = jnp.minimum(
            ((me + jnp.arange(R)) % jnp.int32(slot)).astype(jnp.int32), cap // R
        )
        out, new_count, _drops = X._compact_blocks(
            buf, counts, cap, use_pallas=cfg.use_pallas
        )
        return jnp.sum(out, dtype=jnp.uint32)[None] + (
            new_count * 0 + x[:1].astype(jnp.int32) * 0
        ).astype(jnp.uint32)

    return (
        ("marshal", marshal_kernel),
        ("count_collective", count_collective_kernel),
        ("payload_collective", payload_collective_kernel),
        ("unmarshal", unmarshal_kernel),
    )


def _pipelined_phases(cfg, n_emit, cap, proto) -> Tuple:
    """Per-shard slices of the padded round (the overlap law's schedule):
    shard k marshals / ships / compacts slot rows [k·chunk, (k+1)·chunk),
    via the same ``stages.padded_send_shard`` / ``stages.compact_shard``
    primitives the pipelined exchange composes."""
    from repro.core import exchange as X
    from repro.core import stages as ST
    from repro.core import types as T
    from repro.core.forwarding import flatten_axis_names

    R, slot, S = cfg.num_ranks, cfg.peer_capacity, cfg.pipeline_shards
    words = T.pack_spec(proto).total_words
    axes = flatten_axis_names(cfg.axis_name)
    setup = _setup(cfg, n_emit, cap, proto)
    chunk = slot // S  # config law: pipeline_shards divides peer_capacity
    out = []
    for k in range(S):
        def marshal_shard(x, k=k):
            me = jax.lax.axis_index(axes)
            q = setup(me)
            packed, _spec = T.pack_payload(q.items)
            plan = _marshal_plan(cfg, q)
            send = ST.padded_send_shard(
                packed, plan["perm"], plan["counts"], num_ranks=R,
                peer_capacity=slot, shards=S, k=k,
                marshal=cfg.marshal, dest_clean=plan["dest_clean"],
                dest_rank=plan["dest_rank"], use_pallas=cfg.use_pallas,
            )
            return (jnp.sum(send, dtype=jnp.uint32)[None]
                    + x[:1].astype(jnp.uint32) * 0)

        def payload_shard(x):
            me = jax.lax.axis_index(axes)
            buf = (
                me.astype(jnp.uint32)
                + jnp.arange(R * chunk * words, dtype=jnp.uint32)
            ).reshape(R, chunk, words)
            recv = X._a2a(buf, cfg.axis_name)
            return (jnp.sum(recv, dtype=jnp.uint32)[None]
                    + x[:1].astype(jnp.uint32) * 0)

        def unmarshal_shard(x, k=k):
            me = jax.lax.axis_index(axes)
            buf = (
                me.astype(jnp.uint32)
                + jnp.arange(R * chunk * words, dtype=jnp.uint32)
            ).reshape(R, chunk, words)
            counts = jnp.minimum(
                ((me + jnp.arange(R)) % jnp.int32(slot)).astype(jnp.int32),
                cap // R,
            )
            acc = jnp.zeros((cap, words), jnp.uint32)
            out_q = ST.compact_shard(
                acc, buf, counts, cap, row_offset=k * chunk
            )
            return (jnp.sum(out_q, dtype=jnp.uint32)[None]
                    + x[:1].astype(jnp.uint32) * 0)

        out += [
            (f"shard{k}_marshal", marshal_shard),
            (f"shard{k}_payload_collective", payload_shard),
            (f"shard{k}_unmarshal", unmarshal_shard),
        ]
    return tuple(out)


def _hierarchical_phases(cfg, n_emit, cap, proto) -> Tuple:
    """Per-tier marshal/count/payload phases of the N-level route, each on
    its own mesh axis with that tier's (extent, segment-capacity) layout,
    plus the final receive-side compaction."""
    from repro.core import exchange as X
    from repro.core import types as T
    from repro.core.forwarding import flatten_axis_names

    level_sizes = tuple(int(a) for a in cfg.level_sizes)
    level_caps = tuple(int(c) for c in cfg.level_capacities)
    words = T.pack_spec(proto).total_words
    axes = flatten_axis_names(cfg.axis_name)
    out = []
    tiers = [l for l in reversed(range(len(level_sizes))) if level_sizes[l] > 1]
    for l in tiers:
        A, S = level_sizes[l], level_caps[l]
        ax = cfg.axis_name[l]

        def marshal_tier(x, A=A, S=S):
            # the tier's send-side pass: A sub-segments into (A, S) slots —
            # same primitive as the flat marshal at the tier's shape
            me = jax.lax.axis_index(axes)
            buf = (
                me.astype(jnp.uint32)
                + jnp.arange(max(n_emit, A * S) * words, dtype=jnp.uint32)
            ).reshape(max(n_emit, A * S), words)
            cnt = ((me + jnp.arange(A)) % jnp.int32(S)).astype(jnp.int32)
            send = X.padded_send_buffer(
                buf, jnp.arange(buf.shape[0], dtype=jnp.int32), cnt,
                num_ranks=A, peer_capacity=S, use_pallas=cfg.use_pallas,
            )
            return (jnp.sum(send, dtype=jnp.uint32)[None]
                    + x[:1].astype(jnp.uint32) * 0)

        def count_tier(x, A=A, S=S, ax=ax):
            me = jax.lax.axis_index(axes)
            counts = ((me + jnp.arange(A)) % jnp.int32(S)).astype(jnp.int32)
            recv = X.exchange_counts(counts, ax)
            return jnp.sum(recv)[None] + x[:1].astype(jnp.int32) * 0

        def payload_tier(x, A=A, S=S, ax=ax):
            me = jax.lax.axis_index(axes)
            buf = (
                me.astype(jnp.uint32)
                + jnp.arange(A * S * words, dtype=jnp.uint32)
            ).reshape(A, S, words)
            recv = X._a2a(buf, ax)
            return (jnp.sum(recv, dtype=jnp.uint32)[None]
                    + x[:1].astype(jnp.uint32) * 0)

        out += [
            (f"tier{l}_marshal", marshal_tier),
            (f"tier{l}_count_collective", count_tier),
            (f"tier{l}_payload_collective", payload_tier),
        ]
    A, S = level_sizes[tiers[-1]], level_caps[tiers[-1]]

    def unmarshal_kernel(x, A=A, S=S):
        me = jax.lax.axis_index(axes)
        buf = (
            me.astype(jnp.uint32) + jnp.arange(A * S * words, dtype=jnp.uint32)
        ).reshape(A, S, words)
        counts = jnp.minimum(
            ((me + jnp.arange(A)) % jnp.int32(S)).astype(jnp.int32), cap // A
        )
        out_q, new_count, _drops = X._compact_blocks(
            buf, counts, cap, use_pallas=cfg.use_pallas
        )
        return jnp.sum(out_q, dtype=jnp.uint32)[None] + (
            new_count * 0 + x[:1].astype(jnp.int32) * 0
        ).astype(jnp.uint32)

    out.append(("unmarshal", unmarshal_kernel))
    return tuple(out)


def _ragged_phases(cfg, n_emit, cap, proto) -> Tuple:
    from repro.core import exchange as X
    from repro.core import stages as ST
    from repro.core import types as T
    from repro.core.forwarding import flatten_axis_names

    R = cfg.num_ranks
    words = T.pack_spec(proto).total_words
    axes = flatten_axis_names(cfg.axis_name)
    setup = _setup(cfg, n_emit, cap, proto)

    def marshal_kernel(x):
        # ragged send side: the destination sort IS the marshal (rows ship
        # contiguously per segment, no slot padding)
        from repro.core import sorting as S

        me = jax.lax.axis_index(axes)
        q = setup(me)
        packed, _spec = T.pack_payload(q.items)
        perm, _d, _counts = S.sort_permutation(
            q.dest, q.count, R, method=cfg.sort_method
        )
        send = jnp.take(packed, perm, axis=0)
        return jnp.sum(send, dtype=jnp.uint32)[None] + x[:1].astype(jnp.uint32) * 0

    def count_collective_kernel(x):
        # the one-all_gather control plane: count matrix + replicated
        # per-rank ragged layout derivation (clamps, landing offsets)
        me = jax.lax.axis_index(axes)
        counts = ((me + jnp.arange(R)) % jnp.int32(max(n_emit // R, 1))).astype(
            jnp.int32
        )
        cnt = X.exchange_count_matrix(counts, cfg.axis_name)
        send_sizes, output_offsets, recv_sizes = ST.ragged_control_plane(
            cnt, me, cap
        )
        return (jnp.sum(send_sizes) + jnp.sum(output_offsets)
                + jnp.sum(recv_sizes))[None] + x[:1].astype(jnp.int32) * 0

    phases = [
        ("marshal", marshal_kernel),
        ("count_collective", count_collective_kernel),
    ]
    if compat.ragged_executes():
        def payload_collective_kernel(x):
            me = jax.lax.axis_index(axes)
            n = max(n_emit, R)
            buf = (
                me.astype(jnp.uint32) + jnp.arange(n * words, dtype=jnp.uint32)
            ).reshape(n, words)
            seg = jnp.full((R,), n // R, jnp.int32)
            off = jnp.cumsum(seg) - seg
            recv = jax.lax.ragged_all_to_all(
                buf, jnp.zeros_like(buf),
                input_offsets=off, send_sizes=seg,
                output_offsets=off, recv_sizes=seg,
                axis_name=cfg.axis_name,
            )
            return (jnp.sum(recv, dtype=jnp.uint32)[None]
                    + x[:1].astype(jnp.uint32) * 0)

        phases.append(("payload_collective", payload_collective_kernel))
    return tuple(phases)


# ----------------------------------------------------------- timeline view
def tier_of_phase(key: str) -> int:
    """Tier index encoded in a phase key (``tier2_marshal`` → 2; flat and
    shard keys → 0)."""
    if key.startswith("tier"):
        return int(key[4:].split("_", 1)[0])
    return 0


def to_perfetto(
    phase_us: Dict[str, float], *, num_ranks: int, tag: str = "round",
    t0_us: float = 0.0,
) -> Dict[str, Any]:
    """Measured phase durations → a merged multi-rank Perfetto timeline:
    every rank runs the same SPMD program, so each rank's process track
    (``pid = rank``) carries the phase sequence laid end to end, on the
    thread track of the phase's tier (``tid = tier``).  Compose with a host
    ``obs.trace`` export by concatenating ``traceEvents``."""
    from repro.obs import trace as OT

    events = []
    for rank in range(num_ranks):
        t = t0_us
        for key, us in phase_us.items():
            events.append({
                "name": f"{tag}:{key}", "cat": OT.CAT_PHASE, "ph": "X",
                "ts": t, "dur": float(us), "rank": rank,
                "tier": tier_of_phase(key), "args": {"us": float(us)},
            })
            t += float(us)
    return OT.to_perfetto(events)
