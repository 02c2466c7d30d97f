"""``forwardRays()`` — the full RaFI §4.2 pipeline, on-device.

Per round, inside ``shard_map`` (so collectives bind to a real mesh axis):

  1. marshal plan (§4.2.1, ``core.sorting``) — one of two modes:
     ``marshal="sort"`` packs (dest, lane) keys, sorts them, and keeps only
     the *permutation* (the payload is not touched); ``marshal="scatter"``
     skips the sort entirely — one counting-sort pass over the destination
     vector yields each item's stable in-bucket rank plus the histogram
     (send counts for free), enough to place every row directly;
  2. pack the work-item pytree into ONE ``(capacity, words)`` uint32 buffer
     (``core.types.pack_payload`` — the paper's contiguous trivially-copyable
     ray on the wire);
  3. exchange (§4.2.2, ``core.exchange``): ONE count collective plus ONE
     payload collective move the packed buffer; the send-side marshal is ONE
     payload pass — a single gather composing the sort permutation with the
     send layout (sort mode), or a single scatter straight into the send
     layout at ``base[dest] + rank`` (scatter mode) — so each ray is read
     exactly once and written exactly once (§6.1) either way;
  4. wrap up (§4.2.3): the received buffer is unpacked back into the item
     pytree and becomes the next input queue, destinations reset to DISCARD,
     the emit counter resets, and a ``psum`` of received counts yields the
     *global* in-flight total for distributed termination.

The two marshal modes are bit-exact end to end (the scatter placement
reproduces the sort's lexicographic stable source order — property-tested in
``tests/test_core_scatter.py``); the sort path is kept as the oracle.

Beyond the paper: because sort, exchange and termination test are all traced
into one XLA program, a full multi-round computation runs under a single
``jax.lax.while_loop`` with zero host round-trips (the CUDA/MPI original
synchronises with the host every round to read back segment offsets).  And
where the original issues one RDMA per peer, the packed wire format means
the whole round is one collective regardless of how many leaves the item
type has.

Device scopes: the round runs under ``rafi.forward``; the marshal plan under
``rafi.plan``, each exchange stage under its own (``core.stages``), the
retain merge under ``rafi.merge`` and the in-flight ``psum`` under
``rafi.termination``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core import exchange as X
from repro.core import sorting as S
from repro.core import types as T
from repro.core.health import remap_dest
from repro.core.queue import DISCARD, WorkQueue

__all__ = ["ForwardConfig", "credit_reserve_rows", "flatten_axis_names", "forward_work"]

_EXCHANGES = {
    "padded": X.exchange_padded,
    "ragged": X.exchange_ragged,
    "hierarchical": X.exchange_hierarchical,
    "onehot": X.exchange_onehot,
}


def flatten_axis_names(axis_name) -> Tuple[Any, ...]:
    """``axis_name`` as a flat tuple of plain mesh axis names.

    Hierarchical configs may group several mesh axes into one tier
    (``axis_name=(("pod", "node"), "device")``); collectives that span the
    whole joint axis (``psum``/``all_gather``/``axis_index``) need the
    flattened form.
    """
    if not isinstance(axis_name, (tuple, list)):
        return (axis_name,)
    out = []
    for a in axis_name:
        out.extend(a if isinstance(a, (tuple, list)) else (a,))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class ForwardConfig:
    """Static configuration of a forwarding context.

    Attributes:
      axis_name: mesh axis (or tuple of axes) the queue is distributed over.
        The hierarchical exchange takes a tuple of ≥2 tiers ordered slowest
        fabric first — e.g. ``("node", "device")`` or ``("pod", "node",
        "device")``; an entry may itself be a tuple of mesh axes treated as
        one joint tier.  Every other backend accepts a single axis or a tuple
        treated as one joint flat axis.
      num_ranks: number of shards on that axis (R).
      capacity: per-rank queue capacity (paper: ``resizeRayQueues(N)``).
      peer_capacity: padded exchange only — per-peer slot rows for the send
        buffer (default 2·ceil(C/R): the flat fan-out is R per-rank slots).
        For hierarchical configs this field mirrors ``level_capacities[-1]``
        (the fastest tier) and may be passed as a legacy alias for it.
      level_sizes: hierarchical only — ranks per mesh tier, slowest first;
        must multiply to ``num_ranks``.  For 2-level configs it may be given
        via the legacy ``fast_size`` alias instead.
      level_capacities: hierarchical only — stage-``l`` padded rows per peer
        segment on tier ``l`` (default 2·ceil(C/level_sizes[l]) each: the
        tier-``l`` fan-out is ``level_sizes[l]`` aggregated segments).
      fast_size: legacy 2-level alias, mirrors ``level_sizes[-1]``.
      node_capacity: legacy 2-level alias, mirrors ``level_capacities[0]``
        (the slowest tier's per-segment rows).
      exchange: "ragged" (TPU production) | "padded" (portable) |
        "hierarchical" (N-stage, N-D meshes) | "onehot" (test oracle).
      marshal: "sort" (§4.2.1 key sort + composed send gather — the
        bit-exactness oracle) | "scatter" (sort-free bucket scatter: one
        counting-sort pass over the destination vector, then packed rows are
        scattered straight into the send layout — one payload pass per round
        pre-collective).  The two modes place items identically.
      sort_method: "pack" (paper-faithful packed keys) | "argsort".  Only
        consulted by ``marshal="sort"`` (the scatter plan has no keys).
      use_pallas: route the marshal-plan and payload-pass kernels through
        Pallas (``kernels/sort_keys`` + ``kernels/marshal`` for the sort
        mode, ``kernels/bucket_scatter`` for the scatter mode).
      telemetry: record a ``repro.telemetry.RoundStats`` traffic snapshot per
        round (per-tier segment-demand histograms, max demand, per-stage §3.3
        clamp drops) from control-plane values the round already computes —
        zero additional collectives.  ``forward_work`` then returns the stats
        as a third output and ``run_until_done`` carries a ``StatsRing`` of
        the last ``telemetry_window`` rounds through its while-loop,
        returning it as a fourth output for ``repro.tune`` to re-plan
        capacities from.
      telemetry_window: rounds the on-device ring keeps (oldest overwritten).
      telemetry_buckets: demand-histogram buckets per tier; bucket B-1 is the
        at-or-above-capacity overflow bucket (see ``telemetry.bucket_width``).
      overflow: what a §3.3 capacity clamp does to the rows it cuts.
        ``"drop"`` (default) discards and counts them — the paper's literal
        contract and the bit-exact oracle.  ``"retain"`` keeps every row a
        sender- or tier-clamp would cut in the LOCAL queue with its ``dest``
        intact, to be retried next round (on hierarchical routes a row
        clamped at stage ``l`` stays resident at the intermediate rank it
        reached, where destination routing resumes it).  Retained lanes are
        compacted to the FRONT of the queue, so the marshal's stable
        source-order rank gives them FIFO oldest-first send-slot priority —
        a bounded-delay anti-starvation guarantee (the lossless law; see
        ROADMAP).  Retention is pure local compaction: the lowered
        collective inventory is bit-identical to ``"drop"`` (guarded in
        ``tests/test_collective_budget.py``).  The only remaining loss sites
        are receiver-side (arrivals beyond what the queue can admit next to
        the retained rows, and the onehot oracle's receiver clamp) — both
        still counted in ``drops``; size ``capacity`` at the §6.3 worst case
        to make them unreachable.
      pipeline_shards: micro-shard count S for software-pipelined forwarding
        (the overlap law; default 1 = the bulk-synchronous oracle).  The
        exchange's per-peer slot rows are split into S chunks whose
        marshal→counts→payload→unmarshal chains are issued interleaved, so
        an async-collective backend keeps shard k's payload collective in
        flight while shard k−1 unmarshals and shard k+1 marshals (on
        hierarchical routes, stage-l of shard k additionally overlaps
        stage-(l−1) of shard k+1).  Placement is bit-exact with S=1 and
        payload wire bytes are conserved; the collective inventory becomes
        S payload + S count collectives per mesh axis.  Must divide the
        queue capacity (and each per-tier slot budget); the bulk-synchronous
        backends without a slot dimension — the onehot oracle and ring
        cycling — reject S > 1.
      flow: wire admission policy — the backpressure law.  ``"open"``
        (default) ships every clamped segment regardless of receiver state:
        the §3.3 contract and the bit-exactness oracle.  ``"credit"`` makes
        each receiver advertise its free queue space on the count collective
        the round already runs (the count ``all_to_all`` widens from one i32
        column to two — nothing payload-sized, so the budget law's
        collective inventory is unchanged), and senders spend wire ONLY on
        rows the advertised credit admits: one-round-stale credits are
        apportioned deterministically across the R contending senders
        (floor share + rank-ordered residual, so an incast can never
        overshoot the receiver), and the un-credited tail of each
        destination segment is held locally through the ``overflow="retain"``
        spill/compaction machinery — which credit mode therefore requires —
        instead of being shipped and bounced.  On hierarchical routes each
        tier advertises its own aggregated headroom, so a saturated node
        throttles the slow-fabric stage, not just the last hop.  Credits ride
        the drive's while-loop carry (``forward_work`` takes ``credits=`` and
        returns ``credits_out``); the onehot oracle has no sender clamp to
        gate and rejects credit flow.
      emit_reserve: credit mode only — receive-queue rows each advertisement
        WITHHOLDS for the rank's own next-round emissions (``-1``, the
        default, resolves to ``capacity // 2``).  The drive's emission gate
        hands the app exactly this budget back as per-round headroom, so
        retained backlog + gated emissions + advertised credits never exceed
        ``capacity``: granted arrivals always fit and the flat credit path
        is receiver-drop-free by construction (hierarchical adverts are
        min-aggregated and tier-stale — bounded, counted overshoot).
    """

    axis_name: Any
    num_ranks: int
    capacity: int
    peer_capacity: int = 0
    exchange: str = "padded"
    marshal: str = "sort"
    sort_method: str = "pack"
    use_pallas: bool = False
    fast_size: int = 0
    node_capacity: int = 0
    level_sizes: Tuple[int, ...] = ()
    level_capacities: Tuple[int, ...] = ()
    telemetry: bool = False
    telemetry_window: int = 16
    telemetry_buckets: int = 8
    overflow: str = "drop"
    pipeline_shards: int = 1
    flow: str = "open"
    emit_reserve: int = -1

    def __post_init__(self):
        if self.exchange not in _EXCHANGES:
            raise ValueError(f"unknown exchange {self.exchange!r}")
        if self.overflow not in ("drop", "retain"):
            raise ValueError(
                f"unknown overflow {self.overflow!r} (expected 'drop' — the "
                "§3.3 oracle — or 'retain': spill-and-retry, the lossless law)"
            )
        if self.flow not in ("open", "credit"):
            raise ValueError(
                f"unknown flow {self.flow!r} (expected 'open' — ship every "
                "clamped segment, the §3.3 oracle — or 'credit': "
                "receiver-advertised admission, the backpressure law)"
            )
        if self.flow == "credit" and self.overflow != "retain":
            raise ValueError(
                "flow='credit' requires overflow='retain': the un-credited "
                "tail of each destination segment is held locally through "
                "the retain spill/compaction machinery — with overflow="
                "'drop' the credit gate would convert backpressure into "
                "silent sender-side loss"
            )
        if self.flow == "credit" and self.exchange == "onehot":
            raise ValueError(
                "flow='credit' is not supported by exchange='onehot': the "
                "all-gather oracle ships whole queues (no per-destination "
                "sender clamp exists for a credit gate to tighten)"
            )
        if self.emit_reserve != -1 and not (
            0 <= self.emit_reserve < self.capacity
        ):
            raise ValueError(
                f"emit_reserve ({self.emit_reserve}) must be -1 (auto: "
                f"capacity // 2) or in [0, capacity) — reserving the whole "
                "queue would advertise zero credit forever"
            )
        if self.marshal not in ("sort", "scatter"):
            raise ValueError(f"unknown marshal {self.marshal!r}")
        if self.sort_method not in ("pack", "argsort"):
            raise ValueError(f"unknown sort_method {self.sort_method!r}")
        if self.telemetry_window < 1:
            raise ValueError(
                f"telemetry_window ({self.telemetry_window}) must be >= 1"
            )
        if self.telemetry_buckets < 2:
            raise ValueError(
                f"telemetry_buckets ({self.telemetry_buckets}) must be >= 2 "
                "(bucket B-1 is the at-capacity overflow bucket)"
            )
        if self.num_ranks <= 0 or self.capacity <= 0:
            raise ValueError(
                f"num_ranks ({self.num_ranks}) and capacity ({self.capacity}) "
                "must be positive"
            )
        if self.pipeline_shards < 1:
            raise ValueError(
                f"pipeline_shards ({self.pipeline_shards}) must be >= 1 "
                "(1 = the bulk-synchronous round)"
            )
        if self.capacity % self.pipeline_shards:
            raise ValueError(
                f"pipeline_shards ({self.pipeline_shards}) must divide the "
                f"queue capacity ({self.capacity}) so every micro-shard "
                "covers an equal slice of the wavefront"
            )
        if self.pipeline_shards > 1 and self.exchange == "onehot":
            raise ValueError(
                "pipeline_shards > 1 is not supported by exchange='onehot': "
                "the all-gather oracle is bulk-synchronous by design (whole "
                "queues ship at once — no per-peer slot rows to micro-shard)"
            )
        if self.exchange == "hierarchical":
            self._init_hierarchical()
            return
        # Flat backends ignore the hierarchical fields; passing them is a
        # config bug (the caller expects topology routing they won't get).
        for field in ("fast_size", "node_capacity", "level_sizes", "level_capacities"):
            if getattr(self, field):  # 0 and () are both falsy
                raise ValueError(
                    f"{field} only applies to exchange='hierarchical'; the "
                    f"{self.exchange!r} exchange routes over one flat axis "
                    "and would silently ignore it"
                )
        if self.exchange == "padded":
            if self.peer_capacity <= 0:
                # flat fan-out: R per-rank slots
                object.__setattr__(
                    self, "peer_capacity",
                    max(1, -(-self.capacity // self.num_ranks) * 2),
                )
            if self.peer_capacity % self.pipeline_shards:
                raise ValueError(
                    f"pipeline_shards ({self.pipeline_shards}) must divide "
                    f"peer_capacity ({self.peer_capacity}): micro-shards are "
                    "equal slices of the per-peer slot rows"
                )
        elif self.peer_capacity:
            # ragged segments are contiguous (no slots); onehot gathers all
            raise ValueError(
                f"peer_capacity does not apply to exchange={self.exchange!r} "
                "(no padded per-peer slots exist there) and would be "
                "silently ignored"
            )

    def _init_hierarchical(self):
        n_axes = (
            len(self.axis_name)
            if isinstance(self.axis_name, (tuple, list))
            else 1
        )
        if n_axes < 2:
            raise ValueError(
                "hierarchical exchange routes over a multi-tier mesh and "
                "needs axis_name=(slowest, …, fastest), e.g. "
                f"('node', 'device'); got {self.axis_name!r} ({n_axes} axis)"
            )
        sizes = tuple(int(a) for a in self.level_sizes)
        if sizes:
            if len(sizes) != n_axes:
                raise ValueError(
                    f"level_sizes {sizes} must give one rank count per "
                    f"axis_name tier ({n_axes} tiers: {self.axis_name!r})"
                )
            prod = 1
            for a in sizes:
                if a < 1:
                    raise ValueError(f"level_sizes entries must be >= 1, got {sizes}")
                prod *= a
            if prod != self.num_ranks:
                raise ValueError(
                    f"level_sizes {sizes} multiply to {prod}, not num_ranks "
                    f"{self.num_ranks}"
                )
            if self.fast_size and self.fast_size != sizes[-1]:
                raise ValueError(
                    f"fast_size {self.fast_size} contradicts level_sizes "
                    f"{sizes} (it aliases the fastest tier, {sizes[-1]})"
                )
        else:
            if n_axes != 2:
                raise ValueError(
                    f"a {n_axes}-level hierarchical exchange needs "
                    "level_sizes=(slowest, …, fastest) — fast_size alone only "
                    "determines a 2-level (slow, fast) split"
                )
            if self.fast_size <= 0:
                raise ValueError(
                    "hierarchical exchange needs level_sizes (or the 2-level "
                    "fast_size alias: the number of ranks on the fast mesh axis)"
                )
            if self.num_ranks % self.fast_size:
                raise ValueError(
                    f"fast_size {self.fast_size} must divide num_ranks "
                    f"{self.num_ranks} (ranks are node-major over (slow, fast))"
                )
            sizes = (self.num_ranks // self.fast_size, self.fast_size)

        caps = tuple(int(c) for c in self.level_capacities)
        if caps and len(caps) != len(sizes):
            raise ValueError(
                f"level_capacities {caps} must give one segment size per "
                f"tier ({len(sizes)} tiers)"
            )
        if not caps:
            # tier-l fan-out: level_sizes[l] aggregated segments, 2× headroom
            caps = tuple(max(1, -(-self.capacity // a) * 2) for a in sizes)
            if self.peer_capacity > 0:  # legacy alias: fastest tier
                caps = caps[:-1] + (self.peer_capacity,)
            if self.node_capacity > 0:  # legacy alias: slowest tier
                caps = (self.node_capacity,) + caps[1:]
        else:
            if any(c < 1 for c in caps):
                raise ValueError(f"level_capacities entries must be >= 1, got {caps}")
            if self.peer_capacity and self.peer_capacity != caps[-1]:
                raise ValueError(
                    f"peer_capacity {self.peer_capacity} contradicts "
                    f"level_capacities {caps} (it aliases the fastest tier)"
                )
            if self.node_capacity and self.node_capacity != caps[0]:
                raise ValueError(
                    f"node_capacity {self.node_capacity} contradicts "
                    f"level_capacities {caps} (it aliases the slowest tier)"
                )
        if any(c % self.pipeline_shards for c in caps):
            raise ValueError(
                f"pipeline_shards ({self.pipeline_shards}) must divide every "
                f"level_capacities entry ({caps}): micro-shards are equal "
                "slices of each tier's per-segment slot rows"
            )
        object.__setattr__(self, "level_sizes", sizes)
        object.__setattr__(self, "level_capacities", caps)
        # keep the legacy aliases live so 2-level callers read either form
        object.__setattr__(self, "fast_size", sizes[-1])
        object.__setattr__(self, "peer_capacity", caps[-1])
        object.__setattr__(self, "node_capacity", caps[0])


def credit_reserve_rows(cfg: ForwardConfig) -> int:
    """Resolved ``emit_reserve``: receive rows every credit advertisement
    withholds for the rank's own emissions (the drive's per-round emission
    headroom).  ``-1`` auto-sizes to half the queue."""
    return cfg.capacity // 2 if cfg.emit_reserve < 0 else cfg.emit_reserve


@jax.named_scope("rafi.plan")
def _plan(q: WorkQueue, cfg: ForwardConfig, health):
    """The marshal plan of one round: the health remap, the destination sort
    or ``destination_rank`` with its histogram, and the packed payload.
    Returns ``(q, perm, dest_clean, dest_rank, send_counts, packed, spec)``."""
    R = cfg.num_ranks
    if health is not None:
        q = dataclasses.replace(q, dest=remap_dest(q.dest, health))
    perm = dest_clean = dest_rank = None
    if cfg.marshal == "scatter":
        # Sort-free bucket plan: ONE counting-sort pass over the (cheap,
        # 1-word-per-item) destination vector yields the sanitized dest, each
        # item's stable in-bucket rank, and the histogram — the send counts
        # fall out for free and every exchange stage derives its layout from
        # them (no keys, no sort, no per-tier boundary detection).  Works for
        # flat AND hierarchical routes: ranks are lexicographic in the tier
        # digits, so in-bucket rank against the full destination IS the
        # in-sub-segment rank at every tier.
        if cfg.use_pallas:
            from repro.kernels.bucket_scatter import ops as bs_ops

            dest_clean, dest_rank, hist = bs_ops.rank_and_histogram(
                q.dest, q.count, num_ranks=R
            )
        else:
            dest_clean, dest_rank, hist = S.destination_rank(q.dest, q.count, R)
        send_counts = hist[:R]
    elif cfg.exchange == "hierarchical":
        # Lexicographic N-level keys: ONE sort yields every stage permutation.
        # The Pallas path is routed explicitly through kernels/sort_keys (the
        # flat packed key sorts identically because ranks are lexicographic
        # in the tier digits) — it must never silently fall back to the flat
        # branch below, which would skip the level-shaped count tensor.
        if cfg.use_pallas:
            from repro.kernels.sort_keys import ops as sk_ops

            perm, count_tensor = sk_ops.sort_permutation_hierarchical(
                q.dest, q.count, cfg.level_sizes
            )
        else:
            perm, count_tensor = S.sort_permutation_hierarchical(
                q.dest, q.count, cfg.level_sizes, method=cfg.sort_method
            )
        send_counts = count_tensor.reshape(-1)
    elif cfg.use_pallas:
        from repro.kernels.sort_keys import ops as sk_ops

        perm, sorted_dest, send_counts = sk_ops.sort_permutation(q.dest, q.count, R)
        send_counts = send_counts[:R]
        del sorted_dest  # segments are fully described by the histogram
    else:
        perm, sorted_dest, send_counts = S.sort_permutation(
            q.dest, q.count, R, method=cfg.sort_method
        )
        send_counts = send_counts[:R]
        del sorted_dest

    packed, spec = T.pack_payload(q.items)  # (C, W) uint32 — the wire format
    return q, perm, dest_clean, dest_rank, send_counts, packed, spec


@jax.named_scope("rafi.merge")
def _merge_spill(pending, recv_packed, C: int):
    """Select the exchange's spill blocks into the front of the next queue,
    arrivals behind them.  Returns ``(merged, dest_out, age_out, ret_count,
    spill_over)``."""
    lane = jnp.arange(C, dtype=jnp.int32)
    run = jnp.zeros((), jnp.int32)
    for entry in pending:
        run = run + entry[-1].astype(jnp.int32)
    ret_count = jnp.minimum(run, C)
    spill_over = run - ret_count

    if len(pending) == 1:
        # Flat exchanges: one block at offset 0 — a single select, no
        # index arithmetic at all.
        rows_e, dest_e, age_e, n_e = pending[0]
        sel = lane < n_e
        merged = jnp.where(sel[:, None], rows_e, recv_packed)
        dest_out = jnp.where(sel, dest_e, DISCARD)
        age_out = jnp.where(sel, age_e, 0)
    else:
        # Multi-stage routes: index into the VIRTUAL concatenation
        # [block_0 | block_1 | … | arrivals] with one payload gather
        # instead of a per-block gather+select chain — the lane→source
        # map is all (C,) integer math, so the payload-scale op count
        # stays flat in the number of stages.
        sizes = [r.shape[0] for r, _, _, _ in pending]
        src = lane + sum(sizes)  # default: the arrivals region
        start = jnp.zeros((), jnp.int32)
        off = 0
        for (rows_e, _, _, n_e), sz in zip(pending, sizes):
            sel = (lane >= start) & (lane < start + n_e)
            src = jnp.where(sel, off + lane - start, src)
            start = start + n_e.astype(jnp.int32)
            off += sz
        merged = jnp.take(
            jnp.concatenate([r for r, _, _, _ in pending] + [recv_packed]),
            src,
            axis=0,
        )
        dest_out = jnp.take(
            jnp.concatenate(
                [d for _, d, _, _ in pending]
                + [jnp.full((C,), DISCARD, jnp.int32)]
            ),
            src,
        )
        age_out = jnp.take(
            jnp.concatenate(
                [a for _, _, a, _ in pending] + [jnp.zeros((C,), jnp.int32)]
            ),
            src,
        )
    return merged, dest_out, age_out, ret_count, spill_over


@jax.named_scope("rafi.termination")
def _global_in_flight(q: WorkQueue, cfg: ForwardConfig):
    # §4.2.3: "a final MPI reduce-add on the number of rays received" — the
    # global in-flight total for distributed termination.
    return jax.lax.psum(q.count, flatten_axis_names(cfg.axis_name))


@jax.named_scope("rafi.forward")
def forward_work(
    q: WorkQueue, cfg: ForwardConfig, *, age=None, health=None, credits=None
):
    """One collective forwarding round. Must run inside ``shard_map``.

    Returns ``(new_queue, total_in_flight)`` where ``total_in_flight`` is the
    paper's §4.2.3 global reduce — the number of items alive across *all*
    ranks after the exchange, used for distributed-termination detection.
    With ``cfg.telemetry`` the round's ``RoundStats`` snapshot rides along as
    a third output (``(new_queue, total, stats)``) — the arity is static in
    the config, so traced callers thread it without cost.

    With ``cfg.overflow == "retain"`` the returns become
    ``(new_queue, total, age_out[, stats])``: clamp-cut rows come back
    compacted to the FRONT of ``new_queue`` with their ``dest`` intact
    (arrivals fill in behind, dest reset to DISCARD as usual), ``total``
    counts retained rows so termination can't fire with spilled work, and
    ``age_out`` is the per-lane rounds-waiting counter (feed it back via
    ``age=`` on the next call; ``None`` means all lanes are fresh).  Arrivals
    that don't fit next to the retained rows are the one remaining loss site
    — counted into ``drops``.

    With ``cfg.flow == "credit"`` the returns grow ``credits_out`` after
    ``age_out`` (``(new_queue, total, age_out, credits_out[, stats])``):
    ``credits_out[d]`` is destination ``d``'s free-space advertisement
    received on this round's count collective, to be fed back via
    ``credits=`` on the next call so the sender clamp spends wire only on
    admissible rows.  ``credits=None`` means every receiver starts fully
    credited (``capacity`` each) — the uncontended single-shot assumption
    (benchmarks, examples).  The termination drive instead cold-starts its
    carried credits at ZERO — the first round is advert-only, so no wire is
    risked before any receiver has spoken (see ``drive_start``).

    ``health`` (optional ``(R,) bool``, replicated) drains sick ranks: every
    destination on an unhealthy rank is re-addressed pre-marshal through the
    pure local ``core.health.remap_dest`` law, so unhealthy ranks receive
    nothing while the collective inventory stays bit-identical to the plain
    round (retained rows keep the REMAPPED destination — once re-addressed,
    a row stays re-addressed).  ``None`` and an all-healthy mask are
    bit-identical.
    """
    R = cfg.num_ranks
    retain = cfg.overflow == "retain"
    q, perm, dest_clean, dest_rank, send_counts, packed, spec = _plan(q, cfg, health)

    kwargs = dict(
        axis_name=cfg.axis_name,
        num_ranks=R,
        capacity=cfg.capacity,
        use_pallas=cfg.use_pallas,
        marshal=cfg.marshal,
        dest_clean=dest_clean,
        dest_rank=dest_rank,
        telemetry=cfg.telemetry,
        telemetry_buckets=cfg.telemetry_buckets,
        pipeline_shards=cfg.pipeline_shards,
    )
    if cfg.exchange == "hierarchical":
        kwargs.update(
            level_sizes=cfg.level_sizes, level_capacities=cfg.level_capacities
        )
    else:
        kwargs.update(peer_capacity=cfg.peer_capacity)
    if retain:
        if age is None:
            age = jnp.zeros((cfg.capacity,), jnp.int32)
        kwargs.update(overflow="retain", age=age)
    credit = cfg.flow == "credit"
    if credit:
        if credits is None:
            # single-shot call: assume uncontended, fully credited receivers
            credits = jnp.full((R,), cfg.capacity, jnp.int32)
        kwargs.update(
            flow="credit", credits=credits,
            credit_reserve=credit_reserve_rows(cfg),
        )
    fn = _EXCHANGES[cfg.exchange]
    stats = pending = credits_out = None
    res = fn(packed, perm, send_counts, **kwargs)
    if credit and cfg.telemetry:
        recv_packed, recv_counts, new_count, drops, pending, credits_out, stats = res
    elif credit:
        recv_packed, recv_counts, new_count, drops, pending, credits_out = res
    elif retain and cfg.telemetry:
        recv_packed, recv_counts, new_count, drops, pending, stats = res
    elif retain:
        recv_packed, recv_counts, new_count, drops, pending = res
    elif cfg.telemetry:
        recv_packed, recv_counts, new_count, drops, stats = res
    else:
        recv_packed, recv_counts, new_count, drops = res
    del recv_counts

    if retain:
        # Merge: retained lanes FIRST (their dest survives), arrivals behind
        # (dest reset to DISCARD).  Pure local compaction — zero collectives.
        # The exchange did the heavy lifting in-pass: each clamp site hands
        # back its cut rows as an already-compacted spill block (rows, dest,
        # age, n) — segment tails read with the send gather's own positional
        # arithmetic — and the receive compaction has already landed the
        # arrivals BEHIND the reserved spill front.  All that's left here is
        # selecting each block into its slice of the front (stable
        # block-then-row order = FIFO oldest-first).  Measured on the 8-way
        # shard_map CPU benchmark the round is dispatch-bound (op count, not
        # bytes), so the few selects below — and no lax.cond, whose fixed
        # thunk cost alone breaks the happy-path budget — are what keeps
        # retention free when nothing spills.  Arrivals that didn't fit next
        # to the spill were counted by the exchange; a spill past C
        # (unreachable when capacity bounds the resident population) is
        # counted here as spill_over.
        merged, dest_out, age_out, ret_count, spill_over = _merge_spill(
            pending, recv_packed, cfg.capacity
        )
        new_q = WorkQueue(
            items=T.unpack_payload(merged, spec),
            dest=dest_out,
            count=(ret_count + new_count).astype(jnp.int32),
            drops=q.drops + drops.astype(jnp.int32) + spill_over,
        )
        total = _global_in_flight(new_q, cfg)
        if cfg.telemetry:
            stats = dataclasses.replace(
                stats,
                retained_rows=ret_count,
                age_max=jnp.max(age_out).astype(jnp.int32),
            )
            if credit:
                return new_q, total, age_out, credits_out, stats
            return new_q, total, age_out, stats
        if credit:
            return new_q, total, age_out, credits_out
        return new_q, total, age_out

    new_q = WorkQueue(
        items=T.unpack_payload(recv_packed, spec),
        dest=jnp.full((cfg.capacity,), DISCARD, jnp.int32),
        count=new_count.astype(jnp.int32),
        drops=q.drops + drops.astype(jnp.int32),
    )
    total = _global_in_flight(new_q, cfg)
    if cfg.telemetry:
        return new_q, total, stats
    return new_q, total
