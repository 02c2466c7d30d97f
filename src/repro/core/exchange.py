"""Packed-payload exchange — the TPU adaptation of RaFI §4.2.2 (MPI_Alltoallv).

Wire format: the caller packs the whole work-item pytree into ONE
``(capacity, words)`` uint32 buffer (``core.types.pack_payload`` — the
paper's contiguous 44-byte ray).  Every backend moves that single buffer with
a SINGLE payload collective per round (per mesh axis; per micro-shard under
pipelining — see below), and the send-side marshal is ONE payload pass
(§4.2.1/§6.1) in either of two bit-exact modes:

* ``marshal="sort"`` — the destination-sort permutation is composed with the
  send-layout gather (``packed[perm[off[r] + s]]``): no separate "sort the
  payload, then gather the segments" double pass;
* ``marshal="scatter"`` — sort-free: the caller supplies the counting-sort
  plan (``dest_clean``, in-bucket ``dest_rank`` — one cheap pass over the
  destination vector, ``core.sorting.destination_rank``) and each packed row
  is scattered straight to its send-layout slot ``base[dest] + rank``.  No
  keys, no O(C log C) sort, and the histogram IS the send-count vector.

Both modes place items identically (the scatter reproduces the sort's
lexicographic stable source order), and neither fans out per pytree leaf.
The marshal law, alongside the collective budget below: ONE payload pass per
round pre-collective, whichever mode runs.

Since ISSUE 8 the backends are THIN COMPOSITIONS of the stage objects in
``core.stages`` (SpillExtract → Marshal → CountExchange → PayloadExchange →
Unmarshal over an explicit ``RoundState``): the marshal/clamp/spill/compact
arithmetic lives there exactly once, shared by every backend, and
``stages.compose`` runs each stage under its ``rafi.<stage>`` device scope.
The same layer supplies the overlap law: ``pipeline_shards=S`` splits each exchange's
per-peer slot rows into S micro-shards whose send/recv chains are issued
interleaved (``stages.Pipelined``) — S payload + S count collectives per
mesh axis, payload wire bytes exactly conserved, placement bit-exact with
the bulk-synchronous path (S=1), which remains the oracle.

Collective budget per ``forward_work`` round (guarded by
``tests/test_collective_budget.py``; multiply by ``pipeline_shards``):

  payload   1 × all_to_all (padded) / 1 × ragged_all_to_all (ragged) /
            1 × all_to_all PER MESH AXIS (hierarchical — see below)
  counts    1 × all_to_all of per-peer counts (padded) /
            1 × all_gather of the (R,) send-count vector (ragged — every rank
            reconstructs the full R×R count matrix locally and derives ALL
            offsets/clamps without further communication, replacing the three
            chained count all-to-alls of the naive Alltoallv control plane) /
            1 × tiny all_to_all PER MESH AXIS (hierarchical)

The N-level contract (hierarchical backend): ``axis_name`` is a tuple of
mesh axis names ordered slowest fabric first — e.g. ``("pod", "node",
"device")`` where "pod" spans the DCN, "node" the inter-host fabric, and
"device" the fast intra-node ICI/NVLink (an entry may itself be a tuple of
mesh axes treated as one joint tier).  ``level_sizes`` gives the rank count
per tier; global ranks are lexicographic in the tier digits (slowest-major —
"node-major" in the 2-level case), i.e. ``jax.lax.axis_index(flattened
axes)``.  The round is dimension-ordered routing over the padded wire
format, FASTEST axis first:

  stage l  (for l = L-1 … 0, extent-1 tiers skipped) one padded all_to_all
           over axis ``l``: each rank ships, per peer ``j`` on that axis, the
           concatenation of its sub-segments whose destination digit
           ``d_l == j``, in buffer order.  After the stage, every item sits
           on a rank whose digit ``l`` equals its destination's digit —
           slower stages never revisit the faster fabric.

The routing invariant (proved inductively; property-tested against the
``onehot`` oracle): before stage ``l`` the buffer is ordered lexicographically
by ``(s_{l+1}, …, s_{L-1}, d_0, …, d_l)`` — provenance digits of the already
routed tiers first, then the remaining destination digits.  Gathering each
peer's sub-segments in buffer order and concatenating received blocks in
source-digit order preserves it, so after the final stage items sit in global
source-rank order — bit-identical placement to the flat backends.

Bulk bytes cross each fabric tier exactly once, and padding at tier ``l`` is
per aggregated SEGMENT (``level_capacities[l]`` rows per peer on that axis),
not per rank: with R ranks over N slowest-tier groups that is an R/N×
reduction in worst-case slow-link padding versus routing the flat padded
exchange across the whole mesh.  The 2-level ``(slow, fast)`` route of PR 2
is exactly the L=2 instance.

Four interchangeable backends, all called *inside* ``shard_map`` with a
bound mesh axis:

* ``ragged`` — ``ragged_all_to_all``: the exact XLA analogue of
  ``MPI_Alltoallv`` and the TPU production path (single variable-size
  exchange over contiguous per-peer segments — the whole point of sorting
  first).  XLA:CPU cannot execute the op, so on CPU this backend is only
  ``.lower()``-validated; on JAX builds without the op it raises.
* ``padded`` — fixed per-peer slots of size ``peer_capacity`` exchanged with
  a single tiled ``all_to_all`` of the packed buffer.  Portable (runs on
  CPU; used by the dry-run compile) at the cost of padding bandwidth.  This
  is also the natural MoE-dispatch form (capacity-factor semantics).
* ``hierarchical`` — the N-stage padded exchange over an N-D ``(slowest, …,
  fastest)`` mesh described above: per-tier combine from the fastest axis
  inward, one collective per axis.  Placement is bit-identical to the flat
  backends (lexicographic rank order is preserved end to end).
* ``onehot`` — an all-gather reference oracle with a deliberately different
  code path, used only by tests.  Bulk-synchronous by design: it has no
  per-peer slot structure to micro-shard, so ``pipeline_shards > 1`` is
  rejected.

All backends share the contract: inputs are the *unsorted* packed payload
plus the marshal plan — the destination-sort permutation (``marshal="sort"``)
or the sanitized-dest/in-bucket-rank pair (``marshal="scatter"``) — and the
per-destination send counts; output is a compacted packed receive buffer plus
per-peer receive counts.  Segment overflow (sender-side ``> peer_capacity``,
or receiver-side total ``> capacity``) is dropped and counted EXACTLY ONCE —
the queue-capacity contract of §3.3/§6.3: every drop site clamps counts
*before* they feed any later stage, so an item clamped at one tier never
reappears in a later tier's (or the receiver's) overflow accounting
(regression-tested across stacked tier clamps in
``tests/test_core_scatter.py``).

Telemetry (ISSUE 5): every backend accepts ``telemetry=True`` (plus
``telemetry_buckets``) and then returns a FIFTH element, a
``repro.telemetry.RoundStats`` snapshot of the round's traffic — per-tier
segment-demand histograms, exact max demand, shipped rows, and per-stage
clamp drops.  Everything recorded is derived from control-plane values the
round computes anyway (the marshal histogram, the per-stage count
collectives' results, the clamp arithmetic): stats capture issues ZERO
additional collectives and never touches the payload, so the collective
budget above is bit-for-bit unchanged with telemetry on (guarded in
``tests/test_collective_budget.py``).

Spill-and-retry (ISSUE 6): every backend also accepts ``overflow="retain"``
(plus the per-lane ``age`` counter) and then returns, right before the
stats, a tuple of pending spill blocks ``(rows, dest, age, n_spill)`` — the
rows each sender- or tier-clamp would have cut, already compacted, with
their global destination and aged waiting counter.  The key cost trick: a
clamp's cut rows are exactly the per-segment TAILS of the marshalled order,
so each block is extracted with the same composed positional arithmetic the
send gather uses (one extra gather per clamp site — no conditional, no
per-lane masks, no scatter), and the receive-side compaction lands arrivals
BEHIND a reserved queue front (a shifted offset in the gather it already
runs).  ``forward_work`` then just selects the blocks into that front
(stable block-then-row order = FIFO oldest-first) and retries them next
round: the lossless law.  Retention is pure local compaction: what ships is
the exact clamped traffic the drop path ships (the wire bytes and the
collective inventory are bit-identical; only the drop counters move to the
spill blocks).  On the hierarchical route a row clamped at stage ``l`` is
parked at the intermediate rank it reached — the stage-l sub-segment →
destination map (``seg_dest``) needed to re-address it is derived
rank-consistently from digits every later-stage peer shares, so no extra
collective is spent on it either.  The onehot oracle has no sender clamp,
so its plan is empty by construction (its receiver clamp stays a counted
drop).  Spill extraction always reads the FULL clamp (cut rows never ship),
so retention is unchanged — and bit-exact — under pipelining.

Credit flow (ISSUE 9): with ``flow="credit"`` (requires ``overflow=
"retain"``; the default ``"open"`` ships every clamped segment and stays
the bit-exactness oracle) every backend additionally enforces the
backpressure law — no wire byte is spent on a row its receiver cannot
admit.  Receivers advertise their free queue room ON the count collective
the round already runs (the padded count ``all_to_all`` widens from
``(A_l, R/A_l)`` to ``(A_l, R/A_l + 1)`` i32; the ragged count
``all_gather`` from ``(R,)`` to ``(R+1,)`` — nothing payload-sized, so the
collective *inventory* above is unchanged), senders deterministically
apportion the one-round-stale credits across the R contending peers (floor
share + rank-ordered residual — incast cannot overshoot the advertised
room by design), and the un-credited tail of each destination segment is
parked through the retain spill machinery instead of shipped-and-bounced.
The updated ``(R,)`` credit estimate rides back as an extra ``credits_out``
element right before the stats, to be carried into the next round.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import stages as ST
from repro.telemetry import stats as TS

__all__ = [
    "exchange_padded",
    "exchange_ragged",
    "exchange_hierarchical",
    "exchange_onehot",
]

def exchange_padded(
    packed: jax.Array,  # (C, W) uint32 — UNSORTED packed payload
    perm: jax.Array,  # (C,) destination-sort permutation (sorted pos → lane)
    send_counts: jax.Array,  # (R,) valid-destination counts (histogram[:R])
    *,
    axis_name,
    num_ranks: int,
    capacity: int,
    peer_capacity: int,
    use_pallas: bool = False,
    marshal: str = "sort",
    dest_clean: jax.Array = None,  # (C,) scatter mode: sanitized destination
    dest_rank: jax.Array = None,  # (C,) scatter mode: stable in-bucket rank
    telemetry: bool = False,
    telemetry_buckets: int = 8,
    overflow: str = "drop",
    age: jax.Array = None,  # (C,) retain mode: rounds each lane has waited
    pipeline_shards: int = 1,
    flow: str = "open",
    credits: jax.Array = None,  # (R,) credit mode: advertised free, 1-round stale
    credit_reserve: int = 0,  # credit mode: receive room withheld from adverts
):
    """Padded-slot exchange of the packed payload, as a stage composition:

      [CreditGate →] SpillExtract(sender clamp) → Marshal → CountExchange →
      PayloadExchange → Unmarshal

    Single-pass marshal, either mode: in sort mode the send buffer row for
    (peer r, slot s) is ``packed[perm[off[r] + s]]`` — destination sort and
    slot layout composed into ONE gather; in scatter mode row ``i`` goes
    straight to slot ``dest_clean[i]·S + dest_rank[i]`` (rank ≥ S → the §3.3
    sender clamp) — ONE scatter, no sort at all.  Either way the payload is
    read once and written once on the send side.  Returns ``(recv_packed,
    recv_counts, total, drops)``, plus a trailing ``RoundStats`` when
    ``telemetry`` (segment demand here = the per-peer send counts, measured
    against ``peer_capacity``).  With ``overflow="retain"`` the sender
    clamp's cut rows come back as a pending spill block ``(rows, dest, age,
    n_spill)`` inserted before the stats — extracted as the marshalled
    order's segment tails in the same pass style as the send gather — and
    the receive compaction lands arrivals BEHIND the reserved spill front,
    so ``drops`` reduces to the receiver-side admission count.  The
    receive compaction is the inverse gather of the padded blocks: each
    queue row reads its own source row (``stages.compact_blocks``), so the
    receive side, like the send side, moves the payload in ONE pass.

    With ``pipeline_shards=S > 1`` the Marshal→…→Unmarshal chain runs S
    times over slot-row micro-shards, interleaved (``stages.Pipelined``):
    S payload + S count collectives, payload bytes conserved, placement
    bit-exact with S=1 (each shard lands its rows at their bulk positions).
    """
    R, S = num_ranks, peer_capacity
    retain = overflow == "retain"
    credit = flow == "credit"
    st = ST.RoundState(
        packed=packed, perm=perm, send_counts=send_counts, marshal=marshal,
        dest_clean=dest_clean, dest_rank=dest_rank, use_pallas=use_pallas,
        retain=retain, age=age, flow=flow, credits=credits,
    )
    inner = (
        ST.Marshal(R, S, shards=pipeline_shards),
        ST.CountExchange(axis_name, num_ranks=R, capacity=capacity,
                         flat_axes=axis_name),
        ST.PayloadExchange(axis_name),
        ST.Unmarshal(capacity, shards=pipeline_shards, slot=S),
    )
    if pipeline_shards > 1:
        inner = (ST.Pipelined(inner, pipeline_shards),)
    head = (ST.CreditGate(axis_name, R),) if credit else ()
    st = ST.compose(
        *head,
        ST.SpillExtract(R, capacity, S, retain=retain, reserve=credit_reserve),
        *inner,
    )(st)
    drops = st.send_drops + st.recv_drops
    if telemetry:
        tkw = {}
        if retain:
            tkw["rows_held"] = st.stage_held
        if credit:
            tkw["credits_granted"] = jnp.sum(jnp.minimum(st.credit_allow, S))
        stats = TS.single_tier_stats(
            send_counts, S, telemetry_buckets,
            sent_rows=jnp.sum(st.clamped), stage_drops=st.send_drops,
            recv_total=jnp.sum(st.recv_counts), recv_drops=st.recv_drops,
            **tkw,
        )
        if credit:
            return (st.out, st.recv_counts, st.new_count, drops,
                    tuple(st.pending), st.credits_out, stats)
        if retain:
            return st.out, st.recv_counts, st.new_count, drops, tuple(st.pending), stats
        return st.out, st.recv_counts, st.new_count, drops, stats
    if credit:
        return (st.out, st.recv_counts, st.new_count, drops,
                tuple(st.pending), st.credits_out)
    if retain:
        return st.out, st.recv_counts, st.new_count, drops, tuple(st.pending)
    return st.out, st.recv_counts, st.new_count, drops


def exchange_hierarchical(
    packed: jax.Array,  # (C, W) uint32 — UNSORTED packed payload
    perm: jax.Array,  # (C,) lexicographic destination-sort permutation
    send_counts: jax.Array,  # (R,) valid-destination counts, slowest-major
    *,
    axis_name,  # (slowest, …, fastest) mesh axis names, one per tier
    num_ranks: int,
    capacity: int,
    level_sizes: Tuple[int, ...],  # ranks per tier, slowest first
    level_capacities: Tuple[int, ...],  # padded rows per peer segment, per tier
    use_pallas: bool = False,
    marshal: str = "sort",
    dest_clean: jax.Array = None,  # (C,) scatter mode: sanitized destination
    dest_rank: jax.Array = None,  # (C,) scatter mode: stable in-bucket rank
    telemetry: bool = False,
    telemetry_buckets: int = 8,
    overflow: str = "drop",
    age: jax.Array = None,  # (C,) retain mode: rounds each lane has waited
    pipeline_shards: int = 1,
    flow: str = "open",
    credits: jax.Array = None,  # (R,) credit mode: advertised free, 1-round stale
    credit_reserve: int = 0,  # credit mode: receive room withheld from adverts
):
    """N-stage packed exchange over an N-D ``(slowest, …, fastest)`` mesh —
    one SpillExtract → Marshal → CountExchange → PayloadExchange composition
    per mesh axis, ``AdvanceTier`` threading the sub-segment bookkeeping
    between tiers and ``Unmarshal`` closing the final one.

    Dimension-ordered routing, fastest axis first: stage ``l`` combines
    traffic within axis ``l`` so every item lands on a rank whose digit ``l``
    equals its destination's — slower stages re-exchange only aggregated,
    already-packed segments, and bulk bytes cross each fabric tier exactly
    once, padded per peer SEGMENT at that tier (``level_capacities[l]``
    rows), never per rank.

    Budget: one payload + one count collective per mesh axis (× the
    micro-shard count under pipelining); extent-1 axes skip their stage
    entirely (so a single-node mesh degenerates to flat-exchange cost
    parity).  Returns ``(recv_packed, recv_counts, total, drops)`` — counts
    are per *source group* of the slowest non-trivial axis, unlike the flat
    backends' per-rank counts.

    Marshal modes: the first non-trivial stage is the round's single local
    payload pass — in sort mode the destination-sort permutation is composed
    into that stage's send gather; in scatter mode each row is scattered
    straight to its stage slot ``d_l·S + starts[rest, d_l] + rank`` (the
    in-bucket rank against the FULL destination is exactly the in-sub-segment
    rank, because every sub-segment holds one destination).  Every stage's
    sub-segment counts/offsets derive from the ONE histogram (reshaped per
    tier) and the per-stage count collectives — the sorted destination vector
    is never re-scanned (no per-tier ``segment_bounds_from_sorted`` neighbor
    compares), on either marshal path.

    With ``pipeline_shards=S > 1`` each tier's Marshal/CountExchange/
    PayloadExchange chain runs S times over ``level_capacities[l]/S``-row
    micro-shards (interleaved — stage-l of shard k overlaps stage-(l−1) of
    shard k+1 on an async fabric), non-final tiers reassemble the bulk
    stage buffer locally (``stages.Reassemble`` — zero extra collectives),
    and the final tier's shards compact straight into the receive queue at
    their bulk positions.  Placement stays bit-exact with S=1.

    With ``telemetry`` a trailing ``RoundStats`` is returned: tier ``l``'s
    segment demand is the pre-clamp row total per peer slot COLUMN of stage
    ``l`` (the concatenated sub-segments one ``level_capacities[l]`` budget
    clamps), measured against that budget; extent-1 tiers skip their stage
    and stay zero.  Demand at tier ``l`` is post-clamp of the faster tiers —
    exactly the traffic the stage observes (and the reason the capacity
    controller converges over a few bursts rather than in one).

    With ``overflow="retain"`` every stage clamp parks its cut rows at the
    rank they currently sit on instead of dropping them: the first stage
    spills input LANES (sender clamp — the per-destination segment tails of
    the sorted order, ages carried forward); later stages spill mid-route
    BUFFER rows (sub-segment tails read straight out of the stage buffer)
    re-addressed through ``seg_dest`` — the sub-segment → global-destination
    map, maintained locally because after stage ``l`` every peer of the
    remaining stages shares the already-routed digits (mid-route rows
    restart at age 1: age cannot ride the wire without changing the payload
    bytes).  One pending ``(rows, dest, age, n)`` spill block per non-trivial
    stage rides back before the stats, the final compaction lands arrivals
    behind the reserved spill front, and stage drops move into the blocks —
    ``drops`` reduces to the receiver-side admission count.

    With ``flow="credit"`` (the backpressure law; requires retain) the
    carried ``credits`` vector gates the route's FIRST clamp: the per-
    destination grant (floor share + rank-ordered residual over the R
    contending senders) caps each sub-segment before the fastest tier's
    clamp, so a saturated destination throttles every downstream fabric —
    including the DCN stage — at the source, and the un-granted tail parks
    in the sender's own spill blocks.  Credits aggregate per tier: each
    tier's count ``all_to_all`` widens by ONE i32 column carrying the
    min-aggregated free space of the sender's destination SUBTREE on that
    axis (the final tier folds in this rank's fresh post-spill room first),
    and receivers scatter the advertised column back into their estimate of
    every subtree member — every rank's estimate of every destination
    refreshes every round, conservatively (min over the subtree), with no
    payload-sized traffic added.  The updated ``credits_out`` rides back
    right before the stats.
    """
    level_sizes = tuple(int(a) for a in level_sizes)
    R = num_ranks
    C, W = packed.shape
    rec = TS.make_stats(len(level_sizes), telemetry_buckets) if telemetry else None
    retain = overflow == "retain"
    credit = flow == "credit"
    # One flattened axis spec covering every tier — the global rank index
    # (slowest-major) that the credit bookkeeping addresses by.
    flat_axes = []
    for ax in axis_name:
        flat_axes.extend(ax) if isinstance(ax, (tuple, list)) else flat_axes.append(ax)
    flat_axes = tuple(flat_axes)
    st = ST.RoundState(
        packed=packed, perm=perm, send_counts=send_counts, marshal=marshal,
        dest_clean=dest_clean, dest_rank=dest_rank, use_pallas=use_pallas,
        retain=retain, age=age, flow=flow, credits=credits,
    )
    if credit:
        st = ST.compose(ST.CreditGate(flat_axes, R))(st)
    st.spill_run = jnp.zeros((), send_counts.dtype)  # total rows parked so far
    st.drops = jnp.zeros((), send_counts.dtype)
    if retain:
        if st.age is None:
            st.age = jnp.zeros((C,), jnp.int32)
        # Which global destination does sub-segment k of the current buffer
        # hold?  Identity at the start (sorted destination order); updated
        # after each non-final stage from digits all later-stage peers share.
        st.seg_dest = jnp.arange(R, dtype=jnp.int32)

    # Sub-segment state, always exactly R entries: counts and buffer offsets
    # in the current buffer order (initially the sorted destination order,
    # digits slowest-major).  Each stage reinterprets the vector as
    # (rest, A_l) — its peer digit is the fastest-varying non-trivial field —
    # and afterwards prepends the source digit: (A_l, rest) flattened.
    st.cnt = send_counts
    st.base = jnp.cumsum(st.cnt) - st.cnt
    st.buf, st.n_rows, st.via_perm = packed, C, True

    tiers = [l for l in reversed(range(len(level_sizes))) if level_sizes[l] > 1]
    if not tiers:
        # 1-rank mesh: the round is a local compaction — no collectives
        allowed = jnp.minimum(st.cnt, capacity)
        credits_out = (capacity - allowed).astype(jnp.int32) if credit else None
        if marshal == "scatter":
            keep = (dest_clean < R) & (dest_rank < capacity)
            out = ST.scatter_rows(
                packed,
                jnp.where(keep, dest_rank, capacity),
                capacity,
                use_pallas=use_pallas,
            )
        else:
            rows = jnp.take(perm, jnp.clip(jnp.arange(capacity), 0, C - 1))
            if use_pallas:
                from repro.kernels.marshal import ops as marshal_ops

                out = marshal_ops.fused_marshal(
                    packed, rows, num_ranks=1, slot=capacity
                )[0]
            else:
                out = jnp.take(packed, rows, axis=0).reshape(1, capacity, W)[0]
        local_drops = jnp.sum(st.cnt - allowed)
        if telemetry:
            # no stage ran: only the receiver-side compaction is observable
            rec = dataclasses.replace(
                rec,
                recv_total=jnp.sum(st.cnt).astype(jnp.int32),
                recv_drops=local_drops.astype(jnp.int32),
            )
            if credit:
                return out, allowed, allowed[0], local_drops, (), credits_out, rec
            if retain:  # no stage clamp ran either: nothing to spill
                return out, allowed, allowed[0], local_drops, (), rec
            return out, allowed, allowed[0], local_drops, rec
        if credit:
            return out, allowed, allowed[0], local_drops, (), credits_out
        if retain:
            return out, allowed, allowed[0], local_drops, ()
        return out, allowed, allowed[0], local_drops

    for i, l in enumerate(tiers):
        A, S = level_sizes[l], level_capacities[l]
        stride = 1
        for sz in level_sizes[l + 1:]:
            stride *= sz
        st = ST.compose(
            ST.SpillExtract(R, capacity, S, retain=retain, kind="tier", extent=A)
        )(st)
        if telemetry:
            # segment demand at tier l = pre-clamp rows per peer slot column
            col_demand = jnp.sum(st.cnt.reshape(R // A, A), axis=0)
            rec = dataclasses.replace(
                rec,
                demand_hist=rec.demand_hist.at[l].set(
                    TS.occupancy_histogram(col_demand, S, telemetry_buckets)
                ),
                demand_max=rec.demand_max.at[l].set(jnp.max(col_demand)),
                demand_total=rec.demand_total.at[l].set(jnp.sum(col_demand)),
                sent_rows=rec.sent_rows.at[l].set(jnp.sum(st.allowed)),
                stage_drops=rec.stage_drops.at[l].set(st.stage_drops),
            )
            if retain:
                rec = dataclasses.replace(
                    rec, rows_held=rec.rows_held.at[l].set(st.stage_held)
                )
            if credit and i == 0:
                rec = dataclasses.replace(
                    rec,
                    credits_granted=rec.credits_granted.at[l].set(
                        jnp.sum(jnp.minimum(st.credit_allow, S))
                    ),
                )
        mar = ST.Marshal(A, S, shards=pipeline_shards, kind="tier", num_ranks=R)
        if i == len(tiers) - 1:
            # final stage: per-source-group totals suffice — blocks are
            # contiguous prefixes, compacted straight into the receive queue
            chain = (
                mar,
                ST.CountExchange(axis_name[l], kind="final", num_ranks=R,
                                 stride=stride, capacity=capacity,
                                 flat_axes=flat_axes, reserve=credit_reserve),
                ST.PayloadExchange(axis_name[l]),
                ST.Unmarshal(capacity, shards=pipeline_shards, slot=S, kind="final"),
            )
            if pipeline_shards > 1:
                chain = (ST.Pipelined(chain, pipeline_shards),)
            st = ST.compose(*chain)(st)
            total_drops = st.drops + st.recv_drops
            if telemetry:
                # wasted wire = every row discarded AFTER crossing a wire:
                # the receiver-admission cut plus any stage clamp past the
                # first hop (tiers[0] clamps pre-wire rows — not waste; a
                # tiers[i>0] clamp cuts rows that already spent the earlier
                # tiers' fabric).  Under retain the late stages hold instead
                # of dropping, so their recorded stage_drops are zero and
                # the term collapses to the receiver cut.
                late_drops = jnp.zeros((), jnp.int32)
                for j in tiers[1:]:
                    late_drops = late_drops + rec.stage_drops[j]
                rec = dataclasses.replace(
                    rec,
                    recv_total=jnp.sum(st.recv_counts).astype(jnp.int32),
                    recv_drops=st.recv_drops.astype(jnp.int32),
                    wasted_wire_rows=(
                        st.recv_drops.astype(jnp.int32) + late_drops
                    ),
                )
                if credit:
                    return (st.out, st.recv_counts, st.new_count,
                            total_drops, tuple(st.pending), st.credits_out, rec)
                if retain:
                    return (st.out, st.recv_counts, st.new_count,
                            total_drops, tuple(st.pending), rec)
                return st.out, st.recv_counts, st.new_count, total_drops, rec
            if credit:
                return (st.out, st.recv_counts, st.new_count,
                        total_drops, tuple(st.pending), st.credits_out)
            if retain:
                return (st.out, st.recv_counts, st.new_count,
                        total_drops, tuple(st.pending))
            return st.out, st.recv_counts, st.new_count, total_drops

        # count collective for axis l: per-sub-segment survivor counts, so
        # the receiver can address every sub-segment of each incoming block
        chain = (
            mar,
            ST.CountExchange(
                axis_name[l], kind="tier", shards=pipeline_shards, slot=S,
                num_ranks=R, stride=stride, capacity=capacity,
                flat_axes=flat_axes,
            ),
            ST.PayloadExchange(axis_name[l], collect=pipeline_shards > 1),
        )
        if pipeline_shards > 1:
            chain = (ST.Pipelined(chain, pipeline_shards), ST.Reassemble(A, S))
        st = ST.compose(
            *chain, ST.AdvanceTier(A, S, axis_name[l], retain=retain, num_ranks=R)
        )(st)


def exchange_ragged(
    packed: jax.Array,  # (C, W) uint32 — UNSORTED packed payload
    perm: jax.Array,
    send_counts: jax.Array,  # (R,)
    *,
    axis_name,
    num_ranks: int,
    capacity: int,
    peer_capacity: int = 0,  # unused; signature parity
    use_pallas: bool = False,
    marshal: str = "sort",
    dest_clean: jax.Array = None,  # (C,) scatter mode: sanitized destination
    dest_rank: jax.Array = None,  # (C,) scatter mode: stable in-bucket rank
    telemetry: bool = False,
    telemetry_buckets: int = 8,
    overflow: str = "drop",
    age: jax.Array = None,  # (C,) retain mode: rounds each lane has waited
    pipeline_shards: int = 1,
    flow: str = "open",
    credits: jax.Array = None,  # (R,) credit mode: advertised free, 1-round stale
    credit_reserve: int = 0,  # credit mode: receive room withheld from adverts
):
    """ragged_all_to_all exchange — the MPI_Alltoallv / GPU-RDMA analogue.

    The packed payload is placed ONCE into destination order (contiguous
    per-peer segments) — a gather through the sort permutation, or a sort-free
    scatter to ``off[dest] + rank`` — and shipped in ONE variable-size
    collective; the receive side is written compacted directly (no unpack
    pass), which is the paper's "large contiguous blocks at very high
    bandwidth" property.  The control plane is one all-gather of the
    send-count vector (``stages.CountExchange(kind="ragged")``): every rank
    derives every clamp and landing offset from the (R, R) count matrix.  With
    ``overflow="retain"`` the rows past each segment's control-plane
    allowance (``send_sizes``) come back as a pending spill block instead
    of being dropped — the shipped segments are unchanged.

    With ``pipeline_shards=S > 1`` the single collective becomes S: shard
    ``k`` ships rows ``[k·capacity/S, (k+1)·capacity/S)`` of every
    destination segment (offsets shifted, sizes clipped — the union of the
    shard segments is exactly the bulk segments at the same landing
    offsets), each with its own count all-gather.  The marshal stays ONE
    local pass; only the wire movement is sharded.

    With ``flow="credit"`` (requires retain) the carried ``credits`` vector
    gates each sender's per-destination counts BEFORE the count all-gather
    (floor share + rank-ordered residual), so the replicated control plane —
    and the wire — only ever sees granted traffic; the un-granted tail parks
    in the spill block with the control-plane cut.  The gather widens by ONE
    i32 column carrying each rank's own-entry advert (its post-spill free
    room from last round), and this rank's fresh advert replaces its own
    entry in the returned ``credits_out`` — every rank's estimate of every
    receiver refreshes every round with no payload-sized traffic added.

    Stages: ``[CreditGate →] CountExchange → SpillExtract → Marshal →
    PayloadExchange → Unmarshal``, all ``kind="ragged"`` (the count and
    payload collectives pipelined per shard).
    """
    del peer_capacity  # segments are contiguous: no slot gather
    retain = overflow == "retain"
    credit = flow == "credit"
    R = num_ranks
    st = ST.RoundState(
        packed=packed, perm=perm, send_counts=send_counts, marshal=marshal,
        dest_clean=dest_clean, dest_rank=dest_rank, use_pallas=use_pallas,
        retain=retain, age=age, flow=flow, credits=credits,
    )
    st.base = jnp.cumsum(send_counts) - send_counts  # segment starts, sorted order
    count = ST.CountExchange(axis_name, kind="ragged", num_ranks=R, capacity=capacity)
    payload = ST.PayloadExchange(
        axis_name, kind="ragged", capacity=capacity, shards=pipeline_shards
    )
    wire = (ST.Pipelined((count, payload), pipeline_shards),) if pipeline_shards > 1 else (payload,)
    head = (ST.CreditGate(axis_name, R),) if credit else ()
    st = ST.compose(
        *head,
        count,
        ST.SpillExtract(R, capacity, 0, retain=retain, kind="ragged",
                        reserve=credit_reserve, axis_name=axis_name),
        ST.Marshal(R, 0, kind="ragged"),
        *wire,
        ST.Unmarshal(capacity, kind="ragged"),
    )(st)
    drops = st.send_drops + st.recv_drops
    pending = tuple(st.pending)
    if telemetry:
        # No per-peer slots here — the §3.3 clamp is the receiver queue, so
        # segment demand = the count matrix's per-destination column totals
        # (replicated identically on every rank; quantiles/maxima are
        # unaffected, totals are ×R — documented in telemetry.summarize's
        # population semantics).  Senders own the drop accounting on this
        # backend (each counts what the control plane cut from its row), so
        # recv_drops stays 0 — stats sum to the exchange's drops return.
        me = jax.lax.axis_index(axis_name)
        col_demand = jnp.sum(st.cnt, axis=0)
        tkw = {}
        if retain:
            tkw["rows_held"] = st.stage_held
        if credit:
            tkw["credits_granted"] = jnp.sum(jnp.minimum(st.credit_allow, send_counts))
        stats = TS.single_tier_stats(
            col_demand, capacity, telemetry_buckets,
            sent_rows=jnp.sum(st.send_sizes), stage_drops=st.send_drops,
            recv_total=col_demand[me], recv_drops=st.recv_drops.astype(jnp.int32),
            **tkw,
        )
        if credit:
            return (st.out, st.recv_counts, st.new_count, drops, pending,
                    st.credits_out, stats)
        if retain:
            return st.out, st.recv_counts, st.new_count, drops, pending, stats
        return st.out, st.recv_counts, st.new_count, drops, stats
    if credit:
        return st.out, st.recv_counts, st.new_count, drops, pending, st.credits_out
    if retain:
        return st.out, st.recv_counts, st.new_count, drops, pending
    return st.out, st.recv_counts, st.new_count, drops


def exchange_onehot(
    packed: jax.Array,
    perm: jax.Array,
    send_counts: jax.Array,
    *,
    axis_name,
    num_ranks: int,
    capacity: int,
    peer_capacity: int = 0,
    use_pallas: bool = False,
    marshal: str = "sort",
    dest_clean: jax.Array = None,
    dest_rank: jax.Array = None,
    telemetry: bool = False,
    telemetry_buckets: int = 8,
    overflow: str = "drop",
    age: jax.Array = None,  # unused: the oracle has no sender clamp
    pipeline_shards: int = 1,
):
    """All-gather reference oracle (tests only): every rank sees everything,
    selects what is addressed to it, and compacts stably by (source, lane).
    Deliberately a different code path from the production backends (in
    scatter mode only the initial into-destination-order placement differs):
    it runs no stage objects, so its ops carry ``rafi.forward`` only.
    With ``overflow="retain"`` the pending spill plan is empty by
    construction — there is no sender clamp to spill from; the receiver
    clamp stays a counted drop (there is no bounded place left to keep those
    rows).  Bulk-synchronous by design: the all-gather has no per-peer slot
    rows to micro-shard, so ``pipeline_shards > 1`` raises.
    """
    del peer_capacity, age
    if pipeline_shards != 1:
        raise ValueError(
            "exchange='onehot' is the bulk-synchronous reference oracle: the "
            "all-gather ships whole queues, so there is no per-peer slot "
            "dimension to micro-shard — pipeline_shards must be 1 "
            f"(got {pipeline_shards})"
        )
    retain = overflow == "retain"
    R = num_ranks
    me = jax.lax.axis_index(axis_name)
    off = jnp.cumsum(send_counts) - send_counts
    cap = packed.shape[0]
    if marshal == "scatter":
        keep = dest_clean < R
        pos = off[jnp.clip(dest_clean, 0, R - 1)] + dest_rank
        sorted_packed = ST.scatter_rows(
            packed, jnp.where(keep, pos, cap), cap, use_pallas=use_pallas
        )
    else:
        sorted_packed = jnp.take(packed, perm, axis=0)
    lane = jnp.arange(cap, dtype=jnp.int32)
    # reconstruct per-item dest from segments: dest[i] = r iff off[r] <= i < off[r]+cnt
    seg_end = off + send_counts
    dest = jnp.sum((lane[:, None] >= seg_end[None, :]).astype(jnp.int32), axis=1)
    dest = jnp.where(lane < jnp.sum(send_counts), dest, R)

    all_packed = jax.lax.all_gather(sorted_packed, axis_name)  # (R, cap, W)
    all_dest = jax.lax.all_gather(dest, axis_name)  # (R, cap)
    mine = (all_dest == me).reshape(-1)
    order = jnp.argsort(~mine, stable=True)  # mine first, stable (src, lane) order
    flat = all_packed.reshape(R * cap, -1)
    gathered = jnp.take(flat, order[:capacity], axis=0, mode="clip")
    total = jnp.sum(mine.astype(jnp.int32))
    new_count = jnp.minimum(total, capacity)
    recv_counts = jnp.sum((all_dest == me).astype(jnp.int32), axis=1)
    if telemetry:
        # oracle capture: my per-destination send counts vs the receiver
        # queue (the only clamp this backend has)
        stats = TS.single_tier_stats(
            send_counts, capacity, telemetry_buckets,
            sent_rows=jnp.sum(send_counts), stage_drops=jnp.zeros((), jnp.int32),
            recv_total=total, recv_drops=total - new_count,
        )
        if retain:
            return gathered, recv_counts, new_count, total - new_count, (), stats
        return gathered, recv_counts, new_count, total - new_count, stats
    if retain:
        return gathered, recv_counts, new_count, total - new_count, ()
    return gathered, recv_counts, new_count, total - new_count
