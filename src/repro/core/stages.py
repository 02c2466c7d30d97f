"""Composable exchange stages — the round as a graph, not a monolith.

Every packed-payload exchange backend (``core.exchange``) is the same five
stages in a row, whatever the fabric layout:

  SpillExtract   the §3.3 clamp site: truncate per-segment counts to the
                 slot budget; in ``overflow="retain"`` mode extract the cut
                 rows as a pending spill block (the lossless law), in drop
                 mode count them.
  Marshal        the send-side payload pass: place rows into the stage's
                 (peers, slot, words) wire layout — sort-composed gather or
                 sort-free scatter (the marshal law: ONE pass either way).
  CountExchange  the control plane: the tiny per-peer count collective.
  PayloadExchange the payload collective: ONE all_to_all of the send buffer.
  Unmarshal      receive-side compaction into the destination queue
                 (``out[roff[g] + s] = recv[g, s]``, computed as the inverse
                 gather: each queue row reads its source row), rows past
                 capacity dropped; retain mode lands arrivals behind the
                 spill front.

Pre-refactor each backend inlined all five; here they are small stage
objects over an explicit :class:`RoundState`, and the backends are thin
compositions (``compose`` for bulk-synchronous, :class:`Pipelined` for
micro-sharded).  The hierarchical route runs one
SpillExtract→Marshal→CountExchange→PayloadExchange sequence per mesh axis
(``kind="tier"``), advancing the sub-segment bookkeeping between tiers; the
ragged route runs the same five over contiguous segments
(``kind="ragged"``).  ``compose`` and :class:`Pipelined` run every stage
under its device scope, ``rafi.<stage>`` from the class name, and
:class:`Marshal` declares the rows its send buffers hold
(``rafi_payload_rows_per_forward`` in ``repro.obs.metrics``).

Micro-shard pipelining (the overlap law, ISSUE 8): with
``ForwardConfig(pipeline_shards=S)`` every shard-aware stage also exposes
``.shard(state, k)`` issuing shard ``k``'s slice of the work — the per-peer
slot rows ``[k·S/chunks, (k+1)·S/chunks)`` — and :class:`Pipelined`
interleaves the per-shard chains in issue order:

  marshal(0) count(0) payload(0) unmarshal(0) marshal(1) payload(1) …

The S per-shard chains are mutually independent except for the output-queue
accumulator, so an async-collective backend can keep shard k's payload
collective in flight while shard k−1 compacts and shard k+1 marshals.  Each
shard's count collective ships the FULL clamped count vector (control-plane
bytes, replicated ×S) so every shard derives its own landing offsets
``roff[g] + k·chunk + s`` without waiting on its siblings — which is also
why the sharded round is bit-exact with the bulk one by construction: the
union of shard writes is exactly the bulk compaction's writes.  Payload
wire bytes are conserved exactly (S collectives of chunk-rows vs one of
S·chunk rows); the inventory becomes S payload + S count collectives per
mesh axis (guarded in ``tests/test_collective_budget.py``).

The positional arithmetic every clamp site shares (segment-tail spill
extraction, stacked sub-segment truncation, composed layout gathers) lives
here once — ``spill_positions`` / ``lanes_spill`` / ``clamp_subsegments`` /
``subsegment_gather`` / ``compact_blocks`` — and is regression-covered by
the PR-4/PR-6 exact drop-count tests.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import metrics as OM

__all__ = [
    "RoundState",
    "CreditGate",
    "SpillExtract",
    "Marshal",
    "CountExchange",
    "PayloadExchange",
    "Unmarshal",
    "Reassemble",
    "AdvanceTier",
    "Pipelined",
    "compose",
    "a2a",
    "scatter_rows",
    "spill_positions",
    "lanes_spill",
    "clamp_subsegments",
    "subsegment_gather",
    "compact_blocks",
    "compact_shard",
    "ragged_control_plane",
    "padded_send_buffer",
    "padded_send_shard",
]


# =====================================================================
# shared positional arithmetic (the stage library's primitive layer)
# =====================================================================


def a2a(x: jax.Array, axis_name) -> jax.Array:
    """all_to_all over leading axis: out[p] = what peer p sent me (block p)."""
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=False)


def scatter_rows(
    buf: jax.Array, dstpos: jax.Array, n_slots: int, *, use_pallas: bool
) -> jax.Array:
    """The scatter marshal's single payload pass: ``out[dstpos[i]] = buf[i]``.

    Positions at/past ``n_slots`` (the caller's drop/trash sentinel) are
    discarded — §3.3 semantics.  The Pallas kernel
    (``kernels/bucket_scatter.scatter_rows``) stores rows at their slots
    directly; the XLA fallback scatters only the 1-word LANE INDEX and reads
    the payload back through the inverse — XLA lowers a W-word row scatter
    far worse than the equivalent gather, and the index scatter is
    control-plane-sized (like the histogram), so the payload still moves in
    exactly ONE pass.  Slots no lane claimed hold garbage on this path (row 0)
    and zeros on the Pallas path — both are masked downstream by the
    exchanged counts, exactly like the sort path's past-the-segment slots.
    """
    if use_pallas:
        from repro.kernels.bucket_scatter import ops as bs_ops

        return bs_ops.scatter_rows(buf, dstpos, num_slots=n_slots)
    lane = jnp.arange(buf.shape[0], dtype=jnp.int32)
    inv = jnp.zeros((n_slots,), jnp.int32).at[dstpos].set(lane, mode="drop")
    return jnp.take(buf, inv, axis=0)


def spill_positions(n_slots, cut, seg_start):
    """Source positions of a clamp site's cut rows, compacted segment-major.

    ``cut[k]`` rows were clamped off segment ``k``; they sit contiguously
    from ``seg_start[k]`` (the first position past the segment's allowance).
    Spill slot ``j`` maps to segment ``k = #{inclusive-cumulative cut <= j}``
    and position ``seg_start[k] + j - spill_off[k]`` — the same composed
    positional arithmetic as the send gather, so extracting the spill is
    just a second index vector into the marshal's source space.  In-segment
    order is preserved (stable rank order = FIFO).  Returns ``(k, pos)``;
    slots at/past the total cut hold clamped garbage the caller bounds by
    the spill count.
    """
    incl = jnp.cumsum(cut)
    j = jnp.arange(n_slots, dtype=jnp.int32)
    k = jnp.sum((j[:, None] >= incl[None, :]).astype(jnp.int32), axis=1)
    k = jnp.clip(k, 0, cut.shape[0] - 1)
    pos = jnp.take(seg_start, k) + j - jnp.take(incl - cut, k)
    return k, pos


def lanes_spill(
    packed, perm, age, allow_tbl, cut, seg_start, n_spill, *,
    num_ranks, marshal, dest_clean, dest_rank,
):
    """Pending-spill block for a sender-side clamp over the INPUT lanes.

    ``allow_tbl[d]``/``cut[d]``: per-destination allowance and cut count;
    ``seg_start[d]``: first cut position of destination ``d`` in the
    MARSHALLED (sorted) order.  Sort mode reads the cut rows straight
    through ``perm``; scatter mode inverts the (dest, in-bucket rank) plan
    with one 1-word scatter.  Returns ``(rows, dest, age, n_spill)`` —
    rows/dest/age are valid on the ``[0, n_spill)`` prefix only (the caller
    bounds every read), ages carried forward +1.
    """
    C = packed.shape[0]
    k, pos = spill_positions(C, cut, seg_start)
    if marshal == "scatter":
        lanes = jnp.arange(C, dtype=jnp.int32)
        d = jnp.clip(dest_clean, 0, num_ranks - 1)
        al = jnp.take(allow_tbl, d)
        tgt = jnp.where(
            (dest_clean < num_ranks) & (dest_rank >= al),
            jnp.take(jnp.cumsum(cut) - cut, d) + dest_rank - al,
            C,
        )
        src = jnp.zeros((C,), jnp.int32).at[tgt].set(lanes, mode="drop")
    else:
        src = jnp.take(perm, jnp.clip(pos, 0, C - 1))
    # segment index in marshalled order IS the global destination (flat and
    # first hierarchical stage alike: lexicographic rank order)
    return (
        jnp.take(packed, src, axis=0),
        k.astype(jnp.int32),
        jnp.take(age, src).astype(jnp.int32) + 1,
        n_spill,
    )


def clamp_subsegments(cnt: jax.Array, slot: int) -> Tuple[jax.Array, jax.Array]:
    """Truncate stacked sub-segments (rows of ``cnt``, concatenated in row
    order) to a ``slot``-row budget per column.

    ``cnt[i, j]``: rows of sub-segment ``i`` bound for slot column ``j``.
    Returns ``(allowed, starts)`` with the same shape: ``allowed`` keeps a
    contiguous prefix of each column's concatenation (any segment or segment
    tail past ``slot`` is cut — the §3.3 drop rule), ``starts`` is where each
    surviving sub-segment begins inside its slot.
    """
    raw_pref = jnp.cumsum(cnt, axis=0) - cnt
    allowed = jnp.clip(jnp.minimum(cnt, slot - raw_pref), 0)
    starts = jnp.cumsum(allowed, axis=0) - allowed
    return allowed, starts


def subsegment_gather(
    allowed: jax.Array,  # (G, K) surviving sub-segment sizes per slot column k
    starts: jax.Array,  # (G, K) slot-local sub-segment starts
    src_base: jax.Array,  # (G, K) source offset of sub-segment (g, k)
    slot: int,
) -> jax.Array:
    """Source row index for every (slot column k, slot position s).

    Returns ``(K, slot)`` int32: the flat source row feeding slot ``k``'s
    position ``s`` — rows past a column's total are clamped garbage, masked
    downstream by the exchanged counts.  This is the composed two-stage
    layout: one gather materialises a whole stage's send buffer.
    """
    G, K = allowed.shape
    s_idx = jnp.arange(slot, dtype=jnp.int32)
    incl = jnp.cumsum(allowed, axis=0)  # (G, K) inclusive prefix per column
    # sub-segment owning position s = number of fully-completed predecessors
    g_of = jnp.sum(s_idx[None, :, None] >= incl.T[:, None, :], axis=-1)  # (K, slot)
    g_c = jnp.clip(g_of, 0, G - 1)
    k_grid = jnp.arange(K, dtype=jnp.int32)[:, None]
    s_local = s_idx[None, :] - starts[g_c, k_grid]
    return src_base[g_c, k_grid] + s_local


def ragged_control_plane(
    cnt: jax.Array, me: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """From the (R_src, R_dst) count matrix, derive my ragged-a2a parameters.

    Receiver-capacity clamp, replicated identically on all ranks: at each
    destination column ``d`` the senders' segments land at the exclusive
    prefix of the column; any segment (or segment tail) past ``capacity`` is
    cut — the §3.3 drop rule (:func:`clamp_subsegments`), decided without a
    round trip.

    Returns ``(send_sizes (R,), output_offsets (R,), recv_sizes (R,))``.
    """
    allowed, roff = clamp_subsegments(cnt, capacity)
    send_sizes = allowed[me]  # my row: what each peer lets me deliver
    output_offsets = roff[me]  # where my block lands on each peer
    recv_sizes = allowed[:, me]  # my column: what each peer delivers to me
    return send_sizes, output_offsets, recv_sizes


def compact_blocks(
    recv_buf: jax.Array,  # (G, S, W) received padded blocks
    recv_counts: jax.Array,  # (G,) valid rows per block
    capacity: int,
    *,
    use_pallas: bool,
    front=None,  # retain mode: rows [0, front) are reserved for the spill
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Receive-side compaction shared by the padded-slot exchanges:
    ``out[roff[g] + s] = recv_buf[g, s]`` for ``s < recv_counts[g]``
    (``recv_counts[g] <= S``: the exchanged counts are clamped to the slot),
    rows past ``capacity`` dropped (§3.3), every other row zero.  Returns
    ``(out, new_count, drops)``.

    The XLA path computes it as the INVERSE gather, not as a row scatter
    (XLA lowers a W-word row scatter far worse than the equivalent gather,
    as on the send side — :func:`scatter_rows`): queue row ``j`` with
    ``j' = j − front`` reads flat receive row
    ``j' + Σ_{g<G−1} [j' ≥ incl[g]]·(S − recv_counts[g])`` (``incl`` the
    inclusive count prefix) while ``0 ≤ j' < incl[G−1]``.  G is static, so
    the sum is G − 1 elementwise compare-and-adds that fuse into the
    gather's index; each queue row is read once and written once, with no
    scatter and no index sort.  For ``G = 1`` the index is ``j'`` itself: a
    masked copy.

    With ``front`` the arrivals land shifted by that many rows — BEHIND the
    retained spill at zero extra cost (rows ``[0, front)`` stay zero), and
    ``new_count``/``drops`` account against the reduced room.
    """
    G, S, W = recv_buf.shape
    roff = jnp.cumsum(recv_counts) - recv_counts
    if front is not None:
        roff = roff + front
    if use_pallas:
        from repro.kernels.marshal import ops as marshal_ops

        out = marshal_ops.fused_unmarshal(recv_buf, roff, recv_counts, capacity=capacity)
    else:
        incl = jnp.cumsum(recv_counts)
        j = jnp.arange(capacity, dtype=incl.dtype)
        if front is not None:
            j = j - front
        src = j
        for g in range(G - 1):  # rows past the last block are masked below
            src = src + jnp.where(j >= incl[g], S - recv_counts[g], 0)
        # masked rows may point out of range: the take clips them
        rows = jnp.take(recv_buf.reshape(G * S, W), src, axis=0, mode="clip")
        ok = (j >= 0) & (j < incl[G - 1])
        out = jnp.where(ok[:, None], rows, 0)
    total_recv = jnp.sum(recv_counts)
    room = capacity if front is None else jnp.clip(capacity - front, 0)
    new_count = jnp.minimum(total_recv, room)
    return out, new_count, total_recv - new_count


def compact_shard(
    out: jax.Array,  # (capacity, W) accumulator shared by all shards
    recv_buf: jax.Array,  # (G, chunk, W) shard k's received blocks
    recv_counts: jax.Array,  # (G,) FULL per-block counts (shard-independent)
    capacity: int,
    *,
    row_offset: int,  # k·chunk — where this shard's rows sit in each block
    front=None,
) -> jax.Array:
    """One micro-shard's slice of the receive compaction: shard rows land at
    the SAME final positions the bulk compaction gives them
    (``roff[g] + row_offset + s``, valid while ``row_offset + s <
    recv_counts[g]``), so the union over shards is bit-exact with
    :func:`compact_blocks`.  Always an XLA row scatter, unlike the bulk
    path's inverse gather: each shard writes a disjoint subset of the shared
    queue, which a gather would have to merge into the accumulator by a
    ``where`` over every row per shard, and per-shard accumulation has no
    fused-unmarshal kernel.
    """
    G, chunk, W = recv_buf.shape
    roff = jnp.cumsum(recv_counts) - recv_counts
    if front is not None:
        roff = roff + front
    g_idx = jnp.repeat(jnp.arange(G, dtype=jnp.int32), chunk)
    s_idx = jnp.tile(jnp.arange(chunk, dtype=jnp.int32), G) + row_offset
    dstpos = roff[g_idx] + s_idx
    ok = s_idx < recv_counts[g_idx]
    slot = jnp.where(ok & (dstpos < capacity), dstpos, capacity)
    return out.at[slot].set(recv_buf.reshape(G * chunk, W), mode="drop")


def padded_send_buffer(
    packed: jax.Array,  # (C, W) uint32 — UNSORTED packed payload
    perm: jax.Array,  # (C,) sort mode: destination-sort permutation
    send_counts: jax.Array,  # (R,) valid-destination counts
    *,
    num_ranks: int,
    peer_capacity: int,
    use_pallas: bool = False,
    marshal: str = "sort",
    dest_clean: jax.Array = None,  # (C,) scatter mode: sanitized destination
    dest_rank: jax.Array = None,  # (C,) scatter mode: stable in-bucket rank
) -> jax.Array:
    """The padded exchange's send-side marshal — the round's ONE payload pass
    (isolated so ``benchmarks/run.py --profile`` can time it standalone).

    Sort mode gathers ``packed[perm[off[r] + s]]``; scatter mode scatters row
    ``i`` to ``dest_clean[i]·S + dest_rank[i]`` (rank ≥ S → §3.3 drop).
    Returns the ``(R, S, W)`` send buffer; rows past each segment's clamped
    count are garbage (sort) or zeros (scatter) and masked by the exchanged
    counts downstream.
    """
    R, S = num_ranks, peer_capacity
    cap = packed.shape[0]
    if marshal == "scatter":
        keep = (dest_clean < R) & (dest_rank < S)
        dstpos = jnp.where(keep, dest_clean * S + dest_rank, R * S)
        send_buf = scatter_rows(packed, dstpos, R * S, use_pallas=use_pallas)
        return send_buf.reshape(R, S, -1)
    off = jnp.cumsum(send_counts) - send_counts  # segment starts, sorted order
    r_idx = jnp.repeat(jnp.arange(R, dtype=jnp.int32), S)
    s_idx = jnp.tile(jnp.arange(S, dtype=jnp.int32), R)
    slotpos = jnp.clip(off[r_idx] + s_idx, 0, cap - 1)  # position in sorted order
    src = jnp.take(perm, slotpos)  # compose with the sort → source lane
    if use_pallas:
        from repro.kernels.marshal import ops as marshal_ops

        return marshal_ops.fused_marshal(packed, src, num_ranks=R, slot=S)
    return jnp.take(packed, src, axis=0).reshape(R, S, -1)


def padded_send_shard(
    packed, perm, send_counts, *,
    num_ranks, peer_capacity, shards, k,
    use_pallas=False, marshal="sort", dest_clean=None, dest_rank=None,
):
    """Micro-shard ``k`` of the padded marshal: slot rows ``[k·chunk,
    (k+1)·chunk)`` of every peer segment, as an ``(R, chunk, W)`` buffer.
    The union over shards is row-for-row the :func:`padded_send_buffer`
    layout, so the sharded exchange ships exactly the bulk wire bytes.
    """
    R, S = num_ranks, peer_capacity
    chunk = S // shards
    cap = packed.shape[0]
    if marshal == "scatter":
        inwin = (dest_rank >= k * chunk) & (dest_rank < (k + 1) * chunk)
        keep = (dest_clean < R) & inwin
        dstpos = jnp.where(keep, dest_clean * chunk + dest_rank - k * chunk, R * chunk)
        send = scatter_rows(packed, dstpos, R * chunk, use_pallas=use_pallas)
        return send.reshape(R, chunk, -1)
    off = jnp.cumsum(send_counts) - send_counts
    r_idx = jnp.repeat(jnp.arange(R, dtype=jnp.int32), chunk)
    s_idx = jnp.tile(jnp.arange(chunk, dtype=jnp.int32), R) + k * chunk
    slotpos = jnp.clip(off[r_idx] + s_idx, 0, cap - 1)
    src = jnp.take(perm, slotpos)
    if use_pallas:
        from repro.kernels.marshal import ops as marshal_ops

        return marshal_ops.fused_marshal(packed, src, num_ranks=R, slot=chunk)
    return jnp.take(packed, src, axis=0).reshape(R, chunk, -1)


# =====================================================================
# carried state + the five stage objects
# =====================================================================


@dataclasses.dataclass
class RoundState:
    """Explicit carried state a stage composition threads stage to stage.

    Built once per round from the marshal plan ``forward_work`` computed;
    every field a stage writes is named here rather than flowing through
    positional locals — that is what lets the same five stage objects
    assemble four backends (and lets :class:`Pipelined` interleave per-shard
    slices of them without re-deriving anything).
    """

    # marshal plan + payload (round inputs)
    packed: Any = None
    perm: Any = None
    send_counts: Any = None
    marshal: str = "sort"
    dest_clean: Any = None
    dest_rank: Any = None
    use_pallas: bool = False
    retain: bool = False
    age: Any = None

    # credit flow (the backpressure law, ISSUE 9) — all None/"open" unless
    # ForwardConfig(flow="credit"); the branches they feed are Python-static
    # so the open-flow lowering is byte-identical with or without them.
    flow: str = "open"
    credits: Any = None  # carried-in (R,) per-destination free estimates
    credit_allow: Any = None  # (R,) this round's per-destination grant
    credits_out: Any = None  # working/updated (R,) estimates (returned)
    my_free: Any = None  # this rank's advertised receive room this round
    stage_held: Any = None  # rows the current clamp held locally (telemetry)

    # clamp site (written by SpillExtract)
    clamped: Any = None  # flat: (R,) per-destination clamped counts
    allowed: Any = None  # tier: (G, A) surviving sub-segment sizes
    starts: Any = None  # tier: slot-local sub-segment starts
    send_drops: Any = None
    stage_drops: Any = None  # tier: this tier's clamp loss (telemetry reads it)
    pending: List[Any] = dataclasses.field(default_factory=list)
    front: Any = None
    spill_run: Any = None  # hierarchical: rows parked so far (spill front)
    drops: Any = None  # hierarchical: accumulated stage drops

    # sub-segment bookkeeping (hierarchical tiers)
    cnt: Any = None  # per-sub-segment counts in current buffer order
    base: Any = None  # per-sub-segment start offsets
    buf: Any = None  # current payload buffer (packed, then stage receives)
    n_rows: int = 0
    via_perm: bool = True  # True until the round's first payload pass
    seg_dest: Any = None  # retain: sub-segment → global destination map
    stage_pos: Any = None  # cached (A, S) source positions (sharded gathers)

    # exchange working set (Marshal / CountExchange / PayloadExchange)
    send_buf: Any = None
    payload_rows: int = 0  # rows the send buffers built so far hold (static)
    recv_counts: Any = None
    recv_buf: Any = None
    rcv: Any = None  # tier count exchange: (A, G) per-sub-segment survivors
    recv_blocks: List[Any] = dataclasses.field(default_factory=list)

    # ragged control plane (CountExchange kind="ragged"; recv_counts holds
    # the receive sizes): what each peer lets me deliver, where my segment
    # lands on it, and the current micro-shard's (sizes, offsets, recv)
    send_sizes: Any = None
    out_offsets: Any = None
    shard_plane: Any = None

    # results (Unmarshal)
    out: Any = None
    new_count: Any = None
    recv_drops: Any = None


@dataclasses.dataclass(frozen=True)
class CreditGate:
    """The backpressure law's sender gate (``flow="credit"``, ISSUE 9).

    Deterministically apportions each destination's one-round-stale
    advertised free space across the R contending senders: rank ``me`` may
    ship ``free[d] // R + (me < free[d] % R)`` rows to destination ``d`` —
    floor share plus rank-ordered residual.  The grants over all senders sum
    to EXACTLY the advertised space, so an incast can never overshoot the
    receiver, and every rank computes every grant locally from the same
    replicated credit vector (collective-free, deterministic across marshal
    modes and shard counts).  The grant tightens the §3.3 sender clamp in
    :class:`SpillExtract`; the un-credited tail of each segment follows the
    ``overflow="retain"`` spill path instead of shipping — no wire byte is
    spent on a row its receiver cannot admit.
    """

    axis_name: Any  # FLAT mesh axis name(s): global rank index
    num_ranks: int

    def __call__(self, st: RoundState) -> RoundState:
        me = jax.lax.axis_index(self.axis_name)
        free = jnp.clip(st.credits, 0)
        st.credit_allow = (
            free // self.num_ranks
            + (me < free % self.num_ranks).astype(jnp.int32)
        ).astype(jnp.int32)
        st.credits_out = st.credits
        return st

    def shard(self, st: RoundState, k: int) -> RoundState:
        # grants are shard-independent (the slot chunking happens downstream)
        return self(st) if k == 0 else st


@dataclasses.dataclass(frozen=True)
class SpillExtract:
    """The §3.3 clamp site.  ``kind="flat"``: the sender clamp of the flat
    backends (per-destination counts vs the ``slot`` budget).
    ``kind="tier"``: a hierarchical stage clamp (stacked sub-segments vs the
    tier's segment budget) — input LANES spill through the marshal plan while
    ``state.via_perm``, mid-route BUFFER rows park in place after it.
    Drop mode counts the cut; retain mode extracts it as a pending block."""

    num_ranks: int
    capacity: int
    slot: int
    retain: bool = False
    kind: str = "flat"
    extent: int = 0  # tier: A_l, the stage's axis size
    reserve: int = 0  # credit: receive room withheld for local emissions
    axis_name: Any = None  # ragged credit: the mesh axis (this rank's index)

    def __call__(self, st: RoundState) -> RoundState:
        if self.kind == "tier":
            return self._tier(st)
        if self.kind == "ragged":
            return self._ragged(st)
        S = self.slot
        st.clamped = jnp.minimum(st.send_counts, S)
        if st.flow == "credit":
            # the credit gate's per-destination grant tightens the slot
            # clamp; the extra cut rows ride the same retain spill below
            st.clamped = jnp.minimum(st.clamped, st.credit_allow)
        send_drops = jnp.sum(st.send_counts - st.clamped)
        if self.retain:
            # The clamp's cut rows are the per-destination segment TAILS of
            # the marshalled order — extract them with the same positional
            # arithmetic the send gather uses (one extra (C, W) gather, no
            # conditional, no mask machinery) and reserve the queue front
            # for them.
            if st.age is None:
                st.age = jnp.zeros((st.packed.shape[0],), jnp.int32)
            off = jnp.cumsum(st.send_counts) - st.send_counts
            st.pending.append(lanes_spill(
                st.packed, st.perm, st.age, st.clamped,
                st.send_counts - st.clamped, off + st.clamped, send_drops,
                num_ranks=self.num_ranks, marshal=st.marshal,
                dest_clean=st.dest_clean, dest_rank=st.dest_rank,
            ))
            st.front = jnp.minimum(send_drops, self.capacity)
            st.stage_held = send_drops
            if st.flow == "credit":
                # my advertisement: the receive room left behind the spill
                # front, MINUS the reserve withheld for next round's local
                # emissions.  Senders use it one round stale — with the
                # drive's emission gate (retained + emitted + advert ≤
                # capacity) next round's spill front can never grow into
                # the room advertised here, so granted arrivals always fit:
                # the flat credit path is receiver-drop-free by construction.
                # The liveness floor (min(room, R)) keeps up to one credit
                # PER SENDER alive whenever room exists: the floor never
                # exceeds room, so advert + front still never exceeds
                # capacity (the drop-free proof is untouched), but a backlog
                # that ate into the emission reserve can no longer pin the
                # advert at zero — and because the floor covers all R
                # senders, the rank-ordered residual cannot starve high
                # ranks when every queue saturates at once (a floor of 1
                # would hand the single credit to rank 0 every round and
                # collapse sustained-overload drain to ~1 row/round).
                room = self.capacity - st.front
                st.my_free = jnp.maximum(
                    jnp.clip(room - self.reserve, 0),
                    jnp.minimum(room, self.num_ranks),
                ).astype(jnp.int32)
            send_drops = jnp.zeros_like(send_drops)
        st.send_drops = send_drops
        return st

    def _ragged(self, st: RoundState) -> RoundState:
        # the clamp is the replicated control plane's allowance
        # (``send_sizes``), already tightened by any credit grant
        send_drops = jnp.sum(st.send_counts - st.send_sizes)
        if self.retain:
            if st.age is None:
                st.age = jnp.zeros((st.packed.shape[0],), jnp.int32)
            st.pending.append(lanes_spill(
                st.packed, st.perm, st.age, st.send_sizes,
                st.send_counts - st.send_sizes, st.base + st.send_sizes,
                send_drops, num_ranks=self.num_ranks, marshal=st.marshal,
                dest_clean=st.dest_clean, dest_rank=st.dest_rank,
            ))
            st.front = jnp.minimum(send_drops, self.capacity)
            st.stage_held = send_drops
            if st.flow == "credit":
                # fresh advert: the room left behind the reserved spill
                # front, minus the reserve, floored at one row per sender
                # whenever room exists (liveness — see the flat advert)
                me = jax.lax.axis_index(self.axis_name)
                room = self.capacity - st.front
                st.credits_out = st.credits_out.at[me].set(
                    jnp.maximum(
                        jnp.clip(room - self.reserve, 0),
                        jnp.minimum(room, self.num_ranks),
                    ).astype(jnp.int32)
                )
            send_drops = jnp.zeros_like(send_drops)
        st.send_drops = send_drops
        return st

    def _tier(self, st: RoundState) -> RoundState:
        A, S, R = self.extent, self.slot, self.num_ranks
        cnt2d = st.cnt.reshape(R // A, A)  # rows: buffer order, cols: peer digit
        cnt_eff = cnt2d
        if st.flow == "credit" and st.via_perm:
            # The route's FIRST clamp is the credit gate: at stage one the
            # buffer is in destination order, so the per-destination grant
            # reshapes straight onto the sub-segment grid.  Gating here
            # means the un-credited tail never enters ANY fabric tier — a
            # saturated node throttles the slow/DCN stage at the source,
            # not just the last hop.
            cnt_eff = jnp.minimum(cnt2d, st.credit_allow.reshape(R // A, A))
        st.allowed, st.starts = clamp_subsegments(cnt_eff, S)
        stage_drops = jnp.sum(cnt2d - st.allowed)
        if self.retain:
            alf = st.allowed.reshape(-1)  # flat, current buffer/destination order
            if st.via_perm:
                # Sender-clamp spill from the INPUT lanes: the cut rows are
                # the per-destination segment tails of the sorted order
                # (allowed is indexed [d // A, d % A], so its row-major
                # flatten is the per-destination allowance; at the first
                # stage buffer order == destination order, and the stable
                # in-bucket rank against the full destination IS the
                # in-sub-segment rank — the scatter marshal's equivalence).
                st.pending.append(lanes_spill(
                    st.packed, st.perm, st.age, alf, st.cnt - alf,
                    st.base + alf, stage_drops, num_ranks=R,
                    marshal=st.marshal, dest_clean=st.dest_clean,
                    dest_rank=st.dest_rank,
                ))
            else:
                # Mid-route park: buffer rows whose sub-segment tail this
                # stage cut stay HERE; destination routing resumes them next
                # round.  Tails are read straight out of the stage buffer
                # (marshal-mode-agnostic: positions, not lanes) and
                # re-addressed through ``seg_dest``; ages restart at 1 (age
                # cannot ride the wire without changing the payload bytes).
                k, pos = spill_positions(self.capacity, st.cnt - alf, st.base + alf)
                src = jnp.clip(pos, 0, st.n_rows - 1)
                st.pending.append((
                    jnp.take(st.buf, src, axis=0),
                    jnp.take(st.seg_dest, k),
                    jnp.ones((self.capacity,), jnp.int32),
                    stage_drops,
                ))
            st.spill_run = st.spill_run + stage_drops
            st.stage_held = stage_drops
            stage_drops = jnp.zeros_like(stage_drops)
        st.stage_drops = stage_drops
        st.drops = st.drops + stage_drops
        return st


def _declare_rows(st: RoundState) -> RoundState:
    """Count the rows of the send buffer just built into the round's payload
    pass and declare the running total as ``rafi_payload_rows_per_forward``
    (the last stage of the last program traced leaves the whole round's)."""
    st.payload_rows += math.prod(st.send_buf.shape[:-1])
    OM.REGISTRY.set_gauge(
        OM.PAYLOAD_ROWS, st.payload_rows,
        "rows one rank's payload pass moves per forward, all stages",
    )
    return st


@dataclasses.dataclass(frozen=True)
class Marshal:
    """The send-side payload pass.  ``kind="flat"``: the padded (R, S, W)
    peer-slot layout.  ``kind="tier"``: a hierarchical stage's (A, S, W)
    layout — sort permutation composed into the first stage's gather, or the
    sort-free scatter straight into sub-segment slots; later stages gather
    from the received buffer.  ``.shard(st, k)`` builds only slot rows
    ``[k·chunk, (k+1)·chunk)`` of every segment.  ``kind="ragged"``: the
    (C, W) payload in contiguous destination segments (not sharded: only
    the wire movement is)."""

    num_peers: int  # flat: R ranks; tier: A_l, the stage's axis size
    slot: int
    shards: int = 1
    kind: str = "flat"
    num_ranks: int = 0  # tier: the global rank count R

    def __call__(self, st: RoundState) -> RoundState:
        if self.kind == "tier":
            st = self._tier(st, None)
        elif self.kind == "ragged":
            st.send_buf = self._ragged(st)
        else:
            st.send_buf = padded_send_buffer(
                st.packed, st.perm, st.send_counts,
                num_ranks=self.num_peers, peer_capacity=self.slot,
                use_pallas=st.use_pallas, marshal=st.marshal,
                dest_clean=st.dest_clean, dest_rank=st.dest_rank,
            )
        return _declare_rows(st)

    def shard(self, st: RoundState, k: int) -> RoundState:
        if self.kind == "tier":
            st = self._tier(st, k)
        else:
            st.send_buf = padded_send_shard(
                st.packed, st.perm, st.send_counts,
                num_ranks=self.num_peers, peer_capacity=self.slot,
                shards=self.shards, k=k, use_pallas=st.use_pallas,
                marshal=st.marshal, dest_clean=st.dest_clean,
                dest_rank=st.dest_rank,
            )
        return _declare_rows(st)

    def _ragged(self, st: RoundState) -> jax.Array:
        # contiguous per-destination segments: the (C, W) payload placed once
        # into destination order — a sort-free scatter to ``base[dest] +
        # rank``, or the gather through the sort permutation
        C = st.packed.shape[0]
        if st.marshal == "scatter":
            keep = st.dest_clean < self.num_peers
            pos = st.base[jnp.clip(st.dest_clean, 0, self.num_peers - 1)] + st.dest_rank
            dstpos = jnp.where(keep, pos, C)
            return scatter_rows(st.packed, dstpos, C, use_pallas=st.use_pallas)
        return jnp.take(st.packed, st.perm, axis=0)

    def _gather(self, st, buf, rows, n_slots, slot):
        W = buf.shape[-1]
        if st.use_pallas:
            from repro.kernels.marshal import ops as marshal_ops

            return marshal_ops.fused_marshal(buf, rows, num_ranks=n_slots, slot=slot)
        return jnp.take(buf, rows, axis=0).reshape(n_slots, slot, W)

    def _tier(self, st: RoundState, k: Optional[int]) -> RoundState:
        A, S, R = self.num_peers, self.slot, self.num_ranks
        chunk = S if k is None else S // self.shards
        lo = 0 if k is None else k * chunk
        W = st.packed.shape[-1]
        if st.via_perm and st.marshal == "scatter":
            # first non-trivial stage, sort-free: scatter each row straight
            # into the stage layout — the payload's single local pass of the
            # round.  Sub-segment (rest, d_l) holds exactly one destination,
            # so the in-bucket rank IS the in-sub-segment position; ranks at
            # or past the stage clamp land in the trash slot (§3.3).
            row = jnp.clip(st.dest_clean // A, 0, R // A - 1)
            col = jnp.clip(st.dest_clean % A, 0, A - 1)
            keep = (st.dest_clean < R) & (st.dest_rank < st.allowed[row, col])
            if k is None:
                dstpos = jnp.where(
                    keep, col * S + st.starts[row, col] + st.dest_rank, A * S
                )
            else:
                s_in = st.starts[row, col] + st.dest_rank  # slot pos in column
                keep = keep & (s_in >= lo) & (s_in < lo + chunk)
                dstpos = jnp.where(keep, col * chunk + (s_in - lo), A * chunk)
            send = scatter_rows(st.packed, dstpos, A * chunk, use_pallas=st.use_pallas)
            st.send_buf = send.reshape(A, chunk, W)
            return st
        if k is None or st.stage_pos is None:
            st.stage_pos = subsegment_gather(
                st.allowed, st.starts, st.base.reshape(R // A, A), S
            )
        pos = st.stage_pos if k is None else st.stage_pos[:, lo:lo + chunk]
        if st.via_perm:
            # first non-trivial stage: compose the sort permutation straight
            # into the send gather — the payload's single read of the round
            C = st.packed.shape[0]
            rows = jnp.take(st.perm, jnp.clip(pos, 0, C - 1).reshape(-1))
            st.send_buf = self._gather(st, st.packed, rows, A, chunk)
        else:
            rows = jnp.clip(pos, 0, st.n_rows - 1).reshape(-1)
            st.send_buf = self._gather(st, st.buf, rows, A, chunk)
        return st


@dataclasses.dataclass(frozen=True)
class CountExchange:
    """The control-plane collective.  ``kind="ragged"``: all_gather of the
    (R,) send counts into the full count matrix, from which every rank
    derives the ragged control plane.  ``kind="flat"``: all_to_all of the
    clamped per-peer counts.  ``kind="tier"``: all_to_all of the per-sub-
    segment survivor counts (so the receiver can address every sub-segment
    of each incoming block).  ``kind="final"``: per-source-group totals —
    blocks are contiguous prefixes at the last tier.  Sharded flat/final
    runs repeat the FULL vector per shard (each micro-shard's chain derives
    its own landing offsets — control-plane bytes ×S, payload bytes exact);
    sharded tier runs ship each shard's own chunk counts and sum them back
    on receive.

    Credit flow (ISSUE 9): with ``st.flow == "credit"`` the count matrix
    widens by ONE i32 column carrying the credit advertisement — the SAME
    collective the round already runs, nothing payload-sized, so the budget
    law's inventory is unchanged.  Flat: every rank ships its own receive
    room and reads back all R advertisements.  Hierarchical: credits
    aggregate per tier — at tier ``l`` each peer ships the MIN cached
    estimate over its tier-l subtree (the ranks its already-run faster-tier
    exchanges aggregated: ``r // stride_l == me // stride_l``), the final
    tier folding in its own fresh post-spill headroom first; receivers fan
    the aggregate back over the peer's subtree.  A saturated rank drags its
    node's aggregate down within one round, throttling remote senders at
    the route's FIRST clamp — before the slow fabric.  Conservative by
    construction (a min under-, never over-states any member's room; only
    staleness can overshoot, absorbed by the retain spill)."""

    axis_name: Any
    kind: str = "flat"
    shards: int = 1
    slot: int = 0  # tier: full per-peer slot rows (shard chunking)
    num_ranks: int = 0  # credit: global rank count R
    stride: int = 1  # credit tier: Π level_sizes[l+1:] — the tier's stride
    capacity: int = 0  # credit final: queue capacity (fresh headroom)
    flat_axes: Any = None  # credit hierarchical: flattened axis names
    reserve: int = 0  # credit: receive room withheld for local emissions

    def __call__(self, st: RoundState) -> RoundState:
        if self.kind == "ragged":
            return self._ragged(st)
        if self.kind == "tier":
            if st.flow == "credit":
                st.rcv = self._credit_recv(st, st.allowed.T)
            else:
                st.rcv = a2a(st.allowed.T, self.axis_name)  # (A, G): [src digit, sub-seg]
        elif self.kind == "final":
            sums = jnp.sum(st.allowed, axis=0)[:, None]
            if st.flow == "credit":
                st.recv_counts = self._credit_recv(st, sums).reshape(-1)
            else:
                st.recv_counts = a2a(sums, self.axis_name).reshape(-1)
        else:
            if st.flow == "credit":
                # widen (R, 1) → (R, 2): column 1 carries my receive room to
                # every peer; received column 1 is all R advertisements
                advert = jnp.broadcast_to(st.my_free, st.clamped.shape)
                wide = jnp.stack([st.clamped, advert.astype(st.clamped.dtype)], axis=1)
                recv = a2a(wide, self.axis_name)
                st.recv_counts = recv[:, 0]
                st.credits_out = recv[:, 1]
            else:
                st.recv_counts = a2a(st.clamped[:, None], self.axis_name).reshape(-1)
        return st

    def _ragged(self, st: RoundState) -> RoundState:
        # one all-gather of the (R,) send counts buys the whole control
        # plane: every rank derives every clamp and landing offset locally
        me = jax.lax.axis_index(self.axis_name)
        if st.flow == "credit":
            # the granted counts, widened by this rank's own-entry advert
            st.clamped = jnp.minimum(st.send_counts, st.credit_allow)
            wide = jnp.concatenate(
                [st.clamped, jnp.take(st.credits, me)[None].astype(st.clamped.dtype)]
            )
            gath = jax.lax.all_gather(wide, self.axis_name)  # (R, R+1)
            st.cnt = gath[:, :self.num_ranks]
            st.credits_out = gath[:, self.num_ranks].astype(jnp.int32)
        else:
            st.clamped = st.send_counts
            st.cnt = jax.lax.all_gather(st.send_counts, self.axis_name)
        st.send_sizes, st.out_offsets, st.recv_counts = ragged_control_plane(
            st.cnt, me, self.capacity
        )
        return st

    def _credit_recv(self, st: RoundState, counts: jax.Array) -> jax.Array:
        """Run the tier/final count a2a widened with the advertisement
        column, apply the received aggregates to ``st.credits_out``, and
        return the un-widened count block."""
        A = counts.shape[0]
        me = jax.lax.axis_index(self.flat_axes)
        if self.kind == "final":
            # fold my own fresh post-spill headroom into the carried view
            # before aggregating (spill_run is complete at the final tier —
            # this is exactly the room the final Unmarshal grants arrivals)
            room = jnp.clip(self.capacity - st.spill_run, 0)
            # reserve withheld for local emissions + the per-sender liveness
            # floor (see SpillExtract's flat advert)
            fresh = jnp.maximum(
                jnp.clip(room - self.reserve, 0),
                jnp.minimum(room, self.num_ranks),
            ).astype(jnp.int32)
            st.my_free = fresh
            st.credits_out = st.credits_out.at[me].set(fresh)
        r = jnp.arange(self.num_ranks, dtype=jnp.int32)
        sub = (r // self.stride) == (me // self.stride)  # my tier-l subtree
        adv = jnp.min(
            jnp.where(sub, st.credits_out, jnp.int32(self.capacity))
        )
        wide = jnp.concatenate(
            [counts, jnp.full((A, 1), adv, counts.dtype)], axis=1
        )
        recv = a2a(wide, self.axis_name)
        # peer a's aggregate covers ranks sharing my slower digits with
        # digit_l = a; my own subtree keeps its fresher per-rank entries
        dig = (r // self.stride) % A
        me_dig = (me // self.stride) % A
        blk = (r // (self.stride * A)) == (me // (self.stride * A))
        upd = blk & (dig != me_dig)
        st.credits_out = jnp.where(
            upd, jnp.take(recv[:, -1], dig), st.credits_out
        )
        return recv[:, :-1]

    def shard(self, st: RoundState, k: int) -> RoundState:
        if self.kind == "ragged":
            # shard 0 rides the bulk control plane; every later shard runs
            # its own count collective and replicated control plane
            if k == 0:
                st.shard_plane = (st.send_sizes, st.out_offsets, st.recv_counts)
            else:
                cnt_k = jax.lax.all_gather(st.clamped, self.axis_name)
                me = jax.lax.axis_index(self.axis_name)
                st.shard_plane = ragged_control_plane(cnt_k, me, self.capacity)
            return st
        if self.kind != "tier":
            return self(st)
        # Ship each shard's OWN chunk counts; the receiver sums them back to
        # the full survivor vector: Σ_k clip(allowed − k·chunk, 0, chunk) =
        # allowed.  Keeps every shard's count collective live (the flat and
        # final kinds instead repeat the full vector — each shard derives
        # its landing offsets without waiting on siblings).
        chunk = self.slot // self.shards
        allowed_k = jnp.clip(st.allowed - k * chunk, 0, chunk)
        if st.flow == "credit":
            # same widened collective per shard; the advertisement column is
            # shard-independent, so only shard 0's read updates the credits
            saved = st.credits_out
            part = self._credit_recv(st, allowed_k.T)
            if k > 0:
                st.credits_out = saved
        else:
            part = a2a(allowed_k.T, self.axis_name)
        st.rcv = part if k == 0 else st.rcv + part
        return st


@dataclasses.dataclass(frozen=True)
class PayloadExchange:
    """The payload collective: ONE all_to_all of the (current shard's) send
    buffer.  With ``collect=True`` (sharded non-final tiers) the received
    blocks are accumulated for :class:`Reassemble`.  ``kind="ragged"``: ONE
    ``ragged_all_to_all`` of the contiguous segments straight into the
    (capacity, W) receive queue; shard ``k`` ships rows ``[k·chunk,
    (k+1)·chunk)`` of every segment at the bulk landing offsets."""

    axis_name: Any
    collect: bool = False
    kind: str = "padded"
    capacity: int = 0  # ragged: receive queue rows
    shards: int = 1

    def __call__(self, st: RoundState) -> RoundState:
        if self.kind == "ragged":
            out = jnp.zeros((self.capacity, st.send_buf.shape[1]), st.send_buf.dtype)
            st.out = jax.lax.ragged_all_to_all(
                st.send_buf, out, input_offsets=st.base,
                send_sizes=st.send_sizes, output_offsets=st.out_offsets,
                recv_sizes=st.recv_counts, axis_name=self.axis_name,
            )
            return st
        st.recv_buf = a2a(st.send_buf, self.axis_name)
        if self.collect:
            st.recv_blocks.append(st.recv_buf)
        return st

    def shard(self, st: RoundState, k: int) -> RoundState:
        if self.kind != "ragged":
            return self(st)
        if k == 0:
            st.out = jnp.zeros((self.capacity, st.send_buf.shape[1]), st.send_buf.dtype)
        chunk = self.capacity // self.shards
        s_ss, s_oo, s_rs = st.shard_plane
        st.out = jax.lax.ragged_all_to_all(
            st.send_buf, st.out,
            input_offsets=st.base + jnp.minimum(k * chunk, s_ss),
            send_sizes=jnp.clip(s_ss - k * chunk, 0, chunk),
            output_offsets=s_oo + jnp.minimum(k * chunk, s_ss),
            recv_sizes=jnp.clip(s_rs - k * chunk, 0, chunk),
            axis_name=self.axis_name,
        )
        return st


@dataclasses.dataclass(frozen=True)
class Unmarshal:
    """Receive-side compaction into the destination queue: each queue row
    gathers its source row of the padded receive blocks
    (:func:`compact_blocks`).  ``kind="flat"``
    reads the spill front SpillExtract reserved; ``kind="final"`` (the last
    hierarchical tier) reserves the accumulated mid-route spill run;
    ``kind="ragged"`` only opens the spill front behind arrivals the
    collective already compacted.  Sharded mode accumulates each shard's
    rows at their bulk positions (:func:`compact_shard`) and closes the
    count/drop accounting on the last shard."""

    capacity: int
    shards: int = 1
    slot: int = 0  # full per-peer slot rows (shard row offsets)
    kind: str = "flat"

    def _front(self, st: RoundState):
        if self.kind == "final":
            return jnp.minimum(st.spill_run, self.capacity) if st.retain else None
        return st.front

    def __call__(self, st: RoundState) -> RoundState:
        if self.kind == "ragged":
            return self._ragged(st)
        st.out, st.new_count, st.recv_drops = compact_blocks(
            st.recv_buf, st.recv_counts, self.capacity,
            use_pallas=st.use_pallas, front=self._front(st),
        )
        return st

    def _ragged(self, st: RoundState) -> RoundState:
        # the collective already wrote the arrivals compacted; in retain
        # mode open the spill front by one local gather (the landing
        # offsets are fixed by the replicated control plane) — arrivals
        # pushed past capacity are the receiver-admission loss
        st.new_count = jnp.sum(st.recv_counts)
        st.recv_drops = jnp.zeros((), st.send_counts.dtype)
        if st.retain:
            lane = jnp.arange(self.capacity, dtype=jnp.int32)
            st.out = jnp.take(st.out, jnp.clip(lane - st.front, 0, self.capacity - 1), axis=0)
            admitted = jnp.minimum(st.new_count, self.capacity - st.front)
            st.recv_drops = st.new_count - admitted
            st.new_count = admitted
        return st

    def shard(self, st: RoundState, k: int) -> RoundState:
        chunk = self.slot // self.shards
        if k == 0:
            W = st.recv_buf.shape[-1]
            st.out = jnp.zeros((self.capacity, W), st.recv_buf.dtype)
        st.out = compact_shard(
            st.out, st.recv_buf, st.recv_counts, self.capacity,
            row_offset=k * chunk, front=self._front(st),
        )
        if k == self.shards - 1:
            total_recv = jnp.sum(st.recv_counts)
            front = self._front(st)
            room = (
                self.capacity if front is None
                else jnp.clip(self.capacity - front, 0)
            )
            st.new_count = jnp.minimum(total_recv, room)
            st.recv_drops = total_recv - st.new_count
        return st


@dataclasses.dataclass(frozen=True)
class Reassemble:
    """Stitch a sharded tier's received chunk blocks back into the bulk
    (A, S, W) stage buffer: ``full[a, k·chunk + s] = recv_k[a, s]`` — pure
    local data movement, zero collectives, bit-exact with the bulk receive
    by construction."""

    extent: int
    slot: int

    def __call__(self, st: RoundState) -> RoundState:
        A, S = self.extent, self.slot
        W = st.recv_blocks[0].shape[-1]
        stacked = jnp.stack(st.recv_blocks, axis=1)  # (A, shards, chunk, W)
        st.recv_buf = stacked.reshape(A, S, W)
        st.recv_blocks = []
        return st


@dataclasses.dataclass(frozen=True)
class AdvanceTier:
    """Between hierarchical stages: reinterpret the received blocks as the
    next tier's buffer and derive its sub-segment counts/offsets from the
    count exchange — new buffer order ``(s_l, previous order − d_l)``."""

    extent: int
    slot: int
    axis_name: Any
    retain: bool = False
    num_ranks: int = 0

    def __call__(self, st: RoundState) -> RoundState:
        A, S, R = self.extent, self.slot, self.num_ranks
        W = st.recv_buf.shape[-1]
        st.cnt = st.rcv.reshape(-1)  # new buffer order: (s_l, previous − d_l)
        st.base = (
            jnp.cumsum(st.rcv, axis=1) - st.rcv
            + jnp.arange(A, dtype=jnp.int32)[:, None] * S
        ).reshape(-1)
        st.buf = st.recv_buf.reshape(A * S, W)
        st.n_rows = A * S
        st.via_perm = False
        st.stage_pos = None
        if self.retain:
            # Sub-segment k of the NEW buffer order (s_l, rest) holds the
            # destination whose digit l equals MINE — shared with every peer
            # of the remaining (slower) stages, so the map stays
            # rank-consistent with zero extra communication.
            me_l = jax.lax.axis_index(self.axis_name)
            st.seg_dest = jnp.tile(st.seg_dest.reshape(R // A, A)[:, me_l], A)
        return st


@dataclasses.dataclass(frozen=True)
class Pipelined:
    """Software-pipeline shard-aware stages: issue the per-shard chains
    interleaved (marshal k → counts k → payload k → unmarshal k → marshal
    k+1 → …).  The chains share only the output-queue accumulator, so an
    async-collective backend overlaps shard k's payload collective with
    shard k−1's unmarshal and shard k+1's marshal — the overlap law's
    schedule."""

    stages: Tuple[Any, ...]
    shards: int

    def __call__(self, st: RoundState) -> RoundState:
        for k in range(self.shards):
            for stage in self.stages:
                with _scope(stage):
                    st = stage.shard(st, k)
        return st


def _scope(stage):
    """The stage's device scope, ``rafi.<stage>`` from its class name
    (``SpillExtract`` → ``rafi.spill_extract``): every op the stage emits
    carries it in its ``op_name`` metadata."""
    name = re.sub(r"(?<!^)(?=[A-Z])", "_", type(stage).__name__).lower()
    return jax.named_scope(f"rafi.{name}")


def compose(*stage_seq):
    """Run stages in sequence over a :class:`RoundState` — the bulk graph —
    each under its device scope (a :class:`Pipelined` group scopes each
    shard's stages itself)."""

    def run(st: RoundState) -> RoundState:
        for stage in stage_seq:
            if isinstance(stage, Pipelined):
                st = stage(st)
                continue
            with _scope(stage):
                st = stage(st)
        return st

    return run
