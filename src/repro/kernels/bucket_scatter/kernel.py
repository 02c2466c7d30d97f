"""Pallas kernels: the fused single-pass bucket-scatter marshal.

``rank_and_histogram`` — the counting-sort control plane, replacing key
pack + ``jax.lax.sort``: one pass over the destination vector yields the
sanitized destination, each lane's stable rank among earlier lanes of the
SAME destination, and the per-destination histogram (= the exchange's send
counts, for free).  ``base[dest] + rank`` then reproduces the §4.2.1 stable
sort placement exactly — no key materialization, no O(C log C) sort.

The destination vector is viewed as ``(C/128, 128)`` — lane ``i`` at row
``i // 128``, column ``i % 128`` — and walked in blocks of ``block_rows``
rows.  Per bucket ``b`` the exclusive same-bucket count of every lane is
three MXU products of the 0/1 mask ``m_b``: ``m_b @ U`` (earlier columns of
the same row, ``U`` strictly upper-triangular), ``L @ (m_b @ 1)`` (whole
earlier rows of the block, ``L`` strictly lower-triangular), plus the
running bucket total of all earlier blocks, carried across the sequential
grid in the revisited ``(8, 128)`` histogram block (bucket ``b`` at flat
position ``b``).  The 0/1 operands are exact in bfloat16 and every partial
count is an integer below 2²⁴, exact in the float32 accumulator.

``scatter_rows`` — the single payload pass: ``out[dstpos[i]] = src[i]``.
The caller composes the bucket plan with the send layout
(``dstpos = base[dest] + rank``).  Rows move by DMA from HBM to HBM, one DMA
per row; a trash row past the last slot absorbs dropped lanes (invalid
destination, or rank beyond the segment clamp — the §3.3 drop rule) and is
cut from the result.  The output starts as a zero buffer aliased into the
kernel, so untouched slots are zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANES, call, pad_lanes, sds
from repro.kernels.marshal.kernel import IDX_BLOCK

_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
MAX_BUCKETS = 8 * LANES  # the (8, 128) histogram block


def lane_rows(x: jax.Array, block_rows: int, fill) -> jax.Array:
    """``(C,)`` → ``(rows, 128)`` with ``rows`` a multiple of ``block_rows``,
    padded with ``fill``."""
    n = x.shape[0]
    per_block = block_rows * LANES
    n_pad = -(-n // per_block) * per_block
    if n_pad != n:
        x = jnp.concatenate([x, jnp.full((n_pad - n,), fill, x.dtype)])
    return x.reshape(n_pad // LANES, LANES)


def block_rows_for(cap: int, block_rows: int) -> int:
    """Rows per grid step: ``block_rows`` (a multiple of 8), shrunk to what
    ``cap`` lanes need."""
    if block_rows % 8:
        raise ValueError(f"block_rows ({block_rows}) must be a multiple of 8")
    need = -(-cap // LANES)
    return min(block_rows, -(-need // 8) * 8)


def prefix_operands(block_rows: int):
    """The triangular MXU operands: ``U[j, i] = j < i`` (128×128),
    ``L[t, s] = s < t`` (rows×rows) and the all-ones row-sum matrix."""
    li = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    ri = jax.lax.broadcasted_iota(jnp.int32, (block_rows, block_rows), 0)
    rj = jax.lax.broadcasted_iota(jnp.int32, (block_rows, block_rows), 1)
    upper = (li < lj).astype(jnp.bfloat16)
    lower = (ri > rj).astype(jnp.bfloat16)
    ones = jnp.ones((LANES, LANES), jnp.bfloat16)
    return upper, lower, ones


def _mm(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def exclusive_prefix(m, upper, lower, ones):
    """Exclusive prefix count of the 0/1 bf16 mask ``m`` (rows, 128) in lane
    order within the block, and the block total in every lane of a
    ``(1, 128)`` row (both float32, exact)."""
    rowsum = _mm(m, ones)  # every lane holds its row's total
    excl = _mm(m, upper) + _mm(lower, rowsum.astype(jnp.bfloat16))
    return excl, jnp.sum(rowsum, axis=0, keepdims=True)


def lane_total(x):
    """Sum of an ``(r, 128)`` int32 block, in every lane of a ``(1, 128)``
    row.  Mosaic broadcasts along lanes or along sublanes, never both at
    once, so a total is kept as a row and widened one axis at a time."""
    row = jnp.sum(x, axis=0, keepdims=True)
    return jnp.broadcast_to(jnp.sum(row, axis=1, keepdims=True), row.shape)


def lane_index(step, block_rows):
    row = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1)
    return (step * block_rows + row) * LANES + col


def _rank_hist_kernel(
    count_ref, dest_ref, dclean_ref, rank_ref, hist_ref, *, num_ranks, block_rows
):
    step = pl.program_id(0)
    lane = lane_index(step, block_rows)
    d = dest_ref[...]
    valid = (lane < count_ref[0]) & (d >= 0) & (d < num_ranks)
    d_clean = jnp.where(valid, d, num_ranks)
    dclean_ref[...] = d_clean

    @pl.when(step == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    upper, lower, ones = prefix_operands(block_rows)
    bucket_pos = lane_index(0, 8)  # flat position in the (8, 128) histogram

    def bucket(b, rank):
        hit = d_clean == b
        excl, total = exclusive_prefix(hit.astype(jnp.bfloat16), upper, lower, ones)
        at_b = bucket_pos == b
        hist = hist_ref[...]
        # bucket b's total over all earlier blocks, in every lane
        before = lane_total(jnp.where(at_b, hist, 0))
        rank = jnp.where(hit, excl.astype(jnp.int32) + before, rank)
        hist_ref[...] = hist + jnp.where(at_b, total.astype(jnp.int32), 0)
        return rank

    rank_ref[...] = jax.lax.fori_loop(
        0, num_ranks + 1, bucket, jnp.zeros((block_rows, LANES), jnp.int32)
    )


@functools.partial(
    jax.jit, static_argnames=("num_ranks", "block_rows", "interpret")
)
def rank_and_histogram(
    dest: jax.Array,
    count: jax.Array,
    *,
    num_ranks: int,
    block_rows: int = 256,
    interpret: bool = False,
):
    """Returns ``(d_clean (C,) i32, rank (C,) i32, hist (R+1,) i32)``; invalid
    lanes get destination R and rank among the R-bucket tail.

    Counts ride the MXU in float32, exact only below 2**24 — larger
    capacities raise (the scatter analogue of ``pack_keys``'s 32-bit key
    overflow ValueError; use the XLA path, which scans in int32).
    """
    cap = dest.shape[0]
    if cap > 1 << 24:
        raise ValueError(
            f"capacity {cap} exceeds the float32-exact count range (2**24); "
            "in-bucket ranks would silently collide — use the XLA path "
            "(core.sorting.destination_rank)"
        )
    if num_ranks + 1 > MAX_BUCKETS:
        raise ValueError(
            f"num_ranks + 1 ({num_ranks + 1}) exceeds the kernel's "
            f"{MAX_BUCKETS}-bucket histogram block"
        )
    rows = block_rows_for(cap, block_rows)
    d2 = lane_rows(dest.astype(jnp.int32), rows, -1)
    cnt = jnp.minimum(count.astype(jnp.int32), cap).reshape(1)
    n_rows = d2.shape[0]
    blk = pl.BlockSpec((rows, LANES), lambda i: (i, 0))

    def kernel(cnt, d2):
        return pl.pallas_call(
            functools.partial(
                _rank_hist_kernel, num_ranks=num_ranks, block_rows=rows
            ),
            grid=(n_rows // rows,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk],
            out_specs=[blk, blk, pl.BlockSpec((8, LANES), lambda i: (0, 0))],
            out_shape=[
                sds(d2.shape, jnp.int32, d2, cnt),
                sds(d2.shape, jnp.int32, d2, cnt),
                sds((8, LANES), jnp.int32, d2, cnt),
            ],
            compiler_params=_SEQUENTIAL,
            interpret=interpret,
        )(cnt, d2)

    d_clean, rank, hist = call(kernel, cnt, d2, interpret=interpret)
    hist = hist.reshape(-1)[: num_ranks + 1]
    # padding lanes fell into the invalid bucket R; they are not lanes
    hist = hist.at[num_ranks].add(cap - d2.size)
    return d_clean.reshape(-1)[:cap], rank.reshape(-1)[:cap], hist


def _scatter_rows_kernel(idx_ref, src_ref, zero_ref, out_ref, sem, *, tile):
    del zero_ref  # aliased to out_ref: untouched slots stay zero
    base = pl.program_id(0) * tile

    def start(t, carry):
        pltpu.make_async_copy(
            src_ref.at[pl.ds(base + t, 1)], out_ref.at[pl.ds(idx_ref[t], 1)], sem
        ).start()
        return carry

    def wait(t, carry):  # every copy moves one row: any one-row descriptor
        pltpu.make_async_copy(
            src_ref.at[pl.ds(0, 1)], out_ref.at[pl.ds(0, 1)], sem
        ).wait()
        return carry

    jax.lax.fori_loop(0, tile, start, 0)
    jax.lax.fori_loop(0, tile, wait, 0)


@functools.partial(jax.jit, static_argnames=("num_slots", "interpret"))
def scatter_rows(
    src: jax.Array,  # (N, D) packed payload rows
    dstpos: jax.Array,  # (N,) int32 send-layout row per source row
    *,
    num_slots: int,
    interpret: bool = False,
) -> jax.Array:
    """The fused single-pass scatter marshal: ``out[dstpos[i]] = src[i]``.

    ``dstpos`` is the bucket plan composed with the send layout
    (``base[dest] + rank``), so this one scatter subsumes what used to be
    key-sort-then-segment-gather — each payload row is read exactly once and
    written exactly once.  Rows with ``dstpos`` at/past ``num_slots`` (or
    negative) land in a trash row that is cut from the result (§3.3 drops);
    untouched slots are zero.  Each grid step brings ``IDX_BLOCK`` positions
    into SMEM and issues one row DMA per position (``dstpos`` is padded up to a
    whole tile, padding aimed at the trash row).
    """
    n, d = src.shape
    tile = IDX_BLOCK
    pos = dstpos.astype(jnp.int32)
    # out-of-range EITHER side (negative, or at/past num_slots) → trash row
    idx = jnp.where((pos < 0) | (pos > num_slots), num_slots, pos)
    src = pad_lanes(src)
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        idx = jnp.concatenate([idx, jnp.full((n_pad - n,), num_slots, jnp.int32)])
        src = jnp.concatenate([src, jnp.zeros((n_pad - n, src.shape[1]), src.dtype)])
    zeros = jnp.zeros((num_slots + 1, src.shape[1]), src.dtype)

    def kernel(idx, src, zeros):
        return pl.pallas_call(
            functools.partial(_scatter_rows_kernel, tile=tile),
            grid=(n_pad // tile,),
            in_specs=[
                pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=sds(zeros.shape, src.dtype, src, idx, zeros),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
            input_output_aliases={2: 0},
            compiler_params=_SEQUENTIAL,
            interpret=interpret,
        )(idx, src, zeros)

    (out,) = call(kernel, idx, src, zeros, interpret=interpret)
    return out[:num_slots, :d]
