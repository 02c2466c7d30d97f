"""Pallas kernels: §4.2.2 marshal / unmarshal around the packed exchange.

``gather_rows`` — the production hot path, the SINGLE-pass marshal: the
caller composes the destination-sort permutation with the padded send layout
(``src[i] = perm[off[r] + s]``) and this kernel materialises
``out[i] = packed[src[i]]`` in one gather.  Sort-then-segment-copy used to be
two payload passes; folding the permutation into the gather makes "each ray
gets read exactly once and written exactly once" (§4.2.1/§6.1) hold through
the marshal step too.

``marshal`` — the two-pass formulation kept for cross-validation: copy each
peer's *contiguous* segment of an already-sorted buffer into its fixed
(peer_capacity,) slot (the TPU analogue of the paper's observation that RDMA
needs "single, consistent blocks of (GPU) data").

``unmarshal``: the inverse — land received (R, S) blocks in a compact buffer
at data-dependent offsets.  Rows past a block's count, or past ``capacity``,
are never written (§3.3 drop semantics); every row no block claims is zero.

Payload layout: all kernels act on the packed wire format of
``core.types.pack_payload`` — the whole work-item pytree bitcast into one
(C, words) uint32 buffer, mirroring the paper's "trivially copyable struct"
contract on the wire.  The buffers never enter VMEM: at deployment capacity
(C = 2²⁰ rows) they are hundreds of MB.  They stay in HBM and rows move by
DMA — one row per DMA for the gather, and for the block copies a run of
``n`` rows split into its binary digits (≤ log2 S + 1 DMAs of static power-of-
two length each).  A DMA slice must span whole 128-lane tiles, so the row
width is padded to 128 words around the call (``kernels.pad_lanes``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import call, pad_lanes, sds

_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
# indices per grid step: one (1024,) int32 block, XLA's tiling of a 1-D
# int32 vector — an SMEM block must match it
IDX_BLOCK = 1024


def _copy_run(src_ref, dst_ref, src0, dst0, n, sem, *, max_rows):
    """Copy rows ``[src0, src0+n)`` of ``src_ref`` to ``[dst0, dst0+n)`` of
    ``dst_ref`` (both HBM), ``0 <= n <= max_rows``: one static-length DMA per
    set bit of ``n``."""
    for b in range(max(1, max_rows.bit_length()) - 1, -1, -1):
        size = 1 << b
        hi = (n >> (b + 1)) << (b + 1)  # rows already covered by higher bits

        @pl.when((n & size) != 0)
        def _():
            cp = pltpu.make_async_copy(
                src_ref.at[pl.ds(src0 + hi, size)],
                dst_ref.at[pl.ds(dst0 + hi, size)],
                sem,
            )
            cp.start()
            cp.wait()


def _marshal_kernel(off_ref, in_ref, out_ref, sem, *, slot):
    r = pl.program_id(0)
    cp = pltpu.make_async_copy(in_ref.at[pl.ds(off_ref[r], slot)], out_ref.at[r], sem)
    cp.start()
    cp.wait()


@functools.partial(jax.jit, static_argnames=("num_ranks", "slot", "interpret"))
def marshal(
    sorted_flat: jax.Array,  # (C, D) destination-sorted payload view
    offsets: jax.Array,  # (R,) int32 segment starts (will be clamped to C-S)
    *,
    num_ranks: int,
    slot: int,
    interpret: bool = False,
) -> jax.Array:
    """Returns the (R, S, D) padded send buffer."""
    cap, d = sorted_flat.shape
    if slot > cap:
        raise ValueError(f"peer slot {slot} exceeds capacity {cap}")
    off = jnp.clip(offsets.astype(jnp.int32), 0, cap - slot)
    src = pad_lanes(sorted_flat)
    dp = src.shape[1]

    def kernel(off, src):
        return pl.pallas_call(
            functools.partial(_marshal_kernel, slot=slot),
            grid=(num_ranks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=sds((num_ranks, slot, dp), src.dtype, src, off),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
            compiler_params=_SEQUENTIAL,
            interpret=interpret,
        )(off, src)

    (out,) = call(kernel, off, src, interpret=interpret)
    return out[:, :, :d]


def _gather_rows_kernel(idx_ref, src_ref, out_ref, sem, *, tile):
    base = pl.program_id(0) * tile

    def start(t, carry):
        pltpu.make_async_copy(
            src_ref.at[pl.ds(idx_ref[t], 1)], out_ref.at[pl.ds(base + t, 1)], sem
        ).start()
        return carry

    def wait(t, carry):  # every copy moves one row: any one-row descriptor
        pltpu.make_async_copy(src_ref.at[pl.ds(0, 1)], out_ref.at[pl.ds(0, 1)], sem).wait()
        return carry

    jax.lax.fori_loop(0, tile, start, 0)
    jax.lax.fori_loop(0, tile, wait, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(
    src: jax.Array,  # (C, D) packed payload
    row_idx: jax.Array,  # (N,) int32 source row per output row (clamped)
    *,
    interpret: bool = False,
) -> jax.Array:
    """The fused single-pass marshal: ``out[i] = src[row_idx[i]]``.

    ``row_idx`` is the destination-sort permutation already composed with the
    send-slot layout (``perm[off[r] + s]`` for the flat exchange; either
    stage's layout for the hierarchical one), so this one gather subsumes
    what used to be payload-sort-then-segment-copy — each payload row is read
    exactly once and written exactly once.  Each grid step brings
    ``IDX_BLOCK`` indices into SMEM and issues one row DMA per index, HBM to HBM, then
    waits for all of them.  ``row_idx`` is padded up to a whole tile; the
    padded tail is cut from the result.
    """
    cap, d = src.shape
    n = row_idx.shape[0]
    tile = IDX_BLOCK
    idx = jnp.clip(row_idx.astype(jnp.int32), 0, cap - 1)
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        idx = jnp.concatenate([idx, jnp.zeros((n_pad - n,), jnp.int32)])
    src = pad_lanes(src)

    def kernel(idx, src):
        return pl.pallas_call(
            functools.partial(_gather_rows_kernel, tile=tile),
            grid=(n_pad // tile,),
            in_specs=[
                pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=sds((n_pad, src.shape[1]), src.dtype, src, idx),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
            compiler_params=_SEQUENTIAL,
            interpret=interpret,
        )(idx, src)

    (out,) = call(kernel, idx, src, interpret=interpret)
    return out[:n, :d]


def _unmarshal_kernel(off_ref, n_ref, in_ref, zero_ref, out_ref, sem, *, max_rows):
    del zero_ref  # aliased to out_ref: the rows no block writes stay zero
    r = pl.program_id(0)
    _copy_run(
        in_ref.at[r], out_ref, 0, off_ref[r], n_ref[r], sem, max_rows=max_rows
    )


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def unmarshal(
    recv_buf: jax.Array,  # (R, S, D) received padded blocks
    recv_offsets: jax.Array,  # (R,) compact output offsets
    recv_counts: jax.Array,  # (R,) valid rows per block
    *,
    capacity: int,
    interpret: bool = False,
) -> jax.Array:
    """Returns the (capacity, D) compacted receive buffer (drop-tail applied)."""
    num_ranks, slot, d = recv_buf.shape
    off = jnp.clip(recv_offsets.astype(jnp.int32), 0, capacity)
    # rows of a block that would land at/past `capacity` are never written —
    # §3.3 drop semantics
    n = jnp.clip(jnp.minimum(recv_counts.astype(jnp.int32), capacity - off), 0, slot)
    buf = pad_lanes(recv_buf)
    zeros = jnp.zeros((capacity, buf.shape[2]), buf.dtype)

    def kernel(off, n, buf, zeros):
        return pl.pallas_call(
            functools.partial(_unmarshal_kernel, max_rows=min(slot, capacity)),
            grid=(num_ranks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=sds(zeros.shape, buf.dtype, buf, off, n, zeros),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
            input_output_aliases={3: 0},
            compiler_params=_SEQUENTIAL,
            interpret=interpret,
        )(off, n, buf, zeros)

    (out,) = call(kernel, off, n, buf, zeros, interpret=interpret)
    return out[:, :d]
