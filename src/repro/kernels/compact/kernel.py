"""Pallas kernel: cross-tile exclusive-prefix-sum stream compaction.

This is the TPU replacement for RaFI's ``atomicAdd``-append queue (§3.2): a
mask of emitting lanes becomes a dense list of append positions.  The mask is
viewed as ``(C/128, 128)`` and walked in blocks of rows; inside a block the
exclusive prefix is two triangular MXU products (``kernels/bucket_scatter``'s
``exclusive_prefix``), and the running total rides across the sequential grid
in a revisited ``(8, 128)`` output block (TPU grid steps are sequential, so
no lookback is needed at all; this is *simpler* than the GPU equivalent,
which is the point of the adaptation).

Outputs: positions (C,) int32 (exclusive prefix sum of the mask — the append
slot for every emitting lane) and total (1,) int32 (the final counter value).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANES, call, sds
from repro.kernels.bucket_scatter.kernel import (
    block_rows_for,
    exclusive_prefix,
    lane_rows,
    prefix_operands,
)

_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _compact_kernel(mask_ref, pos_ref, total_ref, *, block_rows):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        total_ref[...] = jnp.zeros_like(total_ref)

    m = mask_ref[...].astype(jnp.bfloat16)
    excl, block_total = exclusive_prefix(m, *prefix_operands(block_rows))
    run = total_ref[...]  # every element holds the total so far
    pos_ref[...] = excl.astype(jnp.int32) + run[:1, :]
    total_ref[...] = run + block_total.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def compact_positions(
    mask: jax.Array, *, block_rows: int = 256, interpret: bool = False
):
    """Exclusive prefix-sum of a boolean mask. Returns (pos (C,), total (1,))."""
    cap = mask.shape[0]
    if cap > 1 << 24:
        raise ValueError(
            f"capacity {cap} exceeds the float32-exact count range (2**24)"
        )
    rows = block_rows_for(cap, block_rows)
    m2 = lane_rows(mask.astype(jnp.int32), rows, 0)
    blk = pl.BlockSpec((rows, LANES), lambda i: (i, 0))

    def kernel(m2):
        return pl.pallas_call(
            functools.partial(_compact_kernel, block_rows=rows),
            grid=(m2.shape[0] // rows,),
            in_specs=[blk],
            out_specs=[blk, pl.BlockSpec((8, LANES), lambda i: (0, 0))],
            out_shape=[
                sds(m2.shape, jnp.int32, m2),
                sds((8, LANES), jnp.int32, m2),
            ],
            compiler_params=_SEQUENTIAL,
            interpret=interpret,
        )(m2)

    pos, total = call(kernel, m2, interpret=interpret)
    return pos.reshape(-1)[:cap], total[0, :1]
