"""Pallas kernel: §4.2.1 sort-key packing + per-destination histogram.

The paper launches a CUDA kernel that writes ``(dest << 32) | i`` uint64 keys
and then radix-sorts them with cub.  The TPU adaptation packs into 32 bits
(rank count ≤ 1023 needs ≤ 10 bits; x64 is off in JAX anyway) and — because
the key distribution is tiny — replaces the generic radix sort with a
counting sort whose histogram is computed *in the same pass* as the key pack.

Tiling: the destination vector is viewed as ``(C/128, 128)`` and walked in
blocks of ``block_rows`` rows.  The histogram is an ``(8, 128)`` int32 block
(bucket ``b`` at flat position ``b``) revisited by every grid step — TPU grid
steps run sequentially, so accumulation into the output block is safe (the
canonical Pallas reduction pattern, shared with ``kernels/bucket_scatter``).
Keys are built in int32 and bitcast to uint32 outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANES, call, sds
from repro.kernels.bucket_scatter.kernel import (
    MAX_BUCKETS,
    block_rows_for,
    lane_index,
    lane_rows,
    lane_total,
)

_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _pack_hist_kernel(
    count_ref, dest_ref, keys_ref, hist_ref, *, num_ranks, idx_bits, block_rows
):
    step = pl.program_id(0)
    lane = lane_index(step, block_rows)
    d = dest_ref[...]
    valid = (lane < count_ref[0]) & (d >= 0) & (d < num_ranks)
    d_clean = jnp.where(valid, d, num_ranks)
    keys_ref[...] = (d_clean << idx_bits) | lane

    @pl.when(step == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    bucket_pos = lane_index(0, 8)

    def bucket(b, counts):
        n = lane_total((d_clean == b).astype(jnp.int32))
        return counts + jnp.where(bucket_pos == b, n, 0)

    hist_ref[...] += jax.lax.fori_loop(
        0, num_ranks + 1, bucket, jnp.zeros((8, LANES), jnp.int32)
    )


@functools.partial(
    jax.jit, static_argnames=("num_ranks", "idx_bits", "block_rows", "interpret")
)
def pack_and_histogram(
    dest: jax.Array,
    count: jax.Array,
    *,
    num_ranks: int,
    idx_bits: int,
    block_rows: int = 256,
    interpret: bool = False,
):
    """Returns (keys uint32 (C,), hist int32 (R+1,)); invalid lanes → dest R."""
    cap = dest.shape[0]
    if num_ranks + 1 > MAX_BUCKETS:
        raise ValueError(
            f"num_ranks + 1 ({num_ranks + 1}) exceeds the kernel's "
            f"{MAX_BUCKETS}-bucket histogram block"
        )
    rows = block_rows_for(cap, block_rows)
    d2 = lane_rows(dest.astype(jnp.int32), rows, -1)
    cnt = jnp.minimum(count.astype(jnp.int32), cap).reshape(1)
    blk = pl.BlockSpec((rows, LANES), lambda i: (i, 0))

    def kernel(cnt, d2):
        return pl.pallas_call(
            functools.partial(
                _pack_hist_kernel, num_ranks=num_ranks, idx_bits=idx_bits,
                block_rows=rows,
            ),
            grid=(d2.shape[0] // rows,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk],
            out_specs=[blk, pl.BlockSpec((8, LANES), lambda i: (0, 0))],
            out_shape=[
                sds(d2.shape, jnp.int32, d2, cnt),
                sds((8, LANES), jnp.int32, d2, cnt),
            ],
            compiler_params=_SEQUENTIAL,
            interpret=interpret,
        )(cnt, d2)

    keys, hist = call(kernel, cnt, d2, interpret=interpret)
    keys = jax.lax.bitcast_convert_type(keys.reshape(-1)[:cap], jnp.uint32)
    hist = hist.reshape(-1)[: num_ranks + 1]
    # padding lanes fell into the invalid bucket R; they are not lanes
    return keys, hist.at[num_ranks].add(cap - d2.size)
