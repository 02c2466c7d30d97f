"""Public wrapper: the sort-free bucket-scatter marshal plan + payload pass.

``ForwardConfig(marshal="scatter", use_pallas=True)`` routes here:
``rank_and_histogram`` replaces the ``sort_keys`` pack+sort (same control
data — sanitized destination, stable in-bucket rank, histogram — no keys, no
sort), and ``scatter_rows`` is the round's single payload pass (the scatter
dual of ``kernels/marshal.gather_rows``).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.bucket_scatter import kernel as K


def rank_and_histogram(
    dest: jax.Array,
    count: jax.Array,
    *,
    num_ranks: int,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas-path equivalent of ``core.sorting.destination_rank``:
    ``(d_clean, rank, hist)`` in one kernel pass over the destination
    vector."""
    if interpret is None:
        interpret = default_interpret()
    return K.rank_and_histogram(
        dest, count, num_ranks=num_ranks, interpret=interpret
    )


def scatter_rows(
    src: jax.Array,
    dstpos: jax.Array,
    *,
    num_slots: int,
    interpret: bool | None = None,
) -> jax.Array:
    """(N, W) packed payload + composed send-layout positions → (num_slots, W)
    send buffer in ONE payload pass (see ``kernel.scatter_rows``)."""
    if interpret is None:
        interpret = default_interpret()
    return K.scatter_rows(src, dstpos, num_slots=num_slots, interpret=interpret)


def compact_rows(
    src: jax.Array,
    mask: jax.Array,
    *,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stable front-compaction of the masked rows — the spill-and-retry
    primitive (``overflow="retain"``): the marked rows move to the front of
    an ``(N, W)`` buffer in their original relative order, unmarked slots
    stay zero.  The position plan is the 1-bucket counting sort (the mask's
    exclusive prefix sum); the payload moves in ONE ``scatter_rows`` pass.

    Returns ``(out, slot, n_kept)`` — ``slot`` is each source row's compacted
    position (``N`` for unmarked rows, the kernel's discard sentinel), handed
    back so callers can scatter side-band vectors (dest, age) to the same
    layout without a second plan."""
    n = src.shape[0]
    m32 = mask.astype(jnp.int32)
    pos = jnp.cumsum(m32) - m32
    slot = jnp.where(mask, pos, n)
    out = scatter_rows(src, slot, num_slots=n, interpret=interpret)
    return out, slot, jnp.sum(m32)
