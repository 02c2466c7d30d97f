"""Public wrapper: Pallas-accelerated sort-by-destination (§4.2.1).

Key pack + histogram run in the Pallas kernel; the key sort uses
``jax.lax.sort`` (XLA's native TPU sorter — the cub analogue) and the payload
permute is an XLA gather ("each ray gets read exactly once and written
exactly once").  Drop-in replacement for ``repro.core.sorting
.sort_by_destination`` — ``ForwardConfig(use_pallas=True)`` routes here.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.sort_keys import kernel as K
from repro.core import types as T


def _idx_bits(capacity: int) -> int:
    return max(1, (capacity - 1).bit_length())


def sort_permutation(
    dest: jax.Array,
    count: jax.Array,
    num_ranks: int,
    *,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas-path equivalent of ``core.sorting.sort_permutation``: key pack +
    histogram in one kernel pass, key sort via ``jax.lax.sort`` — the payload
    is never touched (the caller composes ``perm`` into its single marshal
    gather)."""
    if interpret is None:
        interpret = default_interpret()
    cap = dest.shape[0]
    ib = _idx_bits(cap)
    if (num_ranks + 1).bit_length() + ib > 32:
        raise ValueError("packed key exceeds 32 bits; reduce capacity or ranks")
    keys, hist = K.pack_and_histogram(
        dest, count, num_ranks=num_ranks, idx_bits=ib, interpret=interpret
    )
    sorted_keys = jax.lax.sort(keys)
    d_sorted = (sorted_keys >> ib).astype(jnp.int32)
    perm = (sorted_keys & jnp.uint32((1 << ib) - 1)).astype(jnp.int32)
    return perm, d_sorted, hist


def sort_permutation_hierarchical(
    dest: jax.Array,
    count: jax.Array,
    level_sizes,
    *,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pallas-path equivalent of ``core.sorting.sort_permutation_hierarchical``
    — the N-level key layout routed through the ``sort_keys`` kernel.

    Global ranks are lexicographic in the mesh digits (slowest-major), so the
    flat packed key ``(dest << idx_bits) | lane`` and the multi-field key
    ``(d_0, …, d_{L-1}, slot)`` induce the SAME sort order: concatenating the
    digit bit-fields of a lexicographic rank IS the rank field (cross-validated
    against the XLA path in tests).  The kernel therefore packs the flat key —
    one pack+histogram pass — and this wrapper reshapes the histogram into the
    ``level_sizes``-shaped count tensor every stage of the hierarchical
    exchange addresses.

    Returns ``(perm, count_tensor)``; raises like the flat path when the
    packed key exceeds 32 bits.
    """
    level_sizes = tuple(int(a) for a in level_sizes)
    num_ranks = 1
    for a in level_sizes:
        num_ranks *= a
    perm, _d_sorted, hist = sort_permutation(
        dest, count, num_ranks, interpret=interpret
    )
    return perm, hist[:num_ranks].reshape(level_sizes)


def sort_by_destination(
    items: Any,
    dest: jax.Array,
    count: jax.Array,
    num_ranks: int,
    *,
    interpret: bool | None = None,
) -> Tuple[Any, jax.Array, jax.Array]:
    """Pallas-path equivalent of core.sorting.sort_by_destination."""
    perm, d_sorted, hist = sort_permutation(
        dest, count, num_ranks, interpret=interpret
    )
    sorted_items = T.tree_take(items, perm)
    return sorted_items, d_sorted, hist
