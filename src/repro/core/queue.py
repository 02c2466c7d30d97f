"""Fixed-capacity work queues — the TPU adaptation of RaFI's ray queues (§3.2).

The paper's output queue grows via ``atomicAdd`` on a device counter; each
emit appends ``(ray, destRank)``.  TPUs have no global atomics, so the queue
is adapted to the vector paradigm:

* a queue is a pytree buffer of static capacity ``C`` plus an active ``count``;
  entries ``[0, count)`` are valid and contiguous (same invariant the paper's
  sorted/compacted arrays maintain);
* kernels *emit* by producing per-lane ``(item, dest, mask)`` triples; an
  ``enqueue`` finds the emitting lanes by one sort and appends their rows
  in contiguous blocks — the deterministic, order-stable equivalent of the
  atomic append, whose cost follows the emits rather than the lanes.  A kernel
  round may call ``enqueue`` several times (a shaded ray emitting both a
  bounce ray and a shadow ray — §3.3 "threads can emit more than one").
* emits beyond capacity are dropped and counted, exactly matching §3.3
  ("calls that would exceed the output queue size will simply get dropped").

Destination ``-1`` marks an invalid / discarded item (the paper's early
single-array design used the same sentinel; we keep it as the tombstone).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import types as T

__all__ = ["WorkQueue", "make_queue", "enqueue", "num_incoming", "get_incoming", "clear"]

DISCARD = -1  # sentinel destination: item goes nowhere (paper §3.2)

# An enqueue appends its live rows in blocks of about _APPEND_BYTES, and at
# most _APPEND_ROWS rows, a step
_APPEND_BYTES = 1 << 22
_APPEND_ROWS = 8192


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class WorkQueue:
    """A bounded queue of work items with per-item destination ranks.

    Attributes:
      items: pytree, every leaf shaped ``(capacity, ...)``.
      dest:  ``(capacity,) int32`` destination rank per item; ``-1`` = discard.
      count: ``() int32`` number of valid items at the front.
      drops: ``() int32`` cumulative overflow-dropped emits (observability).
    """

    items: Any
    dest: jax.Array
    count: jax.Array
    drops: jax.Array

    @property
    def capacity(self) -> int:
        return jax.tree.leaves(self.items)[0].shape[0]


@jax.named_scope("rafi.enqueue")
def make_queue(proto, capacity: int) -> WorkQueue:
    """An empty queue for items shaped like ``proto`` (a single-item pytree).

    ``capacity`` must be a positive Python int — it is the queue's static
    shape, so a traced or non-positive value is a config bug worth a clear
    error here rather than an opaque reshape failure downstream.
    """
    if not isinstance(capacity, (int, jnp.integer)) or isinstance(capacity, bool):
        raise ValueError(
            f"capacity must be a static Python int (got {type(capacity).__name__}): "
            "it fixes the queue's buffer shapes"
        )
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    return WorkQueue(
        items=T.batched_zeros(proto, capacity),
        dest=jnp.full((capacity,), DISCARD, dtype=jnp.int32),
        count=jnp.zeros((), jnp.int32),
        drops=jnp.zeros((), jnp.int32),
    )


def num_incoming(q: WorkQueue) -> jax.Array:
    """Paper's ``DeviceInterface::numIncoming()``."""
    return q.count


def get_incoming(q: WorkQueue, i) -> Any:
    """Paper's ``DeviceInterface::getIncoming(rayID)`` — reads item ``i``."""
    return jax.tree.map(lambda a: a[i], q.items)


@jax.named_scope("rafi.enqueue")
def enqueue(q: WorkQueue, items, dest, mask, *, num_ranks: int = None) -> WorkQueue:
    """Paper's ``DeviceInterface::emitOutgoing(ray, dest)``, vectorised.

    Appends the masked lanes of ``items``/``dest`` to the queue in lane order
    (stable).  ``mask`` lanes that would land past capacity are dropped and
    counted.  ``dest`` must be a valid rank (or ``DISCARD`` to drop).

    Args:
      items: pytree with leaves ``(n, ...)``.
      dest:  ``(n,)`` integer dtype.  A float dest raises at trace time — it
        would silently truncate-cast and misroute (a real emit-kernel bug
        class); the marshal's deep sanitize is a backstop, not an API.
      mask:  ``(n,)`` bool — which lanes actually emit.  Integer masks are
        accepted with nonzero-is-emit semantics: the mask is normalised to
        bool BEFORE combining with the dest check, because ``int_mask &
        (dest >= 0)`` is a BITWISE and (an int mask value of 2 & True == 0 —
        a silently lost emit) and an un-normalised int mask would also make
        the emit count add each lane ``mask`` times.  Bool and {0, 1}
        int32 masks are regression-tested equivalent, drops included.
      num_ranks: optional mesh size for an eager out-of-range check: when
        ``dest`` is a CONCRETE array (not traced), any masked lane with
        ``dest >= num_ranks`` raises here instead of being sanitized to a
        silent drop deep in the marshal.  Traced dests skip the value check
        (values don't exist at trace time) — the marshal sanitize still
        guards execution.
    """
    cap = q.capacity
    dest = jnp.asarray(dest)
    if not jnp.issubdtype(dest.dtype, jnp.integer):
        raise ValueError(
            f"dest must have an integer dtype, got {dest.dtype}: a float "
            "dest would truncate-cast and misroute emits silently"
        )
    if num_ranks is not None and not isinstance(dest, jax.core.Tracer):
        m = (jnp.asarray(mask) != 0) & (dest >= 0)
        bad = jnp.where(m, dest, 0) >= num_ranks
        if bool(jnp.any(bad)):
            raise ValueError(
                f"enqueue got dest >= num_ranks ({num_ranks}): max offending "
                f"value {int(jnp.max(jnp.where(bad, dest, 0)))} — emits must "
                "target a rank on the mesh (or DISCARD)"
            )
    live = (jnp.asarray(mask) != 0) & (dest >= 0)
    n = live.shape[0]
    n_emit = jnp.sum(live, dtype=jnp.int32)
    new_count = jnp.minimum(q.count + n_emit, cap)
    dropped = q.count + n_emit - new_count
    if n == 0:
        return WorkQueue(q.items, q.dest, q.count, q.drops + dropped)
    n_ok = new_count - q.count  # the live lanes that fit, in lane order

    # compaction order: one sort of the lane indices puts the live lanes
    # first, in lane order (their keys are distinct, so the sort need not be
    # stable).  ``b`` keys of padding in front let every step below slice
    # the order at its own offset, which can fall before the first live lane.
    leaves = jax.tree.leaves(items)
    wide = [a.ndim > 1 for a in leaves]
    row_bytes = 4 + sum(a.dtype.itemsize * (a.size // n) for a in leaves)
    b = min(max(_APPEND_BYTES // row_bytes, 8), _APPEND_ROWS, n, cap)
    lane = jnp.arange(n, dtype=jnp.int32)
    order = jax.lax.sort(
        jnp.concatenate([jnp.full((b,), -1, jnp.int32), jnp.where(live, lane, n)]),
        is_stable=False,
    )
    # dest and the scalar leaves travel as one packed row of words, so a step
    # gathers them by row (a gather of single words is slow on a TPU); wider
    # leaves are gathered by row as they are
    narrow, spec = T.pack_payload(
        [dest.astype(jnp.int32)] + [a for a, w in zip(leaves, wide) if not w]
    )

    def put(buf, new, s, ok):
        old = jax.lax.dynamic_slice_in_dim(buf, s, b)
        ok = ok.reshape((b,) + (1,) * (buf.ndim - 1))
        new = jnp.where(ok, new.astype(buf.dtype), old)
        return jax.lax.dynamic_update_slice_in_dim(buf, new, s, 0)

    def append(k, bufs):
        # the window of rows [s, s + b): the k-th block of the appended range,
        # the last one ending where it ends (so no window passes ``cap``);
        # row s + j takes the live lane of rank s + j - count
        s = jnp.maximum(jnp.minimum(q.count + k * b, new_count - b), 0)
        r0 = s - q.count
        rank = r0 + jnp.arange(b, dtype=jnp.int32)
        ok = (rank >= 0) & (rank < n_ok)
        src = jax.lax.dynamic_slice_in_dim(order, r0 + b, b)
        row = lambda a: jnp.take(a, src, axis=0, mode="clip")  # noqa: E731
        scalars = iter(T.unpack_payload(row(narrow), spec))
        new = [next(scalars)] + [row(a) if w else next(scalars) for a, w in zip(leaves, wide)]
        return [put(buf, v, s, ok) for buf, v in zip(bufs, new)]

    # inside shard_map the rows written vary over the axes of what is emitted
    # (and of ``count``); a fresh queue's buffers must vary alike to be carried
    held, treedef = jax.tree.flatten(q.items)
    vma = sorted(set().union(*(jax.typeof(a).vma for a in [order, narrow, q.count] + leaves)))
    carry = [compat.pcast_varying(a, vma) for a in [q.dest] + held]
    dest_buf, *held = jax.lax.fori_loop(0, (n_ok + b - 1) // b, append, carry)
    new_items = jax.tree.unflatten(treedef, held)
    return WorkQueue(new_items, dest_buf, new_count, q.drops + dropped)


def clear(q: WorkQueue) -> WorkQueue:
    """Reset to empty (the paper's post-forward counter reset, §4.2.3)."""
    return WorkQueue(
        items=q.items,
        dest=jnp.full_like(q.dest, DISCARD),
        count=jnp.zeros_like(q.count),
        drops=q.drops,
    )
