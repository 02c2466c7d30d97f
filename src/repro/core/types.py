"""Work-item "ray type" registry — the JAX analogue of RaFI's C++ templating.

The paper templates its whole library over an opaque, trivially-copyable
``RayT``; RaFI never looks inside the payload (§3.1).  In JAX the natural
equivalent is a *pytree of arrays*: any dataclass whose fields are arrays (or
nested such dataclasses) can be a work item.  The library only ever applies
structural operations (gather / scatter / exchange) leaf-wise, preserving the
paper's "copy, move, transmit — nothing else" contract.

``@work_item`` registers a dataclass as a JAX pytree and attaches helpers the
infrastructure needs (per-item byte size, batched zeros).  Multiple distinct
work-item types can coexist — the N-body app (§5.5) uses three simultaneously.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import LANES

__all__ = [
    "work_item",
    "item_nbytes",
    "batched_zeros",
    "item_spec",
    "tree_take",
    "tree_scatter",
    "tree_where",
    "PackSpec",
    "pack_spec",
    "pack_payload",
    "unpack_payload",
]


def work_item(cls):
    """Class decorator: register ``cls`` (a dataclass) as a JAX work-item type.

    All fields are treated as array ("data") fields.  The resulting type is a
    pytree, so it can be carried through ``jit``/``shard_map``/``while_loop``
    and exchanged between ranks — the analogue of "trivially copyable".
    """
    if not dataclasses.is_dataclass(cls):
        cls = dataclasses.dataclass(cls)
    fields = [f.name for f in dataclasses.fields(cls)]
    cls = jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=[])
    cls.__work_item__ = True
    return cls


def _leaf_spec(x: Any):
    if hasattr(x, "dtype") and hasattr(x, "shape"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    raise TypeError(f"work item leaves must be arrays, got {type(x)}")


def item_spec(proto) -> Any:
    """ShapeDtypeStruct pytree describing a *single* item (no batch axis)."""
    return jax.tree.map(_leaf_spec, proto)


def item_nbytes(proto) -> int:
    """Bytes of one work item — the paper's ``sizeof(RayT)`` (44 B for Fig. 8)."""
    leaves = jax.tree.leaves(item_spec(proto))
    return int(sum(np.prod(l.shape, dtype=np.int64) * np.dtype(l.dtype).itemsize for l in leaves))


def batched_zeros(proto, n: int):
    """A (n, ...) zero-filled buffer pytree for ``n`` items shaped like ``proto``."""
    return jax.tree.map(
        lambda l: jnp.zeros((n,) + tuple(l.shape), l.dtype), item_spec(proto)
    )


def tree_take(items, idx, *, fill_garbage: bool = True):
    """Gather ``items[idx]`` leaf-wise along axis 0 (clipped indices)."""
    del fill_garbage  # invalid lanes are masked downstream by counts
    return jax.tree.map(lambda a: jnp.take(a, idx, axis=0, mode="clip"), items)


def tree_scatter(buf, pos, vals, *, capacity: int):
    """``buf.at[pos].set(vals)`` leaf-wise; any ``pos >= capacity`` is dropped.

    This is the vectorised analogue of the paper's overflow rule: emits past
    the queue capacity "simply get dropped" (§3.3).
    """
    del capacity  # encoded by mode="drop" against the buffer extent
    return jax.tree.map(lambda b, v: b.at[pos].set(v, mode="drop"), buf, vals)


# --------------------------------------------------------------------------
# Packed wire format (§4.2 "large contiguous blocks"): the whole work-item
# pytree bitcast into ONE (capacity, words) uint32 buffer.  This is the JAX
# rendering of the paper's trivially-copyable RayT on the wire — the 44-byte
# Fig-8 ray becomes 11 words per row.  Structural hot-path operations
# (sort-permute, marshal, exchange) act on this single buffer, so each round
# needs exactly one payload gather and one payload collective instead of one
# per pytree leaf.
#
# Layout: leaves in treedef order, each flattened to its per-item elements
# and bitcast to ≥1 whole uint32 words (sub-word dtypes are zero-padded up to
# a word boundary; the pad travels but carries no information and is
# stripped on unpack).  A 2-byte leaf of n elements puts elements j and
# j + n/2 into word j; a 1-byte leaf puts 4 neighbours into each word.
# Pack ∘ unpack is the identity bit-for-bit.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Static recipe for packing/unpacking one work-item type.

    Attributes:
      treedef: pytree structure of the item type.
      shapes: per-leaf trailing (per-item) shapes.
      dtypes: per-leaf dtype names.
      words: per-leaf packed word counts (incl. sub-word padding).

    A row wider than one lane tile (``LANES`` words) is zero-padded to a
    whole number of tiles: the TPU lays a payload's rows out in (8, 128)
    tiles anyway, and its all-to-all moves a row of whole tiles in place,
    where a ragged width makes it transpose the whole buffer before the
    collective and back after.
    """

    treedef: Any
    shapes: tuple
    dtypes: tuple
    words: tuple

    @property
    def item_words(self) -> int:
        return sum(self.words)

    @property
    def total_words(self) -> int:
        w = self.item_words
        return w if w <= LANES else -(-w // LANES) * LANES

    @property
    def offsets(self) -> tuple:
        out, o = [], 0
        for w in self.words:
            out.append(o)
            o += w
        return tuple(out)


def _leaf_words(shape, dtype) -> int:
    n = int(np.prod(shape, dtype=np.int64))
    b = np.dtype(dtype).itemsize
    return -(-n * b // 4)  # zero-size leaves occupy zero wire words


def pack_spec(proto) -> PackSpec:
    """The :class:`PackSpec` for items shaped like ``proto`` (no batch axis
    required — only leaf trailing shapes and dtypes matter)."""
    leaves, treedef = jax.tree.flatten(item_spec(proto))
    return PackSpec(
        treedef=treedef,
        shapes=tuple(tuple(l.shape) for l in leaves),
        dtypes=tuple(np.dtype(l.dtype).name for l in leaves),
        words=tuple(_leaf_words(l.shape, l.dtype) for l in leaves),
    )


def _leaf_to_words(a: jax.Array) -> jax.Array:
    """(C, ...) leaf → (C, words) uint32, bit-preserving."""
    cap = a.shape[0]
    if a.size == 0:
        return jnp.zeros((cap, 0), jnp.uint32)
    if a.dtype == jnp.bool_:
        a = a.astype(jnp.uint8)
    flat = a.reshape(cap, -1)
    b = np.dtype(flat.dtype).itemsize
    if b == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if b == 8:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(cap, -1)
    if b == 2:
        # 2-byte dtypes: word j holds element j in its low half and element
        # j + n/2 in its high half (minor axis zero-padded to even).  Halves
        # rather than neighbours keep the packing elementwise over the
        # minor axis: a bitcast of interleaved pairs lays a size-2 axis
        # along the TPU's lanes and materialises every element as a word
        flat = jax.lax.bitcast_convert_type(flat, jnp.uint16)
        if flat.shape[1] % 2:
            flat = jnp.pad(flat, ((0, 0), (0, 1)))
        half = flat.shape[1] // 2
        lo = flat[:, :half].astype(jnp.uint32)
        hi = flat[:, half:].astype(jnp.uint32)
        return lo | (hi << 16)
    # 1-byte dtypes: zero-pad the minor axis to a whole number of words,
    # then bitcast groups of 4 elements into each word
    per = 4 // b
    pad = (-flat.shape[1]) % per
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return jax.lax.bitcast_convert_type(flat.reshape(cap, -1, per), jnp.uint32)


def _words_to_leaf(seg: jax.Array, shape, dtype) -> jax.Array:
    """(C, words) uint32 → (C, *shape) leaf of ``dtype`` (inverse bitcast)."""
    cap = seg.shape[0]
    dt = np.dtype(dtype)
    n = int(np.prod(shape, dtype=np.int64))
    if n == 0:
        return jnp.zeros((cap,) + tuple(shape), dt)
    wire_dt = jnp.uint8 if dt == np.bool_ else jnp.dtype(dtype)
    b = np.dtype(wire_dt).itemsize
    if b == 4:
        out = jax.lax.bitcast_convert_type(seg, wire_dt)[:, :n]
    elif b == 2:
        halves = [(seg & 0xFFFF).astype(jnp.uint16), (seg >> 16).astype(jnp.uint16)]
        out = jax.lax.bitcast_convert_type(jnp.concatenate(halves, axis=1), wire_dt)[:, :n]
    elif b == 8:
        out = jax.lax.bitcast_convert_type(seg.reshape(cap, -1, 2), wire_dt)[:, :n]
    else:
        out = jax.lax.bitcast_convert_type(seg, wire_dt).reshape(cap, -1)[:, :n]
    if dt == np.bool_:
        out = out.astype(jnp.bool_)
    return out.reshape((cap,) + tuple(shape))


def pack_payload(items: Any, spec: PackSpec | None = None):
    """Bitcast-concatenate a batched item pytree into one (C, W) uint32
    buffer.  Returns ``(packed, spec)``; ``spec`` round-trips via
    :func:`unpack_payload`."""
    if spec is None:
        spec = pack_spec(jax.tree.map(lambda a: a[0], items))
    cols = [_leaf_to_words(l) for l in jax.tree.leaves(items)]
    pad = spec.total_words - spec.item_words
    if pad:
        cols.append(jnp.zeros((cols[0].shape[0], pad), jnp.uint32))
    packed = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    return packed, spec


def unpack_payload(packed: jax.Array, spec: PackSpec) -> Any:
    """Inverse of :func:`pack_payload` (bit-exact)."""
    leaves, o = [], 0
    for shape, dtype, w in zip(spec.shapes, spec.dtypes, spec.words):
        leaves.append(_words_to_leaf(packed[:, o : o + w], shape, dtype))
        o += w
    return jax.tree.unflatten(spec.treedef, leaves)


def tree_where(mask, a, b):
    """Leaf-wise select with broadcast of a (n,) mask over item trailing dims."""

    def sel(x, y):
        m = mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))
        return jnp.where(m, x, y)

    return jax.tree.map(sel, a, b)
