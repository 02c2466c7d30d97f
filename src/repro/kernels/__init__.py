"""Pallas TPU kernels for the forwarding hot spots and app compute cores.

Layout: one subpackage per kernel —

  sort_keys/       §4.2.1 key-pack + per-destination histogram (MXU prefix)
  bucket_scatter/  sort-free marshal: in-bucket rank + histogram in one pass,
                   payload scattered straight into the send layout
  compact/         cross-tile prefix-sum stream compaction (the TPU "atomic queue")
  marshal/         §4.2.2 segment marshal/unmarshal by DMA between HBM buffers
  nbody_forces/    §5.5 tiled O(N²) pairwise gravity (MXU-aligned)
  rk4_advect/      §5.4 RK4 particle advection on analytic vector fields
  delta_tracking/  §5.1 Woodcock tracking through a procedural density field

Each subpackage has ``kernel.py`` (pl.pallas_call + BlockSpec tiling),
``ops.py`` (jit'd public wrapper with an ``interpret`` switch), and ``ref.py``
(pure-jnp oracle).  Kernels compile through Mosaic on a TPU and run in the
Pallas interpreter everywhere else — there is no override: interpret mode
means exactly "not on a TPU".

Layout rules every kernel here follows (Mosaic refuses the alternatives):

* per-lane control vectors (destinations, ranks, masks) travel as
  ``(rows, 128)`` int32 blocks, never as 1-D blocks;
* prefix sums are triangular matmuls on the MXU (no ``cumsum`` lowering);
* payload rows stay in HBM (``memory_space=pl.ANY``) and move by DMA, with
  the row width padded to whole 128-lane tiles (:func:`pad_lanes`) — a DMA
  slice must cover whole lane tiles;
* index vectors reach SMEM one grid block at a time.
"""
import jax
from jax.extend.core import Primitive
from jax.interpreters import mlir

LANES = 128


def default_interpret() -> bool:
    """Interpret Pallas kernels unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


def sds(shape, dtype, *like) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct whose varying-manual-axes set is the union of the
    inputs' — what a ``pallas_call`` inside ``shard_map(check_vma=True)``
    must declare for its outputs."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def pad_lanes(x: jax.Array) -> jax.Array:
    """Pad the last axis of a payload up to a whole number of lane tiles."""
    width = x.shape[-1]
    padded = -(-width // LANES) * LANES
    if padded == width:
        return x
    pads = [(0, 0)] * (x.ndim - 1) + [(0, padded - width)]
    return jax.numpy.pad(x, pads)


# ------------------------------------------------- interpret-mode call shim
# The Pallas interpreter evaluates the kernel's jaxpr, which was traced with
# no varying-manual-axes types, on the caller's operands.  Inside
# ``shard_map(check_vma=True)`` those operands are varying while the kernel's
# own constants are not, and the evaluation is refused.  Interpreted kernels
# therefore run behind this primitive: its lowering hands the interpreter
# vma-free operand types, and its abstract evaluation gives the results the
# types the ``pallas_call`` declares.  Mosaic kernels never pass through it.
_interpret_call_p = Primitive("rafi_interpret_call")
_interpret_call_p.multiple_results = True


def _interpret_call_abstract(*avals, fn):
    specs = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding, vma=a.vma)
        for a in avals
    ]
    return jax.make_jaxpr(fn)(*specs).out_avals


def _interpret_call_lowering(ctx, *args, fn):
    avals = [a.update(vma=frozenset()) for a in ctx.avals_in]
    return mlir.lower_fun(fn, multiple_results=True)(
        ctx.replace(avals_in=avals), *args
    )


_interpret_call_p.def_impl(lambda *args, fn: fn(*args))
_interpret_call_p.def_abstract_eval(_interpret_call_abstract)
mlir.register_lowering(_interpret_call_p, _interpret_call_lowering)


def call(kernel, *args, interpret: bool):
    """Run ``kernel(*args)`` — a function that builds and applies one
    ``pl.pallas_call``, declaring its output types from the operands it is
    handed (:func:`sds`) — and return its outputs as a list.  Interpreted
    kernels go through the shim above."""
    def fn(*xs):
        out = kernel(*xs)
        return list(out) if isinstance(out, (list, tuple)) else [out]

    if interpret:
        return _interpret_call_p.bind(*args, fn=fn)
    return fn(*args)


__all__ = ["LANES", "call", "default_interpret", "pad_lanes", "sds"]
