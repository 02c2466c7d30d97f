"""Compile the forwarding kernels for a described TPU v5e, without a chip.

The TPU compiler ships with JAX, and it compiles for a topology that is
described rather than attached.  A compile here refuses what Mosaic would
refuse on the chip — an unaligned slice, a missing lowering, more fast memory
than a kernel may use — which interpret mode on the CPU never sees.

Each kernel of the main path compiles at deployment width: C = 2²⁰ queue
rows (one 1024×1024 VoPaT frame) of W = 12 payload words (the 44-byte ray,
packed to 48 bytes).  One forwarding round with ``use_pallas=True`` compiles
under ``shard_map`` on a 4-chip mesh of the described devices, in both
marshal modes.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

C = 1 << 20  # queue rows: one 1024² VoPaT frame at 1 spp
W = 12  # payload words: the 44-byte path ray, packed
R = 4  # ranks of the multi-chip compile


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for a described chip can be written to the persistent cache
    # but never read back without one: keep them out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    return compiled.as_text()


def _kernels():
    from repro.kernels.bucket_scatter import kernel as bs
    from repro.kernels.compact import kernel as ck
    from repro.kernels.marshal import kernel as mk
    from repro.kernels.sort_keys import kernel as sk

    i32, u32 = jnp.int32, jnp.uint32
    idx_bits = (C - 1).bit_length()
    return {
        "rank_and_histogram": (
            lambda d, n: bs.rank_and_histogram(d, n, num_ranks=R),
            [((C,), i32), ((), i32)],
        ),
        "pack_and_histogram": (
            lambda d, n: sk.pack_and_histogram(d, n, num_ranks=R, idx_bits=idx_bits),
            [((C,), i32), ((), i32)],
        ),
        "scatter_rows": (
            lambda s, p: bs.scatter_rows(s, p, num_slots=C),
            [((C, W), u32), ((C,), i32)],
        ),
        "gather_rows": (
            lambda s, i: mk.gather_rows(s, i),
            [((C, W), u32), ((C,), i32)],
        ),
        "unmarshal": (
            lambda b, o, n: mk.unmarshal(b, o, n, capacity=C),
            [((1, C, W), u32), ((1,), i32), ((1,), i32)],
        ),
        "compact_positions": (
            lambda m: ck.compact_positions(m),
            [((C,), jnp.bool_)],
        ),
    }


@pytest.mark.parametrize(
    "name",
    [
        "rank_and_histogram",
        "pack_and_histogram",
        "scatter_rows",
        "gather_rows",
        "unmarshal",
        "compact_positions",
    ],
)
def test_forwarding_kernel_compiles_at_deployment_width(one_chip, name):
    fn, shapes = _kernels()[name]
    text = _compiled_text(fn, *[_spec(s, d, one_chip) for s, d in shapes])
    assert "tpu_custom_call" in text


def test_app_kernels_compile(one_chip):
    from repro.kernels.nbody_forces import kernel as nb
    from repro.kernels.rk4_advect import kernel as rk

    f32 = jnp.float32
    text = _compiled_text(
        lambda xi, xj, m: nb.pairwise_accel(xi, xj, m),
        _spec((4096, 3), f32, one_chip),
        _spec((4096, 3), f32, one_chip),
        _spec((4096,), f32, one_chip),
    )
    assert "tpu_custom_call" in text
    text = _compiled_text(
        lambda p: rk.rk4_step(p, dt=0.05), _spec((4096, 3), f32, one_chip)
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("marshal", ["sort", "scatter"])
def test_pallas_forward_compiles_on_four_chips(topo, monkeypatch, marshal):
    """One ``forward_work`` round over a 4-chip mesh with Pallas kernels at
    2¹⁶ queue rows per chip of the 12-word ray.  ``default_interpret``
    answers for the CPU this test runs on, so the test steers the kernels to
    Mosaic itself."""
    from repro.apps.vopat import _proto
    from repro.core import ForwardConfig, forward_work, make_queue
    from repro.kernels.bucket_scatter import ops as bs_ops
    from repro.kernels.marshal import ops as mk_ops
    from repro.kernels.sort_keys import ops as sk_ops

    for mod in (bs_ops, mk_ops, sk_ops):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)

    cap = 1 << 16
    mesh = Mesh(np.array(topo.devices[:R]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    cfg = ForwardConfig("data", R, cap, marshal=marshal, use_pallas=True)
    local = make_queue(_proto(), cap)
    # every leaf gains a leading rank-major axis; scalars become (R,)
    spec = jax.tree.map(
        lambda x: _spec((R * x.shape[0],) + x.shape[1:], x.dtype, rows)
        if x.ndim else _spec((R,), x.dtype, rows),
        local,
    )

    def per_rank(q):
        q = jax.tree.map(lambda x, p: x.reshape(p.shape), q, local)
        new_q, total = forward_work(q, cfg)
        return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[1:]), new_q), total

    f = jax.shard_map(per_rank, mesh=mesh, in_specs=P("data"), out_specs=(P("data"), P()))
    text = _compiled_text(f, spec)
    assert "tpu_custom_call" in text
    assert "all-to-all" in text


def test_enqueue_appends_in_place_at_prefill_widths(one_chip):
    """One ``enqueue`` of a DeepSeek-V3 prefill rank: 32,768 lanes of 14 KB
    token rows (hidden 7,168 bf16) into a 49,152-row queue.  The append
    writes the queue in place: the program holds less than one copy of the
    queue in temporaries, and no scatter."""
    from repro.core import enqueue, make_queue
    from repro.models.moe import _token_proto

    cap, lanes, d = 49152, 32768, 7168
    proto = _token_proto(d, jnp.bfloat16)
    q = jax.eval_shape(lambda: make_queue(proto, cap))
    items = jax.tree.map(lambda l: jax.ShapeDtypeStruct((lanes,) + l.shape, l.dtype), proto)
    args = jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip),
        (q, items, jax.ShapeDtypeStruct((lanes,), jnp.int32),
         jax.ShapeDtypeStruct((lanes,), jnp.bool_)),
    )
    compiled = jax.jit(enqueue, donate_argnums=0).lower(*args).compile()
    queue_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(q))
    assert compiled.memory_analysis().temp_size_in_bytes < queue_bytes
    assert "scatter" not in compiled.as_text()
