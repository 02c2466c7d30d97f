"""Tests for the packed wire format (§4.2 "large contiguous blocks").

Two families:

  * ``pack_payload ∘ unpack_payload`` is the identity, bit-for-bit, for any
    mixed-dtype work-item pytree (property-tested) — the JAX rendering of
    the paper's trivially-copyable ``RayT`` contract;
  * the packed-path ``forward_work`` is bit-exact against the ``onehot``
    all-gather oracle for every executable backend, including the fused
    Pallas marshal path (``use_pallas=True``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from hypothesis import given, settings, strategies as st

from repro import compat
from repro.core import ForwardConfig, enqueue, forward_work, make_queue, work_item
from repro.core import types as T
from repro.kernels import LANES

from helpers import Ray, make_rays, ray_proto

R, CAP = 8, 64


# ------------------------------------------------------- pack/unpack identity
@given(
    st.integers(1, 33),  # batch
    st.integers(1, 5),   # f32 vector width
    st.integers(0, 3),   # number of extra scalar i32 fields
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_pack_unpack_identity_mixed_f32_i32(n, width, extra, seed):
    rng = np.random.default_rng(seed)
    items = {
        "vec": jnp.asarray(rng.normal(size=(n, width)).astype(np.float32)),
        "idx": jnp.asarray(rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int32)),
    }
    for i in range(extra):
        items[f"s{i}"] = jnp.asarray(
            rng.integers(0, 1000, n, dtype=np.int32)
        )
    packed, spec = T.pack_payload(items)
    assert packed.dtype == jnp.uint32
    assert packed.shape == (n, spec.total_words)
    back = T.unpack_payload(packed, spec)
    assert jax.tree.structure(back) == jax.tree.structure(items)
    for k in items:
        assert back[k].dtype == items[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(items[k]))


def test_pack_unpack_identity_subword_and_bool():
    """Sub-word dtypes ride zero-padded word slots and round-trip exactly."""
    n = 17
    rng = np.random.default_rng(3)
    items = {
        "h": jnp.asarray(rng.integers(-(2**15), 2**15 - 1, (n, 5), dtype=np.int16)),
        "b": jnp.asarray(rng.random(n) < 0.5),
        "x": jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32)),
    }
    packed, spec = T.pack_payload(items)
    back = T.unpack_payload(packed, spec)
    for k in items:
        assert back[k].dtype == items[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(items[k]))


@pytest.mark.parametrize("width", [300, 301, 7167, 7168])
def test_pack_unpack_wide_bf16_rows_pad_to_lane_tiles(width):
    """A bfloat16 row wider than one lane tile packs two elements a word, is
    zero-padded to whole ``LANES``-word tiles, and round-trips bit for bit
    (every 16-bit pattern, NaNs included)."""
    n = 5
    bits = np.random.default_rng(width).integers(0, 2**16, (n, width), dtype=np.uint16)
    items = {"h": jnp.asarray(bits.view(jnp.bfloat16)), "slot": jnp.arange(n, dtype=jnp.int32)}
    packed, spec = T.pack_payload(items)
    assert spec.item_words == -(-width // 2) + 1
    assert packed.shape == (n, spec.total_words)
    assert spec.total_words % LANES == 0 and spec.total_words - spec.item_words < LANES
    np.testing.assert_array_equal(np.asarray(packed[:, spec.item_words:]), 0)
    back = T.unpack_payload(packed, spec)
    assert back["h"].dtype == jnp.bfloat16 and back["h"].shape == (n, width)
    np.testing.assert_array_equal(np.asarray(back["h"]).view(np.uint16), bits)
    np.testing.assert_array_equal(np.asarray(back["slot"]), np.arange(n))


def test_pack_unpack_zero_size_leaf():
    """Zero-size leaves occupy zero wire words and round-trip (an item type
    with an empty field must still forward)."""
    n = 9
    items = {
        "empty": jnp.zeros((n, 0), jnp.float32),
        "x": jnp.arange(n, dtype=jnp.int32),
    }
    packed, spec = T.pack_payload(items)
    assert spec.words == (0, 1) and packed.shape == (n, 1)
    back = T.unpack_payload(packed, spec)
    assert back["empty"].shape == (n, 0) and back["empty"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(back["x"]), np.asarray(items["x"]))


def test_pack_payload_preserves_exact_float_bits():
    """NaN payloads, signed zeros and denormals must survive the wire —
    pack is a bitcast, not a value conversion."""
    vals = np.array(
        [np.nan, -np.nan, 0.0, -0.0, np.inf, -np.inf, 1e-45, -1e-45], np.float32
    )
    items = {"v": jnp.asarray(vals)}
    packed, spec = T.pack_payload(items)
    back = np.asarray(T.unpack_payload(packed, spec)["v"])
    np.testing.assert_array_equal(back.view(np.uint32), vals.view(np.uint32))


def test_pack_spec_matches_item_nbytes():
    """A word-aligned item packs to exactly item_nbytes of wire (44-byte Fig-8
    ray → 11 words)."""
    spec = T.pack_spec(ray_proto())
    assert spec.total_words * 4 == T.item_nbytes(ray_proto()) == 36
    assert spec.offsets == (0, 3, 6, 7, 8)


# --------------------------------------------- packed path vs onehot oracle
def _run(mesh8, cfg, dest_of):
    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index("data")
        n = 10
        k = jnp.arange(n)
        rays = Ray(
            origin=jnp.ones((n, 3)) * me,
            direction=jnp.zeros((n, 3)),
            tmin=k.astype(jnp.float32),
            pixel=(k + me * 100).astype(jnp.int32),
            integral=jnp.zeros(n),
        )
        q = enqueue(q, rays, dest_of(me, k).astype(jnp.int32), jnp.ones(n, bool))
        nq, total = forward_work(q, cfg)
        return nq.count[None], nq.items.pixel, nq.items.origin, nq.items.tmin

    f = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), P("data"), P("data"), P("data")),
        )
    )
    counts, pixels, origins, tmins = f(jnp.arange(8.0))
    return (
        np.asarray(counts),
        np.asarray(pixels).reshape(R, CAP),
        np.asarray(origins).reshape(R, CAP, 3),
        np.asarray(tmins).reshape(R, CAP),
    )


_BACKENDS = [
    pytest.param("padded", False, id="padded"),
    pytest.param("padded", True, id="padded-pallas"),
    pytest.param("ragged", False, id="ragged"),
]


@pytest.mark.parametrize("exchange,use_pallas", _BACKENDS)
def test_packed_forward_bitexact_vs_onehot(mesh8, exchange, use_pallas):
    if exchange == "ragged" and not compat.ragged_executes():
        pytest.skip(f"the {jax.default_backend()} backend cannot execute ragged_all_to_all")
    dest_of = lambda me, k: (me * 5 + k * 3) % R
    got = _run(
        mesh8,
        ForwardConfig("data", R, CAP, exchange=exchange, use_pallas=use_pallas),
        dest_of,
    )
    want = _run(mesh8, ForwardConfig("data", R, CAP, exchange="onehot"), dest_of)
    np.testing.assert_array_equal(got[0], want[0])
    for r in range(R):  # valid prefixes identical (both stable); tails garbage
        n = got[0][r]
        np.testing.assert_array_equal(got[1][r][:n], want[1][r][:n])
        np.testing.assert_array_equal(got[2][r][:n], want[2][r][:n])
        # float payload must be BIT-exact, not just allclose: the wire is a
        # bitcast, forwarding may not perturb a single mantissa bit
        np.testing.assert_array_equal(
            got[3][r][:n].view(np.uint32), want[3][r][:n].view(np.uint32)
        )


def test_packed_forward_multi_leaf_dtypes(mesh8):
    """A work item with i32 + f32 + wide vector leaves forwards exactly
    (the single packed collective carries all of them)."""

    @work_item
    @dataclasses.dataclass
    class Fat:
        mat: jax.Array   # (2, 3) f32
        tag: jax.Array   # () i32

    def proto():
        return Fat(mat=jnp.zeros((2, 3)), tag=jnp.zeros((), jnp.int32))

    cfg = ForwardConfig("data", R, CAP, exchange="padded")

    def kernel(_x):
        q = make_queue(proto(), CAP)
        me = jax.lax.axis_index("data")
        n = 6
        items = Fat(
            mat=jnp.arange(n * 6, dtype=jnp.float32).reshape(n, 2, 3) + me * 1000,
            tag=(jnp.arange(n) + me * 100).astype(jnp.int32),
        )
        dest = ((me + jnp.arange(n)) % R).astype(jnp.int32)
        q = enqueue(q, items, dest, jnp.ones(n, bool))
        nq, total = forward_work(q, cfg)
        return nq.count[None], nq.items.tag, nq.items.mat, total

    f = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), P("data"), P("data"), P()),
        )
    )
    counts, tags, mats, total = f(jnp.arange(8.0))
    counts = np.asarray(counts)
    tags = np.asarray(tags).reshape(R, CAP)
    mats = np.asarray(mats).reshape(R, CAP, 2, 3)
    assert int(total) == 8 * 6 and counts.sum() == 48
    for r in range(R):
        for i in range(counts[r]):
            src, k = divmod(int(tags[r, i]), 100)
            assert (src + k) % R == r  # addressed here
            np.testing.assert_array_equal(
                mats[r, i],
                np.arange(k * 6, k * 6 + 6, dtype=np.float32).reshape(2, 3)
                + src * 1000,
            )


@pytest.mark.parametrize("width", [61, 301, 7168])
def test_packed_forward_wide_bf16_rows(mesh8, width):
    """Rows wider than one lane tile, zero-padded to whole tiles on the
    wire, cross the padded exchange bit for bit, as a row of one tile
    (61 elements) does."""

    @work_item
    @dataclasses.dataclass
    class Wide:
        h: jax.Array    # (width,) bf16
        tag: jax.Array  # () i32

    def proto():
        return Wide(h=jnp.zeros((width,), jnp.bfloat16), tag=jnp.zeros((), jnp.int32))

    words = T.pack_spec(proto()).total_words
    assert words % LANES == 0 if width > 2 * LANES else words == -(-width // 2) + 1
    cfg = ForwardConfig("data", R, CAP, exchange="padded")

    def pattern(tag):
        # finite bfloat16 bit patterns (a NaN's payload is not kept through
        # the queue's float selects, at any width)
        lane = jnp.arange(width, dtype=jnp.int32)
        return ((lane[None] * 7 + tag[:, None] * 131) % 0x7F00).astype(jnp.uint16)

    def kernel(_x):
        q = make_queue(proto(), CAP)
        me = jax.lax.axis_index("data")
        n = 6
        tag = (jnp.arange(n) + me * 100).astype(jnp.int32)
        items = Wide(h=jax.lax.bitcast_convert_type(pattern(tag), jnp.bfloat16), tag=tag)
        dest = ((me + jnp.arange(n)) % R).astype(jnp.int32)
        q = enqueue(q, items, dest, jnp.ones(n, bool))
        nq, total = forward_work(q, cfg)
        return nq.count[None], nq.items.tag, jax.lax.bitcast_convert_type(nq.items.h, jnp.uint16), total

    f = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), P("data"), P("data"), P()),
        )
    )
    counts, tags, hs, total = f(jnp.arange(8.0))
    counts = np.asarray(counts)
    tags = np.asarray(tags).reshape(R, CAP)
    hs = np.asarray(hs).reshape(R, CAP, width)
    assert int(total) == 8 * 6 and counts.sum() == 48
    for r in range(R):
        got = tags[r, : counts[r]]
        assert all((t // 100 + t % 100) % R == r for t in got)  # addressed here
        np.testing.assert_array_equal(hs[r, : counts[r]], np.asarray(pattern(jnp.asarray(got))))
