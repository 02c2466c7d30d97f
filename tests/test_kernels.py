"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes (and dtypes where the kernel is dtype-generic)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import work_item
from repro.kernels.bucket_scatter import (
    kernel as bs_kernel,
    ops as bs_ops,
    ref as bs_ref,
)
from repro.kernels.compact import ops as compact_ops, ref as compact_ref
from repro.kernels.delta_tracking import ops as dt_ops, ref as dt_ref
from repro.kernels.marshal import ops as marshal_ops, kernel as marshal_k, ref as marshal_ref
from repro.kernels.nbody_forces import ops as nb_ops, ref as nb_ref
from repro.kernels.rk4_advect import ops as rk4_ops, ref as rk4_ref
from repro.kernels.sort_keys import kernel as sk_kernel, ops as sk_ops, ref as sk_ref


# ---------------------------------------------------------------- sort_keys
@pytest.mark.parametrize(
    "cap,block_rows", [(64, 8), (3000, 8), (4096, 16), (1024, 256)]
)
@pytest.mark.parametrize("num_ranks", [4, 8, 64])
def test_sort_keys_pack_hist_matches_ref(cap, block_rows, num_ranks):
    rng = np.random.default_rng(cap + num_ranks)
    dest = jnp.array(rng.integers(-2, num_ranks + 1, cap), jnp.int32)
    count = jnp.int32(rng.integers(0, cap + 1))
    ib = max(1, (cap - 1).bit_length())
    keys, hist = sk_kernel.pack_and_histogram(
        dest, count, num_ranks=num_ranks, idx_bits=ib, block_rows=block_rows,
        interpret=True,
    )
    rkeys, rhist = sk_ref.pack_and_histogram(dest, count, num_ranks=num_ranks, idx_bits=ib)
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(rkeys))
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(rhist))


def test_sort_keys_full_sort_matches_core():
    from repro.core import sorting as S

    @work_item
    @dataclasses.dataclass
    class Item:
        a: jax.Array
        b: jax.Array

    cap, R = 256, 16
    rng = np.random.default_rng(7)
    items = Item(
        a=jnp.array(rng.normal(size=(cap, 4)), jnp.float32),
        b=jnp.array(rng.integers(0, 100, cap), jnp.int32),
    )
    dest = jnp.array(rng.integers(-1, R, cap), jnp.int32)
    count = jnp.int32(200)
    pi, pd, pc = sk_ops.sort_by_destination(items, dest, count, R, interpret=True)
    ri, rd, rc = S.sort_by_destination(items, dest, count, R, method="pack")
    np.testing.assert_array_equal(np.asarray(pd), np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(pc), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(pi.b), np.asarray(ri.b))
    np.testing.assert_allclose(np.asarray(pi.a), np.asarray(ri.a))


# ----------------------------------------------------------- bucket_scatter
@pytest.mark.parametrize(
    "cap,block_rows", [(64, 8), (3000, 8), (4096, 16), (1152, 8), (1024, 256)]
)
@pytest.mark.parametrize("num_ranks", [4, 8, 64])
def test_bucket_scatter_rank_hist_matches_ref(cap, block_rows, num_ranks):
    """The triangular-MXU prefix kernel vs the one-hot cumsum oracle —
    d_clean, in-bucket rank, and histogram all bit-equal (incl. capacities
    that are not a whole number of blocks, and multi-block grids that carry
    the running histogram)."""
    rng = np.random.default_rng(cap + num_ranks)
    dest = jnp.array(rng.integers(-2, num_ranks + 2, cap), jnp.int32)
    count = jnp.int32(rng.integers(0, cap + 1))
    dk, rk, hk = bs_kernel.rank_and_histogram(
        dest, count, num_ranks=num_ranks, block_rows=block_rows, interpret=True
    )
    dr, rr, hr = bs_ref.rank_and_histogram(dest, count, num_ranks=num_ranks)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(hk), np.asarray(hr))


@pytest.mark.parametrize("n,slots,D", [(64, 64, 3), (256, 80, 9), (100, 64, 1)])
def test_bucket_scatter_rows_matches_ref(n, slots, D):
    """scatter_rows vs its jnp oracle, incl. out-of-range (dropped) rows and
    duplicate trash positions."""
    rng = np.random.default_rng(n + slots)
    src = jnp.array(rng.integers(0, 2**32, (n, D), dtype=np.uint32))
    pos = jnp.array(rng.integers(-3, slots + 3, n), jnp.int32)
    got = bs_kernel.scatter_rows(src, pos, num_slots=slots, interpret=True)
    want = bs_ref.scatter_rows(src, pos, num_slots=slots)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bucket_scatter_negative_positions_are_dropped():
    """Negative dstpos must land in the trash, not wrap to a valid slot
    (``.at[].set`` wraps negatives even with mode='drop' — the ref guards
    explicitly, the kernel redirects them past the end)."""
    src = jnp.ones((4, 2), jnp.uint32)
    pos = jnp.array([-1, -4, 1, 9], jnp.int32)  # only index 2 survives
    want = jnp.zeros((4, 2), jnp.uint32).at[1].set(1)
    got_k = bs_kernel.scatter_rows(src, pos, num_slots=4, interpret=True)
    got_r = bs_ref.scatter_rows(src, pos, num_slots=4)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_r), np.asarray(want))


def test_bucket_scatter_rejects_f32_inexact_capacity():
    """Counts ride the MXU in f32: capacities past 2**24 must raise loudly
    (the scatter analogue of pack_keys' 32-bit overflow), never collide."""
    with pytest.raises(ValueError, match="2\\*\\*24"):
        bs_kernel.rank_and_histogram(
            jnp.zeros((1 << 25,), jnp.int32), jnp.int32(0), num_ranks=4,
            interpret=True,
        )


def test_bucket_scatter_reproduces_sort_placement():
    """The tentpole equivalence at the kernel level: scattering every row to
    ``off[dest] + rank`` reproduces key-pack + lax.sort + gather bit-exactly
    on the valid prefix — the counting sort IS the stable sort."""
    from repro.core import sorting as S

    cap, R, W = 256, 16, 7
    rng = np.random.default_rng(21)
    dest = jnp.array(rng.integers(-1, R + 1, cap), jnp.int32)
    count = jnp.int32(200)
    packed = jnp.array(rng.integers(0, 2**32, (cap, W), dtype=np.uint32))
    d_clean, rank, hist = bs_ops.rank_and_histogram(
        dest, count, num_ranks=R, interpret=True
    )
    off = jnp.cumsum(hist[:R]) - hist[:R]
    keep = d_clean < R
    dstpos = jnp.where(keep, off[jnp.clip(d_clean, 0, R - 1)] + rank, cap)
    got = bs_ops.scatter_rows(packed, dstpos, num_slots=cap, interpret=True)
    perm, _d, counts = S.sort_permutation(dest, count, R, method="pack")
    want = jnp.take(packed, perm, axis=0)
    n_valid = int(np.asarray(hist[:R]).sum())
    np.testing.assert_array_equal(
        np.asarray(got)[:n_valid], np.asarray(want)[:n_valid]
    )
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(counts))


# ------------------------------------------------------------------ compact
@pytest.mark.parametrize(
    "cap,block_rows", [(32, 8), (5000, 8), (4096, 16), (2048, 256)]
)
def test_compact_positions_matches_ref(cap, block_rows):
    rng = np.random.default_rng(cap)
    mask = jnp.array(rng.random(cap) < 0.4)
    pos, total = compact_ops.K.compact_positions(
        mask, block_rows=block_rows, interpret=True
    )
    rpos, rtotal = compact_ref.compact_positions(mask)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(rpos))
    assert int(total[0]) == int(rtotal[0])


@given(st.lists(st.booleans(), min_size=1, max_size=64))
@settings(max_examples=30, deadline=None)
def test_compact_positions_property(bits):
    n = 64
    mask = jnp.zeros(n, bool).at[: len(bits)].set(jnp.array(bits))
    pos, total = compact_ops.compact_positions(mask)
    m = np.asarray(mask)
    p = np.asarray(pos)[m]
    assert int(total) == m.sum()
    # emitted positions are exactly 0..k-1 in lane order (stable append)
    np.testing.assert_array_equal(p, np.arange(m.sum()))


def test_compact_scatter_roundtrip():
    @work_item
    @dataclasses.dataclass
    class V:
        x: jax.Array

    n = 128
    rng = np.random.default_rng(3)
    items = V(x=jnp.array(rng.normal(size=(n, 2)), jnp.float32))
    mask = jnp.array(rng.random(n) < 0.3)
    out, count = compact_ops.compact(items, mask, 64)
    m = np.asarray(mask)
    np.testing.assert_allclose(
        np.asarray(out.x)[: int(count)], np.asarray(items.x)[m][:64]
    )


# ------------------------------------------------------------------ marshal
@pytest.mark.parametrize("cap,R,S,D", [(64, 4, 16, 3), (256, 8, 8, 11), (128, 16, 8, 1)])
def test_marshal_matches_ref(cap, R, S, D):
    rng = np.random.default_rng(R * S)
    flat = jnp.array(rng.normal(size=(cap, D)), jnp.float32)
    counts = rng.multinomial(cap // 2, np.ones(R) / R)
    off = jnp.array(np.concatenate([[0], np.cumsum(counts)[:-1]]), jnp.int32)
    got = marshal_k.marshal(flat, off, num_ranks=R, slot=S, interpret=True)
    want = marshal_ref.marshal(flat, off, num_ranks=R, slot=S)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("cap,n,D", [(64, 64, 3), (256, 32, 9), (128, 200, 1)])
def test_gather_rows_matches_ref(cap, n, D):
    """The fused single-pass marshal (sort-permutation composed with the
    send-slot layout) against its jnp oracle, incl. out-of-range clamping."""
    rng = np.random.default_rng(cap + n)
    src = jnp.array(rng.integers(0, 2**32, (cap, D), dtype=np.uint32))
    idx = jnp.array(rng.integers(-3, cap + 3, n), jnp.int32)  # some out of range
    got = marshal_k.gather_rows(src, idx, interpret=True)
    want = marshal_ref.gather_rows(src, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_marshal_equals_sort_then_marshal():
    """fused_marshal(packed, perm[off[r]+s]) == marshal(packed[perm], off) —
    the single-pass path is bit-identical to the two-pass formulation."""
    cap, R, S, D = 64, 4, 8, 5
    rng = np.random.default_rng(11)
    packed = jnp.array(rng.integers(0, 2**32, (cap, D), dtype=np.uint32))
    perm = jnp.array(rng.permutation(cap), jnp.int32)
    counts = np.array([7, 0, 8, 5], np.int32)
    off = jnp.array(np.concatenate([[0], np.cumsum(counts)[:-1]]), jnp.int32)
    r_idx = jnp.repeat(jnp.arange(R, dtype=jnp.int32), S)
    s_idx = jnp.tile(jnp.arange(S, dtype=jnp.int32), R)
    src_rows = perm[jnp.clip(off[r_idx] + s_idx, 0, cap - 1)]
    got = marshal_ops.fused_marshal(packed, src_rows, num_ranks=R, slot=S)
    two_pass = marshal_k.marshal(
        jnp.take(packed, perm, axis=0), off, num_ranks=R, slot=S, interpret=True
    )
    for r in range(R):  # rows past the segment count are garbage in both
        np.testing.assert_array_equal(
            np.asarray(got[r][: counts[r]]), np.asarray(two_pass[r][: counts[r]])
        )


@pytest.mark.parametrize("cap,R,S,D", [(64, 4, 16, 3), (256, 8, 8, 5)])
def test_unmarshal_matches_ref(cap, R, S, D):
    rng = np.random.default_rng(cap + D)
    recv = jnp.array(rng.normal(size=(R, S, D)), jnp.float32)
    counts = jnp.array(rng.integers(0, S + 1, R), jnp.int32)
    off = jnp.cumsum(counts) - counts
    got = marshal_k.unmarshal(recv, off, counts, capacity=cap, interpret=True)
    want = marshal_ref.unmarshal(recv, off, counts, capacity=cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_marshal_unmarshal_roundtrip_pytree():
    """marshal → unmarshal with the true counts reproduces the valid prefix."""
    @work_item
    @dataclasses.dataclass
    class W:
        x: jax.Array
        i: jax.Array

    cap, R, S = 64, 4, 16
    rng = np.random.default_rng(0)
    n = 40
    items = W(
        x=jnp.array(rng.normal(size=(cap, 3)), jnp.float32),
        i=jnp.arange(cap, dtype=jnp.int32),
    )
    counts = np.array([10, 0, 16, 5], np.int32)  # every segment fits the slot
    n = int(counts.sum())
    off = jnp.array(np.concatenate([[0], np.cumsum(counts)[:-1]]), jnp.int32)
    buf = marshal_ops.marshal_items(items, off, num_ranks=R, slot=S)
    back = marshal_ops.unmarshal_items(
        buf, off, jnp.array(counts), capacity=cap
    )
    np.testing.assert_array_equal(np.asarray(back.i[:n]), np.asarray(items.i[:n]))
    np.testing.assert_allclose(np.asarray(back.x[:n]), np.asarray(items.x[:n]))


# ------------------------------------------------------------- nbody_forces
@pytest.mark.parametrize("n,m,ti,tj", [(64, 64, 16, 16), (128, 256, 128, 128), (96, 32, 32, 32)])
def test_pairwise_accel_matches_ref(n, m, ti, tj):
    rng = np.random.default_rng(n + m)
    xi = jnp.array(rng.normal(size=(n, 3)), jnp.float32)
    xj = jnp.array(rng.normal(size=(m, 3)), jnp.float32)
    mj = jnp.array(rng.random(m), jnp.float32)
    got = nb_ops.K.pairwise_accel(xi, xj, mj, ti=ti, tj=tj, interpret=True)
    want = nb_ref.pairwise_accel(xi, xj, mj)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_pairwise_accel_zero_mass_padding_is_inert():
    xi = jnp.zeros((8, 3))
    xj = jnp.array(np.random.default_rng(1).normal(size=(16, 3)), jnp.float32)
    mj = jnp.zeros(16)
    got = nb_ops.pairwise_accel(xi, xj, mj)
    np.testing.assert_allclose(np.asarray(got), 0.0)


# -------------------------------------------------------------- rk4_advect
@pytest.mark.parametrize("field", [rk4_ops.ABC, rk4_ops.TORNADO, rk4_ops.TAYLOR_GREEN])
@pytest.mark.parametrize("n", [32, 1024, 96])
def test_rk4_matches_ref(field, n):
    rng = np.random.default_rng(field * 100 + n)
    pos = jnp.array(rng.normal(size=(n, 3)) * 2, jnp.float32)
    got_p, got_v = rk4_ops.rk4_step(pos, dt=0.05, field_id=field)
    want_p, want_v = rk4_ref.rk4_step(pos, dt=0.05, field_id=field)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want_p), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------- delta_tracking
@pytest.mark.parametrize("n,steps,g", [(64, 4, 4), (256, 8, 8), (128, 1, 2)])
def test_delta_tracking_matches_ref(n, steps, g):
    rng = np.random.default_rng(n + steps)
    o = jnp.array(rng.normal(size=(n, 3)), jnp.float32)
    d = jnp.array(rng.normal(size=(n, 3)), jnp.float32)
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    t0 = jnp.zeros(n)
    texit = jnp.array(rng.random(n) * 4 + 0.5, jnp.float32)
    u = jnp.array(rng.random((n, steps, 2)), jnp.float32)
    blobs = jnp.array(
        np.concatenate(
            [rng.normal(size=(g, 3)), rng.random((g, 1)) + 0.3, rng.random((g, 1)) * 2],
            axis=1,
        ),
        jnp.float32,
    )
    got_t, got_s = dt_ops.track(o, d, t0, texit, u, blobs, majorant=4.0, steps=steps)
    want_t, want_s = dt_ref.track(o, d, t0, texit, u, blobs, majorant=4.0, steps=steps)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


def test_delta_tracking_statuses_are_consistent():
    n = 128
    rng = np.random.default_rng(0)
    o = jnp.zeros((n, 3))
    d = jnp.tile(jnp.array([[1.0, 0, 0]]), (n, 1))
    texit = jnp.full((n,), 0.01)  # everyone exits almost immediately
    u = jnp.array(rng.random((n, 4, 2)), jnp.float32)
    blobs = jnp.array([[0, 0, 0, 1.0, 0.0]], jnp.float32)  # zero density
    t, s = dt_ops.track(o, d, jnp.zeros(n), texit, u, blobs, majorant=1.0, steps=4)
    assert np.all(np.asarray(s) == dt_ref.EXITED)
