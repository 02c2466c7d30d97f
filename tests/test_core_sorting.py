"""Unit + property tests for §4.2.1 sort-by-destination."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sorting as S

from helpers import make_rays


@given(
    st.integers(1, 64).flatmap(
        lambda n: st.tuples(
            st.just(n),
            st.lists(st.integers(-1, 7), min_size=n, max_size=n),
            st.integers(0, n),
        )
    )
)
@settings(max_examples=40, deadline=None)
def test_pack_keys_sort_matches_stable_argsort(args):
    n, dests, count = args
    cap = 64
    dest = jnp.zeros(cap, jnp.int32).at[: len(dests)].set(jnp.array(dests, jnp.int32))
    R = 8
    keys = S.pack_keys(dest, jnp.int32(count), R)
    d_sorted, lanes = S.unpack_keys(jax.lax.sort(keys), cap, R)
    # oracle: stable argsort on the sanitized destination
    lane = np.arange(cap)
    valid = (lane < count) & (np.asarray(dest) >= 0) & (np.asarray(dest) < R)
    d = np.where(valid, np.asarray(dest), R)
    perm = np.argsort(d, kind="stable")
    np.testing.assert_array_equal(np.asarray(d_sorted), d[perm])
    np.testing.assert_array_equal(np.asarray(lanes), perm)


@given(
    st.lists(st.integers(-2, 9), min_size=0, max_size=100),
    st.integers(0, 100),
)
@settings(max_examples=40, deadline=None)
def test_histogram_matches_numpy(dests, count):
    cap = 128
    R = 8
    dest = jnp.full((cap,), -1, jnp.int32).at[: len(dests)].set(jnp.array(dests, jnp.int32))
    h = np.asarray(S.destination_histogram(dest, jnp.int32(count), R))
    lane = np.arange(cap)
    d = np.asarray(dest)
    valid = (lane < count) & (d >= 0) & (d < R)
    expect = np.bincount(np.where(valid, d, R), minlength=R + 1)
    np.testing.assert_array_equal(h, expect)
    assert h.sum() == cap


@pytest.mark.parametrize("method", ["pack", "argsort"])
def test_sort_by_destination_full(method):
    cap, R, n = 64, 8, 40
    rays = make_rays(cap)
    rng = np.random.default_rng(0)
    dest = jnp.array(rng.integers(-1, R, cap), jnp.int32)
    items, d_sorted, counts = S.sort_by_destination(rays, dest, jnp.int32(n), R, method=method)
    d = np.asarray(dest)
    lane = np.arange(cap)
    valid = (lane < n) & (d >= 0)
    d_clean = np.where(valid, d, R)
    perm = np.argsort(d_clean, kind="stable")
    np.testing.assert_array_equal(np.asarray(d_sorted), d_clean[perm])
    # payload permuted identically (each ray read exactly once — §4.2.1)
    np.testing.assert_array_equal(np.asarray(items.pixel), np.asarray(rays.pixel)[perm])
    np.testing.assert_allclose(np.asarray(items.origin), np.asarray(rays.origin)[perm])
    np.testing.assert_array_equal(np.asarray(counts), np.bincount(d_clean, minlength=R + 1))


@given(st.lists(st.integers(0, 5), min_size=1, max_size=60))
@settings(max_examples=40, deadline=None)
def test_segment_bounds_match_histogram_offsets(dests):
    """The paper's boundary-detection formulation (§4.2.2 step 1) must agree
    with the histogram+cumsum formulation we actually use."""
    R = 6
    d_sorted = jnp.array(sorted(dests), jnp.int32)
    begin, end = S.segment_bounds_from_sorted(d_sorted, R)
    counts = np.bincount(dests, minlength=R)
    off = np.cumsum(counts) - counts
    np.testing.assert_array_equal(np.asarray(end) - np.asarray(begin), counts)
    np.testing.assert_array_equal(np.asarray(begin), off)


@given(
    st.lists(st.integers(-2, 9), min_size=0, max_size=100),
    st.integers(0, 100),
)
@settings(max_examples=40, deadline=None)
def test_destination_rank_matches_sort(dests, count):
    """The counting-sort plan is the sort's inverse image: item i must land at
    sorted position off[d_clean[i]] + rank[i], and the histogram must equal
    the sort path's — no keys, no sort, same placement."""
    cap = 128
    R = 8
    dest = jnp.full((cap,), -1, jnp.int32).at[: len(dests)].set(
        jnp.array(dests, jnp.int32)
    )
    d_clean, rank, hist = S.destination_rank(dest, jnp.int32(count), R)
    perm, d_sorted, counts = S.sort_permutation(dest, jnp.int32(count), R)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(counts))
    off = np.concatenate([[0], np.cumsum(np.asarray(hist))[:-1]])
    pos = off[np.asarray(d_clean)] + np.asarray(rank)
    # scatter-to-pos inverts the sort permutation exactly
    inv = np.empty(cap, np.int64)
    inv[np.asarray(perm)] = np.arange(cap)
    np.testing.assert_array_equal(pos, inv)


@given(st.lists(st.integers(0, 5), min_size=1, max_size=60))
@settings(max_examples=40, deadline=None)
def test_segment_bounds_from_histogram_match_neighbor_compare(dests):
    """The O(R) histogram-derived bounds must agree with the paper's O(C)
    neighbor-compare boundary detection — the latter survives only as this
    cross-validation oracle; no exchange stage re-scans the sorted vector."""
    R = 6
    d_sorted = jnp.array(sorted(dests), jnp.int32)
    counts = jnp.array(np.bincount(dests, minlength=R), jnp.int32)
    begin_h, end_h = S.segment_bounds_from_histogram(counts)
    begin_s, end_s = S.segment_bounds_from_sorted(d_sorted, R)
    np.testing.assert_array_equal(np.asarray(begin_h), np.asarray(begin_s))
    np.testing.assert_array_equal(np.asarray(end_h), np.asarray(end_s))


def test_pack_keys_rejects_overflow():
    with pytest.raises(ValueError):
        S.pack_keys(jnp.zeros(1 << 26, jnp.int32), jnp.int32(0), 1 << 10)


# --------------------------------------------- hierarchical N-level key sort
@pytest.mark.parametrize(
    "level_sizes",
    [(2, 4), (4, 2), (1, 8), (8, 1), (2, 2, 2), (2, 1, 4), (1, 2, 4), (2, 2, 2, 1)],
)
@pytest.mark.parametrize("method", ["pack", "argsort"])
def test_hierarchical_sort_matches_flat_sort(level_sizes, method):
    """Global ranks are lexicographic in the tier digits (node-major in the
    2-level case), so the (d_0, …, d_{L-1}, slot) N-level key order must
    coincide with the flat (dest, slot) order — one sort serves both the flat
    and the N-stage exchange."""
    cap = 64
    R = int(np.prod(level_sizes))
    rng = np.random.default_rng(sum(level_sizes) * 10 + len(level_sizes))
    dest = jnp.array(rng.integers(-1, R + 1, cap), jnp.int32)
    count = jnp.int32(50)
    perm_h, cnt_tensor = S.sort_permutation_hierarchical(
        dest, count, level_sizes, method=method
    )
    perm_f, _d, counts_f = S.sort_permutation(dest, count, R, method="pack")
    np.testing.assert_array_equal(np.asarray(perm_h), np.asarray(perm_f))
    assert cnt_tensor.shape == level_sizes
    np.testing.assert_array_equal(
        np.asarray(cnt_tensor).reshape(-1), np.asarray(counts_f)[:R]
    )


@pytest.mark.parametrize("level_sizes", [(2, 4), (2, 2, 2), (2, 1, 4)])
@given(data=st.data())
@settings(max_examples=20, deadline=None)
def test_hierarchical_keys_roundtrip(level_sizes, data):
    cap = 64
    R = int(np.prod(level_sizes))
    dests = data.draw(st.lists(st.integers(-1, R), min_size=1, max_size=cap))
    count = data.draw(st.integers(0, cap))
    dest = jnp.zeros(cap, jnp.int32).at[: len(dests)].set(jnp.array(dests, jnp.int32))
    keys = S.pack_keys_hierarchical(dest, jnp.int32(count), level_sizes)
    digits, slot = S.unpack_keys_hierarchical(keys, cap, level_sizes)
    lane = np.arange(cap)
    d = np.asarray(dest)
    valid = (lane < count) & (d >= 0) & (d < R)
    want = d.copy()
    for t, a in reversed(list(enumerate(level_sizes))):
        if t == 0:
            np.testing.assert_array_equal(
                np.asarray(digits[0]), np.where(valid, want, level_sizes[0])
            )
        else:
            np.testing.assert_array_equal(
                np.asarray(digits[t]), np.where(valid, want % a, 0)
            )
            want = want // a
    np.testing.assert_array_equal(np.asarray(slot), lane)


def test_hierarchical_keys_reject_overflow():
    with pytest.raises(ValueError):
        S.pack_keys_hierarchical(
            jnp.zeros(1 << 26, jnp.int32), jnp.int32(0), (1 << 8, 4)
        )
