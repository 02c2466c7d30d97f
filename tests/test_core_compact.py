"""The padded exchange's receive compaction (``stages.compact_blocks``).

The XLA path computes ``out[roff[g] + s] = recv[g, s]`` (``s < cnt[g]``,
rows past ``capacity`` dropped, ``roff`` shifted by ``front``) as the
inverse gather: every queue row reads its own source row.  It must stay
bit-exact with the plain scatter semantics, replayed here in numpy, and it
must not lower back to a row scatter.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.stages import compact_blocks

CAP, W = 16, 2

_compact = jax.jit(
    functools.partial(compact_blocks, use_pallas=False), static_argnums=(2,)
)


def _replay(recv, cnt, capacity, front):
    """Scalar replay of the scatter semantics."""
    out = np.zeros((capacity, recv.shape[2]), recv.dtype)
    base = 0 if front is None else front
    roff = base + np.cumsum(cnt) - cnt
    for g in range(recv.shape[0]):
        for s in range(cnt[g]):
            if roff[g] + s < capacity:
                out[roff[g] + s] = recv[g, s]
    total = int(cnt.sum())
    room = capacity if front is None else max(capacity - front, 0)
    new_count = min(total, room)
    return out, new_count, total - new_count


def _counts(rng, G, S):
    """Zero, full, random and heavy (totals past capacity) count vectors."""
    yield np.zeros(G, np.int32)
    yield np.full(G, S, np.int32)
    for _ in range(4):
        yield rng.integers(0, S + 1, G).astype(np.int32)
        yield rng.integers(S // 2, S + 1, G).astype(np.int32)


@pytest.mark.parametrize("front", [None, 0, CAP // 2, CAP], ids=lambda f: f"front{f}")
@pytest.mark.parametrize("S", [5, CAP, CAP + 7], ids=["S<cap", "S=cap", "S>cap"])
@pytest.mark.parametrize("G", [1, 2, 4, 8])
def test_compact_blocks_matches_scatter_replay(G, S, front):
    rng = np.random.default_rng(1000 * G + 10 * S + (front or 0))
    recv = rng.integers(1, 2**32, (G, S, W), dtype=np.uint64).astype(np.uint32)
    for cnt in _counts(rng, G, S):
        f = None if front is None else jnp.int32(front)
        out, new_count, drops = _compact(jnp.asarray(recv), jnp.asarray(cnt), CAP, front=f)
        want_out, want_new, want_drops = _replay(recv, cnt, CAP, front)
        np.testing.assert_array_equal(np.asarray(out), want_out, err_msg=f"cnt={cnt}")
        assert int(new_count) == want_new, cnt
        assert int(drops) == want_drops, cnt


@pytest.mark.parametrize("with_front", [False, True], ids=["no-front", "front"])
@pytest.mark.parametrize("G", [1, 4])
def test_compact_blocks_lowers_to_a_gather_without_scatter(G, with_front):
    """The receive side must not slide back to a row scatter."""
    S = 2 * CAP
    args = [jnp.zeros((G, S, W), jnp.uint32), jnp.zeros((G,), jnp.int32), CAP]
    front = jnp.int32(3) if with_front else None
    txt = _compact.lower(*args, front=front).as_text()
    assert "scatter" not in txt
    assert "gather" in txt
