"""Unit tests for the device interface: WorkQueue emit/read semantics (§3.2-3.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import DISCARD, clear, enqueue, get_incoming, make_queue, num_incoming
from repro import compat
from repro.core import queue as queue_mod
from repro.core.queue import WorkQueue
from repro.models.moe import TokenItem, _token_proto

from helpers import make_rays, ray_proto


def test_empty_queue():
    q = make_queue(ray_proto(), 16)
    assert int(num_incoming(q)) == 0
    assert q.capacity == 16
    assert np.all(np.asarray(q.dest) == DISCARD)


def test_enqueue_appends_in_lane_order():
    q = make_queue(ray_proto(), 16)
    rays = make_rays(4)
    q = enqueue(q, rays, jnp.array([3, 1, 2, 0], jnp.int32), jnp.ones(4, bool))
    assert int(q.count) == 4
    np.testing.assert_array_equal(np.asarray(q.dest[:4]), [3, 1, 2, 0])
    got = get_incoming(q, 2)
    np.testing.assert_allclose(np.asarray(got.origin), np.asarray(rays.origin[2]))


def test_enqueue_masked_compacts_stably():
    q = make_queue(ray_proto(), 16)
    rays = make_rays(6)
    mask = jnp.array([True, False, True, False, True, False])
    q = enqueue(q, rays, jnp.arange(6, dtype=jnp.int32), mask)
    assert int(q.count) == 3
    np.testing.assert_array_equal(np.asarray(q.items.pixel[:3]), [0, 2, 4])
    np.testing.assert_array_equal(np.asarray(q.dest[:3]), [0, 2, 4])


def test_multiple_enqueues_accumulate():
    """A kernel may emit more than one item per lane (§3.3): e.g. a bounce
    ray and a shadow ray from the same shading event."""
    q = make_queue(ray_proto(), 16)
    q = enqueue(q, make_rays(3), jnp.zeros(3, jnp.int32), jnp.ones(3, bool))
    q = enqueue(q, make_rays(3, pixel_base=100), jnp.ones(3, jnp.int32), jnp.ones(3, bool))
    assert int(q.count) == 6
    np.testing.assert_array_equal(np.asarray(q.items.pixel[:6]), [0, 1, 2, 100, 101, 102])


def test_overflow_drops_and_counts():
    """Paper §3.3: emits past capacity 'simply get dropped'."""
    q = make_queue(ray_proto(), 4)
    q = enqueue(q, make_rays(6), jnp.zeros(6, jnp.int32), jnp.ones(6, bool))
    assert int(q.count) == 4
    assert int(q.drops) == 2
    np.testing.assert_array_equal(np.asarray(q.items.pixel[:4]), [0, 1, 2, 3])


def test_negative_dest_is_discard():
    q = make_queue(ray_proto(), 16)
    dest = jnp.array([0, -1, 1, DISCARD], jnp.int32)
    q = enqueue(q, make_rays(4), dest, jnp.ones(4, bool))
    assert int(q.count) == 2
    np.testing.assert_array_equal(np.asarray(q.items.pixel[:2]), [0, 2])


def test_clear_resets_count_keeps_drops():
    q = make_queue(ray_proto(), 4)
    q = enqueue(q, make_rays(6), jnp.zeros(6, jnp.int32), jnp.ones(6, bool))
    q = clear(q)
    assert int(q.count) == 0
    assert int(q.drops) == 2
    assert np.all(np.asarray(q.dest) == DISCARD)


def test_enqueue_is_jittable_and_donatable():
    @jax.jit
    def step(q):
        return enqueue(q, make_rays(2), jnp.zeros(2, jnp.int32), jnp.ones(2, bool))

    q = step(make_queue(ray_proto(), 8))
    assert int(q.count) == 2


@pytest.mark.parametrize("truthy", [1, 2], ids=["ones", "nonunit"])
def test_enqueue_bool_and_int_masks_are_equivalent(truthy):
    """ISSUE 5 satellite: enqueue accepts any mask dtype with nonzero-is-emit
    semantics.  The regression: an int mask used to be combined with the
    dest check by BITWISE and, so a truthy value of 2 (`2 & True == 0`)
    silently lost the emit, and the raw ints leaked into the position
    prefix-sum.  Bool and int masks must produce identical queues —
    count, placement, AND the overflow drop counter."""
    rays = make_rays(6)
    dest = jnp.array([0, 1, DISCARD, 2, 3, 4], jnp.int32)
    keep = np.array([1, 0, 1, 1, 0, 1])
    masks = {
        "bool": jnp.asarray(keep, bool),
        "int32": jnp.asarray(keep * truthy, jnp.int32),
    }
    # capacity 3 < the 3 valid emits on lanes (0, 3, 5) plus the DISCARD
    # lane: the drop accounting must agree across mask dtypes too
    got = {
        name: enqueue(make_queue(ray_proto(), 3), rays, dest, m)
        for name, m in masks.items()
    }
    b, i = got["bool"], got["int32"]
    assert int(b.count) == int(i.count) == 3
    assert int(b.drops) == int(i.drops) == 0
    np.testing.assert_array_equal(np.asarray(b.dest), np.asarray(i.dest))
    np.testing.assert_array_equal(
        np.asarray(b.items.pixel), np.asarray(i.items.pixel)
    )
    np.testing.assert_array_equal(np.asarray(b.items.pixel), [0, 3, 5])
    # and with a genuine overflow: 4 emits into capacity 3 → 1 drop, both
    full = {
        name: enqueue(
            make_queue(ray_proto(), 3), rays,
            jnp.zeros(6, jnp.int32),
            jnp.asarray(np.array([1, 1, 0, 1, 0, 1]) * (truthy if name == "int32" else 1),
                        bool if name == "bool" else jnp.int32),
        )
        for name in ("bool", "int32")
    }
    assert int(full["bool"].drops) == int(full["int32"].drops) == 1
    np.testing.assert_array_equal(
        np.asarray(full["bool"].items.pixel[:3]),
        np.asarray(full["int32"].items.pixel[:3]),
    )


# -------------------------------------------------- emit-time validation
def test_make_queue_rejects_non_int_capacity():
    for bad in (16.0, "16", None, jnp.zeros(())):
        with pytest.raises(ValueError, match="static Python int"):
            make_queue(ray_proto(), bad)
    with pytest.raises(ValueError, match=">= 1"):
        make_queue(ray_proto(), 0)


def test_enqueue_rejects_float_dest():
    """A float dest would truncate-cast and misroute silently — the classic
    emit-kernel bug this check exists to catch at trace time."""
    q = make_queue(ray_proto(), 16)
    with pytest.raises(ValueError, match="integer dtype"):
        enqueue(q, make_rays(4), jnp.array([0.0, 1.0, 2.0, 3.0]), jnp.ones(4, bool))


def test_enqueue_rejects_out_of_range_concrete_dest():
    q = make_queue(ray_proto(), 16)
    dest = jnp.array([0, 9, 2, 12], jnp.int32)
    with pytest.raises(ValueError, match=r"num_ranks \(8\).*offending value 12"):
        enqueue(q, make_rays(4), dest, jnp.ones(4, bool), num_ranks=8)
    # unmasked and DISCARD lanes are exempt — only real emits are checked
    ok = enqueue(
        q, make_rays(4), jnp.array([0, 9, DISCARD, 12], jnp.int32),
        jnp.array([1, 0, 1, 0], bool), num_ranks=8,
    )
    assert int(ok.count) == 1


def test_enqueue_traced_dest_skips_value_check():
    """Values don't exist at trace time; the marshal sanitize still guards
    execution, so a traced out-of-range dest becomes a counted sanitize-drop
    rather than a trace error."""
    def emit(dest):
        return enqueue(
            make_queue(ray_proto(), 16), make_rays(4), dest,
            jnp.ones(4, bool), num_ranks=8,
        ).count

    n = jax.jit(emit)(jnp.array([0, 9, 2, 12], jnp.int32))
    assert int(n) == 4  # enqueued; forward_work's sanitize would cut 9 and 12


# ------------------------------------------- the scatter form, as reference
def _scatter_enqueue(q, items, dest, mask):
    """``enqueue`` as a prefix sum and one scatter over every lane: the
    reference the contiguous append must match bit for bit."""
    cap = q.capacity
    dest = jnp.asarray(dest)
    mask = (jnp.asarray(mask) != 0) & (dest >= 0)
    m32 = mask.astype(jnp.int32)
    pos = q.count + jnp.cumsum(m32) - m32
    slot = jnp.where(mask & (pos < cap), pos, cap)
    new_items = jax.tree.map(lambda b, v: b.at[slot].set(v, mode="drop"), q.items, items)
    new_dest = q.dest.at[slot].set(dest.astype(jnp.int32), mode="drop")
    n_emit = jnp.sum(m32)
    new_count = jnp.minimum(q.count + n_emit, cap)
    return WorkQueue(new_items, new_dest, new_count, q.drops + q.count + n_emit - new_count)


_D = 48  # hidden width of the wide items at test size


def _items(kind, n, rng):
    """A batch of ``n`` random items: ``narrow`` one int32, ``wide`` one bf16
    row, ``multi`` the MoE's token row (bf16 row, int32 and f32 scalars)
    beside a bool and a (3,) f32 leaf."""
    h = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16)  # noqa: E731
    i32 = lambda: jnp.asarray(rng.integers(-9, 99, n), jnp.int32)  # noqa: E731
    if kind == "narrow":
        return {"v": i32()}
    if kind == "wide":
        return {"h": h(n, _D)}
    return {
        "tok": TokenItem(h=h(n, _D), slot=i32(), weight=jnp.asarray(rng.random(n), jnp.float32),
                         expert=i32(), src=i32()),
        "flag": jnp.asarray(rng.random(n) < 0.5),
        "pos": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32),
    }


def _held_queue(kind, cap, count, rng):
    """A queue whose every row, past ``count`` too, holds a distinct value,
    so a row the append should keep but writes shows up."""
    return WorkQueue(_items(kind, cap, rng), jnp.asarray(rng.integers(-1, 4, cap), jnp.int32),
                     jnp.int32(count), jnp.int32(5))


def _emits(live, n, rng, int_mask):
    """``(dest, mask)`` for ``n`` lanes: ``all``, ``none`` or ``some`` live,
    with ``DISCARD`` dests on a quarter of the masked lanes for ``some``."""
    if live == "all":
        keep, dest = np.ones(n, bool), rng.integers(0, 4, n)
    elif live == "none":
        keep, dest = np.zeros(n, bool), rng.integers(0, 4, n)
    else:
        keep = rng.random(n) < 0.6
        dest = np.where(rng.random(n) < 0.25, DISCARD, rng.integers(0, 4, n))
    mask = jnp.asarray(keep.astype(np.int32)) if int_mask else jnp.asarray(keep)
    return jnp.asarray(dest, jnp.int32), mask


def _moe_emits(rng, tokens=40, top_k=8, ranks=2, experts_per_rank=4):
    """The MoE layer's eight emits at test width: every token to each of its
    ``top_k`` experts, ``DISCARD`` where the expert's rank is not on the mesh."""
    x = jnp.asarray(rng.normal(size=(tokens, _D)), jnp.bfloat16)
    idx = rng.integers(0, 32, (tokens, top_k))
    w = jnp.asarray(rng.random((tokens, top_k)), jnp.float32)
    tok = jnp.arange(tokens, dtype=jnp.int32)
    src = jnp.zeros((tokens,), jnp.int32)
    out = []
    for j in range(top_k):
        owner = idx[:, j] // experts_per_rank
        dest = jnp.asarray(np.where(owner < ranks, owner, DISCARD), jnp.int32)
        it = {"tok": TokenItem(x, tok, w[:, j], jnp.asarray(idx[:, j], jnp.int32), src)}
        out.append((it, dest, jnp.ones((tokens,), bool)))
    return out


# (items, lanes n, capacity, count before, live lanes, int mask, rows a step)
_CASES = {
    "narrow-n<cap": ("narrow", 24, 40, 3, "some", False, None),
    "narrow-n>cap": ("narrow", 70, 40, 0, "some", False, 7),
    "wide-n<cap": ("wide", 24, 40, 10, "some", False, 5),
    "wide-n>cap-all-live": ("wide", 70, 40, 0, "all", False, None),
    "wide-count-near-cap": ("wide", 30, 40, 33, "some", False, 4),
    "multi-count-near-cap": ("multi", 30, 40, 36, "all", True, 3),
    "multi-count-at-cap": ("multi", 30, 40, 40, "all", False, 3),
    "multi-none-live": ("multi", 30, 40, 12, "none", False, 3),
    "multi-all-live": ("multi", 30, 40, 2, "all", False, 8),
    "multi-int-mask": ("multi", 50, 40, 7, "some", True, 6),
    "moe-eight-emits": ("multi", None, 192, 0, "moe", False, 9),
}


@pytest.mark.parametrize("under", ["jit", "shard_map"])
@pytest.mark.parametrize("case", list(_CASES))
def test_enqueue_matches_scatter_reference(case, under, monkeypatch):
    """The contiguous append gives what the scatter gave, bit for bit:
    ``items`` and ``dest`` on every row (past ``count`` too), ``count`` and
    ``drops``, for each case under ``jit`` and under ``shard_map``; with a
    small ``rows a step`` the append takes several steps, the last one
    overlapping the one before it."""
    kind, n, cap, count, live, int_mask, step = _CASES[case]
    if step is not None:
        monkeypatch.setattr(queue_mod, "_APPEND_ROWS", step)
    ranks = 2
    rngs = [np.random.default_rng(17 * r + len(case)) for r in range(ranks)]
    if live == "moe":
        # the layer's queue is made where it is used, inside the program
        calls = [_moe_emits(rng) for rng in rngs]
        held = [jnp.int32(0)] * ranks
    else:
        held = [_held_queue(kind, cap, count, rng) for rng in rngs]
        calls = [[(_items(kind, n, rng),) + _emits(live, n, rng, int_mask)] for rng in rngs]

    def run(fn):
        def per_rank(q, calls):
            if live == "moe":
                q = make_queue({"tok": _token_proto(_D, jnp.bfloat16)}, cap)
            for items, dest, mask in calls:
                q = fn(q, items, dest, mask)
            return q

        if under == "jit":
            return [jax.jit(per_rank)(q, c) for q, c in zip(held, calls)]
        stack = lambda *xs: jnp.stack(xs)  # noqa: E731
        mesh = compat.make_mesh((ranks,), ("r",))
        body = lambda q, c: jax.tree.map(  # noqa: E731
            lambda x: x[None], per_rank(*jax.tree.map(lambda x: x[0], (q, c))))
        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("r"), out_specs=P("r")))
        out = f(jax.tree.map(stack, *held), jax.tree.map(stack, *calls))
        return [jax.tree.map(lambda x: x[r], out) for r in range(ranks)]

    for got, want in zip(run(enqueue), run(_scatter_enqueue)):
        assert int(got.count) == int(want.count)
        assert int(got.drops) == int(want.drops)
        for g, w in zip(jax.tree.leaves((got.items, got.dest)), jax.tree.leaves((want.items, want.dest))):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g).view(np.uint8), np.asarray(w).view(np.uint8))


@pytest.mark.parametrize("kind", ["narrow", "wide"])
def test_enqueue_lowers_without_scatter(kind):
    """The append is a sort and contiguous writes: no scatter in its program,
    for narrow and for wide items."""
    rng = np.random.default_rng(3)
    q = _held_queue(kind, 64, 5, rng)
    dest, mask = _emits("some", 48, rng, False)
    text = jax.jit(enqueue).lower(q, _items(kind, 48, rng), dest, mask).as_text()
    assert "sort" in text
    assert "scatter" not in text
