"""Hypothesis property tests for the forwarding core's invariants.

The jitted program is compiled ONCE (fixed shapes); hypothesis drives the
runtime data (destinations, counts, payload values), so each example is just
an execution.  Invariants:

  * conservation: when every capacity suffices, forwarding neither loses nor
    duplicates items — multiset of (value, dest) pairs is preserved, and
    every item lands on the rank it addressed;
  * accounting: sum(received) + drops == sum(emitted) in all cases;
  * termination total equals the global live count.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.core import DISCARD, ForwardConfig, WorkQueue, forward_work, work_item

R, CAP = 8, 64


@work_item
@dataclasses.dataclass
class Item:
    val: jax.Array
    src: jax.Array


_PROTO_ITEMS = Item(
    val=jnp.zeros((R * CAP,), jnp.float32), src=jnp.zeros((R * CAP,), jnp.int32)
)


def _make_fn(mesh8, exchange):
    cfg = ForwardConfig("data", R, CAP, peer_capacity=CAP, exchange=exchange)

    def fwd(items_val, dest, counts):
        me = jax.lax.axis_index("data")
        q = WorkQueue(
            items=Item(val=items_val, src=me * jnp.ones(CAP, jnp.int32)),
            dest=dest,
            count=counts[0],
            drops=jnp.zeros((), jnp.int32),
        )
        nq, total = forward_work(q, cfg)
        return nq.items.val, nq.items.src, nq.count[None], nq.drops[None], total

    return jax.jit(
        jax.shard_map(
            fwd, mesh=mesh8,
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data"), P("data"), P()),
        )
    )


@pytest.fixture(scope="module")
def fwd_padded(mesh8):
    return _make_fn(mesh8, "padded")


@given(
    data=st.data(),
)
@settings(max_examples=25, deadline=None)
def test_conservation_and_addressing(fwd_padded, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP // R, R).astype(np.int32)  # capacities suffice
    dest = np.full((R, CAP), DISCARD, np.int32)
    val = np.zeros((R, CAP), np.float32)
    sent = []
    for r in range(R):
        d = rng.integers(0, R, counts[r])
        v = rng.normal(size=counts[r]).astype(np.float32)
        dest[r, : counts[r]] = d
        val[r, : counts[r]] = v
        sent += [(round(float(x), 5), int(dd), r) for x, dd in zip(v, d)]

    out_val, out_src, out_counts, out_drops, total = fwd_padded(
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(np.repeat(counts, 1)),
    )
    out_val = np.asarray(out_val).reshape(R, CAP)
    out_src = np.asarray(out_src).reshape(R, CAP)
    out_counts = np.asarray(out_counts)
    got = []
    for r in range(R):
        n = out_counts[r]
        got += [
            (round(float(out_val[r, i]), 5), r, int(out_src[r, i])) for i in range(n)
        ]
    assert int(np.asarray(out_drops).sum()) == 0
    assert sorted(got) == sorted(sent), "items lost, duplicated, or misrouted"
    assert int(total) == len(sent)


@given(data=st.data())
@settings(max_examples=15, deadline=None)
def test_drop_accounting_balances(mesh8, data):
    """Even with pathological routing (everyone → rank 0), emitted ==
    received + dropped, globally."""
    fn = _make_fn(mesh8, "padded")
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    counts = rng.integers(0, CAP + 1, R).astype(np.int32)
    dest = np.zeros((R, CAP), np.int32)  # all to rank 0 — guaranteed overflow
    val = rng.normal(size=(R, CAP)).astype(np.float32)
    out_val, out_src, out_counts, out_drops, total = fn(
        jnp.asarray(val).reshape(-1),
        jnp.asarray(dest).reshape(-1),
        jnp.asarray(counts),
    )
    emitted = int(counts.sum())
    received = int(np.asarray(out_counts).sum())
    dropped = int(np.asarray(out_drops).sum())
    assert received + dropped == emitted
    assert int(total) == received
