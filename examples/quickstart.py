"""Quickstart: the RaFI-JAX work-forwarding core in ~60 lines.

Mirrors the paper's introductory usage: define a work-item type, emit items
to destination ranks from per-rank kernels, call the forwarding collective,
and drive a multi-round computation to distributed termination — here with
the sort-free ``marshal="scatter"`` hot path and the traffic flight recorder
(``telemetry=True``) on, printing the burst's traffic summary at the end,
then closing with the observation law: capture a burst, export the Perfetto
timeline, and run the flight-data analyzer over it.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro import telemetry as TM

from repro.core import (
    DISCARD, ForwardConfig, enqueue, forward_work, make_queue,
    run_until_done, work_item,
)


def section(n, title):
    print(f"== {n}. {title}")


# 1. A work item is any dataclass of arrays — RaFI never looks inside (§3.1).
section(1, "work-item type")
@work_item
@dataclasses.dataclass
class Ray:
    value: jax.Array
    hops: jax.Array


PROTO = Ray(value=jnp.zeros(()), hops=jnp.zeros((), jnp.int32))
R, CAP = 8, 128
mesh = compat.make_mesh((R,), ("data",))
# scatter marshal = the sort-free single-pass hot path (PR 4); telemetry =
# the per-round traffic flight recorder (PR 5) riding the while-loop carry
cfg = ForwardConfig(
    axis_name="data", num_ranks=R, capacity=CAP, exchange="padded",
    marshal="scatter", telemetry=True, telemetry_window=8,
)


# 2. A per-rank "kernel": read incoming work, emit outgoing work (§3.3).
section(2, "per-rank round kernel")


def round_fn(q_in, acc, rnd):
    me = jax.lax.axis_index("data")
    lane = jnp.arange(CAP)
    valid = lane < q_in.count
    items = q_in.items
    moved = Ray(value=items.value * 0.5, hops=items.hops + 1)
    keep = valid & (moved.hops < 4)                      # retire after 4 hops
    dest = jnp.where(keep, (me + 1) % R, DISCARD)        # ring forwarding
    out = make_queue(PROTO, CAP)
    out = enqueue(out, moved, dest.astype(jnp.int32), valid)
    acc = acc + jnp.sum(jnp.where(valid & ~keep, moved.value, 0.0))
    return out, acc


# 3. Drive to distributed termination (§4.2.3) — all on device.  With
#    telemetry on, the StatsRing of the last W rounds rides the loop carry.
section(3, "drive to distributed termination")


def drive(_):
    me = jax.lax.axis_index("data")
    q0 = make_queue(PROTO, CAP)
    q0 = enqueue(
        q0,
        Ray(value=jnp.ones(4) * (me + 1), hops=jnp.zeros(4, jnp.int32)),
        me * jnp.ones(4, jnp.int32),
        jnp.ones(4, bool),
    )
    q, acc, rounds, _done, ring = run_until_done(round_fn, q0, jnp.zeros(()), cfg, max_rounds=16)
    return acc[None], rounds[None], TM.stack_ring(ring)


ring_specs = jax.tree.map(
    lambda _: P("data"),
    TM.make_ring(TM.num_tiers(cfg), window=cfg.telemetry_window,
                 buckets=cfg.telemetry_buckets),
)
f = jax.jit(jax.shard_map(
    drive, mesh=mesh, in_specs=P("data"),
    out_specs=(P("data"), P("data"), ring_specs),
))
acc, rounds, ring = f(jnp.arange(float(R)))
print(f"deposited per rank: {acc}")
print(f"rounds to distributed termination: {int(rounds[0])}")
expected = sum((r + 1) * 4 for r in range(R)) * 0.5**4
print(f"total deposited: {float(acc.sum()):.3f}  (expected {expected:.3f})")
assert abs(float(acc.sum()) - expected) < 1e-3

# 4. Read the flight recorder back on the host — what the burst's traffic
#    looked like, and what repro.tune would size the send slots to.
section(4, "telemetry summary")
summary = TM.summarize(ring, tier_capacities=TM.tier_capacities(cfg))
print(
    f"telemetry: {summary['rounds']} rounds recorded, "
    f"max segment demand {summary['demand_max'][0]} "
    f"(peer slots sized {summary['tier_capacities'][0]}), "
    f"clamp drops {summary['drops']}"
)
assert summary["drops"] == 0

# 5. The overlap law (PR 8): ``pipeline_shards=S`` splits every peer segment
#    into S micro-shards, each on its own payload+count collective pair, so
#    marshal of shard k+1 can overlap the wire time of shard k on an async
#    fabric.  Pipelining changes the SCHEDULE, never the ANSWER — the same
#    drive is bit-exact with the bulk round.
section(5, "pipelined overlap, bit-exact")
cfg = dataclasses.replace(cfg, pipeline_shards=2)
f2 = jax.jit(jax.shard_map(
    drive, mesh=mesh, in_specs=P("data"),
    out_specs=(P("data"), P("data"), ring_specs),
))
acc2, rounds2, _ = f2(jnp.arange(float(R)))
assert (acc2 == acc).all() and int(rounds2[0]) == int(rounds[0])
print(f"pipelined (S=2) drive bit-exact with bulk: {float(acc2.sum()):.3f}")

# 6. The backpressure law (PR 9): under sustained overload, open flow ships
#    rows its receivers must clamp — wire bytes spent on work that is thrown
#    away.  ``flow="credit"`` piggybacks each receiver's free space on the
#    count collective and gates senders on it, so every shipped row lands:
#    slower to drain (credits are one round stale), but goodput 1.0 and zero
#    loss where open flow drops almost half the traffic.
from repro.chaos import run_scenario, sustained_overload
from repro.obs import report as OR
from repro.obs import trace as OT

section(6, "backpressure under sustained overload")
sc = sustained_overload()  # 2 of 8 ranks hot: concentration that persists
results = {}
# ...captured under the ambient span tracer (PR 10): tracing rides the HOST
# side only, so the device program — and every number below — is unchanged.
with OT.capture() as tracer:
    for flow in ("open", "credit"):
        r = results[flow] = run_scenario(
            mesh, sc, capacity=16, max_rounds=256, flow=flow,
            overflow="retain", pipeline_shards=4,
        )
        print(
            f"overload [{flow:6s}]: delivered {r['delivered_total']}/{r['emitted']}"
            f" in {r['rounds']} rounds, goodput {r['goodput']:.3f},"
            f" drops {r['drops']}"
        )
        if flow == "open":
            assert r["goodput"] < 0.9  # wire wasted on clamped rows
        else:
            assert r["goodput"] == 1.0 and r["drops"] == 0 and r["done"]

# 7. The observation law (PR 10): the burst above became flight data.  Export
#    the host span timeline as Perfetto JSON (load it at ui.perfetto.dev),
#    write the chaos runs into a capture file, and let the analyzer re-derive
#    the ledger and flag the degraded run — open flow, and only open flow.
section(7, "observation law: trace export + flight-data report")
import tempfile

outdir = tempfile.mkdtemp(prefix="rafi_quickstart_")
trace_path = os.path.join(outdir, "trace.perfetto.json")
tracer.save(trace_path)
print(f"perfetto timeline: {trace_path} ({len(tracer.events)} events)")

capture_path = os.path.join(outdir, "capture.json")
OR.save_capture(
    capture_path,
    [
        OR.chaos_capture(
            f"{sc.name}_{flow}", results[flow], flow=flow,
            tier_capacities=(4,), capacity=16,
        )
        for flow in ("open", "credit")
    ],
    meta={"source": "quickstart"},
)
report = OR.analyze(OR.load_capture(capture_path))
print(OR.render(report))
assert report["degraded_runs"] == [f"{sc.name}_open"]
print("OK")
