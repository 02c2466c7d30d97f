"""Distributed-termination drive loop (paper §4.2.3 / Chandy-Lamport note).

The paper's applications loop: launch kernel → ``forwardRays()`` → check the
reduced global count → repeat.  Because every stage here is traced JAX, the
whole loop lives on device in one ``jax.lax.while_loop`` — each rank keeps
iterating (possibly with an empty local queue) until the *global* in-flight
count hits zero, which is exactly the paper's observation that "even if a
rank does not receive any work during the current iteration, it may still be
assigned more work from other ranks later on".

Spill-and-retry (``cfg.overflow == "retain"``, ISSUE 6): ``forward_work``
hands back clamp-cut rows compacted at the FRONT of the queue with their
``dest`` intact.  The drive loop keeps them out of ``round_fn``'s way — the
app sees an arrivals-only view — and re-merges them (retained first, so the
marshal's stable source order gives FIFO oldest-first send priority) before
the next forward, threading the per-lane ``age`` counter alongside.  The
termination ``psum`` counts retained rows by construction (they sit in the
queue ``count``), so the loop cannot exit with work still spilled; and since
every nonempty destination ships at least one row per round (every clamp
budget is ≥ 1), the backlog drains in bounded rounds — no livelock.

Segmentation (ISSUE 7, the recovery law): the loop is factored into
``drive_start`` (the initial routing forward → carry) + ``drive_segment``
(run body rounds while ``rnd < seg_end``) + ``drive_finalize`` (carry →
results), with the carry an explicit dict pytree.  ``run_until_done`` is
exactly start + one full-length segment + finalize; the checkpoint/resume
host drive (``repro.core.recovery``) runs W-round segments instead,
snapshotting the carry between them — same traced body, so an uninterrupted
run and a segmented run execute bit-identical programs round for round.

Device scopes: start, segment and finalize run under ``rafi.drive`` and the
app's ``round_fn`` under ``rafi.app``, so every op of a burst's program names
its layer in the ``op_name`` a profiler trace shows (``forward_work`` and the
queue operations add ``rafi.forward``, ``rafi.enqueue`` and finer scopes).
"""
from __future__ import annotations

import inspect

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.core.forwarding import (
    ForwardConfig,
    credit_reserve_rows,
    flatten_axis_names,
    forward_work,
)
from repro.core.queue import DISCARD, WorkQueue
from repro.telemetry import stats as TS

__all__ = ["drive_finalize", "drive_segment", "drive_start", "run_until_done"]


def _vary(tree: Any, axis_name) -> Any:
    """Mark every leaf as device-varying over ``axis_name`` so the while-loop
    carry types stay stable even if the app's aux starts out replicated."""
    axes = flatten_axis_names(axis_name)

    def cast(x):
        return compat.pcast_varying(jnp.asarray(x), axes)

    return jax.tree.map(cast, tree)


def _split_retained(q: WorkQueue) -> Tuple[jax.Array, WorkQueue]:
    """``(n_ret, arrivals_view)``: retained rows sit at the queue FRONT with
    ``dest >= 0``; the view shifts them out so ``round_fn`` consumes only the
    round's arrivals (dest all DISCARD, zero drops — the drops contract)."""
    C = q.capacity
    lane = jnp.arange(C, dtype=jnp.int32)
    n_ret = jnp.sum(((lane < q.count) & (q.dest >= 0)).astype(jnp.int32))
    src = jnp.clip(lane + n_ret, 0, C - 1)
    # happy path (nothing retained): the shift is the identity — skip the
    # per-leaf gather behind a one-predicate cond
    items = jax.lax.cond(
        n_ret > 0,
        lambda its: jax.tree.map(lambda a: jnp.take(a, src, axis=0), its),
        lambda its: its,
        q.items,
    )
    view = WorkQueue(
        items=items,
        dest=jnp.full((C,), DISCARD, jnp.int32),
        count=q.count - n_ret,
        drops=jnp.zeros_like(q.drops),
    )
    return n_ret, view


@jax.named_scope("rafi.merge")
def _merge_retained(
    q: WorkQueue, n_ret: jax.Array, out_q: WorkQueue, age: jax.Array,
    axis_name, limit=None,
) -> Tuple[WorkQueue, jax.Array]:
    """Recombine the retained front of ``q`` with ``round_fn``'s output queue
    (retained FIRST — FIFO priority through the stable marshal).  Emissions
    that don't fit behind the backlog are cut and counted (unreachable when
    the app sizes ``capacity`` for its emission burst plus worst-case spill —
    and surfaced per round as the ``emit_overflow`` telemetry counter).
    Under credit flow the drive passes ``limit = capacity − outstanding
    advert``: emissions may never eat room already promised to in-flight
    arrivals, which is what makes the credit law receiver-drop-free even
    against an app that ignores its emission headroom.  Retained rows are
    never cut — ``limit`` binds emissions only.
    Returns ``(merged_queue, age_in)`` ready for ``forward_work``.

    The merge is one payload pass, and none when nothing is retained: a
    ``lax.cond`` picks the pass.  Both branches' outputs are cast to varying
    over ``axis_name`` so their manual-axes types agree whatever the app's
    queue carries."""
    C = q.capacity
    lane = jnp.arange(C, dtype=jnp.int32)
    tail = jnp.clip(lane - n_ret, 0, C - 1)
    n_tot = n_ret + out_q.count
    cap = C if limit is None else jnp.maximum(limit, n_ret)
    count = jnp.minimum(n_tot, cap)
    front = lane < n_ret

    def merge(_):
        def merge_leaf(a, b):
            keep = front.reshape((C,) + (1,) * (a.ndim - 1))
            return jnp.where(keep, a, jnp.take(b, tail, axis=0))

        items = jax.tree.map(merge_leaf, q.items, out_q.items)
        valid_tail = (~front) & (tail < out_q.count)
        dest = jnp.where(
            front,
            q.dest,
            jnp.where(valid_tail, jnp.take(out_q.dest, tail), DISCARD),
        ).astype(jnp.int32)
        age_in = jnp.where(front, age, 0).astype(jnp.int32)
        return _vary((items, dest, age_in), axis_name)

    def passthrough(_):
        # nothing retained: the merge is out_q verbatim (lanes past count
        # masked to DISCARD, matching the shifted-merge output bit for bit)
        dest = jnp.where(lane < out_q.count, out_q.dest, DISCARD)
        zeros = jnp.zeros((C,), jnp.int32)
        return _vary((out_q.items, dest.astype(jnp.int32), zeros), axis_name)

    items, dest, age_in = jax.lax.cond(n_ret > 0, merge, passthrough, None)
    merged = WorkQueue(
        items=items,
        dest=dest,
        count=count.astype(jnp.int32),
        drops=out_q.drops + (n_tot - count).astype(jnp.int32),
    )
    return merged, age_in


def _fwd(q, age, cfg, health, credits=None):
    """Uniform forward_work unpack: ``(new_q, total, age_out, credits_out,
    stats)`` with Nones where the config doesn't produce the value."""
    retain = cfg.overflow == "retain"
    credit = cfg.flow == "credit"
    if credit and cfg.telemetry:
        new_q, total, age_out, credits_out, stats = forward_work(
            q, cfg, age=age, health=health, credits=credits
        )
    elif credit:
        new_q, total, age_out, credits_out = forward_work(
            q, cfg, age=age, health=health, credits=credits
        )
        stats = None
    elif retain and cfg.telemetry:
        new_q, total, age_out, stats = forward_work(q, cfg, age=age, health=health)
        credits_out = None
    elif retain:
        new_q, total, age_out = forward_work(q, cfg, age=age, health=health)
        credits_out = stats = None
    elif cfg.telemetry:
        new_q, total, stats = forward_work(q, cfg, health=health)
        age_out = credits_out = None
    else:
        new_q, total = forward_work(q, cfg, health=health)
        age_out = credits_out = stats = None
    return new_q, total, age_out, credits_out, stats


@jax.named_scope("rafi.drive")
def drive_start(
    q0: WorkQueue,
    aux0: Any,
    cfg: ForwardConfig,
    *,
    health: Optional[jax.Array] = None,
    accounting: bool = False,
) -> Dict[str, Any]:
    """The drive's initial forward: route the ray-gen output to its owners
    (the paper's VoPaT does exactly this — primary rays are "forwarded to
    itself") and build the loop carry.

    Carry keys: ``q`` (the forwarded queue, per-round drops), ``aux``,
    ``total`` (replicated global in-flight count), ``rnd`` (body iterations
    executed), ``drops`` (cumulative per-rank) — plus ``age`` (retain),
    ``ring`` (telemetry), and, with ``accounting=True``, the per-rank
    ``emitted`` / ``delivered`` conservation counters the recovery watchdog
    closes at every checkpoint boundary (``emitted`` counts ATTEMPTED
    emissions — accepted rows plus their enqueue clips — so the identity
    ``emitted == delivered + in-flight + drops`` holds exactly; both are
    values the loop computes anyway, so the cost is two scalar adds).
    """
    credit = cfg.flow == "credit"
    credits0 = None
    if credit:
        # cold start at ZERO credit: the first forward is advert-only (all
        # rows retained), so no wire byte is risked before any receiver has
        # advertised — the backpressure law holds from round one
        credits0 = jnp.zeros((cfg.num_ranks,), jnp.int32)
    q1, total0, age1, credits1, stats0 = _fwd(q0, None, cfg, health, credits0)
    if cfg.telemetry and stats0 is not None:
        # round 0's local emission loss is the ray-gen enqueue overflow
        stats0 = TS.attach_emit_overflow(stats0, q0.drops)
    carry: Dict[str, Any] = {
        "q": _vary(q1, cfg.axis_name),
        "aux": _vary(aux0, cfg.axis_name),
        "total": total0,
        "rnd": jnp.zeros((), jnp.int32),
        "drops": _vary(q1.drops, cfg.axis_name),
    }
    if cfg.overflow == "retain":
        carry["age"] = _vary(age1, cfg.axis_name)
    if credit:
        carry["credits"] = _vary(credits1, cfg.axis_name)
    if cfg.telemetry:
        ring0 = TS.ring_push(
            TS.make_ring(
                TS.num_tiers(cfg),
                window=cfg.telemetry_window,
                buckets=cfg.telemetry_buckets,
            ),
            stats0,
        )
        carry["ring"] = _vary(ring0, cfg.axis_name)
    if accounting:
        emitted0 = (q0.count + q0.drops).astype(jnp.int32)
        carry["emitted"] = _vary(emitted0, cfg.axis_name)
        carry["delivered"] = _vary(jnp.zeros((), jnp.int32), cfg.axis_name)
    return carry


@jax.named_scope("rafi.drive")
def drive_segment(
    round_fn: Callable[[WorkQueue, Any, jax.Array], Tuple[WorkQueue, Any]],
    carry: Dict[str, Any],
    cfg: ForwardConfig,
    *,
    seg_end,
    health: Optional[jax.Array] = None,
) -> Dict[str, Any]:
    """Run body rounds while ``total > 0`` and ``rnd < seg_end``.

    ``seg_end`` may be a static int (``run_until_done`` passes
    ``max_rounds``) or a traced scalar (the checkpoint drive passes each
    segment's boundary into ONE compiled program).  The body is identical
    either way, so a segmented run replays the uninterrupted run's rounds
    bit for bit.  Accounting counters ride along iff present in ``carry``.
    """
    telem = cfg.telemetry
    retain = cfg.overflow == "retain"
    credit = cfg.flow == "credit"
    track = "emitted" in carry
    # Emission gate (credit flow): round_fn may declare a ``headroom``
    # keyword to receive its per-round emission budget — the receive room
    # not already owed to retained backlog or outstanding advertised
    # credits.  An app that emits within it never sees emit_overflow; one
    # that ignores it degrades locally (counted), never on the wire.
    wants_headroom = False
    try:
        wants_headroom = "headroom" in inspect.signature(round_fn).parameters
    except (TypeError, ValueError):  # builtins / exotic callables: no gate
        pass

    def cond(c):
        return (c["total"] > 0) & (c["rnd"] < seg_end)

    def body(c):
        q, aux, rnd, drops = c["q"], c["aux"], c["rnd"], c["drops"]
        # The input queue's cumulative drops already ride the loop carry;
        # hand round_fn a zero-drop view so a round_fn that threads the input
        # queue's drops into its output cannot double-count them (see the
        # drops contract in the run_until_done docstring).
        q = WorkQueue(items=q.items, dest=q.dest, count=q.count,
                      drops=jnp.zeros_like(q.drops))
        if retain:
            n_ret, view = _split_retained(q)
            consumed = view.count
            limit = None
            kw = {}
            if credit:
                # my outstanding advert = my own carried entry (the count
                # collective hands every rank its own fresh value back)
                me = jax.lax.axis_index(flatten_axis_names(cfg.axis_name))
                adv = jnp.clip(jnp.take(c["credits"], me), 0)
                limit = (cfg.capacity - adv).astype(jnp.int32)
                if wants_headroom:
                    kw["headroom"] = jnp.maximum(limit - n_ret, 0)
            elif wants_headroom:
                kw["headroom"] = jnp.maximum(cfg.capacity - n_ret, 0)
            with jax.named_scope("rafi.app"):
                out_q, aux = round_fn(view, aux, rnd, **kw)
            fwd_q, age_in = _merge_retained(
                q, n_ret, out_q, c["age"], cfg.axis_name, limit
            )
            attempted = out_q.count + out_q.drops
        else:
            consumed = q.count
            kw = {"headroom": jnp.int32(cfg.capacity)} if wants_headroom else {}
            with jax.named_scope("rafi.app"):
                fwd_q, aux = round_fn(q, aux, rnd, **kw)
            age_in = None
            attempted = fwd_q.count + fwd_q.drops
        new_q, total, age_out, credits_out, stats = _fwd(
            fwd_q, age_in, cfg, health, c.get("credits")
        )
        if telem and stats is not None:
            # local emission loss this round: enqueue overflow inside
            # round_fn plus the merge's emission cut — rows lost BEFORE the
            # wire, distinct from every clamp/admission counter
            stats = TS.attach_emit_overflow(stats, fwd_q.drops)
        # Per-round queues are fresh, so cumulative overflow drops must ride
        # the loop carry (observability: silent loss is a capacity bug).
        drops = drops + new_q.drops
        out = {
            "q": _vary(new_q, cfg.axis_name),
            "aux": _vary(aux, cfg.axis_name),
            "total": total,
            "rnd": rnd + 1,
            "drops": _vary(drops, cfg.axis_name),
        }
        if retain:
            out["age"] = _vary(age_out, cfg.axis_name)
        if credit:
            out["credits"] = _vary(credits_out, cfg.axis_name)
        if telem:
            out["ring"] = _vary(TS.ring_push(c["ring"], stats), cfg.axis_name)
        if track:
            out["emitted"] = _vary(
                c["emitted"] + attempted.astype(jnp.int32), cfg.axis_name
            )
            out["delivered"] = _vary(
                c["delivered"] + consumed.astype(jnp.int32), cfg.axis_name
            )
        return out

    return jax.lax.while_loop(cond, body, carry)


@jax.named_scope("rafi.drive")
def drive_finalize(carry: Dict[str, Any], cfg: ForwardConfig):
    """Carry → results: fold the cumulative drops into the final queue and
    emit the ``run_until_done`` return tuple (see its docstring)."""
    q = carry["q"]
    q = WorkQueue(items=q.items, dest=q.dest, count=q.count, drops=carry["drops"])
    out = (q, carry["aux"], carry["rnd"], carry["total"] == 0)
    if cfg.overflow == "retain":
        out = out + (carry["age"],)
    if cfg.telemetry:
        out = out + (carry["ring"],)
    return out


def run_until_done(
    round_fn: Callable[[WorkQueue, Any, jax.Array], Tuple[WorkQueue, Any]],
    q0: WorkQueue,
    aux0: Any,
    cfg: ForwardConfig,
    *,
    max_rounds: int = 64,
    health: Optional[jax.Array] = None,
) -> Tuple:
    """Iterate ``round_fn`` + ``forward_work`` until global termination.

    Args:
      round_fn: ``(in_queue, aux, round_idx) -> (out_queue, aux)`` — consumes
        the input queue and *emits* into a fresh output queue (the paper's
        separate in/out arrays, §3.2).  ``aux`` is arbitrary app state
        (framebuffer, particle traces, ...).

        Drops contract: the driver owns the cumulative drop count.  Each
        round it accumulates the OUTPUT queue's ``drops`` (the round's own
        enqueue overflows plus the forwarding round's clamps); the input
        queue round_fn receives always carries ``drops == 0``, so a round_fn
        that copies its input queue's ``drops`` into the output queue (a
        natural thing to do when threading queue state through) cannot
        double-count earlier rounds.  round_fn must not invent a nonzero
        starting ``drops`` of its own beyond what its enqueues produce.
      q0: initial queue (already filled by the app's ray-gen stage).
      aux0: initial app state.
      cfg: forwarding configuration.
      max_rounds: hard bound (XLA while loops need no bound, but runaway
        protection mirrors the paper's capacity pragmatism).
      health: optional replicated ``(R,) bool`` rank-health mask, constant
        for the burst — every forward re-addresses traffic away from
        unhealthy ranks via the pure local ``core.health`` remap (zero
        collective-inventory change).  For a mask that CHANGES mid-run, use
        the segmented checkpoint drive (``repro.core.recovery``), which
        re-reads it at every segment boundary.

    Returns ``(final_queue, final_aux, rounds_executed, done)``.  ``done`` is
    the termination verdict: True when the loop exited because the global
    in-flight count hit zero, False when ``max_rounds`` ran out with work
    still in flight (a truncated run).  Under ``overflow="retain"`` the
    final per-lane ``age`` vector is returned as a fifth output — on a
    truncated run these are the REAL rounds-waiting counters of the rows
    still in the queue, so a continuation (``repro.core.recovery`` resume,
    or a manual re-drive threading ``age`` back in) preserves the FIFO
    anti-starvation clock instead of silently resetting it.  With
    ``cfg.telemetry`` a ``telemetry.StatsRing`` of the last
    ``cfg.telemetry_window`` rounds rides the while-loop carry and is
    returned as the last output — EVERY forwarding round is recorded,
    including the initial ray-gen routing round (so a drive that runs
    ``rounds`` body iterations returns ``ring.pos == rounds + 1``).
    """
    carry = drive_start(q0, aux0, cfg, health=health)
    carry = drive_segment(round_fn, carry, cfg, seg_end=max_rounds, health=health)
    return drive_finalize(carry, cfg)
