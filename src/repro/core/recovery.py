"""Preemption-tolerant drive loop (ISSUE 7 — the recovery law).

At thousand-rank scale a burst's survival is probabilistic: spot instances
are reclaimed, hosts brown out, maintenance windows drain racks.  The
recovery law makes the forwarding drive itself restartable:

  * **Segmented drive** — ``run_checkpointed`` runs the SAME traced loop
    body as ``run_until_done`` (``termination.drive_segment``), but in
    W-round segments with the carry surfacing to the host at each boundary.
    The carry — queue, cumulative drops, retained-row ages, telemetry ring,
    round counter, app aux — is snapshotted with ``repro.ckpt``'s atomic
    integrity-checked writer, so a kill at ANY point leaves a resumable
    prefix.  Because segmentation changes only WHERE the while-loop pauses,
    never what the body computes, a resumed trajectory is bit-exact with the
    uninterrupted one, round for round (the carry is integer state: uid
    checksums, counts, ages; float payloads are moved, never reduced).
  * **Elastic restore** — checkpoints store the queue in its logical
    rank-stacked layout plus a structure-free manifest ``meta`` (rank count,
    capacity, overflow mode), so ``resume_run`` can land a burst saved on R
    ranks onto R′ ≠ R: surviving ranks keep their rows, rows stranded on
    retired ranks are dealt out toward the emptiest survivors, and
    destinations addressed beyond R′ are re-destinated by the same
    deficit-fill rule.  Conservation closes across the relayout (rows that
    no longer fit are counted as drops, never vanished).
  * **Watchdog** — every boundary asserts the conservation identity
    ``Σ emitted == Σ delivered + in-flight + Σ drops`` from counters the
    loop computes anyway (``termination.drive_start(accounting=True)``).  A
    violated identity means corrupted forwarding state; failing loudly at
    the boundary beats checkpointing the corruption and resuming it forever.
  * **Draining** — ``health`` may be a mask or a host callable ``rnd →
    mask`` re-evaluated at every segment boundary, so a rank reported
    unhealthy stops receiving work within one segment (the pure local remap
    of ``repro.core.health`` — zero collective-inventory change).  Resident
    work is evacuated with ``rebalance(…, health=…)`` before the drain.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro import ckpt
from repro.core import queue as Q
from repro.telemetry import stats as TS

__all__ = [
    "conservation_check",
    "resume_run",
    "run_checkpointed",
]

_SCHEMA = "rafi-drive-carry-v1"


# ----------------------------------------------------------------- watchdog
def conservation_check(carry: Dict[str, Any], *, where: str = "") -> None:
    """Raise ``RuntimeError`` unless the stacked carry closes the books:
    ``Σ emitted == Σ delivered + in-flight + Σ drops`` (uint64 sums — the
    per-rank counters are int32 and a long burst could wrap a 32-bit
    total)."""
    emitted = int(np.asarray(carry["emitted"]).astype(np.uint64).sum())
    delivered = int(np.asarray(carry["delivered"]).astype(np.uint64).sum())
    inflight = int(np.asarray(carry["total"]))
    drops = int(np.asarray(carry["drops"]).astype(np.uint64).sum())
    if emitted != delivered + inflight + drops:
        raise RuntimeError(
            f"conservation violated{' at ' + where if where else ''}: "
            f"emitted={emitted} != delivered={delivered} + "
            f"in-flight={inflight} + drops={drops} "
            f"(leak of {emitted - delivered - inflight - drops} rows) — "
            f"refusing to checkpoint corrupted forwarding state"
        )


# ------------------------------------------------------------ carry plumbing
def _carry_like(ctx, aux_like: Any, *, accounting: bool = True) -> Dict[str, Any]:
    """Host zeros tree with the structure/shape/dtype of the STACKED drive
    carry for ``ctx``'s mesh — the ``like`` target ``ckpt.restore_checkpoint``
    validates against."""
    cfg = ctx.cfg
    R, C = ctx.num_ranks, cfg.capacity
    q = Q.make_queue(ctx.proto, R * C)
    like: Dict[str, Any] = {
        "q": Q.WorkQueue(
            items=jax.tree.map(np.asarray, q.items),
            dest=np.asarray(q.dest),
            count=np.zeros((R,), np.int32),
            drops=np.zeros((R,), np.int32),
        ),
        "aux": jax.tree.map(np.asarray, aux_like),
        "total": np.zeros((), np.int32),
        "rnd": np.zeros((), np.int32),
        "drops": np.zeros((R,), np.int32),
    }
    if cfg.overflow == "retain":
        like["age"] = np.zeros((R * C,), np.int32)
    if cfg.flow == "credit":
        like["credits"] = np.zeros((R * R,), np.int32)
    if cfg.telemetry:
        ring = TS.make_ring(
            TS.num_tiers(cfg),
            window=cfg.telemetry_window,
            buckets=cfg.telemetry_buckets,
        )
        like["ring"] = jax.tree.map(
            lambda a: np.zeros((R,) + a.shape, a.dtype), ring
        )
    if accounting:
        like["emitted"] = np.zeros((R,), np.int32)
        like["delivered"] = np.zeros((R,), np.int32)
    return like


def _meta_of(ctx, rnd: int) -> Dict[str, Any]:
    cfg = ctx.cfg
    return {
        "schema": _SCHEMA,
        "round": int(rnd),
        "num_ranks": int(ctx.num_ranks),
        "capacity": int(cfg.capacity),
        "overflow": cfg.overflow,
        "flow": cfg.flow,
        "telemetry": bool(cfg.telemetry),
        "telemetry_window": int(cfg.telemetry_window),
        "pipeline_shards": int(cfg.pipeline_shards),
    }


def _health_at(health, R: int, rnd: int) -> np.ndarray:
    """Resolve the drive's ``health`` argument at a segment boundary:
    ``None`` → all healthy; a mask → constant; a host callable ``rnd →
    mask`` → re-evaluated (how a brownout enters a running burst)."""
    if health is None:
        return np.ones((R,), bool)
    if callable(health):
        health = health(rnd)
    h = np.asarray(health).astype(bool)
    if h.shape != (R,):
        raise ValueError(f"health mask shape {h.shape} != ({R},)")
    return h


def _finalize(ctx, carry: Dict[str, Any], *, step: int) -> Dict[str, Any]:
    """Stacked carry → host result dict (the segmented analogue of
    ``termination.drive_finalize``)."""
    cfg = ctx.cfg
    carry = jax.device_get(carry)
    q = carry["q"]
    res: Dict[str, Any] = {
        "q": Q.WorkQueue(
            items=q.items, dest=q.dest, count=q.count,
            drops=np.asarray(carry["drops"]),
        ),
        "aux": carry["aux"],
        "rounds": int(np.asarray(carry["rnd"])),
        "done": int(np.asarray(carry["total"])) == 0,
        "emitted": int(np.asarray(carry["emitted"]).astype(np.uint64).sum()),
        "delivered": int(np.asarray(carry["delivered"]).astype(np.uint64).sum()),
        "step": step,
        "preempted": False,
    }
    if cfg.overflow == "retain":
        res["age"] = carry["age"]
    if cfg.telemetry:
        res["ring"] = carry["ring"]
    return res


# ------------------------------------------------------------ the host loop
def _drive_loop(
    ctx,
    segment_p: Callable,
    carry,
    *,
    ckpt_dir,
    checkpoint_every: int,
    max_rounds: int,
    health,
    keep: int,
    halt_after_round: Optional[int],
):
    """Boundary loop shared by fresh and resumed drives: watchdog → save →
    (maybe simulated preemption) → next segment.  Returns the result dict,
    or ``None`` if the drive halted at a boundary (state is on disk; call
    :func:`resume_run` to continue)."""
    from repro.obs import trace as OT

    R = ctx.num_ranks
    last_step = None
    prev_health = None
    while True:
        rnd = int(np.asarray(carry["rnd"]))
        total = int(np.asarray(carry["total"]))
        OT.event(
            "recovery.boundary", OT.CAT_RECOVERY, round=rnd, total=total
        )
        host_carry = jax.device_get(carry)
        conservation_check(host_carry, where=f"round {rnd}")
        if ckpt_dir is not None:
            ckpt.save_checkpoint(
                ckpt_dir, rnd, host_carry, keep=keep, meta=_meta_of(ctx, rnd)
            )
            last_step = rnd
            if OT.enabled():
                man = ckpt.load_manifest(ckpt_dir, rnd)
                leaves = man.get("leaves", [])
                OT.event(
                    "recovery.save", OT.CAT_RECOVERY, step=rnd,
                    leaves=len(leaves),
                    bytes=sum(
                        int(np.prod(e["shape"]) * np.dtype(e["dtype"]).itemsize)
                        for e in leaves
                    ),
                    digest=leaves[0]["sha256"][:16] if leaves else "",
                )
        if total == 0 or rnd >= max_rounds:
            return _finalize(ctx, carry, step=last_step)
        seg_end = min(rnd + checkpoint_every, max_rounds)
        if halt_after_round is not None and seg_end > halt_after_round:
            OT.event(
                "recovery.preempt", OT.CAT_RECOVERY, round=rnd, step=last_step
            )
            return None  # preempted: the boundary just saved is the restart point
        mask = _health_at(health, R, rnd)
        if OT.enabled() and mask is not None:
            cur = np.asarray(mask).astype(bool).tolist()
            if prev_health is not None and cur != prev_health:
                OT.event(
                    "health.transition", OT.CAT_HEALTH, round=rnd,
                    before=prev_health, after=cur,
                )
            prev_health = cur
        with OT.span("recovery.segment", OT.CAT_RECOVERY, round=rnd, seg_end=seg_end):
            carry = segment_p(carry, np.int32(seg_end), mask)


def run_checkpointed(
    ctx,
    round_fn: Callable,
    q0_stacked,
    aux0,
    *,
    aux_specs,
    ckpt_dir,
    checkpoint_every: int = 8,
    max_rounds: int = 64,
    health=None,
    keep: int = 3,
    halt_after_round: Optional[int] = None,
) -> Optional[Dict[str, Any]]:
    """Drive ``round_fn`` to termination with a checkpoint every
    ``checkpoint_every`` rounds (the boundary also runs the conservation
    watchdog).  Same contract as ``RafiContext.run_until_done`` — the traced
    body is literally the same code — plus:

      * ``ckpt_dir``: checkpoints land here (``None`` → segmented drive with
        no saves, the apples-to-apples baseline for overhead measurement);
      * ``health``: ``(R,) bool`` mask OR host callable ``rnd → mask``,
        re-read at every segment boundary (draining / brownout);
      * ``halt_after_round``: simulate preemption — stop at the first
        boundary whose next segment would pass this round and return
        ``None`` (the test/chaos hook; a REAL preemption is just the process
        dying, which leaves the same on-disk state).

    Returns the result dict ``{"q", "aux", "rounds", "done"[, "age"]
    [, "ring"], "emitted", "delivered", "step", "preempted"}`` or ``None``
    when halted.
    """
    from repro.obs import trace as OT

    start_p, segment_p = ctx.checkpoint_drive_programs(
        round_fn, aux_specs=aux_specs, accounting=True
    )
    carry = start_p(
        q0_stacked, aux0, _health_at(health, ctx.num_ranks, 0)
    )
    with OT.span(
        "recovery.run_checkpointed", OT.CAT_RECOVERY,
        checkpoint_every=checkpoint_every, max_rounds=max_rounds,
        num_ranks=ctx.num_ranks,
    ) as sp:
        res = _drive_loop(
            ctx, segment_p, carry,
            ckpt_dir=ckpt_dir, checkpoint_every=checkpoint_every,
            max_rounds=max_rounds, health=health, keep=keep,
            halt_after_round=halt_after_round,
        )
        sp.set(preempted=res is None,
               rounds=None if res is None else res["rounds"])
    return res


def resume_run(
    ctx,
    round_fn: Callable,
    ckpt_dir,
    *,
    aux_specs,
    aux_like,
    step: Optional[int] = None,
    checkpoint_every: int = 8,
    max_rounds: int = 64,
    health=None,
    keep: int = 3,
    halt_after_round: Optional[int] = None,
    aux_restore: Optional[Callable] = None,
) -> Optional[Dict[str, Any]]:
    """Continue a checkpointed drive from ``ckpt_dir`` (latest boundary, or
    an explicit ``step``).

    ``ctx`` is the RESUME-side context — it may span a different rank count
    than the one that saved (elastic restore; see :func:`_elastic_restore`
    for the relayout law).  ``aux_like`` is a host zeros-tree of the aux in
    the NEW mesh's shape (structure must match the saved aux); on an elastic
    resume the aux leaves are refitted with ``aux_restore(old_aux, R_new)``
    if given, else by the default modular fold (new rank ``r`` sums old
    ranks ``o ≡ r (mod R′)`` along each leaf's leading rank axis — correct
    for the additive per-rank accumulators the chaos harness uses; pass
    ``aux_restore`` for anything else).
    """
    if step is None:
        step = ckpt.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no published checkpoint under {ckpt_dir}")
    manifest = ckpt.load_manifest(ckpt_dir, step)
    meta = manifest.get("meta", {})
    if meta.get("schema") != _SCHEMA:
        raise ValueError(
            f"checkpoint at step {step} is not a drive carry "
            f"(schema={meta.get('schema')!r})"
        )
    cfg = ctx.cfg
    if meta.get("overflow") != cfg.overflow or bool(meta.get("telemetry")) != bool(
        cfg.telemetry
    ):
        raise ValueError(
            f"resume context disagrees with checkpoint: overflow "
            f"{cfg.overflow!r} vs {meta.get('overflow')!r}, telemetry "
            f"{cfg.telemetry} vs {meta.get('telemetry')}"
        )
    # pre-backpressure checkpoints have no "flow" key: they are open-flow
    if meta.get("flow", "open") != cfg.flow:
        raise ValueError(
            f"resume context disagrees with checkpoint: flow "
            f"{cfg.flow!r} vs {meta.get('flow', 'open')!r}"
        )
    like_new = _carry_like(ctx, aux_like, accounting=True)
    R_old, C_old = int(meta["num_ranks"]), int(meta["capacity"])
    if R_old == ctx.num_ranks and C_old == cfg.capacity:
        carry = ckpt.restore_checkpoint(ckpt_dir, step, like_new)
    else:
        # same STRUCTURE, different leaf shapes: borrow the new carry's
        # treedef and take the saved shapes/dtypes from the manifest
        _, treedef = jax.tree.flatten(like_new)
        like_old = jax.tree.unflatten(
            treedef,
            [
                np.zeros(tuple(e["shape"]), np.dtype(e["dtype"]))
                for e in manifest["leaves"]
            ],
        )
        old_carry = ckpt.restore_checkpoint(ckpt_dir, step, like_old)
        carry = _elastic_restore(
            old_carry, ctx, R_old=R_old, C_old=C_old, aux_restore=aux_restore
        )
    from repro.obs import trace as OT

    _, segment_p = ctx.checkpoint_drive_programs(
        round_fn, aux_specs=aux_specs, accounting=True
    )
    with OT.span(
        "recovery.resume_run", OT.CAT_RECOVERY, step=step,
        elastic=R_old != ctx.num_ranks or C_old != cfg.capacity,
        num_ranks=ctx.num_ranks,
    ) as sp:
        res = _drive_loop(
            ctx, segment_p, carry,
            ckpt_dir=ckpt_dir, checkpoint_every=checkpoint_every,
            max_rounds=max_rounds, health=health, keep=keep,
            halt_after_round=halt_after_round,
        )
        sp.set(preempted=res is None,
               rounds=None if res is None else res["rounds"])
    return res


# ------------------------------------------------------------ elastic restore
def _fold_rank_counter(a: np.ndarray, R_new: int) -> np.ndarray:
    """New rank ``r`` absorbs old ranks ``o ≡ r (mod R_new)`` — the modular
    fold for additive per-rank counters (uint64 accumulate, cast back)."""
    out = np.zeros((R_new,) + a.shape[1:], np.uint64)
    for o in range(a.shape[0]):
        out[o % R_new] += a[o].astype(np.uint64)
    return (out % (1 << 32)).astype(a.dtype)


def _default_aux_restore(aux, R_new: int):
    return jax.tree.map(lambda a: _fold_rank_counter(np.asarray(a), R_new), aux)


def _elastic_restore(
    old: Dict[str, Any], ctx, *, R_old: int, C_old: int, aux_restore
) -> Dict[str, Any]:
    """Relayout a carry saved on ``R_old`` ranks onto ``ctx``'s mesh.

    The relayout law (host numpy, deterministic):

      * rows resident on a surviving rank (``o < R′``) stay put;
      * rows stranded on retired ranks are dealt to survivors in old-rank /
        lane order, each row to the survivor furthest below the even-split
        quota ``ceil(total/R′)`` (ties → lowest rank);
      * destinations addressed beyond R′ are re-pointed by the same
        deficit-fill rule over the pending per-destination load;
      * per rank, retained rows (``dest >= 0``) are packed FIRST, keeping
        their ages — ``termination._split_retained`` requires the retained
        block front-contiguous — then residents with age 0;
      * rows past the new capacity are cut INTO the drop counter (the
        conservation identity closes: in-flight shrinks by exactly what
        drops grows by);
      * the telemetry ring restarts empty (per-rank round history has no
        meaning across a rank-count change);
      * ``emitted`` / ``delivered`` / ``drops`` fold modularly
        (new ``r`` sums old ``o ≡ r mod R′``).
    """
    cfg = ctx.cfg
    R_new, C_new = ctx.num_ranks, cfg.capacity
    retain = cfg.overflow == "retain"
    q = old["q"]
    counts = np.asarray(q.count).astype(np.int64)
    dest = np.asarray(q.dest).copy()
    age_old = (
        np.asarray(old["age"]).copy() if retain else np.zeros_like(dest)
    )
    item_leaves, item_def = jax.tree.flatten(
        jax.tree.map(np.asarray, q.items)
    )

    # live rows in deterministic (old rank, lane) order
    rows = []  # (old_rank, global_lane, dest, age)
    for o in range(R_old):
        base = o * C_old
        for lane in range(int(counts[o])):
            rows.append([o, base + lane, int(dest[base + lane]), int(age_old[base + lane])])

    # re-destinate addresses beyond the new mesh: deficit fill over the
    # pending per-destination load (out-of-range rows go wherever the least
    # work is already headed)
    load = np.zeros((R_new,), np.int64)
    for r in rows:
        if 0 <= r[2] < R_new:
            load[r[2]] += 1
    for r in rows:
        if r[2] >= R_new:
            d = int(np.argmin(load))
            r[2] = d
            load[d] += 1

    # deal stranded rows to survivors, emptiest-first toward the even split
    occupancy = np.zeros((R_new,), np.int64)
    for r in rows:
        if r[0] < R_new:
            occupancy[r[0]] += 1
    placed = []  # (new_rank, global_lane, dest, age)
    for o, gl, d, ag in rows:
        if o < R_new:
            placed.append((o, gl, d, ag))
        else:
            nr = int(np.argmin(occupancy))
            occupancy[nr] += 1
            placed.append((nr, gl, d, ag))

    # pack per new rank: retained first (stable), cut at capacity → drops
    new_dest = np.full((R_new * C_new,), Q.DISCARD, np.int32)
    new_age = np.zeros((R_new * C_new,), np.int32)
    new_count = np.zeros((R_new,), np.int32)
    cut = np.zeros((R_new,), np.int32)
    new_leaves = [
        np.zeros((R_new * C_new,) + l.shape[1:], l.dtype) for l in item_leaves
    ]
    for nr in range(R_new):
        mine = [p for p in placed if p[0] == nr]
        mine = [p for p in mine if p[2] >= 0] + [p for p in mine if p[2] < 0]
        kept = mine[:C_new]
        cut[nr] = len(mine) - len(kept)
        new_count[nr] = len(kept)
        for j, (_, gl, d, ag) in enumerate(kept):
            tl = nr * C_new + j
            new_dest[tl] = d
            new_age[tl] = ag
            for leaf, src in zip(new_leaves, item_leaves):
                leaf[tl] = src[gl]

    new_drops = _fold_rank_counter(np.asarray(old["drops"]), R_new)
    new_drops = (new_drops.astype(np.int64) + cut).astype(np.int32)
    aux_fit = aux_restore if aux_restore is not None else _default_aux_restore
    carry: Dict[str, Any] = {
        "q": Q.WorkQueue(
            items=jax.tree.unflatten(item_def, new_leaves),
            dest=new_dest,
            count=new_count,
            drops=new_drops,  # queue drops mirror the cumulative carry
        ),
        "aux": aux_fit(old["aux"], R_new),
        "total": np.int32(new_count.sum()),
        "rnd": np.asarray(old["rnd"]).astype(np.int32),
        "drops": new_drops,
        "emitted": _fold_rank_counter(np.asarray(old["emitted"]), R_new),
        "delivered": _fold_rank_counter(np.asarray(old["delivered"]), R_new),
    }
    if retain:
        carry["age"] = new_age
    if cfg.flow == "credit":
        # conservative cold restart: zero credits → the first resumed round
        # is advert-only, exactly like a fresh drive_start (no wire risked
        # against adverts computed for the retired mesh shape)
        carry["credits"] = np.zeros((R_new * R_new,), np.int32)
    if cfg.telemetry:
        ring = TS.make_ring(
            TS.num_tiers(cfg),
            window=cfg.telemetry_window,
            buckets=cfg.telemetry_buckets,
        )
        carry["ring"] = jax.tree.map(
            lambda a: np.zeros((R_new,) + a.shape, a.dtype), ring
        )
    return carry
