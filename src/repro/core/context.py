"""Host-side RaFI context (paper §3.4) — mesh plumbing around the core.

``RafiContext`` is the JAX analogue of ``HostContext<T>``: it owns the static
configuration (item type, capacities, exchange backend, mesh axis), builds
per-rank queues, and wraps the collective entry points in ``shard_map`` so
applications never touch sharding specs.  The paper's three host operations
map directly:

  resizeRayQueues(N)     → ``capacity``/``peer_capacity`` in the constructor
                           (static shapes; see DESIGN.md on why this is the
                           faithful mapping of the paper's §6.3 contract)
  getDeviceInterface()   → ``repro.core.queue`` (enqueue/get/num_incoming) —
                           plain functions usable inside any traced kernel
  forwardRays()          → :meth:`forward` (single round) /
                           :meth:`run_until_done` (whole drive loop on device)

Multiple contexts with different item types in the same program are fully
supported (the N-body app uses three, §5.5) — contexts are just values.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import queue as Q
from repro.core import termination as term
from repro.core.forwarding import ForwardConfig, flatten_axis_names, forward_work
from repro.core.types import item_nbytes
from repro.telemetry import stats as TS

__all__ = ["RafiContext"]


def _axis_size(mesh: Mesh, axis_name) -> int:
    if isinstance(axis_name, (tuple, list)):
        n = 1
        for a in axis_name:
            n *= _axis_size(mesh, a)  # an entry may be a joint tier (tuple)
        return n
    return mesh.shape[axis_name]


class RafiContext:
    """A typed work-forwarding context bound to one mesh axis."""

    def __init__(
        self,
        mesh: Mesh,
        proto: Any,
        *,
        axis_name: Any = "data",
        capacity: int,
        peer_capacity: int = 0,
        exchange: str = "padded",
        marshal: str = "sort",
        sort_method: str = "pack",
        use_pallas: bool = False,
        fast_size: int = 0,
        node_capacity: int = 0,
        level_sizes=(),
        level_capacities=(),
        telemetry: bool = False,
        telemetry_window: int = 16,
        telemetry_buckets: int = 8,
        overflow: str = "drop",
        pipeline_shards: int = 1,
        flow: str = "open",
        emit_reserve: int = -1,
    ):
        self.mesh = mesh
        self.proto = proto
        self.item_nbytes = item_nbytes(proto)
        if (
            exchange == "hierarchical"
            and not level_sizes
            and fast_size <= 0
            and isinstance(axis_name, (tuple, list))
        ):
            # derive one rank count per tier from the bound mesh (a tier may
            # itself be a tuple of mesh axes — one joint fabric)
            level_sizes = tuple(_axis_size(mesh, a) for a in axis_name)
        self.cfg = ForwardConfig(
            axis_name=axis_name,
            num_ranks=_axis_size(mesh, axis_name),
            capacity=capacity,
            peer_capacity=peer_capacity,
            exchange=exchange,
            marshal=marshal,
            sort_method=sort_method,
            use_pallas=use_pallas,
            fast_size=fast_size,
            node_capacity=node_capacity,
            level_sizes=tuple(level_sizes),
            level_capacities=tuple(level_capacities),
            telemetry=telemetry,
            telemetry_window=telemetry_window,
            telemetry_buckets=telemetry_buckets,
            overflow=overflow,
            pipeline_shards=pipeline_shards,
            flow=flow,
            emit_reserve=emit_reserve,
        )
        # PartitionSpec entries cannot nest: a joint-tier axis_name like
        # (("pod", "node"), "device") shards dim 0 over the flattened axes
        self._spec = P(flatten_axis_names(axis_name))

    # -- queue construction -------------------------------------------------
    @property
    def num_ranks(self) -> int:
        return self.cfg.num_ranks

    def local_queue(self) -> Q.WorkQueue:
        """Per-rank empty queue (for use *inside* shard_map'ed code)."""
        return Q.make_queue(self.proto, self.cfg.capacity)

    def global_queue(self) -> Q.WorkQueue:
        """Global (host-visible) empty queue: leaves (R*capacity, ...) sharded
        over the context axis."""
        q = Q.make_queue(self.proto, self.cfg.capacity * self.num_ranks)
        return jax.device_put(q, jax.NamedSharding(self.mesh, self._spec))

    def queue_specs(self):
        """PartitionSpecs of a global queue (items leaves, dest: sharded;
        count/drops: per-rank scalars stacked — see shard wrappers below)."""
        return Q.WorkQueue(
            items=jax.tree.map(lambda _: self._spec, self.proto),
            dest=self._spec,
            count=self._spec,
            drops=self._spec,
        )

    # -- collective entry points --------------------------------------------
    def shard(self, fn: Callable, *, in_specs, out_specs) -> Callable:
        """shard_map + jit a per-rank function over the context's mesh."""
        return jax.jit(
            jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs)
        )

    def forward_rays(self) -> Callable:
        """The paper's ``forwardRays()``: a jitted global function taking a
        stacked global queue and returning ``(forwarded_queue, total)`` —
        plus, with ``overflow="retain"``, the per-lane ``age`` counter
        (sharded ``(R·C,)``; each standalone call starts ages fresh — the
        on-device drive loop is where ages thread across rounds), and the
        round's rank-stacked ``RoundStats`` when the context has
        ``telemetry`` on."""
        cfg = self.cfg
        retain = cfg.overflow == "retain"

        def step(q_stacked):
            q = _unstack_queue(q_stacked)
            if retain and cfg.telemetry:
                new_q, total, age, stats = forward_work(q, cfg)
                return _stack_queue(new_q), total, age, TS.stack_ring(stats)
            if retain:
                new_q, total, age = forward_work(q, cfg)
                return _stack_queue(new_q), total, age
            if cfg.telemetry:
                new_q, total, stats = forward_work(q, cfg)
                return _stack_queue(new_q), total, TS.stack_ring(stats)
            new_q, total = forward_work(q, cfg)
            return _stack_queue(new_q), total

        out_specs = (self._queue_out_specs(), P())
        if retain:
            out_specs = out_specs + (self._spec,)
        if cfg.telemetry:
            out_specs = out_specs + (self._stats_specs(),)
        return self.shard(
            step,
            in_specs=(self._queue_out_specs(),),
            out_specs=out_specs,
        )

    def run_until_done(
        self,
        round_fn: Callable,
        *,
        aux_specs: Any,
        max_rounds: int = 64,
        with_health: bool = False,
    ) -> Callable:
        """Jitted global driver: ``(q0_stacked, aux0) -> (q, aux, rounds,
        done, …)``.  ``done`` is True when the drive terminated cleanly
        (global in-flight count hit zero), False when ``max_rounds``
        truncated it with work still in flight.

        ``round_fn(in_queue, aux, round_idx) -> (out_queue, aux)`` is per-rank
        traced code using the device interface (enqueue/get_incoming).

        With ``overflow="retain"`` on the context, the final per-lane ``age``
        vector (sharded ``(R·C,)``) follows ``done`` — on a truncated run
        these are the live rounds-waiting counters of the still-queued rows,
        so a continuation preserves the FIFO anti-starvation clock.  With
        ``telemetry`` on the context, the rank-stacked ``telemetry.StatsRing``
        of the burst's last ``telemetry_window`` rounds is the last output
        (leaves ``(R, window, …)`` on the host) — feed it to
        ``telemetry.summarize`` / ``tune.plan_capacities``.

        ``with_health=True`` makes the returned callable accept a third
        argument: a replicated ``(R,) bool`` rank-health mask re-addressing
        traffic away from unhealthy ranks (see ``repro.core.health``).
        """
        cfg = self.cfg
        retain = cfg.overflow == "retain"

        def drive(q0_stacked, aux0, health=None):
            q0 = _unstack_queue(q0_stacked)
            out = term.run_until_done(
                round_fn, q0, aux0, cfg, max_rounds=max_rounds, health=health
            )
            q, aux, rounds, done = out[:4]
            rest = out[4:]
            packed = (_stack_queue(q), aux, rounds, done)
            if retain:
                packed = packed + (rest[0],)
                rest = rest[1:]
            if cfg.telemetry:
                packed = packed + (TS.stack_ring(rest[0]),)
            return packed

        out_specs = (self._queue_out_specs(), aux_specs, P(), P())
        if retain:
            out_specs = out_specs + (self._spec,)
        if cfg.telemetry:
            out_specs = out_specs + (self._ring_specs(),)
        in_specs = (self._queue_out_specs(), aux_specs)
        if with_health:
            in_specs = in_specs + (P(),)
            drive_p = self.shard(drive, in_specs=in_specs, out_specs=out_specs)
        else:
            drive_p = self.shard(
                lambda q0s, aux0: drive(q0s, aux0),
                in_specs=in_specs,
                out_specs=out_specs,
            )

        # Observation hook (host-side only — the traced program is untouched,
        # so the lowered HLO is bit-identical with tracing on or off): each
        # burst invocation becomes one span carrying the drive's outcome, and
        # a ``rafi.drive.run_until_done`` annotation on any profiler trace.
        def traced_drive(*args):
            from repro.obs import trace as OT

            with OT.span(
                "drive.run_until_done", OT.CAT_DRIVE,
                exchange=cfg.exchange, flow=cfg.flow, overflow=cfg.overflow,
                max_rounds=max_rounds, num_ranks=self.num_ranks,
            ) as sp:
                out = drive_p(*args)
                sp.set(rounds=out[2], done=out[3])
            return out

        # keep the jit inspection surface (tests lower the drive to audit
        # its collective inventory; the host-side span wrapper must not
        # hide it)
        traced_drive.lower = drive_p.lower
        return traced_drive

    # -- segmented (checkpointable) drive ------------------------------------
    def carry_specs(self, aux_specs: Any, *, accounting: bool = True):
        """PartitionSpecs of the *stacked* drive-loop carry dict (see
        ``termination.drive_start``): per-rank leaves sharded over the
        context axis, ``total``/``rnd`` replicated."""
        cfg = self.cfg
        specs = {
            "q": self._queue_out_specs(),
            "aux": aux_specs,
            "total": P(),
            "rnd": P(),
            "drops": self._spec,
        }
        if cfg.overflow == "retain":
            specs["age"] = self._spec
        if cfg.flow == "credit":
            # per-rank (R,) credit vector stacks to (R·R,), like age's lanes
            specs["credits"] = self._spec
        if cfg.telemetry:
            specs["ring"] = self._ring_specs()
        if accounting:
            specs["emitted"] = self._spec
            specs["delivered"] = self._spec
        return specs

    def checkpoint_drive_programs(
        self, round_fn: Callable, *, aux_specs: Any, accounting: bool = True
    ) -> Tuple[Callable, Callable]:
        """The segmented drive as TWO jitted programs (the recovery law's
        device side — ``repro.core.recovery`` owns the host loop):

          ``start(q0_stacked, aux0, health) -> carry``   (initial forward)
          ``segment(carry, seg_end, health) -> carry``   (rounds until
                                                          ``rnd == seg_end``
                                                          or termination)

        The carry is the stacked ``termination`` dict carry — a plain pytree
        the host can snapshot with ``repro.ckpt`` between segments.
        ``seg_end`` and ``health`` are *traced* (replicated) arguments, so
        every segment of every length reuses one compiled program and the
        segmented trajectory is bit-identical to ``run_until_done``'s.  With
        ``accounting`` the carry grows the ``emitted``/``delivered`` counters
        the recovery watchdog closes at each boundary.
        """
        cfg = self.cfg

        def start(q0_stacked, aux0, health):
            carry = term.drive_start(
                _unstack_queue(q0_stacked), aux0, cfg,
                health=health, accounting=accounting,
            )
            return _stack_carry(carry)

        def segment(carry_stacked, seg_end, health):
            carry = term.drive_segment(
                round_fn, _unstack_carry(carry_stacked), cfg,
                seg_end=seg_end, health=health,
            )
            return _stack_carry(carry)

        cspecs = self.carry_specs(aux_specs, accounting=accounting)
        start_p = self.shard(
            start,
            in_specs=(self._queue_out_specs(), aux_specs, P()),
            out_specs=cspecs,
        )
        segment_p = self.shard(
            segment, in_specs=(cspecs, P(), P()), out_specs=cspecs
        )
        return start_p, segment_p

    def _queue_out_specs(self):
        return Q.WorkQueue(
            items=jax.tree.map(lambda _: self._spec, self.proto),
            dest=self._spec,
            count=self._spec,
            drops=self._spec,
        )

    def _stats_specs(self):
        """Specs of a rank-stacked ``RoundStats`` (every leaf sharded on the
        prepended rank dim)."""
        proto = TS.make_stats(TS.num_tiers(self.cfg), self.cfg.telemetry_buckets)
        return jax.tree.map(lambda _: self._spec, proto)

    def _ring_specs(self):
        """Specs of a rank-stacked ``StatsRing``."""
        proto = TS.make_ring(
            TS.num_tiers(self.cfg),
            window=self.cfg.telemetry_window,
            buckets=self.cfg.telemetry_buckets,
        )
        return jax.tree.map(lambda _: self._spec, proto)


def _stack_queue(q: Q.WorkQueue) -> Q.WorkQueue:
    """Per-rank queue -> globally concatenable form (scalars become (1,))."""
    return Q.WorkQueue(
        items=q.items, dest=q.dest, count=q.count[None], drops=q.drops[None]
    )


def _unstack_queue(q: Q.WorkQueue) -> Q.WorkQueue:
    return Q.WorkQueue(
        items=q.items, dest=q.dest, count=q.count[0], drops=q.drops[0]
    )


def _stack_carry(carry: dict) -> dict:
    """Per-rank drive carry -> globally concatenable form: per-rank scalars
    become (1,) (so the stacked leaf is (R,)), the ring gains a leading rank
    dim; ``total``/``rnd`` stay replicated scalars; ``age`` is already a
    per-lane vector."""
    out = dict(carry)
    out["q"] = _stack_queue(carry["q"])
    out["drops"] = carry["drops"][None]
    if "ring" in carry:
        out["ring"] = TS.stack_ring(carry["ring"])
    if "emitted" in carry:
        out["emitted"] = carry["emitted"][None]
        out["delivered"] = carry["delivered"][None]
    return out


def _unstack_carry(carry: dict) -> dict:
    out = dict(carry)
    out["q"] = _unstack_queue(carry["q"])
    out["drops"] = carry["drops"][0]
    if "ring" in carry:
        out["ring"] = jax.tree.map(lambda a: a[0], carry["ring"])
    if "emitted" in carry:
        out["emitted"] = carry["emitted"][0]
        out["delivered"] = carry["delivered"][0]
    return out
