"""Faults planted in the miniApp's timed path, each of which the comparison
with the replay must catch.  Each breaks the drive underneath the harness,
in the program's own ``repro.core.termination``, the way a faulty change
could.

``FAULTS`` maps a name to ``plant(monkeypatch)``; those named in
``ACROSS_CHIPS`` exist only in a cell on more than one chip.
"""
import dataclasses

import jax
import jax.numpy as jnp


def _termination():
    from repro.core import termination

    return termination


def unchanged(monkeypatch):
    """A round that returns its state unchanged."""
    T = _termination()
    orig = T.drive_segment

    def segment(round_fn, carry, cfg, **kw):
        return orig(lambda q, aux, rnd, **_: (q, aux), carry, cfg, **kw)

    monkeypatch.setattr(T, "drive_segment", segment)


def half(monkeypatch):
    """Half of every round's rows left out of the forward."""
    T = _termination()
    orig = T.forward_work

    def forward(q, cfg, **kw):
        lane = jnp.arange(q.dest.shape[0])
        dest = jnp.where(lane >= (q.count + 1) // 2, -1, q.dest)
        return orig(dataclasses.replace(q, dest=dest), cfg, **kw)

    monkeypatch.setattr(T, "forward_work", forward)


def no_exchange(monkeypatch):
    """The exchange between chips left out: every row stays on its rank."""
    T = _termination()
    orig = T.forward_work

    def forward(q, cfg, **kw):
        me = jax.lax.axis_index(cfg.axis_name)
        return orig(dataclasses.replace(q, dest=jnp.where(q.dest >= 0, me, q.dest)), cfg, **kw)

    monkeypatch.setattr(T, "forward_work", forward)


def altered(monkeypatch):
    """A few delivered items altered where the forward produces them."""
    T = _termination()
    orig = T.forward_work

    def forward(q, cfg, **kw):
        out = orig(q, cfg, **kw)
        lane = jnp.arange(out[0].dest.shape[0])

        def alter(a):
            sel = (lane < 8).reshape((-1,) + (1,) * (a.ndim - 1))
            return jnp.where(sel, a + 1, a).astype(a.dtype)

        new_q = dataclasses.replace(out[0], items=jax.tree.map(alter, out[0].items))
        return (new_q,) + tuple(out[1:])

    monkeypatch.setattr(T, "forward_work", forward)


FAULTS = {"unchanged": unchanged, "half": half, "no_exchange": no_exchange, "altered": altered}
ACROSS_CHIPS = {"no_exchange"}
