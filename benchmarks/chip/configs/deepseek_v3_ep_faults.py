"""Faults planted in the DeepSeek-V3 MoE layer's timed path, each of which the
comparison with the reference must catch.  Each breaks the program
underneath the harness, in ``repro.models.moe`` or in the drive's
``repro.core.termination``, the way a faulty change could.

``FAULTS`` maps a name to ``plant(monkeypatch)``; those named in
``ACROSS_CHIPS`` exist only in a cell on more than one chip.
"""
import dataclasses

import jax
import jax.numpy as jnp


def _moe():
    from repro.models import moe

    return moe


def _termination():
    from repro.core import termination

    return termination


def no_shared(monkeypatch):
    """The shared expert skipped."""
    M = _moe()
    monkeypatch.setattr(M, "glu_mlp", lambda x, *a, **k: jnp.zeros(x.shape, jnp.float32))


def expert_zeroed(monkeypatch):
    """The output of each rank's first held expert zeroed."""
    M = _moe()
    orig = M.grouped_glu

    def grouped(x, sizes, wi, wg, wo):
        out = orig(x, sizes, wi, wg, wo)
        return jnp.where((jnp.arange(x.shape[0]) < sizes[0])[:, None], 0.0, out)

    monkeypatch.setattr(M, "grouped_glu", grouped)


def no_bias(monkeypatch):
    """The selection made without ``e_score_correction_bias``."""
    M = _moe()
    orig = M.group_limited_route
    monkeypatch.setattr(M, "group_limited_route",
                        lambda x, w, bias, cfg: orig(x, w, jnp.zeros_like(bias), cfg))


def combine_off_by_one(monkeypatch):
    """Each returned row added to the token after its own."""
    M = _moe()
    orig = M._combiner

    def combiner(tokens):
        combine = orig(tokens)

        def shifted(view, y, rnd):
            items = dataclasses.replace(view.items, slot=view.items.slot + 1)
            return combine(dataclasses.replace(view, items=items), y, rnd)

        return shifted

    monkeypatch.setattr(M, "_combiner", combiner)


def fp8_wire(monkeypatch):
    """Rows through float8 (e4m3) on the wire, both trips."""
    T = _termination()
    orig = T.forward_work

    def forward(q, cfg, **kw):
        h = q.items.h.astype(jnp.float8_e4m3fn).astype(q.items.h.dtype)
        return orig(dataclasses.replace(q, items=dataclasses.replace(q.items, h=h)), cfg, **kw)

    monkeypatch.setattr(T, "forward_work", forward)


def no_exchange(monkeypatch):
    """The exchange between chips left out: every row stays on its rank."""
    T = _termination()
    orig = T.forward_work

    def forward(q, cfg, **kw):
        me = jax.lax.axis_index(cfg.axis_name)
        return orig(dataclasses.replace(q, dest=jnp.where(q.dest >= 0, me, q.dest)), cfg, **kw)

    monkeypatch.setattr(T, "forward_work", forward)


FAULTS = {
    "no_shared": no_shared,
    "expert_zeroed": expert_zeroed,
    "no_bias": no_bias,
    "combine_off_by_one": combine_off_by_one,
    "fp8_wire": fp8_wire,
    "no_exchange": no_exchange,
}
ACROSS_CHIPS = {"no_exchange"}
