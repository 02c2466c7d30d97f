"""Mixture-of-Experts with RaFI forwarding as the dispatch plane.

This is the paper's technique integrated as a first-class LM feature: under
expert parallelism, routed tokens are *work items* that must migrate to the
rank owning their expert — semi-random, data-dependent, batched: precisely
RaFI's domain.  Two dispatch planes are implemented:

* ``rafi_ep`` (paper technique): experts are sharded over the "model" axis.
  Inside a ``shard_map`` over ("data", "model"), each shard takes its token
  slice, *emits* (hidden, slot, weight) items with destination
  ``expert // experts_per_rank`` via the §3 queue API, and one
  ``forward_work`` round (§4.2: sort by destination → count exchange →
  payload all-to-all) moves them.  Local experts run; a second forwarding
  round returns results to the stored origin rank (the ray's ``pixelID``
  pattern), where they are combined by router weight.  Top-k > 1 simply
  emits k items per token — §3.3's "threads can emit more than one ray".
* ``dense_tp`` (baseline, no forwarding): every rank holds every expert,
  sharded over d_ff; dispatch is a local capacity-bucketed gather and the
  only communication is the usual tensor-parallel reduction.

Both planes share the router and the capacity-factor drop rule (queue
overflow == token drop — the same §3.3/§6.3 semantics, observable via the
drop counters).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import DISCARD, ForwardConfig, enqueue, forward_work, make_queue, work_item
from repro.models.common import MODEL_AXIS, ModelConfig, ParamDef, shard


@work_item
@dataclasses.dataclass
class TokenItem:
    """A routed token in flight (the MoE 'ray')."""

    h: jax.Array       # (D,) hidden state
    slot: jax.Array    # () i32 original position in the sender's token slice
    weight: jax.Array  # () f32 router weight
    expert: jax.Array  # () i32 global expert id
    src: jax.Array     # () i32 origin rank (the 'pixelID' for the return trip)


def moe_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    if cfg.moe_dispatch == "rafi_ep":
        # expert parallelism: experts over the model axis, full d_ff each
        wi_spec = wg_spec = P(MODEL_AXIS, None, None)
        wo_spec = P(MODEL_AXIS, None, None)
    else:
        # tensor parallelism: every expert everywhere, d_ff over the model axis
        wi_spec = wg_spec = P(None, None, MODEL_AXIS)
        wo_spec = P(None, MODEL_AXIS, None)
    return {
        "router": ParamDef((d, e), P(None, None), scale=0.02),
        "wi": ParamDef((e, d, f), wi_spec),
        "wg": ParamDef((e, d, f), wg_spec),
        "wo": ParamDef((e, f, d), wo_spec, scale=1.0 / np.sqrt(f)),
    }


def _router(params, x2d, cfg: ModelConfig):
    """x2d (N, D) → (topk_idx (N,k), topk_w (N,k)) with softmax-over-topk."""
    logits = (x2d.astype(jnp.float32)) @ params["router"].astype(jnp.float32)
    w, idx = jax.lax.top_k(logits, cfg.top_k)
    w = jax.nn.softmax(w, axis=-1)
    return idx.astype(jnp.int32), w.astype(x2d.dtype)


def _expert_ffn(wi, wg, wo, x, act: str):
    """Batched per-expert GLU: x (E, C, D) → (E, C, D)."""
    gate = jnp.einsum("ecd,edf->ecf", x, wg)
    up = jnp.einsum("ecd,edf->ecf", x, wi)
    a = jax.nn.silu(gate) if act == "silu" else jax.nn.gelu(gate)
    return jnp.einsum("ecf,efd->ecd", a * up, wo)


# ------------------------------------------------------------ dense_tp plane

def moe_dense_tp(params, x, cfg: ModelConfig):
    """Baseline: local capacity-bucketed dispatch, experts TP-sharded on d_ff."""
    b, s, d = x.shape
    n = b * s
    x2 = x.reshape(n, d)
    idx, w = _router(params, x2, cfg)
    e, k = cfg.num_experts, cfg.top_k
    cap = int(np.ceil(n * k / e * cfg.capacity_factor))

    flat_e = idx.reshape(-1)                      # (N·k,)
    flat_t = jnp.repeat(jnp.arange(n), k)         # token of each assignment
    flat_w = w.reshape(-1)
    # position of each assignment within its expert's bucket (counting sort)
    order = jnp.argsort(flat_e, stable=True)
    ranked = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32)
    )
    seg_start = jnp.cumsum(jnp.bincount(flat_e, length=e)) - jnp.bincount(flat_e, length=e)
    pos_in_e = ranked - seg_start[flat_e]
    keep = pos_in_e < cap

    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[
        jnp.where(keep, flat_e, e), jnp.where(keep, pos_in_e, 0)
    ].set(x2[flat_t], mode="drop")
    out_buf = _expert_ffn(params["wi"], params["wg"], params["wo"], buf, cfg.act)
    gathered = out_buf[jnp.where(keep, flat_e, 0), jnp.where(keep, pos_in_e, 0)]
    contrib = jnp.where(keep[:, None], gathered * flat_w[:, None], 0.0)
    y = jnp.zeros((n, d), x.dtype).at[flat_t].add(contrib)
    return y.reshape(b, s, d), jnp.sum(~keep)


# ------------------------------------------------------------- rafi_ep plane

def moe_rafi_ep(params, x, cfg: ModelConfig, *, mesh) -> Tuple[jax.Array, jax.Array]:
    """Paper-technique dispatch: forwarding over the model axis.

    ``x`` arrives replicated over "model" (post-attention layout); each model
    rank takes its 1/tp token slice, routes, exchanges, computes its local
    experts, and routes results back; a final all-gather restores the layout.
    """
    b, s, d = x.shape
    tp = mesh.shape[MODEL_AXIS]
    e, k = cfg.num_experts, cfg.top_k
    assert e % tp == 0, "experts must divide the model axis"
    e_loc = e // tp

    def proto():
        return TokenItem(
            h=jnp.zeros((d,), x.dtype),
            slot=jnp.zeros((), jnp.int32),
            weight=jnp.zeros((), x.dtype),
            expert=jnp.zeros((), jnp.int32),
            src=jnp.zeros((), jnp.int32),
        )

    def block(xb, wi, wg, wo, router):
        # xb: (B/dp, S, D) — replicated over model; take my token slice.
        # n_all may not divide tp (decode: one token) — pad with masked lanes.
        me = jax.lax.axis_index(MODEL_AXIS)
        bl, sl, _ = xb.shape
        n_all = bl * sl
        n_loc = -(-n_all // tp)
        x2 = xb.reshape(n_all, d)
        gslot = me * n_loc + jnp.arange(n_loc)
        tok_ok = gslot < n_all
        xs = x2[jnp.clip(gslot, 0, n_all - 1)]
        idx, w = _router({"router": router}, xs, cfg)

        n_emit = n_loc * k
        cap_send = n_emit
        # every peer can receive at most its expert capacity
        cap_e = int(np.ceil(n_all * k / e * cfg.capacity_factor))
        cap_recv = cap_e * e_loc
        cap = max(cap_send, cap_recv)
        # per-(src,dst) slots sized for balanced routing (+2× slack), not the
        # all-to-one worst case — the padded send buffer is R×slot×D, which
        # dominated MoE memory at worst-case sizing (§Perf dbrx iter).  Slot
        # overflow drops are counted (the §3.3 contract); production TPU uses
        # exchange="ragged" where slots don't exist at all.
        fcfg = ForwardConfig(
            MODEL_AXIS, tp, cap,
            peer_capacity=min(cap, max(64, -(-2 * cap // tp))),
            exchange="padded",
        )

        items = TokenItem(
            h=jnp.repeat(xs, k, axis=0),
            slot=jnp.repeat(jnp.arange(n_loc, dtype=jnp.int32), k),
            weight=w.reshape(-1),
            expert=idx.reshape(-1),
            src=jnp.full((n_emit,), me, jnp.int32),
        )
        dest = (items.expert // e_loc).astype(jnp.int32)
        q = make_queue(proto(), fcfg.capacity)
        q = enqueue(q, items, dest, jnp.repeat(tok_ok, k))
        q, _ = forward_work(q, fcfg)  # §4.2 — tokens travel to expert owners

        # local expert compute with per-expert capacity buckets
        lane = jnp.arange(fcfg.capacity)
        valid = lane < q.count
        it = q.items
        le = jnp.where(valid, it.expert - me * e_loc, e_loc)  # local expert id
        le = jnp.clip(le, 0, e_loc)
        order = jnp.argsort(jnp.where(valid, le, e_loc), stable=True)
        ranked = jnp.zeros((fcfg.capacity,), jnp.int32).at[order].set(
            jnp.arange(fcfg.capacity, dtype=jnp.int32)
        )
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[le].add(valid.astype(jnp.int32))
        seg = jnp.cumsum(counts) - counts
        pos = ranked - seg[le]
        keep = valid & (pos < cap_e) & (le < e_loc)
        drops_cap = jnp.sum(valid & ~keep)

        buf = jnp.zeros((e_loc, cap_e, d), x.dtype)
        buf = buf.at[jnp.where(keep, le, e_loc), jnp.where(keep, pos, 0)].set(
            it.h, mode="drop"
        )
        out = _expert_ffn(wi, wg, wo, buf, cfg.act)  # wi/wg/wo already (e_loc,...)
        hout = out[jnp.where(keep, le, 0), jnp.where(keep, pos, 0)]

        # return trip: dest = stored origin rank (the 'pixelID' pattern)
        back = TokenItem(
            h=hout, slot=it.slot, weight=it.weight, expert=it.expert, src=it.src
        )
        q2 = make_queue(proto(), fcfg.capacity)
        q2 = enqueue(q2, back, jnp.where(keep, it.src, DISCARD).astype(jnp.int32), valid)
        q2, _ = forward_work(q2, fcfg)

        lane2 = jnp.arange(fcfg.capacity)
        valid2 = lane2 < q2.count
        r = q2.items
        contrib = jnp.where(valid2[:, None], r.h * r.weight[:, None], 0.0)
        ys = jnp.zeros((n_loc, d), x.dtype).at[
            jnp.where(valid2, r.slot, n_loc)
        ].add(contrib, mode="drop")

        # restore replicated layout
        y_all = jax.lax.all_gather(ys, MODEL_AXIS, axis=0, tiled=True)
        y_all = y_all[:n_all].reshape(bl, sl, d)
        drops = drops_cap + q.drops + q2.drops
        return y_all, drops[None]

    baxes = tuple(a for a in mesh.axis_names if a != MODEL_AXIS)  # pod?, data
    y, drops = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(
            P(baxes, None, None),
            P(MODEL_AXIS, None, None),
            P(MODEL_AXIS, None, None),
            P(MODEL_AXIS, None, None),
            P(None, None),
        ),
        out_specs=(P(baxes, None, None), P(baxes + (MODEL_AXIS,))),
        check_vma=False,
    )(x, params["wi"], params["wg"], params["wo"], params["router"])
    return y, jnp.sum(drops)


def moe_block(params, x, cfg: ModelConfig, *, mesh=None):
    if cfg.moe_dispatch == "rafi_ep":
        assert mesh is not None, "rafi_ep dispatch needs the mesh"
        return moe_rafi_ep(params, x, cfg, mesh=mesh)
    return moe_dense_tp(params, x, cfg)
