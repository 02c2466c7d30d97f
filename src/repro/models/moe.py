"""Mixture-of-Experts with RaFI forwarding as the dispatch plane.

This is the paper's technique integrated as a first-class LM feature: under
expert parallelism, routed tokens are *work items* that must migrate to the
rank owning their expert — semi-random, data-dependent, batched: precisely
RaFI's domain.  Three dispatch planes are implemented:

* ``rafi_ep`` (paper technique): experts are sharded over the "model" axis.
  Inside a ``shard_map`` over ("data", "model"), each shard takes its token
  slice, *emits* (hidden, slot, weight) items with destination
  ``expert // experts_per_rank`` via the §3 queue API, and one
  ``forward_work`` round (§4.2: sort by destination → count exchange →
  payload all-to-all) moves them.  Local experts run as one grouped matmul
  over the arrivals sorted by expert; a second forwarding round returns
  results to the stored origin rank (the ray's ``pixelID`` pattern), where
  they are combined by router weight.  Top-k > 1 simply emits k items per
  token — §3.3's "threads can emit more than one ray".  A token drops only
  at the peer-slot clamp (counted).
* ``dense_tp`` (baseline, no forwarding): every rank holds every expert,
  sharded over d_ff; dispatch is a local capacity-bucketed gather (tokens
  past ``capacity_factor`` drop, counted) and the only communication is the
  usual tensor-parallel reduction.
* :func:`moe_ep` (DeepSeek-V3's layer as an expert-parallel deployment):
  the group-limited sigmoid router, a shared expert, each rank on its own
  tokens (data-parallel attention's layout), and dropless dispatch — both
  trips driven by ``run_until_done`` with ``overflow="retain"``, so a hot
  peer costs forwarding rounds, not tokens.

``rafi_ep`` and ``dense_tp`` share the softmax-over-top-k router.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.sharding import PartitionSpec as P

from repro import kernels
from repro.core import (
    DISCARD,
    ForwardConfig,
    WorkQueue,
    batched_zeros,
    enqueue,
    forward_work,
    make_queue,
    run_until_done,
    work_item,
)
from repro.models.common import MODEL_AXIS, ModelConfig, ParamDef, glu_mlp, rmsnorm, shard
from repro.obs import metrics as OM


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

def _gmm(x, w, group_sizes, out_dtype=jnp.float32):
    """Megablox's grouped matmul, float32 accumulation: rows in 512-row tiles
    where they divide (128 otherwise), weights in 1024-wide tiles."""
    m, k = x.shape
    tiling = (512 if m % 512 == 0 else 128, min(1024, k), min(1024, w.shape[2]))
    return gmm(x, w, group_sizes, preferred_element_type=out_dtype, tiling=tiling,
               interpret=kernels.default_interpret())


@jax.named_scope("rafi.moe_experts")
def grouped_glu(x, group_sizes, wi, wg, wo):
    """SwiGLU experts over rows sorted by expert: rows ``[o_e, o_e + n_e)``
    go through expert ``e`` (``n_e = group_sizes[e]``).  One grouped matmul
    per weight over however many rows each expert holds: no capacity bucket,
    no padding rows; rows past the last group are left undefined.  x (M,
    D), wi/wg (E, D, F), wo (E, F, D) → (M, D) in x's dtype, every product
    accumulated in float32.  (``lax.ragged_dot``
    computes the same; the TPU's compiler renames its ops, so they would
    fall outside this scope.)"""
    gate = _gmm(x, wg, group_sizes)
    up = _gmm(x, wi, group_sizes)
    a = (jax.nn.silu(gate) * up).astype(x.dtype)
    return _gmm(a, wo, group_sizes, x.dtype)


def _sort_by_expert(items, local, valid, e_loc: int):
    """Arrivals ordered by local expert for :func:`grouped_glu`: ``(sorted
    items, group sizes (e_loc,))``.  Lanes that are not ``valid`` or whose
    expert this rank does not hold sort last, outside every group."""
    key = jnp.where(valid & (local >= 0) & (local < e_loc), local, e_loc)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((e_loc + 1,), jnp.int32).at[key].add(1)[:e_loc]
    return jax.tree.map(lambda a: jnp.take(a, order, axis=0), items), sizes


def moe_rafi_ep(params, x, cfg: ModelConfig, *, mesh) -> Tuple[jax.Array, jax.Array]:
    """Paper-technique dispatch: forwarding over the model axis.

    ``x`` arrives replicated over "model" (post-attention layout); each model
    rank takes its 1/tp token slice, routes, exchanges, computes its local
    experts, and routes results back; a final all-gather restores the layout.
    Each trip is ONE ``forward_work`` round (differentiable: no drive loop),
    so the peer-slot clamp is the only place a token can drop (counted).
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
        # per-(src,dst) slots sized for balanced routing with 2× slack, not
        # the all-to-one worst case: the padded send buffer is R×slot×D.
        # Slot overflow drops are counted (the §3.3 contract); every peer's
        # slots together fit the queue, so nothing drops on receipt.
        peer = max(64, -(-2 * n_emit // tp))
        fcfg = ForwardConfig(MODEL_AXIS, tp, tp * peer, peer_capacity=peer, exchange="padded")

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

        # local experts: one grouped matmul over the arrivals, sorted by expert
        lane = jnp.arange(fcfg.capacity)
        it, sizes = _sort_by_expert(q.items, q.items.expert - me * e_loc,
                                    lane < q.count, e_loc)
        hout = grouped_glu(it.h, sizes, wi, wg, wo)

        # return trip: dest = stored origin rank (the 'pixelID' pattern)
        back = dataclasses.replace(it, h=hout)
        done = lane < jnp.sum(sizes)
        q2 = make_queue(proto(), fcfg.capacity)
        q2 = enqueue(q2, back, jnp.where(done, it.src, DISCARD).astype(jnp.int32), done)
        q2, _ = forward_work(q2, fcfg)

        valid2 = lane < q2.count
        r = q2.items
        contrib = jnp.where(valid2[:, None], r.h * r.weight[:, None], 0.0)
        ys = jnp.zeros((n_loc, d), x.dtype).at[
            jnp.where(valid2, r.slot, n_loc)
        ].add(contrib, mode="drop")

        # restore replicated layout
        y_all = jax.lax.all_gather(ys, MODEL_AXIS, axis=0, tiled=True)
        y_all = y_all[:n_all].reshape(bl, sl, d)
        drops = q.drops + q2.drops
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


# ------------------------------------------- DeepSeek-V3 expert parallelism

@dataclasses.dataclass(frozen=True)
class ExpertParallelConfig:
    """One DeepSeek-V3 MoE layer as an expert-parallel deployment.

    The router scores all ``num_experts`` routed experts; the deployment
    spreads them over ``ep_ranks`` chips, ``experts_per_rank`` each, and mesh
    rank ``r`` holds experts ``r·experts_per_rank …``.  A mesh of R ≤
    ``ep_ranks`` chips holds the first R shares: assignments to an expert of
    an absent rank are not emitted, so the layer computes the part of its
    result that the present experts give.

    ``capacity`` is each rank's queue (and arrival buffer) in rows,
    ``peer_capacity`` the padded exchange's slot rows per peer; a trip that
    has more rows for a peer than its slots hold retains the rest and ships
    them in later rounds, so skew costs rounds, not tokens.
    """

    num_experts: int          # routed experts the router scores
    top_k: int
    n_group: int
    topk_group: int
    routed_scaling: float
    ep_ranks: int
    capacity: int
    peer_capacity: int
    max_rounds: int = 64
    eps: float = 1e-6
    axis: str = "data"

    def __post_init__(self):
        if self.num_experts % self.ep_ranks or self.num_experts % self.n_group:
            raise ValueError(
                f"{self.num_experts} experts must divide over {self.ep_ranks} ranks "
                f"and {self.n_group} groups"
            )
        if not self.topk_group <= self.n_group or self.top_k > self.num_experts:
            raise ValueError("topk_group must not exceed n_group, nor top_k the experts")

    @property
    def experts_per_rank(self) -> int:
        return self.num_experts // self.ep_ranks


# per-layer counters a job returns, one column each, then the rows each held
# expert processed
COUNTERS = ("rounds_dispatch", "rounds_return", "emitted", "delivered", "drops", "done")


def group_limited_route(x, w_router, bias, cfg: ExpertParallelConfig):
    """DeepSeek-V3's router (``scoring_func`` sigmoid, ``topk_method``
    noaux_tc): sigmoid scores in float32; the selection adds
    ``e_score_correction_bias``, scores each of ``n_group`` groups by the sum
    of its two best biased scores, keeps the best ``topk_group`` groups and
    takes the ``top_k`` best biased experts inside them.  The weights are
    the chosen experts' UNBIASED scores, normalised to sum 1 and scaled by
    ``routed_scaling``.  x (N, D) → (experts (N, k) int32, weights (N, k)
    float32)."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias.astype(jnp.float32)
    n, e = biased.shape
    per = e // cfg.n_group
    top2 = jax.lax.top_k(biased.reshape(n, cfg.n_group, per), 2)[0]
    _, groups = jax.lax.top_k(jnp.sum(top2, axis=-1), cfg.topk_group)
    kept = jnp.any(groups[:, :, None] == jnp.arange(cfg.n_group), axis=1)
    choice = jnp.where(jnp.repeat(kept, per, axis=1), biased, -jnp.inf)
    _, idx = jax.lax.top_k(choice, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scaling
    return idx.astype(jnp.int32), w


def _token_proto(d: int, dtype) -> TokenItem:
    return TokenItem(
        h=jnp.zeros((d,), dtype),
        slot=jnp.zeros((), jnp.int32),
        weight=jnp.zeros((), jnp.float32),
        expert=jnp.zeros((), jnp.int32),
        src=jnp.zeros((), jnp.int32),
    )


def _emptied(view: WorkQueue) -> WorkQueue:
    """A round's output queue that emits nothing, reusing the view's rows."""
    return WorkQueue(view.items, view.dest, jnp.zeros_like(view.count),
                     jnp.zeros_like(view.drops))


def _land(view: WorkQueue, acc, rnd):
    """Dispatch round: append the round's arrivals to the arrival buffer
    ``(rows, n, overflow)``; arrivals past its end are counted."""
    del rnd
    rows, n, over = acc
    cap = view.capacity
    lane = jnp.arange(cap, dtype=jnp.int32)
    src = jnp.clip(lane - n, 0, cap - 1)
    new = (lane >= n) & (lane < n + view.count)

    def put(a, b):
        sel = new.reshape((cap,) + (1,) * (a.ndim - 1))
        return jnp.where(sel, jnp.take(b, src, axis=0), a)

    rows = jax.tree.map(put, rows, view.items)
    total = n + view.count
    n_new = jnp.minimum(total, cap)
    return _emptied(view), (rows, n_new, over + total - n_new)


_COMBINE_ROWS = 4096  # returned rows added per step of the combine


def _combiner(tokens: int):
    """Return round: add each returned row, weighted by its router weight,
    into the token it came from, ``_COMBINE_ROWS`` rows a step over the
    round's arrivals only (a float32 copy of every row at once would cost a
    whole queue of float32 rows)."""

    def combine(view: WorkQueue, y, rnd):
        del rnd
        with jax.named_scope("rafi.moe_combine"):
            it = view.items
            cap = view.capacity
            step = min(_COMBINE_ROWS, cap)
            pad = -cap % step
            rows = jnp.pad(it.h, ((0, pad), (0, 0)))
            w = jnp.pad(it.weight, (0, pad))
            slot = jnp.pad(it.slot, (0, pad))

            def add(i, y):
                lo = i * step
                lane = lo + jnp.arange(step)
                h = jax.lax.dynamic_slice_in_dim(rows, lo, step).astype(jnp.float32)
                wt = jax.lax.dynamic_slice_in_dim(w, lo, step)
                at = jnp.where(lane < view.count, jax.lax.dynamic_slice_in_dim(slot, lo, step), tokens)
                return y.at[at].add(h * wt[:, None], mode="drop")

            y = jax.lax.fori_loop(0, (view.count + step - 1) // step, add, y)
        return _emptied(view), y

    return combine


def ep_moe_layer(p, h, cfg: ExpertParallelConfig, ranks: int):
    """One DeepSeek-V3 MoE layer on this rank's own tokens, inside
    ``shard_map`` over ``cfg.axis`` (``ranks`` chips): ``h + MoE(RMSNorm(h))``.

    Dispatch: each token's ``top_k`` assignments to experts held on the mesh
    are enqueued as :class:`TokenItem` rows (hidden, slot, weight, expert,
    source; ``DISCARD`` for an absent rank's expert) and driven by
    ``run_until_done`` → ``forward_work`` (padded exchange,
    ``overflow="retain"``) until every one has arrived.  The arrivals, sorted
    by local expert, go through one grouped matmul; a second drive returns
    each row to its source rank and slot, where it is added, weighted by its
    router weight, to the shared expert's output.

    Device scopes: ``rafi.moe_router`` (the norm and the router),
    ``rafi.moe_shared``, ``rafi.moe_sort`` (arrivals by local expert),
    ``rafi.moe_experts`` (every op of the grouped matmul) and
    ``rafi.moe_combine``; the dispatch runs under the core's ``rafi.*``
    scopes.  Each name is one word after ``rafi.``, as a trace's scope
    names are read.

    ``p``: ``norm`` (D,), ``router`` (D, E) f32, ``bias`` (E,) f32,
    ``shared`` {wi, wg (D, F), wo (F, D)}, and the held experts' ``wi``,
    ``wg`` (e_loc, D, F), ``wo`` (e_loc, F, D).  Returns ``(h_out (T, D),
    routed (T, D) f32 — the routed experts' part, experts (T, k), counters
    (len(COUNTERS) + e_loc,) int32)``.
    """
    T, D = h.shape
    e_loc = cfg.experts_per_rank
    C = cfg.capacity
    me = jax.lax.axis_index(cfg.axis)
    fcfg = ForwardConfig(cfg.axis, ranks, C, peer_capacity=cfg.peer_capacity,
                         exchange="padded", overflow="retain")
    proto = _token_proto(D, h.dtype)

    with jax.named_scope("rafi.moe_router"):
        # the published RMSNorm scales by its weight w; rmsnorm's by 1 + gamma
        x = rmsnorm(h, p["norm"] - 1.0, cfg.eps)
        idx, w = group_limited_route(x, p["router"], p["bias"], cfg)
    with jax.named_scope("rafi.moe_shared"):
        shared = glu_mlp(x, p["shared"]["wi"], p["shared"]["wg"], p["shared"]["wo"], "silu",
                         acc=jnp.float32)

    # dispatch: k emits per token (§3.3), each to its expert's rank
    q = make_queue(proto, C)
    tok = jnp.arange(T, dtype=jnp.int32)
    src = jnp.full((T,), me, jnp.int32)
    for j in range(cfg.top_k):
        owner = idx[:, j] // e_loc
        dest = jnp.where(owner < ranks, owner, DISCARD).astype(jnp.int32)
        q = enqueue(q, TokenItem(x, tok, w[:, j], idx[:, j], src), dest,
                    jnp.ones((T,), bool))
    emitted = q.count + q.drops
    acc0 = (batched_zeros(proto, C), jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    qd, (arrived, n_arr, over), r_disp, done_d, _ = run_until_done(
        _land, q, acc0, fcfg, max_rounds=cfg.max_rounds)

    OM.REGISTRY.set_gauge(OM.MOE_ROWS, C, "rows one rank's grouped expert matmul is given per layer")
    with jax.named_scope("rafi.moe_sort"):
        lane = jnp.arange(C, dtype=jnp.int32)
        rows, sizes = _sort_by_expert(arrived, arrived.expert - me * e_loc,
                                      lane < n_arr, e_loc)
    out = grouped_glu(rows.h, sizes, p["wi"], p["wg"], p["wo"])

    # return trip to the stored source rank and slot
    delivered = jnp.sum(sizes)
    back = lane < delivered
    q2 = enqueue(make_queue(proto, C), dataclasses.replace(rows, h=out),
                 jnp.where(back, rows.src, DISCARD).astype(jnp.int32), back)
    with jax.named_scope("rafi.moe_combine"):
        y0 = jnp.zeros((T, D), jnp.float32)
    qr, routed, r_ret, done_r, _ = run_until_done(
        _combiner(T), q2, y0, fcfg, max_rounds=cfg.max_rounds)
    with jax.named_scope("rafi.moe_combine"):
        h_out = (h.astype(jnp.float32) + shared + routed).astype(h.dtype)

    counters = jnp.concatenate([
        jnp.stack([r_disp, r_ret, emitted, delivered, qd.drops + over + qr.drops,
                   (done_d & done_r).astype(jnp.int32)]).astype(jnp.int32),
        sizes,
    ])
    return h_out, routed, idx, counters


def moe_ep(params, h, sample, cfg: ExpertParallelConfig, *, mesh):
    """A stack of :func:`ep_moe_layer`, ``h ← h + MoE(RMSNorm(h))`` per layer,
    each rank on its own tokens (data-parallel attention's layout: no
    replicated input, no all-gather out).

    ``params``: the per-layer parameters stacked on a leading layer axis, the
    held experts' (L, R·e_loc, …) sharded over ``cfg.axis`` (mesh rank r
    holds experts ``r·e_loc …``), the rest replicated.  ``h`` (R·T, D) and
    ``sample`` (R·S,) token positions, both over ``cfg.axis``.

    Returns ``(h_out (R·T, D), trail, counters (R, L, n))``: ``trail`` holds
    for the sampled tokens of each rank the input of every layer and the
    output of the last (``h``, (R, L+1, S, D)), each layer's routed part
    (``routed``, (R, L, S, D) f32) and chosen experts (``experts``, (R, L,
    S, k)).
    """
    ranks = mesh.shape[cfg.axis]
    if ranks > cfg.ep_ranks:
        raise ValueError(f"{ranks} chips, but the deployment spreads a layer over {cfg.ep_ranks}")
    ax = cfg.axis

    def stack(params, h, sample):
        def layer(h, p):
            h_out, routed, idx, counters = ep_moe_layer(p, h, cfg, ranks)
            return h_out, (h[sample], routed[sample], idx[sample], counters)

        h_out, (hs, routed, idx, counters) = jax.lax.scan(layer, h, params)
        hs = jnp.concatenate([hs, h_out[sample][None]])
        trail = {"h": hs[None], "routed": routed[None], "experts": idx[None]}
        return h_out, trail, counters[None]

    held = P(None, ax)
    specs = jax.tree.map(lambda _: P(), params)
    specs.update({k: held for k in ("wi", "wg", "wo")})
    return jax.shard_map(
        stack, mesh=mesh, in_specs=(specs, P(ax), P(ax)),
        out_specs=(P(ax), P(ax), P(ax)), check_vma=False,
    )(params, h, sample)
