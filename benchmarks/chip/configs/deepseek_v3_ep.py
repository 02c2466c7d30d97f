"""DeepSeek-V3's MoE layer as an expert-parallel prefill deployment on R ranks.

One job runs ``num_hidden_layers`` (4) consecutive MoE layers, ``h ← h +
MoE(RMSNorm(h))``, on one staged block of every rank's tokens through the
program's own path (``repro.models.moe.moe_ep``): the group-limited
sigmoid router, the shared expert on each rank's own tokens, dispatch of
every assignment to a present expert by ``enqueue`` → ``run_until_done`` →
``forward_work`` (padded exchange, ``overflow="retain"``), one grouped
matmul over each rank's arrivals, and the return trip that combines them.
Mesh rank r holds experts ``r·8 … r·8+7`` of EP32; assignments to experts
of absent ranks are not emitted.

Weights come from the configuration's ``weights_seed``, so the hot experts
are the same on every ``--seed``; the router's weights are float32, the
experts' bfloat16.  Tokens are drawn on the device in set-up from
``--seed`` (traffic kind ``moe_prefill``, :func:`jobs`): ``staged_blocks``
blocks of ``tokens_per_rank`` hidden states a rank, each a topic centroid
plus noise, topics Zipf-distributed.  Job n reads block ``n mod
staged_blocks`` by an index the previous job returned, with no transfer
between jobs.  Each job keeps on the device, for ``samples_per_rank``
tokens a rank drawn from the seed, every layer's input, routed part and
chosen experts; after the window :meth:`Deployment.check` recomputes them
with the float32 reference in ``deepseek_v3_ep_reference.py``.
"""
from __future__ import annotations

import gc
import sys
from pathlib import Path
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from harness import load_module

# the router this configuration needs: a program without it fails here, at
# once, before anything is built or compiled
from repro.models.moe import ExpertParallelConfig, moe_ep

REFERENCE = Path(__file__).with_name("deepseek_v3_ep_reference.py")
AXIS = "data"

# the sizes a CPU test replaces in configs/deepseek_v3_ep.json: 32 routed
# experts in 8 groups over 8 EP ranks, top 8 of 4 groups.  Routing at these
# widths is more skewed: the fullest of 4 ranks receives up to 1.67 times
# the mean arrivals (on 3 seeds), so its queue holds twice the mean
SMALL = {"hidden_size": 64, "moe_intermediate_size": 32, "n_routed_experts": 4, "ep_ranks": 8,
         "queue_margin": 2.0}

# A chosen expert set counts as wrong only where the reference's selection
# margin (the gap at the last kept group and at the last chosen expert)
# exceeds DELTA.  The program routes from bfloat16 RMSNorm rows, the
# reference from float32 ones: a 2^-9 relative rounding per element moves
# a logit by about 1e-3 of its spread and a sigmoid score by a quarter of
# that; DELTA is ten times the largest such shift.
DELTA = 4e-3

# name -> limit (PERF.md has the readings each was set from)
LIMITS = {
    # sampled token-layers whose routed part or update is off: relative
    # error of the routed part over TOL_ROUTED, or of the layer's update
    # over TOL_UPDATE
    "rows_off": 0,
    # sampled token-layers whose expert set differs where margin > DELTA
    "routing_off": 0,
    # |assignments delivered to their expert's rank - emitted|, all jobs
    "deliveries_off": 0,
    "drops": 0,
    "not_done": 0,   # job-layers whose trips ran out of rounds
}
# bfloat16 rows on the wire, bfloat16 hidden of each GLU and bfloat16
# expert outputs put about 3 x 2^-9 relative error into the routed part and
# the residual's bfloat16 rounding about 2^-9 |h| / |update| into the
# update; a float8 (e4m3) wire puts 2^-4 into the routed part.  At the
# published widths the sound program's largest errors read 0.39% (routed)
# and 0.64% (update), the float8 stand-in's least 7.4% and 7.2%: each
# limit sits near the middle of its two readings
TOL_ROUTED = 0.02
TOL_UPDATE = 0.03

_CENTROIDS, _ROUTER, _BIAS, _NORM, _SHARED, _EXPERTS = range(6)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *stream])


def layer_config(spec: Dict[str, Any], mix: Dict[str, Any], ranks: int) -> ExpertParallelConfig:
    """The layer's static shape and the dispatch's sizes on ``ranks`` chips
    (``sizing`` in the configuration file)."""
    e_loc, ep = spec["n_routed_experts"], spec["ep_ranks"]
    per_peer = mix["tokens_per_rank"] * spec["num_experts_per_tok"] / ep

    def rows(x):
        return int(-(-x // 128) * 128)

    return ExpertParallelConfig(
        num_experts=e_loc * ep, top_k=spec["num_experts_per_tok"],
        n_group=spec["n_group"], topk_group=spec["topk_group"],
        routed_scaling=spec["routed_scaling_factor"], ep_ranks=ep,
        capacity=rows(spec["queue_margin"] * ranks * per_peer),
        peer_capacity=rows(spec["peer_margin"] * per_peer),
        max_rounds=spec["max_rounds"], eps=spec["rms_norm_eps"], axis=AXIS,
    )


# ------------------------------------------------------------------ weights

def _key(seed: int, *path: int):
    k = jax.random.PRNGKey(seed)
    for p in path:
        k = jax.random.fold_in(k, p)
    return k


def _normal(key, shape, std, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def dense_weights(spec: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """One layer's norm, router, bias and shared expert, from
    ``weights_seed`` (every rank holds the same)."""
    ws, D, F = spec["weights_seed"], spec["hidden_size"], spec["moe_intermediate_size"]
    E = spec["n_routed_experts"] * spec["ep_ranks"]
    sk = jax.random.split(_key(ws, layer, _SHARED), 3)
    bf = jnp.bfloat16
    return {
        "norm": 1.0 + _normal(_key(ws, layer, _NORM), (D,), 0.1),
        "router": _normal(_key(ws, layer, _ROUTER), (D, E), D ** -0.5),
        "bias": _normal(_key(ws, layer, _BIAS), (E,), spec["init"]["bias_std"]),
        "shared": {"wi": _normal(sk[0], (D, F), D ** -0.5, bf),
                   "wg": _normal(sk[1], (D, F), D ** -0.5, bf),
                   "wo": _normal(sk[2], (F, D), F ** -0.5, bf)},
    }


def expert_weights(spec: Dict[str, Any], layer: int, experts) -> Dict[str, Any]:
    """``wi``, ``wg`` (n, D, F) and ``wo`` (n, F, D) in bfloat16 of the
    global experts ``experts`` (n,) of ``layer``: expert e's weights are
    the same whichever rank holds it."""
    ws, D, F = spec["weights_seed"], spec["hidden_size"], spec["moe_intermediate_size"]
    base = _key(ws, layer, _EXPERTS)

    def one(e):
        k = jax.random.split(jax.random.fold_in(base, e), 3)
        bf = jnp.bfloat16
        return (_normal(k[0], (D, F), D ** -0.5, bf), _normal(k[1], (D, F), D ** -0.5, bf),
                _normal(k[2], (F, D), F ** -0.5, bf))

    wi, wg, wo = jax.vmap(one)(jnp.asarray(experts, jnp.int32))
    return {"wi": wi, "wg": wg, "wo": wo}


def centroids(spec: Dict[str, Any], mix: Dict[str, Any]):
    return _normal(_key(spec["weights_seed"], _CENTROIDS), (mix["topics"], spec["hidden_size"]), 1.0)


def token_block(spec, mix, cents, key, block, rank):
    """``tokens_per_rank`` hidden states of one rank's block: a centroid of
    a Zipf-drawn topic plus ``noise_scale`` N(0, 1), in bfloat16."""
    k = jax.random.fold_in(jax.random.fold_in(key, block), rank)
    kt, kn = jax.random.split(k)
    logits = -mix["zipf_s"] * jnp.log(jnp.arange(1, mix["topics"] + 1, dtype=jnp.float32))
    topic = jax.random.categorical(kt, logits, shape=(mix["tokens_per_rank"],))
    noise = jax.random.normal(kn, (mix["tokens_per_rank"], spec["hidden_size"]), jnp.float32)
    return (cents[topic] + mix["noise_scale"] * noise).astype(jnp.bfloat16)


# ------------------------------------------------------------------ traffic

def jobs(mix: Dict[str, Any], seed: int, ranks: int) -> List[Dict[str, Any]]:
    """The window's jobs: job n reads staged block ``n mod staged_blocks``,
    whose tokens come from ``tokens_seed``, and keeps ``samples`` (R, S),
    token positions drawn from the seed.  Raises ``ValueError`` for a
    traffic kind other than ``moe_prefill``."""
    if mix["kind"] != "moe_prefill":
        raise ValueError(f"traffic kind {mix['kind']!r}: deepseek_v3_ep draws only 'moe_prefill'")
    rng = _rng(seed, ranks)
    tokens_seed = int(rng.integers(0, 2**31 - 1))
    n, T = mix["staged_jobs"], mix["tokens_per_rank"]
    S = mix["samples_per_rank"]
    samples = np.sort(rng.integers(0, T, size=(n, ranks, S), dtype=np.int32), axis=-1)
    return [{"block": i % mix["staged_blocks"], "tokens_seed": tokens_seed, "samples": samples[i]}
            for i in range(n)]


# ------------------------------------------------------------------ deployment

class Deployment:
    def __init__(self, spec, mix, devices):
        from repro import compat

        self.spec, self.mix = spec, mix
        self.ranks = R = len(devices)
        if mix.get("ranks", R) != R:
            raise ValueError(f"traffic is offered by {mix['ranks']} ranks, the cell has {R}")
        self.cfg = layer_config(spec, mix, R)
        self.mesh = compat.make_mesh((R,), (AXIS,), devices=devices)
        self.layers = spec["num_hidden_layers"]
        self._job = None

    def _sharding(self, *spec):
        return NamedSharding(self.mesh, P(*spec))

    def _params(self):
        """Every layer's weights on the device, stacked on a layer axis;
        each rank generates its own experts'."""
        spec, R, L = self.spec, self.ranks, self.layers
        e_loc = self.cfg.experts_per_rank
        dense = jax.jit(lambda: jax.tree.map(
            lambda *a: jnp.stack(a), *[dense_weights(spec, l) for l in range(L)]),
            out_shardings=self._sharding())()

        def held():
            me = jax.lax.axis_index(AXIS)
            mine = me * e_loc + jnp.arange(e_loc)
            per = [expert_weights(spec, l, mine) for l in range(L)]
            return jax.tree.map(lambda *a: jnp.stack(a), *per)

        experts = jax.jit(jax.shard_map(
            held, mesh=self.mesh, in_specs=(), out_specs=P(None, AXIS)))()
        return {**dense, **experts}

    def _pool(self, tokens_seed):
        spec, mix = self.spec, self.mix

        def draw():
            me = jax.lax.axis_index(AXIS)
            cents = centroids(spec, mix)
            key = jax.random.PRNGKey(tokens_seed)
            return jnp.stack([token_block(spec, mix, cents, key, b, me)
                              for b in range(mix["staged_blocks"])])

        return jax.jit(jax.shard_map(draw, mesh=self.mesh, in_specs=(),
                                     out_specs=P(None, AXIS)))()

    def _program(self):
        cfg, mesh, nb, R = self.cfg, self.mesh, self.mix["staged_blocks"], self.ranks
        T = self.mix["tokens_per_rank"]

        def job(params, pool, table, idx):
            h = pool[idx % nb]
            sample = table[idx].reshape(-1)
            h_out, trail, counters = moe_ep(params, h, sample, cfg, mesh=mesh)
            digest = jnp.sum(h_out.astype(jnp.float32).reshape(R, T, -1), axis=(1, 2))
            return counters, trail, digest, idx + 1

        return jax.jit(job)

    def warm(self, jobs):
        """Weights and the token pool drawn on the device, the jobs' sample
        positions staged, and the one job program compiled (or loaded from
        the cache)."""
        with TraceAnnotation("bench.seed"):
            self._weights = self._params()
            self._tokens = self._pool(jobs[0]["tokens_seed"])
            table = np.stack([j["samples"] for j in jobs]).astype(np.int32)
            self._table = jax.device_put(table, self._sharding(None, AXIS, None))
            self._next = jax.device_put(np.int32(0), self._sharding())
        fn = self._program()
        self._job = fn.lower(self._weights, self._tokens, self._table, self._next).compile()

    def run(self, job):
        """The next staged job: ``job`` names its block and samples."""
        with TraceAnnotation("bench.dispatch"):
            counters, trail, _, self._next = self._job(
                self._weights, self._tokens, self._table, self._next)
        with TraceAnnotation("bench.readback"):
            c = np.asarray(counters)  # (R, L, 6 + e_loc)
        processed = c[:, :, 6:]
        return {
            "block": job["block"],
            "layers": self.layers,
            "rounds": int(c[0, :, 0:2].sum()),
            "emitted": int(c[:, :, 2].sum()),
            "deliveries": int(c[:, :, 3].sum()),
            "drops": int(c[:, :, 4].sum()),
            "not_done": int((c[:, :, 5] == 0).any(axis=0).sum()),
            "processed": int(processed.sum()),
            "processed_max": int(processed.max()),
            "trail": trail,
        }

    def release(self):
        self._job = self._weights = self._tokens = self._table = self._next = None
        gc.collect()

    # ---------------------------------------------------------- comparison

    def _trails(self, records):
        """The sampled tokens of every job, per layer: inputs ``h`` (L+1, N,
        D), routed parts (L, N, D) and chosen experts (L, N, k)."""
        get = lambda k: np.concatenate(  # noqa: E731
            [np.asarray(r["trail"][k], np.float32 if k != "experts" else np.int32)
             .swapaxes(0, 1).reshape(np.shape(r["trail"][k])[1], -1,
                                     *np.shape(r["trail"][k])[3:])
             for r in records], axis=1)
        return get("h"), get("routed"), get("experts")

    def _reference(self, hs, use_bias=True, adopt=None, cast=None):
        """The reference's ``(experts, margin, shared, routed)`` per layer for
        the inputs ``hs[l]``; where ``adopt`` (the program's choice) differs
        from the reference's inside DELTA, the reference takes it.  ``cast``
        rounds the experts' inputs and weights."""
        ref = load_module(REFERENCE)
        spec = self.spec
        held = range(self.ranks * self.cfg.experts_per_rank)
        kw = dict(n_group=spec["n_group"], topk_group=spec["topk_group"],
                  top_k=spec["num_experts_per_tok"], scaling=spec["routed_scaling_factor"])
        eps = spec["rms_norm_eps"]
        gen = jax.jit(lambda l, e: expert_weights(spec, l, e[None]))
        out = []
        with jax.default_matmul_precision("highest"):
            for l in range(self.layers):
                d = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), dense_weights(spec, l))
                h = jnp.asarray(hs[l])
                x = ref.rms_norm(h, d["norm"], eps)
                experts, w, margin = ref.route(x, d["router"], d["bias"], use_bias=use_bias, **kw)
                experts, margin = np.asarray(experts), np.asarray(margin)
                if adopt is not None:
                    same = _same_sets(experts, adopt[l])
                    take = ~same & (margin <= DELTA)
                    experts = np.where(take[:, None], adopt[l], experts)
                    w = ref.weights_for(x, d["router"], jnp.asarray(experts),
                                        scaling=kw["scaling"])

                def weights_of(e, l=l):
                    ew = gen(l, jnp.int32(e))
                    return tuple(jnp.asarray(ew[k][0], jnp.float32) for k in ("wi", "wg", "wo"))

                p = {"norm": d["norm"], "shared": tuple(d["shared"][k] for k in ("wi", "wg", "wo"))}
                shared, routed = ref.layer(h, p, experts, w, held, weights_of, eps=eps, cast=cast)
                out.append((experts, margin, np.asarray(shared), np.asarray(routed)))
        return out

    def _compare(self, hs, routed_p, experts_p, ref):
        """Readings of the program's (or a stand-in's) sampled layers against
        the reference's: rows_off, routing_off, the largest errors (and the
        least, of rows the held experts touch), and which sampled tokens are
        off in some layer."""
        rows_off = routing_off = 0
        worst = {"routed": 0.0, "update": 0.0, "mismatch_margin": 0.0,
                 "least_routed": np.inf, "least_update": np.inf}
        off = np.zeros(hs.shape[1], bool)
        for l, (experts, margin, shared, routed) in enumerate(ref):
            same = _same_sets(experts, experts_p[l])
            wrong_set = ~same & (margin > DELTA)
            routing_off += int(np.sum(wrong_set))
            if np.any(~same):
                worst["mismatch_margin"] = max(worst["mismatch_margin"], float(margin[~same].max()))
            e_r = _rel(routed_p[l], routed)
            e_u = _rel(hs[l + 1] - hs[l], shared + routed)
            worst["routed"] = max(worst["routed"], float(e_r.max()))
            worst["update"] = max(worst["update"], float(e_u.max()))
            held = np.linalg.norm(routed, axis=-1) > 0
            if held.any():
                worst["least_routed"] = min(worst["least_routed"], float(e_r[held].min()))
            worst["least_update"] = min(worst["least_update"], float(e_u.min()))
            row = (e_r > TOL_ROUTED) | (e_u > TOL_UPDATE)
            rows_off += int(np.sum(row))
            off |= row | wrong_set
        return rows_off, routing_off, worst, off

    def check(self, records):
        """``(checks, failed)`` of every job of the window; each check is
        ``(name, value, limit)``."""
        hs, routed_p, experts_p = self._trails(records)
        ref = self._reference(hs, adopt=experts_p)
        rows_off, routing_off, worst, off = self._compare(hs, routed_p, experts_p, ref)
        print(f"reference: samples={hs.shape[1]} worst={worst}", file=sys.stderr, flush=True)
        readings = {
            "rows_off": rows_off,
            "routing_off": routing_off,
            "deliveries_off": sum(abs(r["deliveries"] - r["emitted"]) for r in records),
            "drops": sum(r["drops"] for r in records),
            "not_done": sum(r["not_done"] for r in records),
        }
        sampled_off = off.reshape(len(records), -1).any(axis=1)
        bad = sum(bool(r["drops"] or r["not_done"] or r["deliveries"] != r["emitted"] or o)
                  for r, o in zip(records, sampled_off))
        return [(k, readings[k], LIMITS[k]) for k in LIMITS], bad

    def _stand_in(self, hs, layers):
        """The checks of reference ``layers`` put in the program's place (each
        layer's update added to the program's input), against the reference;
        the largest errors go to standard error."""
        stand_in = np.stack([hs[0]] + [hs[l] + shared + routed
                                       for l, (_, _, shared, routed) in enumerate(layers)])
        rows_off, routing_off, worst, _ = self._compare(
            stand_in, [r for _, _, _, r in layers], [e for e, _, _, _ in layers],
            self._reference(hs))
        print(f"stand-in: samples={hs.shape[1]} worst={worst}", file=sys.stderr, flush=True)
        readings = dict(rows_off=rows_off, routing_off=routing_off, deliveries_off=0,
                        drops=0, not_done=0)
        return [(k, readings[k], LIMITS[k]) for k in LIMITS]

    def control(self, records):
        """The control's checks: the reference with the bias ignored in the
        program's place."""
        hs, _, _ = self._trails(records)
        return self._stand_in(hs, self._reference(hs, use_bias=False))

    def lower_precision(self, records):
        """The checks of the nearest precision below the configuration's
        bfloat16: the reference with every expert's inputs and weights
        rounded to float8 (e4m3), routed as the reference routes, in the
        program's place."""
        hs, _, _ = self._trails(records)
        fp8 = lambda a: jnp.asarray(a).astype(jnp.float8_e4m3fn).astype(jnp.float32)  # noqa: E731
        return self._stand_in(hs, self._reference(hs, cast=fp8))


def _same_sets(a, b):
    return np.all(np.sort(np.asarray(a), axis=-1) == np.sort(np.asarray(b), axis=-1), axis=-1)


def _rel(got, want):
    """Per-row relative error; a row the reference gives as zero must be
    zero."""
    err = np.linalg.norm(np.asarray(got, np.float64) - want, axis=-1)
    norm = np.linalg.norm(np.asarray(want, np.float64), axis=-1)
    return np.where(norm > 0, err / np.where(norm > 0, norm, 1.0), np.where(err > 0, np.inf, 0.0))
