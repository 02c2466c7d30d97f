"""DeepSeek-V3's expert-parallel MoE layer (``repro.models.moe``) against the
plain float32 reference in ``benchmarks/chip/configs/deepseek_v3_ep_reference.py``
at CPU test widths: 32 routed experts in 8 groups, top 8 of the best 4
groups, spread over 8 EP ranks, on the 8-rank mesh of ``conftest.py``."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.models import moe as M

REF_PATH = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "configs"
            / "deepseek_v3_ep_reference.py")
D, F, E, EP, T = 64, 32, 32, 8, 64
ROUTE = dict(n_group=8, topk_group=4, top_k=8, scaling=2.5)


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("deepseek_v3_ep_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(capacity=1024, peer=128, **kw):
    return M.ExpertParallelConfig(E, ROUTE["top_k"], ROUTE["n_group"], ROUTE["topk_group"],
                                  ROUTE["scaling"], EP, capacity=capacity, peer_capacity=peer,
                                  **kw)


def _weights(seed, bias_std=0.01):
    """One layer's float32 weights and every expert's, from ``seed``."""
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = lambda key, shape, std: jax.random.normal(key, shape, jnp.float32) * std  # noqa: E731
    dense = {
        "norm": 1.0 + n(k[0], (D,), 0.1),
        "router": n(k[1], (D, E), D ** -0.5),
        "bias": n(k[2], (E,), bias_std),
        "shared": {"wi": n(k[3], (D, F), D ** -0.5), "wg": n(k[4], (D, F), D ** -0.5),
                   "wo": n(k[5], (F, D), F ** -0.5)},
    }
    experts = {"wi": n(k[6], (E, D, F), D ** -0.5), "wg": n(k[7], (E, D, F), D ** -0.5),
               "wo": n(k[8], (E, F, D), F ** -0.5)}
    return dense, experts


def _tokens(seed, ranks):
    return jax.random.normal(jax.random.PRNGKey(seed), (ranks * T, D), jnp.float32)


def _program(dense, experts, h, cfg, ranks):
    """One layer through ``moe_ep`` on ``ranks`` chips: ``(h_out, routed,
    experts chosen, counters)`` for every token."""
    mesh = compat.make_mesh((ranks,), ("data",), devices=jax.devices()[:ranks])
    held = ranks * cfg.experts_per_rank
    params = jax.tree.map(lambda a: a[None], {**dense, **{k: v[:held] for k, v in experts.items()}})
    sample = jnp.tile(jnp.arange(T, dtype=jnp.int32), ranks)
    run = jax.jit(lambda p, h, s: M.moe_ep(p, h, s, cfg, mesh=mesh))
    h_out, trail, counters = run(params, h, sample)
    routed = np.asarray(trail["routed"][:, 0]).reshape(ranks * T, D)
    chosen = np.asarray(trail["experts"][:, 0]).reshape(ranks * T, -1)
    return np.asarray(h_out), routed, chosen, np.asarray(counters)


def _reference(ref, dense, experts, h, held):
    x = ref.rms_norm(h, dense["norm"], 1e-6)
    chosen, w, _ = ref.route(x, dense["router"], dense["bias"], **ROUTE)
    p = {"norm": dense["norm"], "shared": tuple(dense["shared"][k] for k in ("wi", "wg", "wo"))}
    shared, routed = ref.layer(h, p, chosen, w, held,
                               lambda e: tuple(experts[k][e] for k in ("wi", "wg", "wo")), eps=1e-6)
    return np.asarray(chosen), np.asarray(shared), np.asarray(routed)


def _sets(a):
    return np.sort(np.asarray(a), axis=-1)


# ------------------------------------------------------------------ router

def test_router_matches_reference(ref):
    dense, _ = _weights(1, bias_std=0.05)
    x = jax.random.normal(jax.random.PRNGKey(2), (256, D), jnp.float32)
    idx, w = M.group_limited_route(x, dense["router"], dense["bias"], _cfg())
    r_idx, r_w, _ = ref.route(x, dense["router"], dense["bias"], **ROUTE)
    np.testing.assert_array_equal(_sets(idx), _sets(r_idx))
    order, r_order = np.argsort(np.asarray(idx)), np.argsort(np.asarray(r_idx))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, 1),
                               np.take_along_axis(np.asarray(r_w), r_order, 1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), ROUTE["scaling"], rtol=1e-5)


def test_bias_changes_the_choice_not_the_weights(ref):
    """Eight experts in four groups of two, the best two groups, top 2.  The
    scores put experts 0 and 2 first; a bias on expert 5 (group 2) moves the
    choice to {0, 5}, whose weights are their unbiased scores, normalised."""
    cfg = M.ExpertParallelConfig(8, 2, 4, 2, 1.0, 1, capacity=8, peer_capacity=8)
    logits = jnp.asarray([[2.0, -1.0, 1.5, -2.0, -3.0, 1.0, -2.5, -3.5]])
    eye = jnp.eye(8, dtype=jnp.float32)
    bias = jnp.zeros((8,)).at[5].set(0.2)
    scores = jax.nn.sigmoid(logits[0])
    for b, want in ((jnp.zeros((8,)), [0, 2]), (bias, [0, 5])):
        idx, w = M.group_limited_route(logits, eye, b, cfg)
        assert sorted(np.asarray(idx)[0].tolist()) == want
        r_idx, r_w, _ = ref.route(logits, eye, b, n_group=4, topk_group=2, top_k=2, scaling=1.0)
        assert sorted(np.asarray(r_idx)[0].tolist()) == want
        expect = {e: float(scores[e] / scores[jnp.asarray(want)].sum()) for e in want}
        got = dict(zip(np.asarray(idx)[0].tolist(), np.asarray(w)[0].tolist()))
        assert got == pytest.approx(expect, rel=1e-6)


# ------------------------------------------------------------------ layer

@pytest.mark.parametrize("ranks", [1, 4, 8])
def test_ep_layer_matches_reference(ref, ranks):
    """Each rank's own tokens through the layer on ``ranks`` of 8 EP ranks
    equal the reference given the same share of experts, in float32."""
    dense, experts = _weights(3)
    h = _tokens(4, ranks)
    h_out, routed, chosen, counters = _program(dense, experts, h, _cfg(), ranks)
    r_chosen, shared, r_routed = _reference(ref, dense, experts, h,
                                            range(ranks * E // EP))
    np.testing.assert_array_equal(_sets(chosen), _sets(r_chosen))
    np.testing.assert_allclose(routed, r_routed, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h_out, np.asarray(h) + shared + r_routed, rtol=2e-4, atol=2e-5)
    c = dict(zip(M.COUNTERS, counters[:, 0, :len(M.COUNTERS)].sum(0)))
    assert c["drops"] == 0 and c["done"] == ranks and c["delivered"] == c["emitted"] > 0
    held = np.isin(r_chosen, np.arange(ranks * E // EP)).sum()
    assert c["emitted"] == held


def test_shares_sum_to_the_uncut_layer(ref):
    """The routed parts that the 8 shares of the layer give, each alone, sum
    with the shared expert counted once to the uncut reference layer; the
    program on 8 ranks (every share present) and on 2 (the first two) gives
    the sum of the shares it holds."""
    dense, experts = _weights(5)
    h = _tokens(6, 8)
    e_loc = E // EP
    _, shared, uncut = _reference(ref, dense, experts, h, range(E))
    parts = [_reference(ref, dense, experts, h, range(s * e_loc, (s + 1) * e_loc))[2]
             for s in range(EP)]
    np.testing.assert_allclose(shared + sum(parts), shared + uncut, rtol=1e-5, atol=1e-6)
    _, routed8, _, _ = _program(dense, experts, h, _cfg(), 8)
    np.testing.assert_allclose(routed8, sum(parts), rtol=2e-4, atol=2e-5)
    h2 = h[:2 * T]
    _, routed2, _, _ = _program(dense, experts, h2, _cfg(), 2)
    first_two = _reference(ref, dense, experts, h2, range(2 * e_loc))[2]
    np.testing.assert_allclose(routed2, first_two, rtol=2e-4, atol=2e-5)


def test_every_token_to_one_expert_drops_nothing(ref):
    """A bias that sends every token to expert 5: each rank has four times
    the mean per-peer load for its owner, which takes more than one
    forwarding round a trip under retain, and nothing drops."""
    dense, experts = _weights(7)
    dense["bias"] = dense["bias"].at[5].set(10.0)
    h = _tokens(8, 8)
    cfg = _cfg(capacity=4096, peer=128)
    h_out, routed, chosen, counters = _program(dense, experts, h, cfg, 8)
    assert np.all((chosen == 5).any(axis=1))
    c = dict(zip(M.COUNTERS, counters[:, 0, :len(M.COUNTERS)].T))
    assert c["drops"].sum() == 0 and c["done"].all()
    assert c["delivered"].sum() == c["emitted"].sum() == 8 * T * 8
    assert c["rounds_dispatch"][0] > 1 and c["rounds_return"][0] > 1
    _, shared, r_routed = _reference(ref, dense, experts, h, range(E))
    np.testing.assert_allclose(routed, r_routed, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h_out, np.asarray(h) + shared + r_routed, rtol=2e-4, atol=2e-5)


def test_grouped_glu_matches_ragged_dot():
    """The grouped SwiGLU (megablox ``gmm``) equals the same computed with
    ``lax.ragged_dot`` on every row inside a group, in float32."""
    k = jax.random.split(jax.random.PRNGKey(9), 4)
    m, e = 512, 4
    x = jax.random.normal(k[0], (m, D), jnp.float32)
    wi, wg = (jax.random.normal(kk, (e, D, F), jnp.float32) * D ** -0.5 for kk in k[1:3])
    wo = jax.random.normal(k[3], (e, F, D), jnp.float32) * F ** -0.5
    sizes = jnp.asarray([200, 0, 61, 150], jnp.int32)
    got = M.grouped_glu(x, sizes, wi, wg, wo)

    def rd(a, w):
        return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=jnp.float32)

    want = rd(jax.nn.silu(rd(x, wg)) * rd(x, wi), wo)
    n = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n], rtol=1e-5, atol=1e-5)
