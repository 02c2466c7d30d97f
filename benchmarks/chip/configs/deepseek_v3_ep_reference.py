"""The plain reference of DeepSeek-V3's MoE layer, per token, in float32.

From the published description (``deepseek-ai/DeepSeek-V3`` ``config.json``
and its modelling code): RMSNorm; the router's sigmoid scores; the
selection adds ``e_score_correction_bias``, scores each of ``n_group``
groups by the sum of its two best biased scores, keeps the best
``topk_group`` groups and takes the ``top_k`` best biased experts inside
them; the weights are the chosen experts' unbiased scores, normalised to
sum 1 and scaled by ``routed_scaling_factor``; each expert and the shared
expert are SwiGLU MLPs; the layer adds their weighted sum to its input.

Only the experts in ``held`` contribute (the chip's share of an
expert-parallel deployment: what the absent experts would add is left
out, as in the program).  Plain ``jax.numpy`` at ``highest`` matmul
precision; nothing of the program is imported.  MoE mixes no tokens, so
any set of tokens can be recomputed alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm(h, w, eps):
    h = jnp.asarray(h, jnp.float32)
    return h / jnp.sqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps) * w


@jax.jit
def swiglu(x, wi, wg, wo):
    up = jnp.dot(x, wi, precision=HIGHEST)
    gate = jnp.dot(x, wg, precision=HIGHEST)
    return jnp.dot(gate / (1.0 + jnp.exp(-gate)) * up, wo, precision=HIGHEST)


def route(x, w_router, bias, *, n_group, topk_group, top_k, scaling, use_bias=True):
    """``(experts (N, k), weights (N, k), margin (N,))`` for normed tokens
    ``x``.  ``margin`` is how far the selection is from changing: the
    smaller of the gap between the last kept and the first dropped group
    score and the gap between the last chosen and the first unchosen
    biased score inside the kept groups."""
    scores = jax.nn.sigmoid(jnp.dot(x, w_router, precision=HIGHEST))
    biased = scores + bias if use_bias else scores
    n, e = biased.shape
    per = e // n_group
    grouped = jnp.sort(biased.reshape(n, n_group, per), axis=-1)
    gscore = grouped[..., -1] + grouped[..., -2]
    order = jnp.argsort(-gscore, axis=-1)
    kept = jnp.zeros((n, n_group), bool).at[
        jnp.arange(n)[:, None], order[:, :topk_group]].set(True)
    ranked = jnp.sort(gscore, axis=-1)[:, ::-1]
    g_margin = (ranked[:, topk_group - 1] - ranked[:, topk_group]
                if topk_group < n_group else jnp.full((n,), jnp.inf))
    choice = jnp.where(jnp.repeat(kept, per, axis=1), biased, -jnp.inf)
    top = jnp.argsort(-choice, axis=-1)
    experts = top[:, :top_k]
    best = jnp.take_along_axis(choice, top[:, :top_k + 1], axis=1)
    e_margin = best[:, top_k - 1] - best[:, top_k]
    w = jnp.take_along_axis(scores, experts, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scaling
    return experts, w, jnp.minimum(g_margin, e_margin)


def weights_for(x, w_router, experts, *, scaling):
    """Router weights of a given expert choice (the unbiased scores,
    normalised and scaled)."""
    scores = jax.nn.sigmoid(jnp.dot(x, w_router, precision=HIGHEST))
    w = jnp.take_along_axis(scores, experts, axis=1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scaling


def layer(h, p, experts, weights, held, expert_weights, *, eps, cast=None):
    """One MoE layer for tokens ``h`` (N, D) whose choice is ``experts``
    with ``weights`` (N, k): returns ``(shared (N, D), routed (N, D))``, so
    that the layer's output is ``h + shared + routed``.  ``p`` holds
    ``norm``, and ``shared`` as ``(wi, wg, wo)``; ``expert_weights(e)``
    gives expert ``e``'s ``(wi, wg, wo)`` for each ``e`` in ``held``.
    ``cast``, if given, rounds the experts' inputs and weights (a lower
    precision's reading); the reference proper leaves them in float32."""
    x = rms_norm(h, p["norm"], eps)
    if cast is not None:
        x = cast(x)
        swiglu_ = lambda x, *w: swiglu(x, *map(cast, w))  # noqa: E731
    else:
        swiglu_ = swiglu
    shared = swiglu_(x, *p["shared"])
    routed = jnp.zeros_like(shared)
    experts = np.asarray(experts)
    for e in held:
        coef = jnp.sum(jnp.where(jnp.asarray(experts == e), weights, 0.0), axis=-1)
        if np.any(experts == e):
            routed = routed + coef[:, None] * swiglu_(x, *expert_weights(e))
    return shared, routed
