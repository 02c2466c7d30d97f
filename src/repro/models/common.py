"""Shared model machinery: config, params, sharding rules, norms, MLPs.

Everything is functional: a model is (init_fn, apply_fn) over an explicit
params pytree of jnp arrays.  Sharding is expressed as PartitionSpec trees
produced from the *same* path rules used by both init and the dry-run, so
``jax.jit(..., in_shardings=...)`` sees a consistent layout:

  * "model"-axis tensor parallelism: attention heads, FFN hidden, vocab;
  * optional FSDP: the non-TP dim of every large parameter is additionally
    sharded over "data" (needed to fit the 72B configs; gathered per-layer by
    XLA at use);
  * MoE experts: sharded over "model" for expert parallelism (EP).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    kind: str                     # dense | moe | ssm | hybrid | encdec
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rope_kind: str = "full"       # full | mrope
    act: str = "silu"             # silu (SwiGLU) | gelu (GeGLU)
    tie_embeddings: bool = False
    window: int = 0               # local-attention window size
    pattern: Tuple[str, ...] = ("global",)  # repeating per-layer block kinds
    num_experts: int = 0
    top_k: int = 0
    moe_dispatch: str = "rafi_ep"  # rafi_ep (paper technique) | dense_tp
    capacity_factor: float = 1.25
    encoder_layers: int = 0
    frontend: str = "none"        # none | vision | audio (stub embeddings)
    scale_embed: bool = False     # gemma-style sqrt(d_model) embedding scale
    dtype: str = "bfloat16"
    fsdp: bool = False            # shard big params over data axis too
    remat: bool = True            # activation checkpoint each layer
    scan_unroll: bool = False     # fully unroll layer scans (cost probes)
    blocked_attention: bool = True  # online-softmax KV-blocked attention
                                    # (False = paper-faithful naive baseline)
    microbatches: int = 1         # gradient-accumulation splits of the batch
    dp_over_model: bool = False   # TP width policy: fold the model axis into
                                  # data parallelism (right call when d_model
                                  # is too small to amortize TP collectives)
    # notes for DESIGN/EXPERIMENTS
    source: str = ""

    @property
    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def layer_kind(self, i: int) -> str:
        return self.pattern[i % len(self.pattern)]


# --------------------------------------------------------------- parameters

def truncated_normal(key, shape, dtype, scale):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


class ParamDef:
    """Declarative parameter: shape + init scale + partition spec."""

    def __init__(self, shape, spec, *, scale=None, init="normal"):
        self.shape = tuple(int(s) for s in shape)
        self.spec = spec
        self.scale = scale
        self.init = init

    def make(self, key, dtype):
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        scale = self.scale if self.scale is not None else 1.0 / np.sqrt(self.shape[0])
        return truncated_normal(key, self.shape, dtype, scale)


def _maybe_fsdp(spec: P, cfg: ModelConfig) -> P:
    """Apply the config's parallelism policy to a parameter spec:
    dp_over_model strips the model axis (params replicated, both mesh axes
    become data parallel); fsdp additionally shards the first free dim over
    "data" (ZeRO-3 style)."""
    if cfg.dp_over_model:
        spec = P(*[None if s == MODEL_AXIS else s for s in spec])
    if not cfg.fsdp:
        return spec
    parts = list(spec) + [None] * 8
    for i, s in enumerate(parts[: len(spec) if len(spec) else 1]):
        if s is None:
            parts[i] = DATA_AXIS
            return P(*parts[: len(spec)])
    return spec


def batch_axes(cfg: Optional[ModelConfig] = None):
    """Mesh axes carrying the batch dim of activations."""
    if cfg is not None and cfg.dp_over_model:
        return (DATA_AXIS, MODEL_AXIS)
    return DATA_AXIS


def init_params(defs: Dict[str, Any], key, dtype) -> Dict[str, Any]:
    """Materialize a (possibly nested) dict of ParamDefs."""
    flat = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(flat))
    it = iter(range(len(flat)))

    def make(d):
        return d.make(keys[next(it)], dtype)

    return jax.tree.map(make, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def param_specs(defs: Dict[str, Any], cfg: ModelConfig):
    """PartitionSpec tree matching init_params' output."""
    return jax.tree.map(
        lambda d: _maybe_fsdp(d.spec, cfg),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def abstract_params(defs: Dict[str, Any], dtype):
    """ShapeDtypeStruct tree (no allocation) — the dry-run path."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


# ------------------------------------------------------------------- layers

def rmsnorm(x, gamma, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def dense(x, w, b=None):
    y = x @ w
    if b is not None:
        y = y + b
    return y


def glu_mlp(x, wi, wg, wo, act: str, acc=None):
    """Gated MLP (SwiGLU/GeGLU): down( act(gate(x)) * up(x) ).  With ``acc``
    (e.g. float32) every product accumulates in that dtype, the hidden is
    cast back to ``x``'s dtype for the down product, and the result is in
    ``acc``."""
    mm = functools.partial(jnp.matmul, preferred_element_type=acc)
    g = mm(x, wg)
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    h = a * mm(x, wi)
    return mm(h if acc is None else h.astype(x.dtype), wo)


def mlp_defs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, ParamDef]:
    f = d_ff or cfg.d_ff
    d = cfg.d_model
    return {
        "wi": ParamDef((d, f), P(None, MODEL_AXIS)),
        "wg": ParamDef((d, f), P(None, MODEL_AXIS)),
        "wo": ParamDef((f, d), P(MODEL_AXIS, None), scale=1.0 / np.sqrt(f)),
    }


def shard(x, *spec):
    """with_sharding_constraint shortcut (no-op outside jit-with-mesh)."""
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):
        return x


def cross_entropy_loss(logits, labels, *, vocab: int):
    """Mean token CE in f32 (logits may be bf16)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
