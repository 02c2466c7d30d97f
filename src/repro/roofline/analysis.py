"""Roofline-term derivation from a compiled dry-run artifact.

  compute    = HLO_FLOPs   / (chips × peak_FLOP/s)
  memory     = HLO_bytes   / (chips × HBM_bw)
  collective = collective_bytes / (chips × link_bw)

``cost_analysis`` supplies FLOPs and bytes-accessed; collective bytes are
NOT in cost_analysis, so we parse the compiled (post-SPMD) HLO text and sum
the operand bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute / ragged-all-to-all.  Hardware constants
are the TPU v5e targets given in the assignment.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import numpy as np

HW = {
    "peak_flops": 197e12,   # bf16 FLOP/s per chip
    "hbm_bw": 819e9,        # B/s per chip
    "link_bw": 50e9,        # B/s per ICI link (the fast, intra-node axis)
    "dcn_bw": 25e9,         # B/s per chip across the slow inter-node fabric
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "ragged-all-to-all",
)

# e.g.  %x = bf16[16,512,128]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*((?:[a-z0-9]+\[[0-9,]*\][^\s]*\s*,?\s*)+)\s*"
    r"(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_SHLO_OPS = {
    "stablehlo.all_to_all": "all-to-all",
    "stablehlo.all_reduce": "all-reduce",
    "stablehlo.all_gather": "all-gather",
    "stablehlo.reduce_scatter": "reduce-scatter",
    "stablehlo.collective_permute": "collective-permute",
    "ragged_all_to_all": "ragged-all-to-all",
}
_TENSOR_RE = re.compile(r"tensor<([0-9x]*?)x?(f64|f32|bf16|f16|i64|i32|i16|i8|ui32|i1)>")
_SHLO_DTYPES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "i64": 8, "i32": 4,
                "ui32": 4, "i16": 2, "i8": 1, "i1": 1}


# replica groups / source-target pairs, both dialects:
#   StableHLO:  replica_groups = dense<[[0, 1, 2, 3], [4, 5, 6, 7]]> : ...
#               source_target_pairs = dense<[[0, 1], [1, 2]]> : ...
#   post-SPMD:  replica_groups={{0,1,2,3},{4,5,6,7}}
_SHLO_GROUPS_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)\s*=\s*dense<\s*\[(.*?)\]\s*>"
)
_HLO_GROUPS_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)=\{(\{[0-9,\s]*\}(?:\s*,\s*\{[0-9,\s]*\})*)\}"
)
_GROUP_RE = re.compile(r"[\[{]([0-9,\s]*)[\]}]")


def _parse_groups(line: str):
    """The line's replica groups (or permute pairs) as a tuple of int tuples;
    ``None`` when the op carries neither attribute."""
    m = _SHLO_GROUPS_RE.search(line) or _HLO_GROUPS_RE.search(line)
    if not m:
        return None
    groups = []
    for g in _GROUP_RE.findall(m.group(1)):
        ids = tuple(int(t) for t in g.replace(",", " ").split())
        if ids:
            groups.append(ids)
    return tuple(groups) or None


def _rank_digits(rank: int, level_sizes) -> tuple:
    """Decompose a lexicographic (slowest-major) rank id into its per-tier
    digits.  The slowest tier's extent is not needed — its digit is whatever
    remains above the faster strides — so ``level_sizes[0]`` may be 0."""
    ds = []
    for a in reversed(tuple(level_sizes)[1:]):
        ds.append(rank % a)
        rank //= a
    ds.append(rank)
    return tuple(reversed(ds))


def group_tier(groups, level_sizes):
    """Classify one collective's participant groups against a lexicographic
    N-level mesh (``level_sizes`` ranks per tier, slowest first).

    Returns the tier index (0 = slowest) when every group varies in exactly
    ONE tier digit — the pure single-fabric pattern of a hierarchical-
    exchange stage — or ``"cross"`` (some group spans several tiers, e.g. a
    flat all_to_all routed over the whole mesh, or a global psum),
    ``"local"`` (singleton groups), or ``"unknown"`` (no group info)."""
    if not groups:
        return "unknown"
    tiers = set()
    for g in groups:
        if len(g) <= 1:
            continue
        digits = [_rank_digits(i, level_sizes) for i in g]
        varying = {
            t
            for t in range(len(level_sizes))
            if len({d[t] for d in digits}) > 1
        }
        tiers.add(next(iter(varying)) if len(varying) == 1 else "cross")
    if not tiers:
        return "local"
    return tiers.pop() if len(tiers) == 1 else "cross"


def group_axis(groups, fast_size: int) -> str:
    """2-level wrapper over :func:`group_tier` for node-major ``(slow, fast)``
    meshes with ``fast_size`` ranks per node.

    Returns ``"fast"`` (every group stays inside one node), ``"slow"`` (every
    group holds one lane across nodes — the pure inter-node pattern),
    ``"cross"`` (groups span nodes AND lanes), ``"local"`` (singleton
    groups), or ``"unknown"`` (no group info)."""
    tier = group_tier(groups, (0, fast_size))
    return {0: "slow", 1: "fast"}.get(tier, tier)


def collective_ops(hlo_text: str, *, with_groups: bool = False) -> list:
    """Per-op collective inventory in program order.  Handles both post-SPMD
    HLO and StableHLO.  This is the basis of the collective-budget regression
    tests (one payload collective + one count collective per forwarding
    round; two of each for the hierarchical two-stage exchange).

    Returns ``[(kind, result_bytes), ...]``, or with ``with_groups=True``
    ``[(kind, result_bytes, groups), ...]`` where ``groups`` is the op's
    replica groups (permute source-target pairs for collective-permute) as a
    tuple of int tuples — the input of :func:`group_axis` / the per-axis
    accounting of :func:`per_axis_collective_bytes`."""
    ops = []
    if "stablehlo." in hlo_text:
        for line in hlo_text.splitlines():
            kind = next((v for k, v in _SHLO_OPS.items() if k in line), None)
            if kind is None or "->" not in line:
                continue
            result = line.split("->", 1)[1]
            nbytes = 0
            for dims, dt in _TENSOR_RE.findall(result):
                n = 1
                for d in dims.split("x"):
                    if d:
                        n *= int(d)
                nbytes += n * _SHLO_DTYPES.get(dt, 4)
            ops.append(
                (kind, nbytes, _parse_groups(line)) if with_groups else (kind, nbytes)
            )
        return ops
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        if "-done(" in line and kind + "-done" in line:
            continue  # counted at -start
        shapes = _SHAPE_RE.findall(m.group(1))
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        ops.append(
            (kind, nbytes, _parse_groups(line)) if with_groups else (kind, nbytes)
        )
    return ops


def per_axis_collective_bytes(hlo_text: str, fast_size: int) -> Dict[str, int]:
    """Collective result bytes bucketed by which mesh fabric they traverse
    (see :func:`group_axis`): ``fast`` stays on the intra-node links, ``slow``
    is the pure inter-node pattern, ``cross`` spans both (flat collectives
    routed over the whole 2-D mesh pay slow-fabric cost too)."""
    out: Dict[str, int] = {
        "fast": 0, "slow": 0, "cross": 0, "local": 0, "unknown": 0
    }
    for _kind, nbytes, groups in collective_ops(hlo_text, with_groups=True):
        out[group_axis(groups, fast_size)] += nbytes
    return out


def per_tier_collective_bytes(
    hlo_text: str, level_sizes, *, min_bytes: int = 0
) -> Dict:
    """Collective result bytes bucketed by mesh tier (see :func:`group_tier`):
    integer keys ``0 … L-1`` (0 = slowest fabric) for single-tier patterns,
    plus ``"cross"`` / ``"local"`` / ``"unknown"``.

    ``min_bytes`` filters the inventory to payload-sized ops — the natural
    form of "zero slow-fabric payload bytes" assertions, which must ignore
    the tiny count/termination control plane."""
    out: Dict = {t: 0 for t in range(len(tuple(level_sizes)))}
    out.update({"cross": 0, "local": 0, "unknown": 0})
    for _kind, nbytes, groups in collective_ops(hlo_text, with_groups=True):
        if nbytes >= min_bytes:
            out[group_tier(groups, level_sizes)] += nbytes
    return out


def tier_bytes_model(level_sizes, level_capacities, item_bytes: int) -> list:
    """Model: bulk payload bytes ONE rank pushes across each mesh tier per
    hierarchical forwarding round, slowest tier first.

    Stage ``l`` ships ``level_sizes[l]`` padded segments of
    ``level_capacities[l]`` rows over tier ``l``'s fabric; the
    ``level_sizes[l] - 1`` segments addressed off-group actually cross it
    (extent-1 tiers skip their stage: 0 bytes)."""
    return [
        float((a - 1) * s * item_bytes) if a > 1 else 0.0
        for a, s in zip(level_sizes, level_capacities)
    ]


def slow_axis_bytes_model(
    exchange: str,
    *,
    num_ranks: int,
    fast_size: int,
    item_bytes: int,
    peer_capacity: int = 0,
    node_capacity: int = 0,
    n_items: int = 0,
) -> float:
    """Model: bulk payload bytes ONE rank pushes across the slow (inter-node)
    fabric per forwarding round.

    * flat ``padded`` routed over the joint 2-D axis: R per-rank slots of
      ``peer_capacity`` rows; the ``R - fast_size`` slots addressed to remote
      nodes cross the slow fabric, each padded per RANK.
    * ``hierarchical``: only stage B crosses — ``num_nodes - 1`` per-NODE
      segments of ``node_capacity`` rows.  At equal burst tolerance K per
      destination (``peer_capacity == node_capacity == K``) the padded rows
      crossing the slow fabric shrink from (R - F)·K to (N - 1)·K — exactly
      R/N×, since R - F = F·(N - 1).
    * ``ragged``: data-dependent — exactly the useful bytes headed off-node
      (uniform-destination estimate from ``n_items``).
    """
    num_nodes = num_ranks // fast_size
    if exchange in ("padded", "flat"):
        return float((num_ranks - fast_size) * peer_capacity * item_bytes)
    if exchange == "hierarchical":
        return float((num_nodes - 1) * node_capacity * item_bytes)
    if exchange == "ragged":
        return float(n_items * item_bytes) * (num_ranks - fast_size) / num_ranks
    raise ValueError(f"no slow-axis model for exchange {exchange!r}")


def padded_wire_rows(level_sizes, level_capacities) -> list:
    """Padded send rows ONE rank puts on the wire per round, per tier: stage
    ``l`` always ships ``level_sizes[l]`` segments of ``level_capacities[l]``
    rows regardless of demand (that is the price of the padded format);
    extent-1 tiers skip their stage entirely.  A flat padded exchange is the
    1-tier instance ``(num_ranks,), (peer_capacity,)``."""
    return [
        a * s if a > 1 else 0
        for a, s in zip(tuple(level_sizes), tuple(level_capacities))
    ]


def occupancy_waste_model(
    level_sizes,
    level_capacities,
    item_bytes: int,
    *,
    useful_rows=None,
    rounds: int = 1,
    num_ranks: int = 1,
) -> Dict:
    """The telemetry subsystem's cost side: padded wire bytes vs useful bytes
    per tier, the quantity the capacity controller trades against drops.

    ``wire_B`` covers ``num_ranks`` senders over ``rounds`` rounds (each rank
    pays :func:`padded_wire_rows` per round regardless of demand).  MATCH THE
    POPULATIONS when passing ``useful_rows``: ``telemetry.summarize(...)
    ["sent_rows"]`` is summed over every rank and recorded round, so pass
    ``num_ranks=R`` and ``rounds=window_filled`` alongside it — the defaults
    (1, 1) are the single-rank single-round static view, and mixing a
    rank-summed ``useful_rows`` into them would inflate ``useful_B`` by R
    (waste_frac could even go negative).  Pass ``useful_rows=None`` for the
    pure static-wire view.  Returns per-tier ``wire_B`` (always paid),
    ``useful_B`` and ``waste_frac`` (padding fraction of the wire), plus
    totals — the "modeled padded bytes" gated by the autotune benchmark: a
    tuned config must never pay more wire than the static worst-case config
    it replaces.
    """
    rows = padded_wire_rows(level_sizes, level_capacities)
    wire = [float(r * rounds * num_ranks * item_bytes) for r in rows]
    out = {"tiers": []}
    for l, w in enumerate(wire):
        useful = (
            float(useful_rows[l]) * item_bytes if useful_rows is not None else None
        )
        out["tiers"].append(
            {
                "wire_B": w,
                "useful_B": useful,
                "waste_frac": (
                    1.0 - useful / w if useful is not None and w else None
                ),
            }
        )
    out["wire_B"] = sum(wire)
    if useful_rows is not None:
        total_useful = float(sum(useful_rows)) * item_bytes
        out["useful_B"] = total_useful
        out["waste_frac"] = (
            1.0 - total_useful / out["wire_B"] if out["wire_B"] else 0.0
        )
    return out


def spill_drain_model(backlog_rows: int, allowance_rows_per_round: int) -> Dict:
    """Model: bounded-delay drain of a spill-and-retry backlog (the lossless
    law's analytical half, gated by the chaos benchmark).

    Under ``overflow="retain"`` a clamp never loses a row — it re-queues it
    at the FRONT of the carry (FIFO oldest-first), so a backlog of
    ``backlog_rows`` rows contending for one destination drains at
    ``allowance_rows_per_round`` rows per round (the per-destination clamp
    budget — ``peer_capacity`` flat, the stage's segment capacity per tier
    hierarchically).  Every budget is ≥ 1 row, so the oldest row always
    ships within ``ceil(backlog / allowance)`` rounds:

        rounds = age_bound = ceil(backlog_rows / allowance_rows_per_round)

    The chaos harness asserts the measured ``age_max`` never exceeds this
    bound (+ the emission span, since the backlog builds over the scenario's
    emitting rounds rather than all at once)."""
    if allowance_rows_per_round < 1:
        raise ValueError(
            "allowance must be >= 1 row/round — every clamp budget admits at "
            f"least one row (got {allowance_rows_per_round})"
        )
    rounds = -(-int(backlog_rows) // int(allowance_rows_per_round))
    return {"rounds": rounds, "age_bound": rounds}


def goodput_model(
    offered_rows_per_round: int,
    drain_rows_per_round: int,
    *,
    rounds: int = 1,
    item_bytes: int = 1,
) -> Dict:
    """Model: wire goodput under sustained overload, open vs credit flow
    (the backpressure law's analytical half, gated by the chaos benchmark).

    ``offered_rows_per_round`` rows per round contend for a receiver that
    can consume (drain) ``drain_rows_per_round``.  With ``flow="open"`` the
    senders ship the full offered load every round; once the receiver's
    bounded queue saturates it admits only what it drains, so every other
    shipped row is wire spent on a row the receiver throws away:

        goodput_open  →  min(1, drain / offered)

    With ``flow="credit"`` senders ship only rows the receiver's advertised
    free space admits — a shipped row is an admitted row by construction:

        goodput_credit = 1.0

    at the price of the excess being HELD at the source through the retain
    spill path (``held_rows``), draining after the overload subsides.  The
    chaos gate asserts the measured goodputs respect this ordering on every
    overload scenario: credit ≥ open, with open below 0.7 where the
    scenario offers ≥ 1.43× the drain rate.

    Returns ``{"open": {wire_B, admitted_B, wasted_B, goodput},
    "credit": {wire_B, admitted_B, wasted_B, goodput, held_rows},
    "goodput_gain"}`` — totals over ``rounds`` rounds.
    """
    if drain_rows_per_round < 1:
        raise ValueError(
            "drain must be >= 1 row/round — every clamp/credit budget admits "
            f"at least one row (got {drain_rows_per_round})"
        )
    offered = float(offered_rows_per_round) * rounds
    admitted = float(min(offered_rows_per_round, drain_rows_per_round)) * rounds
    open_flow = {
        "wire_B": offered * item_bytes,
        "admitted_B": admitted * item_bytes,
        "wasted_B": (offered - admitted) * item_bytes,
        "goodput": admitted / offered if offered else 1.0,
    }
    credit_flow = {
        "wire_B": admitted * item_bytes,
        "admitted_B": admitted * item_bytes,
        "wasted_B": 0.0,
        "goodput": 1.0,
        "held_rows": offered - admitted,
    }
    return {
        "open": open_flow,
        "credit": credit_flow,
        "goodput_gain": credit_flow["goodput"] - open_flow["goodput"],
    }


def marshal_cost_model(
    marshal: str,
    *,
    capacity: int,
    item_bytes: int,
    send_rows: int,
    num_ranks: int = 0,
) -> Dict[str, float]:
    """Model: send-side marshal work ONE rank does per forwarding round —
    the §6.1 "all of [sort/marshal] are trivially cheap" claim, made
    checkable next to the collective byte models.

    Both modes obey the marshal law — exactly ONE pass over the PACKED
    PAYLOAD pre-collective (read C rows, write ``send_rows`` padded rows);
    what ``marshal="scatter"`` deletes is everything the sort did to the KEY
    vector first:

    * ``sort``: key pack (read C dest words, write C keys) + the
      compare-exchange sort — modeled as ``ceil(log2 C)`` read+write passes
      over the C-word key vector (XLA's bitonic/merge family) — then the one
      composed payload gather.
    * ``scatter``: the counting-sort plan (read C dest words, write C ranks +
      C sanitized dests, accumulate the (R+1)-word histogram) — a single
      O(C) pass, no keys — then the one payload scatter.

    Returns ``{"payload_passes", "payload_bytes", "plan_bytes",
    "total_bytes"}`` (bytes are on-chip traffic, not wire bytes; compare
    against the exchange's collective bytes to see marshal overhead shrink
    from O(C log C) + 2-passes-equivalent to the single-pass floor).
    """
    payload_bytes = float((capacity + send_rows) * item_bytes)
    word = 4.0
    if marshal == "sort":
        log2c = max(1, int(np.ceil(np.log2(max(capacity, 2)))))
        plan = capacity * word * 2  # key pack: read dest, write keys
        plan += log2c * 2 * capacity * word  # sort passes over the keys
    elif marshal == "scatter":
        plan = capacity * word  # read dest
        plan += 2 * capacity * word  # write d_clean + in-bucket rank
        plan += (num_ranks + 1) * word  # histogram accumulator
    else:
        raise ValueError(f"no marshal model for {marshal!r}")
    return {
        "payload_passes": 1.0,  # the marshal law, either mode
        "payload_bytes": payload_bytes,
        "plan_bytes": float(plan),
        "total_bytes": payload_bytes + float(plan),
    }


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum of result-shape bytes per collective kind; handles both post-SPMD
    HLO (``all-gather(...)``) and StableHLO (``"stablehlo.all_gather"``)."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for kind, nbytes in collective_ops(hlo_text):
        out[kind] += nbytes
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float
    bytes_accessed: float
    coll_bytes: float
    chips: int
    coll_breakdown: Dict[str, int]
    bytes_per_chip: Optional[float] = None

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * HW["peak_flops"])

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / (self.chips * HW["hbm_bw"])

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (self.chips * HW["link_bw"])

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self):
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "coll_bytes": self.coll_bytes,
            "chips": self.chips,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "dominant": self.dominant,
            "coll_breakdown": self.coll_breakdown,
            "bytes_per_chip": self.bytes_per_chip,
        }


def analyze_lowered(lowered, compiled, chips: int) -> RooflineTerms:
    """Derive the three terms from (lowered, compiled) jit artifacts.

    cost_analysis FLOPs/bytes are per-device on SPMD modules (XLA reports
    the per-partition HLO); we convert to whole-job numbers by × chips.
    """
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0)) * chips
    bytes_accessed = float(cost.get("bytes accessed", 0.0)) * chips
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes(hlo)
    coll_total = float(sum(coll.values())) * chips

    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = float(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
        )
    except Exception:
        pass
    return RooflineTerms(
        flops=flops,
        bytes_accessed=bytes_accessed,
        coll_bytes=coll_total,
        chips=chips,
        coll_breakdown=coll,
        bytes_per_chip=mem,
    )


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE), D = processed tokens.

    For prefill/decode the factor is 2·N per token (forward only)."""
    import jax

    from repro.models.api import build_model

    model = build_model(cfg)
    n_params = model.param_count()
    if cfg.kind == "moe":
        # active params: replace expert count by top_k in the FFN share
        e, k = cfg.num_experts, cfg.top_k
        ffn = 3 * cfg.d_model * cfg.d_ff * e * cfg.num_layers
        active_ffn = ffn * k / e
        n_active = n_params - ffn + active_ffn
    else:
        n_active = n_params
    tokens = shape.global_batch * (shape.seq_len if shape.step != "decode" else 1)
    factor = 6.0 if shape.step == "train" else 2.0
    return factor * n_active * tokens
