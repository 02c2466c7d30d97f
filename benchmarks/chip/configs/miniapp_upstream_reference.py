"""Plain replay of the upstream miniApp's schedule (``miniApp/miniApp.cu``).

Independent of the program: numpy, one burst at a time.  Rank ``s`` seeds
rays ``{srcRank: s, srcID: tid}`` for ``tid < n_s`` and emits a ray to
``(123 + 13·17·23·tid) mod 2R`` when that is a rank.  A round delivers every
emitted ray to its rank; the rays a rank receives form its incoming queue
in order of source rank, then of the order their source emitted them (the
stable marshal the deployment states).  Processing round ``k`` sends the ray
at incoming position ``tid`` on to ``(1234 + (k+1)·(13 + 17·(srcID +
23·tid))) mod 2R`` when ``tid > 1`` and that is a rank; the burst ends when
a round delivers nothing.

For each delivery the replay folds ``mix(srcRank, srcID, round, rank,
tid)`` into a uint32 sum, so a burst's answer is ``(rounds, deliveries,
digest)``: a ray lost, duplicated, altered or placed elsewhere changes it.

``stable=False`` is the control: each source's rays arrive in reverse, the
one guarantee of order broken.
"""
from __future__ import annotations

import numpy as np

_M = [np.uint32(c) for c in (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1)]
_INT32_MAX = np.int64(2**31 - 1)


def mix(src_rank, src_id, rnd, rank, tid):
    """The per-delivery hash, uint32 with wrap-around (arrays in, array out)."""
    words = np.broadcast_arrays(*(np.asarray(x, np.int64) for x in (src_rank, src_id, rnd, rank, tid)))
    with np.errstate(over="ignore"):
        h = np.zeros(words[0].shape, np.uint32)
        for w, m in zip(words, _M):
            h = h + w.astype(np.uint32) * m
        h = h ^ (h >> np.uint32(15))
        h = h * np.uint32(0x2C1B3C6D)
        h = h ^ (h >> np.uint32(12))
        h = h * np.uint32(0x297A2D39)
        return h ^ (h >> np.uint32(15))


def _hash(value):
    # the program computes in int32: the schedule must not overflow it
    if value.size and value.max() > _INT32_MAX:
        raise OverflowError("miniApp hash leaves int32")
    return value


def burst(sizes, *, stable=True, max_rounds=1 << 16):
    """``(rounds, deliveries, digest)`` of one burst seeded with
    ``sizes[s]`` rays on rank ``s``."""
    R = len(sizes)
    out = []  # per source rank: (src_rank, src_id, dest) in emission order
    for s, n in enumerate(sizes):
        tid = np.arange(int(n), dtype=np.int64)
        dst = _hash(123 + 13 * 17 * 23 * tid) % (2 * R)
        keep = dst < R
        out.append((np.full(int(keep.sum()), s, np.int64), tid[keep], dst[keep]))
    rounds = deliveries = 0
    digest = 0
    while True:
        incoming = []
        for d in range(R):
            parts = []
            for src_rank, src_id, dst in out:
                sel = dst == d
                part = (src_rank[sel], src_id[sel])
                parts.append(part if stable else (part[0][::-1], part[1][::-1]))
            incoming.append((
                np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]),
            ))
        if sum(len(r) for r, _ in incoming) == 0 or rounds >= max_rounds:
            return rounds, deliveries, digest
        out = []
        for d, (src_rank, src_id) in enumerate(incoming):
            tid = np.arange(len(src_id), dtype=np.int64)
            deliveries += len(src_id)
            h = mix(src_rank, src_id, rounds, d, tid)
            digest = (digest + int(np.sum(h, dtype=np.uint64))) % (1 << 32)
            dst = _hash((1234 + (rounds + 1) * (13 + 17 * (src_id + 23 * tid)))) % (2 * R)
            keep = (tid > 1) & (dst < R)
            out.append((src_rank[keep], src_id[keep], dst[keep]))
        rounds += 1
