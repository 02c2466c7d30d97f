"""Shared benchmark harness for ``benchmarks/run.py`` (and future drivers).

Everything stateful lives here so sections can be split across files without
forking the row sink: ``ROWS`` / ``CONFIGS`` are the single mutable
collectors every ``emit``/``record_cfg`` call feeds, ``_write_json`` dumps
them with run metadata, and the timing helpers (``_timeit`` one-config
windows, ``_paired_times`` interleaved per-config medians) encode the
methodology the compare gates rely on.  The shared fixture is the paper's
Fig-8 payload: the 44-byte :class:`Ray44` and its 8-way mesh.
"""
import dataclasses
import json
import os
import platform
import sys
import time

# Must run before jax locks the backend on first init (idempotent with
# run.py's own setdefault — whichever module imports first wins).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import work_item

ROWS = []
CONFIGS = {}  # tag -> ForwardConfig fields + mesh shape (JSON provenance)


def record_cfg(tag: str, cfg, mesh=None) -> None:
    """Register a benchmarked ForwardConfig (+ its mesh shape) for the JSON
    dump's provenance block — every BENCH_*.json names the exact configs it
    measured, not just the row names."""
    d = dataclasses.asdict(cfg)
    if mesh is not None:
        d["mesh_shape"] = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    CONFIGS.setdefault(tag, d)


def _git_sha():
    import subprocess

    try:
        out = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
        return out.stdout.strip() or None
    except Exception:
        return None


def _parse_derived(derived: str):
    """'k=v;k2=v2' → dict with floats where they parse."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def emit(name: str, us_per_call: float, derived: str):
    ROWS.append(
        {"name": name, "us_per_call": us_per_call, "derived": _parse_derived(derived)}
    )
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _timeit(fn, *args, warmup=2, iters=5):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6, out


# ----------------------------------------------------------- shared fixture
@dataclasses.dataclass
class Ray44:
    """The paper's Fig-8 payload: a 44-byte ray (11 × f32/i32)."""

    origin: jax.Array
    direction: jax.Array
    tmin: jax.Array
    pixel: jax.Array
    integral: jax.Array
    extra: jax.Array


Ray44 = work_item(Ray44)


def _ray_proto():
    return Ray44(
        origin=jnp.zeros(3), direction=jnp.zeros(3), tmin=jnp.zeros(()),
        pixel=jnp.zeros((), jnp.int32), integral=jnp.zeros(()), extra=jnp.zeros(2),
    )


def _mesh8():
    return compat.make_mesh((8,), ("data",))


def _emit_kernel(cfg, n_emit, cap, ballast_iters=0):
    from repro.core import enqueue, forward_work, make_queue
    from repro.core.forwarding import flatten_axis_names

    def kernel(x):
        me = jax.lax.axis_index(flatten_axis_names(cfg.axis_name))
        q = make_queue(_ray_proto(), cap)
        lane = jnp.arange(n_emit)
        rays = Ray44(
            origin=jnp.ones((n_emit, 3)), direction=jnp.ones((n_emit, 3)),
            tmin=lane.astype(jnp.float32), pixel=lane.astype(jnp.int32),
            integral=jnp.zeros(n_emit), extra=jnp.zeros((n_emit, 2)),
        )
        dest = ((me * 7 + lane * 131) % cfg.num_ranks).astype(jnp.int32)
        q = enqueue(q, rays, dest, jnp.ones(n_emit, bool))
        res = forward_work(q, cfg)
        nq = res[0]
        if cfg.telemetry:
            # add every stats leaf into the output VALUE (no ×0 that XLA
            # could fold away) so the telemetry-on timing pays for the full
            # capture; nothing reads the kernel's value, only its walltime
            telem_sum = sum(jnp.sum(l) for l in jax.tree.leaves(res[-1]))
        else:
            telem_sum = jnp.int32(0)
        if cfg.overflow == "retain":
            # same trick: the age vector keeps the spill compaction live
            telem_sum = telem_sum + jnp.sum(res[2])
        if cfg.flow == "credit":
            # and the returned credit vector keeps the advert/grant plumbing
            # live (credits=None: the uncontended full-capacity assumption)
            telem_sum = telem_sum + jnp.sum(res[3])
        if ballast_iters:
            # app-realistic per-round compute (a ray-march-shaped loop over
            # received payload) folded in through a branch XLA cannot
            # constant-fold — the overlap-law sweep must ballast the round
            # the same way the ckpt gate ballasts the drive (see
            # _ballast_round_fn): a bare round overstates the exchange's
            # relative cost by an order of magnitude
            z = nq.items.tmin[:256, None] * jnp.ones((1, 16)) + 1.0
            z = jax.lax.fori_loop(
                0, ballast_iters, lambda i, v: v * 0.999 + jnp.sin(v) * 1e-3, z
            )
            telem_sum = telem_sum + jnp.where(
                jnp.isnan(jnp.sum(z)), jnp.int32(1), jnp.int32(0)
            )
        # depend on the payload so the exchange isn't DCE'd out of the HLO
        checksum = (
            jnp.sum(nq.items.tmin) + jnp.sum(nq.items.origin) + jnp.sum(nq.items.extra)
        )
        return (
            nq.count[None] + (checksum * 0).astype(jnp.int32)
            + telem_sum.astype(jnp.int32) + x[:1].astype(jnp.int32) * 0
        )

    return kernel


def _paired_times(cfgs, mesh, axes, n_emit, cap, samples, ballast_iters=0,
                  raw=False):
    """Time several configs of one mesh point INTERLEAVED (a, b, a, b, …)
    and report the per-config MEDIAN: on a shared CPU host the load drifts
    on second scales, so timing the variants in separate windows (as
    ``_timeit`` would) swings their ratio by far more than a 5% gate margin
    — interleaving cancels the drift, and the median is robust to the
    scheduler spikes that dominate these ~2 ms programs.  Returns
    ``{name: us}``, or ``({name: us}, {name: samples})`` with ``raw=True``
    for gates that need a per-sample estimator (see ``_pair_ratio``)."""
    fns, x = {}, jnp.arange(8.0)
    for name, cfg in cfgs.items():
        f = jax.jit(
            jax.shard_map(
                _emit_kernel(cfg, n_emit, cap, ballast_iters), mesh=mesh,
                in_specs=P(axes), out_specs=P(axes),
            )
        )
        jax.block_until_ready(f(x))  # compile + warm
        jax.block_until_ready(f(x))
        fns[name] = f
    ts = {name: [] for name in cfgs}
    for _ in range(samples):
        for name in cfgs:
            t0 = time.perf_counter()
            jax.block_until_ready(fns[name](x))
            ts[name].append((time.perf_counter() - t0) * 1e6)
    med = {m: float(np.median(v)) for m, v in ts.items()}
    if raw:
        return med, {m: np.asarray(v) for m, v in ts.items()}
    return med


def _pair_ratio(samples_us, num, den):
    """Median of ADJACENT-PAIR ratios ``num[i] / den[i]`` from one
    interleaved ``_paired_times(raw=True)`` window.  Sample i of both
    variants ran back-to-back, so each pair saw the same instantaneous host
    load and its ratio cancels drift that even the per-variant median
    cannot: when the load ramps mid-window the two medians land on samples
    from DIFFERENT load regimes and their quotient swings by several
    percent, while the pair-ratio median stays put.  This is the estimator
    the tight (≤1.0×) gates quote."""
    return float(np.median(np.asarray(samples_us[num]) / np.asarray(samples_us[den])))


def _write_json(path: str, **extra_meta) -> None:
    """Machine-readable dump of ROWS with run metadata (perf trajectory)."""
    payload = {
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "platform": platform.platform(),
            "git_sha": _git_sha(),
            "argv": sys.argv[1:],
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
            "configs": CONFIGS,
            **extra_meta,
        },
        "rows": ROWS,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {path}")
