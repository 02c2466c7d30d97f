"""One run of one cell of the chip benchmark (``BENCHMARK.json``).

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: load the cell by name; fail unless JAX sees a TPU with as many
chips as the cell asks for; turn on the compile cache at its fixed place in
the checkout; build the cell's deployment on the device; draw the window's
jobs from the seed and warm its programs, staging the jobs; run them back to back for ``--seconds``; compare every job of the window
with the plain reference; print the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, from ``metrics/<name>.py``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit.  The checks are also the last lines of
standard error.  With ``--trace 1`` the profiler records the first
``TRACE_SECONDS`` of the window (at least one job); its device time is
split by ``rafi.*`` scope for the readers, once the window has closed.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

TRACE_SECONDS = 5.0


def _log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class _Tracer:
    """Profiles the window from its start until the first job that ends
    ``TRACE_SECONDS`` or more into it."""

    def __init__(self, jax):
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.records = []
        self.window_s = None
        self._span = None

    def start(self):
        self.jax.profiler.start_trace(self.dir)
        self._span = self.jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self._t = time.perf_counter()

    def on_job(self, rec):
        if self.window_s is not None:
            return
        self.records.append(rec)
        if rec["t1"] >= TRACE_SECONDS:
            self.stop()

    def stop(self):
        if self.window_s is None:
            self.window_s = time.perf_counter() - self._t
            self._span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()

    def summary(self):
        import tracefile

        try:
            return tracefile.summarize(tracefile.load(tracefile.find(self.dir)), self.window_s)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def main(argv=None):
    """Run one cell on the chip; returns the exit code."""
    args = _parse(argv)
    cell = harness.resolve(harness.load_bench(), args.workload)

    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"no TPU: JAX's first device is {devices[0].platform}")
        return 2
    if len(devices) < cell.chips:
        _log(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
        return 2
    import peaks

    peaks.lookup(devices[0].device_kind)

    cache = enable_compile_cache(harness.ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _log(f"init: compile cache {cache}")
    return execute(cell, devices[: cell.chips], args)


def execute(cell, devices, args, *, on_chip=True):
    """Everything of a run after the look for a chip: build, warm, window,
    comparison, result.  The tests call it on the CPU (``on_chip=False``)
    with a cell cut to a size a test can hold."""
    import jax

    # JAX's own start-up (the runtime and its chips) is printed apart: it
    # is the same for every program and varies by some seconds from run
    # to run, which would hide any change in the set-up that follows
    t0 = time.perf_counter()
    module = harness.load_module(cell.config_module)
    deploy = module.Deployment(cell.spec, cell.mix, devices)
    t_build = time.perf_counter()
    jobs = harness.jobs(cell, args.seed)
    deploy.warm(jobs)
    setup_s = time.perf_counter() - t0
    _log(f"setup: init_s={t0 - T0:.3f} build_s={t_build - t0:.3f} "
         f"warm_s={t0 + setup_s - t_build:.3f} setup_s={setup_s:.3f}")

    tracer = _Tracer(jax) if args.trace else None
    if tracer:
        tracer.start()
    try:
        records, window_s = harness.drive(
            deploy, jobs, args.seconds, unit=cell.mix.get("window_unit_jobs", 1),
            on_job=tracer.on_job if tracer else None,
        )
    finally:
        if tracer:
            tracer.stop()
    _log(f"window: jobs={len(records)} window_s={window_s:.3f}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    deploy.release()
    gc.collect()
    t_check = time.perf_counter()
    checks, failed = deploy.check(records)
    _log(f"reference: check_s={time.perf_counter() - t_check:.3f}")

    run = harness.Run(cell, setup_s, window_s, records)
    dev = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(peak),
    }
    if tracer:
        run.trace, run.traced = tracer.summary(), tracer.records
        if run.trace is None and on_chip:
            _log("the trace holds no device operation")
            return 3
    if run.trace is not None:
        dev.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        _log("scopes: " + json.dumps({
            "busy_s": run.trace.busy_s,
            "ms": {n: t * 1e3 for n, t in run.trace.scopes.items()},
        }))
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = harness.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct = all(value <= limit for _, value, limit in checks)
    result = {
        "correct": correct,
        "attempted": len(records),
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if run.trace is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps],
        }
    result["checks"] = {name: {"value": value, "limit": limit} for name, value, limit in checks}
    for name, value, limit in checks:
        _log(f"check {name} {value} limit {limit}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
