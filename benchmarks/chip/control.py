"""Readings that set a cell's limits: the program's and the control's
numbers on many seeds, in one process on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it runs a window of ``--seconds`` as ``run.py`` does, then
prints one JSON line with the program's checks against the reference and
the control's checks (the reference put in the program's place with a
stated guarantee broken).  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache(harness.ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.resolve(harness.load_bench(), args.workload)
    devices = jax.devices()[: cell.chips]
    module = harness.load_module(cell.config_module)
    for seed in (int(s) for s in args.seeds.split(",")):
        deploy = module.Deployment(cell.spec, cell.mix, devices)
        jobs = harness.jobs(cell, seed)
        deploy.warm(jobs)
        records, window_s = harness.drive(
            deploy, jobs, args.seconds, unit=cell.mix.get("window_unit_jobs", 1)
        )
        deploy.release()
        checks, failed = deploy.check(records)
        control = deploy.control(records)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "jobs": len(records), "window_s": window_s, "failed": failed,
            "program": {n: v for n, v, _ in checks},
            "control": {n: v for n, v, _ in control},
            "limits": {n: lim for n, _, lim in checks},
        }), flush=True)
        del deploy
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
