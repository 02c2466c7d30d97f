"""Cells of the benchmark cut to a size a CPU test can hold, and a helper that
drives the rest of a run (everything after the look for a chip)."""
from __future__ import annotations

import argparse
import contextlib
import io
import json

import harness

# per configuration: sizes of the deployment and of its traffic at test size
SPEC = {
    "miniapp_upstream": {"queue_rows_per_mesh_rank": 1024},
}
MIX = {
    "r1": {"seed_blocks_min": 2, "seed_blocks_spread": 8, "block_rows": 16},
    "r4": {"seed_blocks_min": 2, "seed_blocks_spread": 8, "block_rows": 16},
}
CELLS = [w["name"] for w in harness.load_bench()["workloads"]]


def cell(name):
    c = harness.resolve(harness.load_bench(), name)
    c.spec.update(SPEC[c.config])
    c.mix.update(MIX[c.traffic])
    return c


def deployment(name):
    import jax

    c = cell(name)
    mod = harness.load_module(c.config_module)
    return c, mod.Deployment(c.spec, c.mix, jax.devices()[: c.chips])


def run(name, *, seed=2**31 + 7, seconds=0.5, trace=0):
    """Drive the rest of a run of cell ``name`` on the CPU; returns the
    result line as a dict."""
    import jax

    run_mod = harness.load_module(harness.HERE / "run.py")
    c = cell(name)
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=trace)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.execute(c, jax.devices()[: c.chips], args, on_chip=False)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
