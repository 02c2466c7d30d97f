"""Cells of the benchmark cut to a size a CPU test can hold, and a helper that
drives the rest of a run (everything after the look for a chip).

A configuration brings its test sizes as ``SMALL`` in ``configs/<config>.py``
and a traffic mix as ``traffic/<mix>.small.json``; each replaces those keys
of the sizes as run."""
from __future__ import annotations

import argparse
import contextlib
import io
import json

import harness

CELLS = [w["name"] for w in harness.load_bench()["workloads"]]


def small_traffic(traffic):
    return harness.HERE / "traffic" / f"{traffic}.small.json"


def cell(name):
    c = harness.resolve(harness.load_bench(), name)
    c.spec.update(harness.load_module(c.config_module).SMALL)
    with open(small_traffic(c.traffic)) as f:
        c.mix.update(json.load(f))
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
