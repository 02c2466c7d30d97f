"""Whole runs of every cell at a small size on the CPU: the result line, the
refusal without a chip or without the program, and ``correct`` coming out
false when the timed path is broken underneath."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
from small_cells import CELLS, run

RUN = harness.HERE / "run.py"


def _args(workload):
    return [sys.executable, str(RUN), "--workload", workload, "--seed", "5",
            "--seconds", "1", "--trace", "0"]


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(_args("miniapp_upstream.r1"), env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A checkout that holds BENCHMARK.json and the benchmark's own files,
    and not the program, prints no result."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = _args("miniapp_upstream.r1")
    cmd[1] = str(tmp_path / "benchmarks" / "chip" / "run.py")
    out = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "repro" in out.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_small_run_prints_its_result(name, trace):
    res = run(name, trace=trace)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    cell = harness.resolve(harness.load_bench(), name)
    if trace:
        # on the CPU no device trace exists: only the program's counters
        counters = {m["name"] for m in cell.per_layer if m["source"] == "program_counter"}
        assert set(res["metrics"]) == counters
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


# ---------------------------------------------------------------- faults
# Each configuration lists in configs/<config>_faults.py the faults planted
# in its timed path, underneath the harness, the way a faulty change could.

def _faults(name):
    cell = harness.resolve(harness.load_bench(), name)
    mod = harness.load_module(cell.config_module.with_name(f"{cell.config}_faults.py"))
    across = getattr(mod, "ACROSS_CHIPS", set())
    return {f: plant for f, plant in mod.FAULTS.items() if f not in across or cell.chips > 1}


FAULTS = {c: _faults(c) for c in CELLS}
CASES = [(c, f) for c in CELLS for f in FAULTS[c]]


@pytest.mark.parametrize("name,fault", CASES)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    FAULTS[name][fault](monkeypatch)
    res = run(name)
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
    json.dumps(res)
