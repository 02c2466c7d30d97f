"""A configuration joins the benchmark as files and entries only.

A stub second configuration, a sort of int32 keys with a traffic kind that
only it draws, is added to a copy of the benchmark: its sizes, module,
plain reference and planted faults, its traffic and the traffic's test
size, its metrics' readers, and its entries in ``BENCHMARK.json``.  No file
that was there changes.  The copy's own tests, unchanged, then pass on the
stub: it resolves to its files, draws its jobs from the seed, runs at its
test size with and without a trace, comes out not correct under each of
its faults, and the benchmark file keeps its shape.
"""
import json
import os
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET
from pathlib import Path

import harness

CELL = "stub_sort.keys"

STUB_FILES = {
    "configs/stub_sort.json": json.dumps({
        "deployment": "sort each job's keys on one chip and return the smallest top of them",
        "top": 256,
        "guarantees": ["exact: the smallest top keys of the job, in ascending order"],
    }),
    "configs/stub_sort.py": '''"""A stub deployment: each job sorts its int32 keys on the device and
returns the smallest ``top``."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from harness import load_module

REFERENCE = Path(__file__).with_name("stub_sort_reference.py")
SMALL = {"top": 8}


def jobs(mix, seed, ranks):
    rng = np.random.default_rng([seed % (1 << 64), ranks])
    shape = (mix["staged_jobs"], ranks * mix["keys_per_job"])
    return list(rng.integers(0, mix["key_max"], size=shape, dtype=np.int32))


class Deployment:
    def __init__(self, spec, mix, devices):
        self.top = spec["top"]
        self._fn = jax.jit(lambda keys: jnp.sort(keys)[: self.top])

    def warm(self, jobs):
        self._run = self._fn.lower(jax.ShapeDtypeStruct(jobs[0].shape, jnp.int32)).compile()

    def run(self, keys):
        return {"keys": keys, "top": np.asarray(self._run(keys))}

    def release(self):
        self._fn = self._run = None

    def check(self, records):
        ref = load_module(REFERENCE)
        bad = sum(not np.array_equal(r["top"], ref.top(r["keys"], self.top)) for r in records)
        return [("wrong_jobs", bad, 0)], bad
''',
    "configs/stub_sort_reference.py": '''import numpy as np


def top(keys, n):
    return np.sort(np.asarray(keys))[:n]
''',
    "configs/stub_sort_faults.py": '''import jax.numpy as jnp


def unchanged(monkeypatch):
    monkeypatch.setattr(jnp, "sort", lambda a, *args, **kw: a)


def altered(monkeypatch):
    orig = jnp.sort
    monkeypatch.setattr(jnp, "sort", lambda a, *args, **kw: orig(a, *args, **kw).at[0].add(1))


FAULTS = {"unchanged": unchanged, "altered": altered}
''',
    "traffic/stub_keys.json": json.dumps({
        "kind": "stub_keys", "keys_per_job": 4096, "key_max": 1 << 30,
        "staged_jobs": 64, "window_unit_jobs": 1,
    }),
    "traffic/stub_keys.small.json": json.dumps({"keys_per_job": 64, "staged_jobs": 50000}),
    "metrics/stub_keys_per_s.py": '''def read(run):
    return sum(len(j["keys"]) for j in run.jobs) / run.window_s
''',
    "metrics/stub_jobs.py": '''def read(run):
    return float(len(run.jobs))
''',
}

ENTRIES = {
    "configs": {"name": "stub_sort", "source": "https://numpy.org/doc/stable/reference/generated/numpy.sort.html",
                "file": "benchmarks/chip/configs/stub_sort.json", "reduced": [],
                "why": "a stub configuration with a traffic kind of its own"},
    "workloads": {"name": CELL, "config": "stub_sort", "traffic": "stub_keys", "chips": 1,
                  "why": "batches of 4096 random int32 keys back to back; bypasses forwarding"},
    "end_to_end": {"name": "stub_keys_per_s", "unit": "keys/s", "better": "higher", "bound": 0.05,
                   "source": "host_clock", "workloads": [CELL]},
    "per_layer": {"name": "stub_jobs", "unit": "jobs", "better": "higher",
                  "source": "program_counter", "layer": "stub", "moves": "stub_keys_per_s",
                  "workloads": [CELL]},
}

EXPECTED = {
    f"test_bench_cells.py::test_cell_resolves_to_its_files[{CELL}]",
    f"test_bench_cells.py::test_every_cell_draws_its_jobs_from_the_seed[{CELL}]",
    "test_bench_cells.py::test_benchmark_file_keeps_its_shape",
    "test_bench_cells.py::test_every_metric_has_a_reader[stub_keys_per_s]",
    "test_bench_cells.py::test_every_metric_has_a_reader[stub_jobs]",
    f"test_bench_run.py::test_small_run_prints_its_result[{CELL}-0]",
    f"test_bench_run.py::test_small_run_prints_its_result[{CELL}-1]",
    f"test_bench_run.py::test_broken_timed_path_is_not_correct[{CELL}-unchanged]",
    f"test_bench_run.py::test_broken_timed_path_is_not_correct[{CELL}-altered]",
}


def _files(root):
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_configuration_joins_as_files_only(tmp_path):
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(bench_dir)
    for rel, text in STUB_FILES.items():
        assert not (bench_dir / rel).exists(), rel
        (bench_dir / rel).write_text(text)
    bench = harness.load_bench()
    for key, entry in ENTRIES.items():
        bench[key] = bench[key] + [entry]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))

    after = _files(bench_dir)
    assert {rel: after[rel] for rel in before} == before
    assert set(after) - set(before) == {Path(rel) for rel in STUB_FILES}

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(harness.ROOT / "src"))
    report = tmp_path / "report.xml"
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "no:randomly",
         "-k", "stub or keeps_its_shape", f"--junitxml={report}", str(bench_dir / "tests")],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-4000:]
    passed = set()
    for case in ET.parse(report).iter("testcase"):
        if not list(case):  # no failure, error or skipped element
            module = case.get("classname").rpartition(".")[2]
            passed.add(f"{module}.py::{case.get('name')}")
    assert EXPECTED <= passed, sorted(EXPECTED - passed)
