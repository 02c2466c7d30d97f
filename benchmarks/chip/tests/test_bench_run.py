"""Whole runs of every cell at a small size on the CPU: the result line, the
refusal without a chip or without the program, and ``correct`` coming out
false when the timed path is broken underneath."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
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
# Each breaks the timed path underneath the harness, in the program's own
# drive (repro.core.termination), the way a faulty change could.

def _unchanged(monkeypatch, T):
    """A round that returns its state unchanged."""
    orig = T.drive_segment

    def segment(round_fn, carry, cfg, **kw):
        return orig(lambda q, aux, rnd, **_: (q, aux), carry, cfg, **kw)

    monkeypatch.setattr(T, "drive_segment", segment)


def _half(monkeypatch, T):
    """Half of every round's rows left out of the forward."""
    orig = T.forward_work

    def forward(q, cfg, **kw):
        lane = jnp.arange(q.dest.shape[0])
        dest = jnp.where(lane >= (q.count + 1) // 2, -1, q.dest)
        return orig(dataclasses.replace(q, dest=dest), cfg, **kw)

    monkeypatch.setattr(T, "forward_work", forward)


def _no_exchange(monkeypatch, T):
    """The exchange between chips left out: every row stays on its rank."""
    orig = T.forward_work

    def forward(q, cfg, **kw):
        me = jax.lax.axis_index(cfg.axis_name)
        return orig(dataclasses.replace(q, dest=jnp.where(q.dest >= 0, me, q.dest)), cfg, **kw)

    monkeypatch.setattr(T, "forward_work", forward)


def _altered(monkeypatch, T):
    """A few delivered items altered where the forward produces them."""
    orig = T.forward_work

    def forward(q, cfg, **kw):
        out = orig(q, cfg, **kw)
        lane = jnp.arange(out[0].dest.shape[0])

        def alter(a):
            sel = (lane < 8).reshape((-1,) + (1,) * (a.ndim - 1))
            return jnp.where(sel, a + 1, a).astype(a.dtype)

        new_q = dataclasses.replace(out[0], items=jax.tree.map(alter, out[0].items))
        return (new_q,) + tuple(out[1:])

    monkeypatch.setattr(T, "forward_work", forward)


FAULTS = {"unchanged": _unchanged, "half": _half, "no_exchange": _no_exchange,
          "altered": _altered}
CASES = [(c, f) for c in CELLS for f in FAULTS
         if f != "no_exchange" or harness.resolve(harness.load_bench(), c).chips > 1]


@pytest.mark.parametrize("name,fault", CASES)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.core import termination

    FAULTS[fault](monkeypatch, termination)
    res = run(name)
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
    json.dumps(res)
