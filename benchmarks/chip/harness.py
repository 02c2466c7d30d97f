"""The chip benchmark's cell table, job draw and measured window.

Everything here is found by name from ``BENCHMARK.json``:

  configs/<config>.json   the deployment's sizes, as run
  configs/<config>.py     how to build it on R ranks, drive one job, and
                          compare the answers with the plain reference;
                          ``SMALL``, the sizes a CPU test replaces; and
                          ``jobs(mix, seed, ranks)``, which draws the
                          window's jobs of its traffic kinds from the seed
  configs/<config>_reference.py   the plain reference
  configs/<config>_faults.py      ``FAULTS``, the faults planted in the
                          timed path that the comparison must catch
  traffic/<mix>.json      parameters that the configuration's ``jobs``
                          turns into the window's jobs
  traffic/<mix>.small.json        the parameters a CPU test replaces
  metrics/<metric>.py     ``read(run) -> float | None`` for one metric;
                          ``<base>.<part>`` falls back to ``metrics/<base>.py``,
                          so one reader serves a quantity split by the
                          end-to-end metric it moves

so a later cell, configuration or metric is added as files and entries,
without an edit here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import time
from pathlib import Path
from typing import Any, Callable, Dict, Iterable, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    config: str
    traffic: str
    chips: int
    spec: Dict[str, Any]
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def config_module(self) -> Path:
        return HERE / "configs" / f"{self.config}.py"


def load_bench(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def resolve(bench: Dict[str, Any], name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name``, with its configuration, traffic and the
    metrics it reports.  Raises ``KeyError`` for an unknown cell."""
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / cfg["file"]) as f:
        spec = json.load(f)
    with open(HERE / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name, w["config"], w["traffic"], int(w["chips"]), spec, mix, e2e, layer)


def load_module(path: Path):
    """Import a benchmark file by its path (names may hold dots)."""
    mod_name = "chipbench_" + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[["Run"], Optional[float]]:
    """``read`` of ``metrics/<name>.py``, else of ``metrics/<base>.py`` for a
    name ``<base>.<part>``.  Raises ``FileNotFoundError`` when neither is
    there."""
    for stem in (name, name.rpartition(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if stem and path.is_file():
            return load_module(path).read
    raise FileNotFoundError(f"no reader metrics/{name}.py for metric {name!r}")


# ------------------------------------------------------------------ traffic

def jobs(cell: Cell, seed: int) -> List[Any]:
    """The jobs a window of ``cell`` may run, in order, drawn from ``seed``
    before the window opens, so that a deployment can put them on the
    device in set-up: ``jobs(mix, seed, ranks)`` of the cell's
    configuration module."""
    draw = load_module(cell.config_module).jobs
    return list(draw(cell.mix, seed, cell.chips))


# ------------------------------------------------------------------ window

@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""

    cell: Cell
    setup_s: float
    window_s: float
    jobs: List[Dict[str, Any]]
    trace: Any = None  # tracefile.Summary of the traced part, if traced
    traced: List[Dict[str, Any]] = dataclasses.field(default_factory=list)


def drive(deploy, stream: Iterable[Any], seconds: float, *, unit: int = 1, on_job=None,
          clock: Callable[[], float] = time.perf_counter):
    """Run jobs back to back until ``seconds`` have passed and the jobs run
    are a whole number of ``unit``s (the traffic's ``window_unit_jobs``): the
    jobs in flight at the deadline finish and count.  Raises
    ``RuntimeError`` when ``stream`` ends first.  Each record gets ``t0`` and
    ``t1``, seconds from the window's start to the job's dispatch and to its
    answer on the host.  ``on_job(record)`` runs after each job, outside
    its timing.  ``clock`` gives the time in seconds.  Returns ``(records,
    window_s)``."""
    records: List[Dict[str, Any]] = []
    start = clock()
    for job in stream:
        t0 = clock()
        rec = deploy.run(job)
        t1 = clock()
        rec["t0"], rec["t1"] = t0 - start, t1 - start
        records.append(rec)
        if on_job is not None:
            on_job(rec)
        if t1 - start >= seconds and len(records) % unit == 0:
            break
    else:
        raise RuntimeError(f"the {len(records)} jobs drawn ran out before the window closed")
    return records, records[-1]["t1"]
