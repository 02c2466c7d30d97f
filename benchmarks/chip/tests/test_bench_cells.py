"""Every cell of BENCHMARK.json resolves to its files by name, the file keeps
the shape its format fixes, and the traffic generator draws every seed's
bursts from one mix."""
import dataclasses
import hashlib
import itertools
import json
import re

import numpy as np
import pytest

import harness
from small_cells import CELLS, small_traffic

BENCH = harness.load_bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = harness.resolve(BENCH, name)
    assert cell.config_module.is_file()
    assert cell.config_module.with_name(f"{cell.config}_reference.py").is_file()
    module = harness.load_module(cell.config_module)
    assert callable(module.Deployment)
    assert set(module.SMALL) <= set(cell.spec)
    with open(small_traffic(cell.traffic)) as f:
        assert set(json.load(f)) <= set(cell.mix)
    faults = harness.load_module(cell.config_module.with_name(f"{cell.config}_faults.py"))
    assert faults.FAULTS and all(callable(plant) for plant in faults.FAULTS.values())
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_a_reader(name):
    assert callable(harness.metric_reader(name))


def test_reader_falls_back_to_the_base_name():
    read = harness.metric_reader("idle_pct.any_cell")
    assert read.__code__.co_filename == str(harness.HERE / "metrics" / "idle_pct.py")
    for name in ("no_such", "no_such.part", ".part"):
        with pytest.raises(FileNotFoundError):
            harness.metric_reader(name)


@pytest.mark.parametrize(
    "name", [m["name"] for m in BENCH["per_layer"] if m["source"] == "device_trace"])
def test_trace_reader_without_a_trace_returns_nothing(name):
    metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
    cell = harness.resolve(BENCH, metric["workloads"][0])
    run = harness.Run(cell, setup_s=1.0, window_s=1.0, jobs=[{"rounds": 3, "deliveries": 5}])
    assert harness.metric_reader(name)(run) is None


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.resolve(BENCH, "no_such.cell")


def test_benchmark_file_keeps_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    reported = {n: set() for n in cells}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        for n in m.get("workloads", cells):
            reported[n].add(m["name"])
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        for n in m["workloads"]:
            assert m["moves"] in reported[n], (m["name"], n)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    texts = [w["why"] for w in cells.values()] + [c["why"] for c in BENCH["configs"]]
    texts += [c["source"] for c in BENCH["configs"]] + [m["layer"] for m in BENCH["per_layer"]]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    for n in list(cells) + list(configs) + [w["traffic"] for w in cells.values()]:
        assert NAME.match(n)


def _bursts(seed, n, traffic="r4"):
    cell = harness.resolve(BENCH, f"miniapp_upstream.{traffic}")
    return np.stack(harness.jobs(cell, seed)[:n])


@pytest.mark.parametrize("seed", [0, 2**31 + 5, -3, 2**70])
def test_bursts_are_drawn_from_the_seed(seed):
    a, b = _bursts(seed, 300), _bursts(seed, 300)
    assert np.array_equal(a, b)
    assert a.shape == (300, 4) and a.dtype == np.int32
    assert np.all(a % 128 == 0) and a.min() >= 10 * 128 and a.max() <= 137 * 128
    assert not np.array_equal(a, _bursts(seed + 1, 300))


def test_every_seed_offers_the_same_mix():
    """Each block of 128 bursts of a rank holds every size once, and any
    even number of bursts has the same total on every seed."""
    runs = [_bursts(seed, 256) for seed in (1, 2, 2**40)]
    for a in runs:
        for block in (a[:128], a[128:]):
            for r in range(a.shape[1]):
                assert sorted(block[:, r] // 128) == list(range(10, 138))
    for n in (2, 40, 202):
        totals = {int(a[:n].sum()) for a in runs}
        assert len(totals) == 1


def test_coupled_ranks_seed_the_same_rays_every_burst():
    a = _bursts(2**33 + 1, 64)
    pair = (2 * 10 + 127) * 128
    assert np.all(a[:, 0] + a[:, 1] == pair) and np.all(a[:, 2] + a[:, 3] == pair)


# the first 512 bursts of each traffic at two seeds, as the shared harness
# drew them before the draw moved into configs/miniapp_upstream.py
BURSTS_SHA256 = {
    ("r1", 2**31 + 7): "bbd9915f7c703512efa827d2e14d6092d403a8c7f20eb784f9e1d923a8107a8f",
    ("r1", 1414213562): "8a13ba3f9cb1abd9902d517a21390f153012271a23a85d990eb799e320c7e738",
    ("r4", 2**31 + 7): "61f98964609d718d9f00bf654731cd5d6c4b6445d27256d91d048c310a2d1b96",
    ("r4", 1414213562): "8d42320806a916973a8b35bfa4618790b8a8f91244263d237a004e60cc3271af",
}


@pytest.mark.parametrize("traffic,seed", list(BURSTS_SHA256))
def test_bursts_are_unchanged(traffic, seed):
    a = _bursts(seed, 512, traffic)
    assert a.dtype == np.int32 and a.shape == (512, 1 if traffic == "r1" else 4)
    assert hashlib.sha256(a.tobytes()).hexdigest() == BURSTS_SHA256[traffic, seed]


def _digest(jobs):
    return hashlib.sha256(json.dumps(
        jobs, default=lambda a: np.asarray(a).tolist(), sort_keys=True).encode()).hexdigest()


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_draws_its_jobs_from_the_seed(name):
    """Whatever its traffic kind, a cell's jobs come from the seed alone:
    the same seed draws the same jobs, another seed others, and a window
    can end on a whole unit of them."""
    cell = harness.resolve(BENCH, name)
    jobs = harness.jobs(cell, 2**33 + 3)
    assert len(jobs) >= cell.mix.get("window_unit_jobs", 1)
    assert _digest(jobs) == _digest(harness.jobs(cell, 2**33 + 3))
    assert _digest(jobs) != _digest(harness.jobs(cell, 2**33 + 4))


def test_a_kind_no_one_draws_is_refused():
    cell = harness.resolve(BENCH, "miniapp_upstream.r1")
    cell = dataclasses.replace(cell, mix=dict(cell.mix, kind="no_such_kind"))
    with pytest.raises(ValueError, match="no_such_kind"):
        harness.jobs(cell, 1)


class _FakeClock:
    """Seconds that pass only when a job runs."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


class _Clock:
    """A deployment whose jobs take 0.01 s each on its clock."""

    def __init__(self):
        self.clock = _FakeClock()

    def run(self, job):
        self.clock.now += 0.01
        return {"job": job}


def test_window_that_outruns_its_jobs_is_refused():
    deploy = _Clock()
    with pytest.raises(RuntimeError, match="ran out"):
        harness.drive(deploy, range(3), 1.0, clock=deploy.clock)


@pytest.mark.parametrize("traffic", ["r1", "r4"])
def test_jobs_are_drawn_before_the_window(traffic):
    cell = harness.resolve(BENCH, f"miniapp_upstream.{traffic}")
    jobs = harness.jobs(cell, 7)
    assert isinstance(jobs, list) and len(jobs) == cell.mix["staged_jobs"]
    assert len(jobs) % cell.mix["window_unit_jobs"] == 0


@pytest.mark.parametrize("unit", [1, 2, 3])
def test_window_ends_on_a_whole_unit(unit):
    deploy = _Clock()
    records, window_s = harness.drive(deploy, itertools.count(), 0.025, unit=unit,
                                      clock=deploy.clock)
    assert len(records) % unit == 0 and len(records) >= 3
    assert window_s >= 0.025 and window_s == records[-1]["t1"]
    assert [r["job"] for r in records] == list(range(len(records)))
