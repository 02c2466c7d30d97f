"""Golden-output tests for the roofline reporters (ISSUE 10, satellite 3).

``roofline/report.py`` renders dry-run artifacts into the EXPERIMENTS.md
tables and ``roofline/inspect.py`` parses compiled HLO into the collective
byte inventory.  Both are read by humans chasing regressions, so their
output is pinned EXACTLY here — a formatting drift is a real break for the
diffing workflow, not cosmetics.

The inspect goldens cover both HLO result spellings — the bare shape list
of unoptimized/StableHLO text and the parenthesized tuple form the
optimized CPU/TPU HLO uses (one component per participant) — and close the
loop against the collective-budget law: parsing the COMPILED padded round
must recover the same payload byte total the lowering-level budget tests
pin (``R * peer_capacity * WORDS * 4``).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import ForwardConfig, enqueue, forward_work, make_queue
from repro.core import types as T

from helpers import make_rays, ray_proto

# importing the inspector force-sets XLA_FLAGS for its CLI use; restore the
# suite's 8-device setting so subprocess-spawning tests are unaffected
_saved_flags = os.environ.get("XLA_FLAGS")
from repro.roofline import inspect as RI  # noqa: E402
from repro.roofline import report as RR  # noqa: E402

if _saved_flags is None:
    os.environ.pop("XLA_FLAGS", None)
else:
    os.environ["XLA_FLAGS"] = _saved_flags

R, CAP = 8, 64
WORDS = T.pack_spec(ray_proto()).total_words


# ------------------------------------------------------------ report.py
def _artifact(name, rec, root):
    (root / name).write_text(json.dumps(rec))


def _ok(arch, shape, step, t_comp, t_mem, t_coll, dominant, mem_bytes, uf,
        coll_breakdown=None, tag=""):
    return {
        "status": "ok", "arch": arch, "shape": shape, "step": step,
        "tag": tag,
        "roofline": {
            "t_compute": t_comp, "t_memory": t_mem, "t_collective": t_coll,
            "dominant": dominant, "coll_breakdown": coll_breakdown or {},
        },
        "memory": {"peak_bytes_per_device": mem_bytes},
        "useful_flops_ratio": uf,
    }


@pytest.fixture
def artifacts(tmp_path, monkeypatch):
    monkeypatch.setattr(RR, "ARTIFACTS", tmp_path)
    _artifact("a__pod1.json", _ok(
        "toy", "train_1k", 12, 1.5, 0.8, 0.2, "compute", 12.3e9, 0.55,
    ), tmp_path)
    _artifact("b__pod1.json", _ok(
        "toy", "train_4k", 3, 0.4, 0.9, 0.1, "memory", 30.0e9, 0.40,
    ), tmp_path)
    _artifact("c__pod1.json", _ok(
        "big", "train_8k", 7, 0.2, 0.3, 0.6, "collective", 64.0e9, 0.35,
        coll_breakdown={"all-gather": 0.2, "all-to-all": 0.4},
    ), tmp_path)
    _artifact("d__pod1.json", {
        "status": "skip", "arch": "huge", "shape": "train_32k",
        "tag": "", "reason": "needs 512 chips",
    }, tmp_path)
    _artifact("e__pod1.json", {
        "status": "error", "arch": "bad", "shape": "train_1k",
        "tag": "", "error": "OOM during layout assignment",
    }, tmp_path)
    # excluded: wrong mesh tag in the file name
    _artifact("f__pod2.json", _ok(
        "other", "x", 1, 1.0, 0.1, 0.1, "compute", 1e9, 0.9,
    ), tmp_path)
    # excluded: file name matches but the record carries a different tag
    _artifact("g__pod1.json", _ok(
        "other", "y", 1, 1.0, 0.1, 0.1, "compute", 1e9, 0.9, tag="probe",
    ), tmp_path)
    return tmp_path


def test_roofline_table_golden(artifacts):
    assert RR.roofline_table("pod1") == "\n".join([
        "| arch | shape | step | t_comp | t_mem | t_coll | bound | HBM/chip | useful_F | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
        "| toy | train_1k | 12 | 1.50s | 800.0ms | 200.0ms | **comp** | "
        "12.3GB | 0.55 | cf=1.00; near compute roofline |",
        "| toy | train_4k | 3 | 400.0ms | 900.0ms | 100.0ms | **memo** | "
        "30.0GB | 0.40 | cf=0.44; cut bytes: fused/banded attention, bf16 CE, less remat |",
        "| big | train_8k | 7 | 200.0ms | 300.0ms | 600.0ms | **coll** | "
        "64.0GB | 0.35 | cf=0.33; dominant coll=all-to-all: reshard/overlap or shrink TP |",
        "| huge | train_32k | skip | - | - | - | - | - | - | needs 512 chips |",
        "| bad | train_1k | ERR | - | - | - | - | - | - | OOM during layout assignment |",
    ])


def test_roofline_summary_golden(artifacts):
    # ok records only, sorted ascending by compute fraction
    assert RR.summary("pod1") == [
        ("big", "train_8k", 7, "collective", 0.333, 64.0),
        ("toy", "train_4k", 3, "memory", 0.444, 30.0),
        ("toy", "train_1k", 12, "compute", 1.0, 12.3),
    ]


def test_roofline_load_filters_mesh_and_tag(artifacts):
    assert [r["arch"] for r in RR.load("pod1")] == [
        "toy", "toy", "big", "huge", "bad"
    ]
    assert [r["arch"] for r in RR.load("pod2")] == ["other"]
    assert [r["shape"] for r in RR.load("pod1", tag="probe")] == []


def test_fmt_s_units():
    assert RR._fmt_s(None) == "-"
    assert RR._fmt_s(1.0) == "1.00s"
    assert RR._fmt_s(0.0125) == "12.5ms"


# ----------------------------------------------------------- inspect.py
_SYNTHETIC_HLO = "\n".join([
    # bare shape list (StableHLO / unoptimized spelling)
    "  %ag = f32[8,64]{1,0} all-gather(f32[1,64]{1,0} %p), dimensions={0}",
    "  %ag2 = f32[8,64]{1,0} all-gather(f32[1,64]{1,0} %q), dimensions={0}",
    # tuple form (optimized HLO): bytes summed over every component
    "  %all-to-all.5 = (u32[1,16,9]{2,1,0}, u32[1,16,9]{2,1,0}) "
    "all-to-all(u32[1,16,9]{2,1,0} %a, u32[1,16,9]{2,1,0} %b)",
    # async start variant is counted once
    "  %ar = bf16[1024]{0} all-reduce-start(bf16[1024]{0} %x), to_apply=%add",
    # a get-tuple-element naming an all-to-all is NOT a collective op
    "  %gte = u32[1,16,9]{2,1,0} get-tuple-element((u32[1,16,9]{2,1,0}, "
    "u32[1,16,9]{2,1,0}) %all-to-all.5), index=0",
])


def test_top_collectives_synthetic_golden():
    got = RI.top_collectives(_SYNTHETIC_HLO)
    by_kind = {kind: b for (kind, _shape), b in got}
    # two identical all-gathers aggregate: 2 * 8*64*4
    assert by_kind["all-gather"] == 2 * 8 * 64 * 4
    # tuple form sums both components: 2 * 1*16*9 * 4
    assert by_kind["all-to-all"] == 2 * 16 * 9 * 4
    assert by_kind["all-reduce"] == 1024 * 2
    # exactly three inventory rows — the gte line contributed nothing
    assert len(got) == 3


def _compile_padded_round(mesh8, cfg):
    def kernel(_x):
        q = make_queue(ray_proto(), CAP)
        me = jax.lax.axis_index("data")
        q = enqueue(
            q, make_rays(10), ((me + jnp.arange(10)) % R).astype(jnp.int32),
            jnp.ones(10, bool),
        )
        nq, total = forward_work(q, cfg)
        return nq.count[None], total, nq.items.tmin

    return jax.jit(
        jax.shard_map(
            kernel, mesh=mesh8, in_specs=P("data"),
            out_specs=(P("data"), P(), P("data")),
        )
    ).lower(jnp.arange(8.0)).compile()


def test_top_collectives_recovers_budget_law_from_compiled_hlo(mesh8):
    """End to end: the inspector, reading only the optimized HLO text of a
    compiled padded round, re-derives the wire budget the lowering-level
    tests pin — ONE payload all_to_all of ``R*S*W*4`` bytes and ONE count
    all_to_all of ``R*4`` bytes."""
    cfg = ForwardConfig("data", R, CAP, exchange="padded")
    compiled = _compile_padded_round(mesh8, cfg)
    got = RI.top_collectives(compiled.as_text())
    a2a = sorted(b for (kind, _s), b in got if kind == "all-to-all")
    assert a2a == [R * 4, R * cfg.peer_capacity * WORDS * 4]
    # the only other traffic is the scalar count reduction
    others = [(k, b) for (k, _s), b in got if k != "all-to-all"]
    assert all(b <= R * R * 4 for _k, b in others), others


def test_buffer_report_golden(mesh8):
    class _Mem:
        argument_size_in_bytes = 2.0e9
        output_size_in_bytes = 5.0e8
        temp_size_in_bytes = 0.0

    class _Compiled:
        def memory_analysis(self):
            return _Mem()

    assert RI.buffer_report(_Compiled()) == "args=2.00GB out=0.50GB temp=0.00GB"

    class _Broken:
        def memory_analysis(self):
            raise RuntimeError("unsupported on this backend")

    assert RI.buffer_report(_Broken()) == "unsupported on this backend"

    # the real compiled round is tiny — every term rounds to 0.00GB
    cfg = ForwardConfig("data", R, CAP, exchange="padded")
    compiled = _compile_padded_round(mesh8, cfg)
    assert RI.buffer_report(compiled) == "args=0.00GB out=0.00GB temp=0.00GB"
