"""Tests for the roofline analysis layer and the launch-time spec resolver."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_test_mesh
from repro.launch.steps import resolve_spec
from repro.roofline.analysis import (
    HW,
    RooflineTerms,
    collective_bytes,
    model_flops,
)


# -------------------------------------------------------------- HLO parsing
def test_collective_bytes_post_spmd_hlo():
    hlo = """
  %ag = bf16[16,512,128]{2,1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[256,1024]{1,0} all-reduce(%y), to_apply=%sum
  %a2a = (f32[64,32]{1,0}, f32[64,32]{1,0}) all-to-all(%a, %b)
  %rs = bf16[8,128]{1,0} reduce-scatter(%z), dimensions={0}
  %cp = f32[4,4]{1,0} collective-permute(%w)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 512 * 128 * 2
    assert out["all-reduce"] == 256 * 1024 * 4
    assert out["all-to-all"] == 2 * 64 * 32 * 4
    assert out["reduce-scatter"] == 8 * 128 * 2
    assert out["collective-permute"] == 16 * 4


def test_collective_bytes_stablehlo():
    txt = """
    %5 = "stablehlo.all_to_all"(%4) <{...}> : (tensor<256x44xf32>) -> tensor<256x44xf32>
    %6 = "stablehlo.all_reduce"(%5) ({ ... }) : (tensor<128xbf16>) -> tensor<128xbf16>
"""
    out = collective_bytes(txt)
    assert out["all-to-all"] == 256 * 44 * 4
    assert out["all-reduce"] == 128 * 2


def test_marshal_cost_model_scatter_undercuts_sort():
    """The marshal law: both modes make exactly ONE payload pass, and the
    scatter plan's O(C) bytes must undercut the sort's O(C log C) key traffic
    at every size (the whole point of the bucket-scatter marshal)."""
    from repro.roofline.analysis import marshal_cost_model

    for cap in (256, 4096, 1 << 16):
        send_rows = 2 * cap
        kw = dict(capacity=cap, item_bytes=44, send_rows=send_rows, num_ranks=256)
        sort = marshal_cost_model("sort", **kw)
        scat = marshal_cost_model("scatter", **kw)
        assert sort["payload_passes"] == scat["payload_passes"] == 1.0
        assert sort["payload_bytes"] == scat["payload_bytes"]
        assert scat["plan_bytes"] < sort["plan_bytes"]
        assert scat["total_bytes"] < sort["total_bytes"]
    with pytest.raises(ValueError):
        marshal_cost_model("bogus", capacity=8, item_bytes=4, send_rows=8)


def test_roofline_terms_dominance():
    t = RooflineTerms(
        flops=197e12 * 256,          # exactly 1 s of compute on 256 chips
        bytes_accessed=819e9 * 256 * 2,  # 2 s of HBM
        coll_bytes=50e9 * 256 * 0.5,     # 0.5 s of wire
        chips=256,
        coll_breakdown={},
    )
    assert abs(t.t_compute - 1.0) < 1e-9
    assert abs(t.t_memory - 2.0) < 1e-9
    assert abs(t.t_collective - 0.5) < 1e-9
    assert t.dominant == "memory"
    assert t.bound_time == t.t_memory


def test_model_flops_moe_counts_active_params_only():
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES

    dense = model_flops(get_config("qwen2-7b"), SHAPES["train_4k"])
    moe = model_flops(get_config("dbrx-132b"), SHAPES["train_4k"])
    from repro.models.api import build_model

    n_dbrx = build_model(get_config("dbrx-132b")).param_count()
    # top-4 of 16 experts ⇒ active fraction of the FFN share
    assert moe < 6 * n_dbrx * 256 * 4096
    assert moe > 0.2 * 6 * n_dbrx * 256 * 4096


# ----------------------------------------------------------- resolve_spec
class TestResolveSpec:
    mesh = make_test_mesh(data=2, model=4)

    def test_passthrough_when_divisible(self):
        s = resolve_spec((8, 12), P("data", "model"), self.mesh)
        assert s == P("data", "model")

    def test_drop_when_indivisible_no_move(self):
        s = resolve_spec((3, 5), P("data", "model"), self.mesh, allow_move=False)
        assert s == P(None, None)

    def test_move_to_divisible_dim(self):
        # 4 kv heads can't split model=4? they can; use 3 heads instead
        s = resolve_spec((4, 16, 3, 128), P("data", None, "model", None), self.mesh)
        assert s == P("data", "model", None, None) or s == P(
            "data", None, None, "model"
        )

    def test_tuple_axes_partial_keep(self):
        # batch 2 divides data(2) but not data×model(8)
        s = resolve_spec((2, 7), P(("data", "model"), None), self.mesh)
        assert s == P("data", None)

    @given(
        st.tuples(st.integers(1, 64), st.integers(1, 64), st.integers(1, 64)),
    )
    @settings(max_examples=50, deadline=None)
    def test_result_is_always_legal(self, shape):
        spec = P(("data", "model"), "model", None)
        # spec mentions model twice — dedup across dims must hold
        s = resolve_spec(shape, P(("data",), "model", None), self.mesh)
        used = []
        for i, part in enumerate(s):
            axes = () if part is None else (part if isinstance(part, tuple) else (part,))
            n = 1
            for a in axes:
                n *= self.mesh.shape[a]
                used.append(a)
            assert shape[i] % n == 0, (shape, s)
        assert len(used) == len(set(used))


# --------------------------------------------------------------- rebalance
def test_rebalance_under_heavy_skew(mesh8):
    """Straggler mitigation: 97%-skewed load ends within ±1 of the mean."""
    import dataclasses

    from repro.core import (
        DISCARD, ForwardConfig, WorkQueue, enqueue, make_queue, rebalance,
        work_item,
    )

    @work_item
    @dataclasses.dataclass
    class W:
        v: jax.Array

    proto = W(v=jnp.zeros(()))
    CAP = 256
    cfg = ForwardConfig("data", 8, CAP, peer_capacity=CAP, exchange="padded")

    def bal(_x):
        me = jax.lax.axis_index("data")
        q = make_queue(proto, CAP)
        n = jnp.where(me == 3, 199, jnp.where(me == 5, 7, 0))
        mask = jnp.arange(CAP) < n
        q = enqueue(q, W(v=jnp.arange(CAP, dtype=jnp.float32)), jnp.zeros(CAP, jnp.int32), mask)
        q = WorkQueue(items=q.items, dest=jnp.full((CAP,), DISCARD, jnp.int32),
                      count=q.count, drops=q.drops)
        nq, total = rebalance(q, cfg)
        return nq.count[None], total

    from jax.sharding import PartitionSpec as P

    f = jax.jit(jax.shard_map(bal, mesh=mesh8, in_specs=P("data"),
                              out_specs=(P("data"), P())))
    counts, total = f(jnp.arange(8.0))
    counts = np.asarray(counts)
    assert int(total) == 206
    # order-preserving ceil assignment: every rank ≤ ⌈total/R⌉, none idle
    assert counts.max() <= int(np.ceil(206 / 8))
    assert counts.sum() == 206
    assert counts.min() >= 206 - 7 * int(np.ceil(206 / 8))


# ------------------------------------------------------ compilation cache
@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test sets it."""
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_defaults_to_the_checkout(tmp_path, monkeypatch, cache_config):
    from repro.launch.cache import ENV_VAR, enable_compile_cache

    monkeypatch.delenv(ENV_VAR, raising=False)
    path = enable_compile_cache(tmp_path)
    assert path == tmp_path.resolve() / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(path)
    # the same checkout always gives the same directory
    assert enable_compile_cache(tmp_path) == path


def test_compile_cache_env_var_wins(tmp_path, monkeypatch, cache_config):
    from repro.launch.cache import ENV_VAR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "shared"))
    assert enable_compile_cache(tmp_path / "checkout") == tmp_path / "shared"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code
