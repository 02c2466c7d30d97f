"""Integration tests for the five paper applications (§5).

The central claim under test is the paper's §5.1 statement that forwarding
"does not in any way change which rays are traced": every app must produce
R-invariant results (bitwise where the math allows it), and the §5.2 baseline
comparison must reproduce deep compositing's artifact mechanism.
"""
import numpy as np
import pytest

from repro import compat
from repro.apps import lander, nbody, schlieren, streamlines, vopat


@pytest.fixture(scope="module")
def mesh1():
    return compat.make_mesh((1,), ("data",))


# ---------------------------------------------------------------- VoPaT §5.1
class TestVopat:
    scene = vopat.VopatScene(width=16, height=16, spp=1, max_bounces=3)

    def test_r_invariance_bitwise(self, mesh1, mesh8):
        img1, s1 = vopat.render(mesh1, self.scene)
        img8, s8 = vopat.render(mesh8, self.scene)
        assert s1["drops"] == 0 and s8["drops"] == 0
        np.testing.assert_array_equal(img1, img8)

    def test_image_is_sane(self, mesh8):
        img, stats = vopat.render(mesh8, self.scene)
        assert np.isfinite(img).all()
        assert 0.0 <= img.min() and img.max() <= 1.0 + 1e-6
        assert img.std() > 0.01  # not a constant field
        assert stats["rounds"] < 512

    def test_truncated_frame_reports_not_done(self, mesh1):
        """A frame cut at ``max_rounds`` must say so: ``done`` is False and
        the image differs from the finished one."""
        full, s_full = vopat.render(mesh1, self.scene)
        cut, s_cut = vopat.render(mesh1, self.scene, max_rounds=2)
        assert s_full["done"] and s_full["rounds"] > 2
        assert not s_cut["done"] and s_cut["rounds"] == 2
        assert not np.array_equal(full, cut)

    def test_spp_accumulation_close(self, mesh1, mesh8):
        scene = vopat.VopatScene(width=8, height=8, spp=4)
        i1, _ = vopat.render(mesh1, scene)
        i8, _ = vopat.render(mesh8, scene)
        np.testing.assert_allclose(i1, i8, atol=1e-6)

    def test_pallas_sort_path_matches(self, mesh8):
        img_x, _ = vopat.render(mesh8, self.scene, use_pallas=False)
        img_p, _ = vopat.render(mesh8, self.scene, use_pallas=True)
        np.testing.assert_array_equal(img_x, img_p)


# --------------------------------------------------------------- Lander §5.2
class TestLander:
    scene = lander.LanderScene(width=16, height=16, num_slabs=32, samples_per_slab=4)

    def test_forwarding_r_invariant(self, mesh1, mesh8):
        f1, _ = lander.render_forwarding(mesh1, self.scene)
        f8, _ = lander.render_forwarding(mesh8, self.scene)
        np.testing.assert_array_equal(f1, f8)

    def test_deep_compositing_agrees_when_fragments_suffice(self, mesh8):
        """num_slabs/R = 4 segments per rank ⇒ F=4 fragments lose nothing."""
        fwd, _ = lander.render_forwarding(mesh8, self.scene)
        dc, stats = lander.render_deep_compositing(mesh8, self.scene, max_fragments=4)
        assert stats["dropped_fragments"] == 0
        np.testing.assert_allclose(dc, fwd, atol=1e-5)

    def test_deep_compositing_artifacts_when_fragments_overflow(self, mesh8):
        """The §5.2 limitation: too few fragment slots ⇒ dropped fragments ⇒
        artifacts — while the forwarding renderer is unaffected."""
        fwd, _ = lander.render_forwarding(mesh8, self.scene)
        dc, stats = lander.render_deep_compositing(mesh8, self.scene, max_fragments=1)
        assert stats["dropped_fragments"] > 0
        assert np.abs(dc - fwd).max() > 1e-3


# ------------------------------------------------------------ Schlieren §5.3
class TestSchlieren:
    scene = schlieren.SchlierenScene(width=16, height=16, num_slabs=32, samples_per_slab=4)

    def test_r_invariance_bitwise(self, mesh1, mesh8):
        u1, v1, _ = schlieren.render(mesh1, self.scene)
        u8, v8, _ = schlieren.render(mesh8, self.scene)
        np.testing.assert_array_equal(u1, u8)
        np.testing.assert_array_equal(v1, v8)

    def test_knife_edges_differ(self, mesh8):
        u, v, _ = schlieren.render(mesh8, self.scene)
        assert np.abs(u - v).max() > 0.01


# ---------------------------------------------------------- Streamlines §5.4
class TestStreamlines:
    cfg = streamlines.StreamlineConfig(num_particles=16, max_steps=24, dt=0.15)

    def test_matches_single_device_oracle(self, mesh8):
        tr8, lengths, stats = streamlines.run(mesh8, self.cfg)
        orc = streamlines.oracle(self.cfg)
        f8, fo = np.isfinite(tr8), np.isfinite(orc)
        np.testing.assert_array_equal(f8, fo)
        m = f8 & fo
        # XLA:CPU may fuse the RK4 chain differently inside the forwarding
        # while_loop vs the standalone oracle — ulp-level divergence is
        # expected; R-invariance below stays bitwise (same program).
        np.testing.assert_allclose(tr8[m], orc[m], atol=5e-4)
        assert stats["drops"] == 0

    def test_r_invariance(self, mesh1, mesh8):
        tr1, _, _ = streamlines.run(mesh1, self.cfg)
        tr8, _, _ = streamlines.run(mesh8, self.cfg)
        f1, f8 = np.isfinite(tr1), np.isfinite(tr8)
        np.testing.assert_array_equal(f1, f8)
        np.testing.assert_array_equal(tr1[f1], tr8[f8])

    def test_all_fields_terminate(self, mesh8):
        from repro.kernels.rk4_advect import ops as rk4

        for fid in (rk4.TORNADO, rk4.TAYLOR_GREEN):
            cfg = streamlines.StreamlineConfig(
                num_particles=8, max_steps=16, dt=0.2, field_id=fid
            )
            tr, lengths, stats = streamlines.run(mesh8, cfg)
            assert stats["rounds"] <= cfg.max_steps + 2
            assert (lengths >= 1).all()


# ---------------------------------------------------------------- NBody §5.5
class TestNBody:
    cfg = nbody.NBodyConfig(num_particles=64, steps=3, dt=1e-3, theta=0.3)

    def test_single_rank_matches_direct_sum(self, mesh1):
        p1, v1, s1 = nbody.run(mesh1, self.cfg)
        po, vo = nbody.oracle(self.cfg)
        np.testing.assert_allclose(p1, po, atol=1e-5)
        assert s1["drops"] == 0

    def test_multi_rank_approximation_and_conservation(self, mesh8):
        p8, v8, s8 = nbody.run(mesh8, self.cfg)
        po, vo = nbody.oracle(self.cfg)
        # particle count conserved every step (distributed migration intact)
        assert s8["totals"] == [self.cfg.num_particles] * self.cfg.steps
        assert s8["drops"] == 0
        # Barnes-Hut with octant refinement: positions stay close to direct sum
        assert np.abs(p8 - po).max() < 1e-2
        assert np.isfinite(v8).all()

    def test_three_contexts_coexist(self):
        """Structural: the three Listing-2 item types are distinct pytrees."""
        from repro.core import item_nbytes

        assert item_nbytes(nbody._p_proto()) == 9 * 4 + 4 + 4  # pos+vel+force+mass+uid
        assert item_nbytes(nbody._vp_proto()) == 3 * 4 + 4 + 4 + 4
        assert item_nbytes(nbody._rq_proto()) == 4
