"""The plain references agree with the program at a small size, and each
cell's control comes out wrong by its own numbers."""
import pytest

import harness
from small_cells import deployment

MINIAPP = ["miniapp_upstream.r1", "miniapp_upstream.r4"]


def _window(name, n):
    cell, d = deployment(name)
    jobs = harness.jobs(cell, 2**31 + 11)
    d.warm(jobs)
    return cell, d, [d.run(job) for job in jobs[:n]]


def _ok(checks):
    return all(value <= limit for _, value, limit in checks)


@pytest.mark.parametrize("name", MINIAPP)
def test_miniapp_replay_agrees_with_forward_work(name):
    """Every burst's rounds, deliveries and delivery digest, driven through
    run_until_done -> forward_work, equal the numpy replay's."""
    _, d, recs = _window(name, 6)
    d.release()
    checks, failed = d.check(recs)
    assert failed == 0 and all(value == 0 for _, value, _ in checks)
    assert all(r["deliveries"] > 0 and r["done"] for r in recs)


@pytest.mark.parametrize("name", MINIAPP)
def test_miniapp_control_fails(name):
    _, d, recs = _window(name, 4)
    d.release()
    assert not _ok(d.control(recs))


def test_replay_counts_upstream_schedule():
    """Hand-checked small bursts: at R=1 the seeds with odd tid survive the
    seed hash, and lanes 0 and 1 never go on."""
    ref = harness.load_module(harness.HERE / "configs" / "miniapp_upstream_reference.py")
    assert ref.burst([0]) == (0, 0, 0)
    assert ref.burst([2])[:2] == (1, 1)   # tid 1 arrives, sits at lane 0, ends
    assert ref.burst([4])[:2] == (1, 2)   # tids 1 and 3 arrive at lanes 0 and 1
    rounds, deliv, digest = ref.burst([9344])
    assert (rounds, deliv) == (20, 13931)
    assert ref.burst([9344], stable=False)[2] != digest
