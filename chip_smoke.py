"""Smoke run of RaFI's forwarding path on a TPU: one 1024×1024 VoPaT frame.

Drives the main path — ``repro.apps.vopat.renderer`` → ``run_until_done`` →
``forward_work`` → the padded exchange — at deployment size: 1,048,576 path
rays of 44 bytes (12 packed words) in a queue of 2²⁰ rows, one process, one
chip (or four with ``--chips 4``).  Every phase checks its result against a
reference; any failure raises, exits nonzero and prints no ok line.

  A  XLA path, scatter marshal: done, no drops, a sane image
  B  XLA path, sort marshal: bitwise equal to A (the marshal law)
  C  Pallas kernels, both marshal modes: bitwise equal to A
  D  a 128×128 frame on the host CPU against the same frame on the chip

``--chips 4`` runs only the multi-chip phase: the frame on a 4-rank mesh
against the 1-rank render of the same frame, bitwise, and prints which
device held each rank.

Run on a machine with a TPU:  python chip_smoke.py [--chips 4]
The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SIZE = 1024  # frame edge: 2²⁰ primary paths at 1 spp
CPU_SIZE = 128  # phase D frame edge


def scene(size):
    from repro.apps.vopat import VopatScene

    return VopatScene(width=size, height=size, spp=1, max_bounces=4, albedo=0.85)


def mesh_of(devices):
    from repro import compat

    return compat.make_mesh((len(devices),), ("data",), devices=devices)


def check(cond, what):
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def run_phase(name, devices, size, **kw):
    """Render ``size``² twice on ``devices``: the first call compiles, the
    second is the warm render.  Prints the phase line; returns (image,
    stats)."""
    from repro.apps import vopat

    run = vopat.renderer(mesh_of(devices), scene(size), **kw)
    t0 = time.perf_counter()
    run()
    t1 = time.perf_counter()
    img, stats = run()  # renderer returns host arrays: the device is done
    t2 = time.perf_counter()
    warm = t2 - t1
    rays = size * size
    check(stats["done"], f"{name}: frame finished before max_rounds")
    check(stats["drops"] == 0, f"{name}: no ray dropped")
    print(
        f"phase {name}: {size}x{size} on {len(devices)} {devices[0].platform} "
        f"device(s) {kw} compile_s={t1 - t0 - warm:.3f} render_s={warm:.4f} "
        f"rounds={stats['rounds']} rays_per_s={rays / warm:.1f}",
        flush=True,
    )
    return img, stats


def check_image(img, name):
    """The bounds of tests/test_apps.py::TestVopat::test_image_is_sane."""
    check(np.isfinite(img).all(), f"{name}: finite image")
    check(0.0 <= img.min() and img.max() <= 1.0 + 1e-6, f"{name}: radiance in [0, 1]")
    check(img.std() > 0.01, f"{name}: not a constant field")


def one_chip(jax):
    dev = jax.devices()[:1]
    img_a, _ = run_phase("A xla/scatter", dev, SIZE, marshal="scatter")
    check_image(img_a, "A")
    img_b, _ = run_phase("B xla/sort", dev, SIZE, marshal="sort")
    check(np.array_equal(img_a, img_b), "B: sort image bitwise equals scatter image")
    for marshal in ("scatter", "sort"):
        img_c, _ = run_phase(f"C pallas/{marshal}", dev, SIZE, marshal=marshal,
                             use_pallas=True)
        check(np.array_equal(img_a, img_c), f"C pallas/{marshal}: bitwise equals A")
    img_t, _ = run_phase("D chip", dev, CPU_SIZE, marshal="scatter")
    img_h, _ = run_phase("D cpu", jax.devices("cpu")[:1], CPU_SIZE, marshal="scatter")
    diff = np.abs(img_t - img_h)
    rel = abs(img_t.mean() - img_h.mean()) / max(abs(img_h.mean()), 1e-12)
    print(
        f"phase D compare: max_abs_diff={diff.max():.3e} "
        f"differing_pixels={np.mean(diff > 0):.4f} rel_mean_diff={rel:.3e}",
        flush=True,
    )
    check(rel <= 1e-2, "D: image means of chip and CPU agree within 1e-2")
    return 1


def four_chips(jax):
    devs = jax.devices()[:4]
    check(len(devs) == 4, f"--chips 4 needs four devices, found {len(jax.devices())}")
    img4, s4 = run_phase("R=4", devs, SIZE, marshal="scatter")
    img1, _ = run_phase("R=1", devs[:1], SIZE, marshal="scatter")
    held = [f"{d.platform}:{d.id}" for d in s4["devices"]]
    print(f"R=4 rank shards on devices: {held}", flush=True)
    check(len(set(held)) == 4, "R=4: the four ranks ran on four distinct devices")
    check(np.array_equal(img1, img4), "R=4 image bitwise equals R=1 image")
    return 4


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache(ROOT)}", flush=True)
    import jax

    from repro.kernels import default_interpret

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}", file=sys.stderr)
        return 1
    if default_interpret():
        print("Pallas kernels would run in interpret mode", file=sys.stderr)
        return 1
    count = four_chips(jax) if args.chips == 4 else one_chip(jax)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": count},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
