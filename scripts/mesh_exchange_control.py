#!/usr/bin/env python3
"""The control of the four-chip cell's exchange, on the chip: the benchmark's
own run of `free_fibers_mesh4.run` (`chipbench/run.py` `run_cell`:
`build_simulation` -> `System.run` on four chips -> `check.check_window`
with the plain reference) on the cell's scene with every fiber BENT to an
arc — sound, and with one ring hop dropped under it.

Why a bent scene: the cell's own fibers are straight and free, stay
tension-free and exert no force on the fluid, so every block its rings carry
is zeros to rounding and `correct` cannot see a dropped hop THERE (PERF.md
section 7 row 10). A bent fiber's bending force and tension drive a flow at
every other fiber: the exchanged flows are part of the answer, and the plain
reference (which sums all pairs on its own, in float64) holds them. Same
fibers, same box, same program, same compiled step (bending is data); the
only change is `Fiber.fill_node_positions`, replaced here and nowhere else,
because `chipbench/scene.py`'s generators make straight fibers only.

Sound: `correct` has to come out true (``ref_residual`` <= ``gmres_tol``).
Dropped hop: `correct` has to come out FALSE by the reference alone — the
program's own residual is taken with the same broken ring and reports
nothing (``failed`` 0).

    chiprun --chips 4 -- python scripts/mesh_exchange_control.py
    # off the chip, at a size a CPU steps (not a device number):
    python scripts/mesh_exchange_control.py --cpu --n-fibers 64 --box 3.7

Every line it prints is kept in ``chiprun_out/mesh_exchange_control.jsonl``;
the exit code is 0 only where both cases came out as they have to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "free_fibers_mesh4.run"
OUT = os.path.join(ROOT, "chiprun_out", "mesh_exchange_control.jsonl")


def emit(**row):
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as fh:
        fh.write(line + "\n")


def bend_the_scene(curvature: float) -> None:
    """Every fiber the scene builder lays is a circular arc of this
    curvature from its origin, leaving along its direction, bent toward the
    coordinate axis it is least along (nothing drawn: the scene's seed
    decides origins and directions as before)."""
    import numpy as np

    from skellysim_tpu.config import Fiber

    def fill_node_positions(self, x0, normal):
        x0, t = np.asarray(x0, float), np.asarray(normal, float)
        n = np.cross(t, np.eye(3)[np.argmin(np.abs(t))])
        n /= np.linalg.norm(n)
        k, s = curvature, np.linspace(0.0, self.length, self.n_nodes)
        x = (x0[None, :] + (np.sin(k * s) / k)[:, None] * t[None, :]
             + ((1.0 - np.cos(k * s)) / k)[:, None] * n[None, :])
        self.x = x.ravel().tolist()

    Fiber.fill_node_positions = fill_node_positions


def drop_one_ring_hop() -> None:
    """The `lax.ppermute` ring with its last position left out: each target
    misses the flow of one neighbour's sources — in the right-hand side, in
    the Krylov loop and in the program's own explicit residual alike
    (`tests/test_mesh_run.py` plants the same fault at a test's size)."""
    import jax
    from jax import lax

    from skellysim_tpu.parallel import ring

    def dropped(block_fn, axis_name, n_dev, u0, *rotating, unroll=False):
        perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
        u, rot = u0, tuple(rotating)
        for _ in range(n_dev - 1):
            nxt = jax.tree_util.tree_map(
                lambda a: lax.ppermute(a, axis_name, perm), rot)
            u = u + block_fn(*rot)
            rot = nxt
        return u

    ring._ring_accumulate = dropped


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="sound,dropped_hop")
    ap.add_argument("--seconds", type=float, default=8.0,
                    help="the window of each case (three steps are checked)")
    ap.add_argument("--seed", type=int, default=2147520301)
    ap.add_argument("--curvature", type=float, default=2.0)
    ap.add_argument("--cpu", action="store_true",
                    help="four CPU devices in the chip's place (a dry run)")
    ap.add_argument("--n-fibers", type=int)
    ap.add_argument("--box", type=float)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    for p in (ROOT, os.path.join(ROOT, "chipbench")):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    import run as harness

    find_cell = harness.find_cell

    def find_resized(root, workload):
        bench, cell, entry, cfg, traffic = find_cell(root, workload)
        cfg = json.loads(json.dumps(cfg))
        if args.n_fibers:
            cfg["n_fibers"] = args.n_fibers
        if args.box:
            cfg["box"] = args.box
        if args.cpu:
            cfg["params"]["kernel_impl"] = "exact"   # no Mosaic off the chip
        return bench, cell, entry, cfg, traffic

    harness.find_cell = find_resized
    if args.cpu:
        harness.require_accelerator = lambda chips: {
            "platform": "cpu", "kind": "TPU v5 lite", "count": chips}
    bend_the_scene(args.curvature)
    emit(start="mesh_exchange_control", cell=CELL, curvature=args.curvature,
         seed=args.seed, seconds=args.seconds, cpu=args.cpu,
         n_fibers=args.n_fibers, box=args.box)

    ok = True
    for case in args.cases.split(","):
        if case == "dropped_hop":
            drop_one_ring_hop()
        t0 = time.perf_counter()
        res = harness.run_cell(argparse.Namespace(
            workload=CELL, seed=args.seed, seconds=args.seconds, trace=0))
        ref = res["checks"]["ref_residual"]
        # sound: correct. Broken: not correct, by the reference alone
        good = (res["correct"] if case == "sound" else
                (not res["correct"] and ref["value"] > ref["limit"]
                 and res["failed"] == 0))
        ok &= bool(good)
        emit(case=case, as_it_has_to_be=bool(good), correct=res["correct"],
             checks=res["checks"], attempted=res["attempted"],
             failed=res["failed"], iters=res["run"]["iters"],
             step_wall_s=res["metrics"].get("step_wall_s", {}).get("value"),
             device=res["device"], case_seconds=round(
                 time.perf_counter() - t0, 1))
    emit(ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
