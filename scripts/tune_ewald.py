#!/usr/bin/env python3
"""Ewald crossover tuning harness (run on the real TPU when reachable).

Scans plan knobs (target_occ, max_grid) against dense at a ladder of node
counts and prints one JSON line per measurement — the data behind the
near/far balance defaults in `ops.ewald.plan_ewald` and the
`ewald_crossover` section of bench.py. Usage:

    python scripts/tune_ewald.py [--sizes 40000,160000,640000] \
        [--occ 16,32,64] [--grids 256,384,448] [--tol 1e-4]

Each measurement times to a host fetch and reports rel err vs dense on a 512-target subsample.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="40000,160000,640000")
    ap.add_argument("--occ", default="16,32,64")
    ap.add_argument("--grids", default="448")
    ap.add_argument("--tol", type=float, default=1e-4)
    ap.add_argument("--trials", type=int, default=2)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from skellysim_tpu.utils.bootstrap import enable_compilation_cache

    enable_compilation_cache("auto")

    from skellysim_tpu.ops import ewald as ew
    from skellysim_tpu.ops import kernels

    print(json.dumps({"backend": jax.default_backend(),
                      "device": str(jax.devices()[0])}), flush=True)

    rng = np.random.default_rng(100)
    for n in [int(s) for s in args.sizes.split(",")]:
        n_fibers = max(1, n // 64)
        box = 20.0 * (n / 640000.0) ** (1 / 3)
        origins = rng.uniform(-box / 2, box / 2, (n_fibers, 3))
        dirs = rng.normal(size=(n_fibers, 3))
        dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
        t = np.linspace(0, 1.0, 64)
        r = (origins[:, None, :] + t[None, :, None]
             * dirs[:, None, :]).reshape(-1, 3)[:n]
        rj = jnp.asarray(r, dtype=jnp.float32)
        f = jnp.asarray(rng.standard_normal((n, 3)), dtype=jnp.float32)

        np.asarray(kernels.stokeslet_direct(rj, rj, f, 1.0, impl="mxu"))
        t0 = time.perf_counter()
        for _ in range(args.trials):
            out = kernels.stokeslet_direct(rj, rj, f, 1.0, impl="mxu")
        np.asarray(out)
        dense_wall = (time.perf_counter() - t0) / args.trials
        sub = np.random.default_rng(0).choice(n, size=min(n, 512),
                                              replace=False)
        uD = np.asarray(kernels.stokeslet_direct(rj, rj[sub], f, 1.0))
        print(json.dumps({"n": n, "dense_wall_s": round(dense_wall, 4)}),
              flush=True)

        for occ in [float(s) for s in args.occ.split(",")]:
            for grid in [int(s) for s in args.grids.split(",")]:
                try:
                    t0 = time.perf_counter()
                    plan = ew.plan_ewald(r, eta=1.0, tol=args.tol,
                                         max_grid=grid, target_occ=occ)
                    np.asarray(ew.stokeslet_ewald(plan, rj, rj, f))
                    first = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    for _ in range(args.trials):
                        uE = ew.stokeslet_ewald(plan, rj, rj, f)
                    uE = np.asarray(uE)
                    wall = (time.perf_counter() - t0) / args.trials
                    err = (np.linalg.norm(uE[sub] - uD)
                           / max(np.linalg.norm(uD), 1e-300))
                    print(json.dumps({
                        "n": n, "occ": occ, "grid": grid,
                        "wall_s": round(wall, 4), "first_s": round(first, 1),
                        "speedup": round(dense_wall / max(wall, 1e-9), 2),
                        "rel_err": float(err), "M": plan.M,
                        "near_mode": plan.near_mode, "K": plan.K,
                        "max_occ": plan.max_occ}), flush=True)
                except Exception as e:
                    print(json.dumps({"n": n, "occ": occ, "grid": grid,
                                      "error": repr(e)[:160]}), flush=True)


if __name__ == "__main__":
    main()
