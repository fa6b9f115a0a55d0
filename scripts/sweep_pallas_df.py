"""Sweep Pallas DF tile shapes on the live backend.

The exact Pallas tiles were swept in round 5 ((256, 1024) stokeslet /
(128, 2048) stresslet on v5e); the DF tiles hold ~3x the live temporaries,
so their VMEM-feasible frontier is different. This sweeps (tile_t, tile_s)
for both DF kernels, printing rate + accuracy per shape — run it on the
TPU and pin the winners as `ops.pallas_df.DF_TILE_T/S`.

Usage: python scripts/sweep_pallas_df.py [--n 16384] [--trials 2]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

TILES_T = (64, 128, 256)
TILES_S = (128, 256, 512, 1024)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument("--kernel", choices=("stokeslet", "stresslet", "both"),
                    default="both")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU smoke mode: force the CPU backend and run "
                         "the tiles in interpret mode")
    args = ap.parse_args()

    if args.interpret:
        from skellysim_tpu.utils.bootstrap import force_cpu_devices

        force_cpu_devices()
        # interpret mode evaluates grid cells at Python speed: the TPU
        # default (16384) would run for hours; clamp to smoke scale
        args.n = min(args.n, 512)
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.ops import kernels
    from skellysim_tpu.ops.pallas_df import (stokeslet_pallas_df,
                                             stresslet_pallas_df)

    n = args.n
    rng = np.random.default_rng(1)
    r = jnp.asarray(rng.uniform(-5, 5, (n, 3)), dtype=jnp.float64)
    f = jnp.asarray(rng.standard_normal((n, 3)), dtype=jnp.float64)
    S = jnp.asarray(rng.standard_normal((n, 3, 3)), dtype=jnp.float64)
    print(json.dumps({"backend": jax.default_backend(), "n": n}), flush=True)

    import bench  # shared timing helper (host-fetch barrier, see bench._rate)

    # accuracy oracle on a subsample (full f64 dense is slow on TPU);
    # compute only the selected kernels' references — emulated-f64 work for
    # a deselected kernel is pure waste on the chip
    sub = np.random.default_rng(0).choice(n, size=min(n, 256), replace=False)
    cases = []
    if args.kernel in ("stokeslet", "both"):
        cases.append(("stokeslet", stokeslet_pallas_df, f,
                      np.asarray(kernels.stokeslet_direct(r, r[sub], f, 1.0))))
    if args.kernel in ("stresslet", "both"):
        cases.append(("stresslet", stresslet_pallas_df, S,
                      np.asarray(kernels.stresslet_direct(r, r[sub], S, 1.0))))

    for tt, ts in itertools.product(TILES_T, TILES_S):
        for name, fn, payload, ref in cases:
            try:
                call = lambda: fn(r, r, payload, 1.0, tile_t=tt, tile_s=ts,
                                  interpret=args.interpret)
                rr = bench._rate(call, n * n, trials=args.trials)
                err = (np.linalg.norm(np.asarray(call())[sub] - ref)
                       / np.linalg.norm(ref))
                print(json.dumps({"kernel": name, "tile": [tt, ts],
                                  "gpairs_per_s": round(rr / 1e9, 3),
                                  "rel_err": float(err)}), flush=True)
            except Exception as e:
                print(json.dumps({"kernel": name, "tile": [tt, ts],
                                  "error": repr(e).splitlines()[0][:160]}),
                      flush=True)


if __name__ == "__main__":
    main()
