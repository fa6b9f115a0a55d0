"""Sweep the mixed solver's inner tolerance on the live backend.

The mixed-precision walkthrough solve (`solver.gmres_ir`) trades refinement
sweeps against inner iterations: each sweep costs one HIGH-precision
residual matvec (double-float pairwise tiles + emulated-f64 dense ops —
tens of times an f32 inner iteration at scale), while a tighter
``inner_tol`` costs extra f32 Krylov iterations. The r3 default (1e-4) was
chosen by total-inner-iteration count; at shell-6000 scale the hi matvec
dominates, so fewer sweeps may win. This script measures the actual wall
per solve across an inner_tol ladder at a given scene scale.

Usage:
    python scripts/mixed_tune.py [--shell-n 6000] [--tols 1e-3,1e-4,1e-5,3e-6]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shell-n", type=int, default=6000)
    ap.add_argument("--body-n", type=int, default=400)
    ap.add_argument("--tol", type=float, default=1e-10)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--tols", type=str, default="1e-3,1e-4,1e-5,3e-6")
    ap.add_argument("--refine-impls", type=str, default="",
                    help="comma list to sweep refine_pair_impl at the best "
                         "inner_tol, e.g. 'df,pallas_df,exact'")
    args = ap.parse_args()
    from skellysim_tpu.params import REFINE_PAIR_IMPLS

    impls = [s for s in args.refine_impls.split(",") if s]
    bad = set(impls) - set(REFINE_PAIR_IMPLS)
    if bad:
        # dataclasses.replace skips System.__init__'s validation; a typo'd
        # name would silently bench the exact tile under the wrong label —
        # and must fail HERE, not after the minutes-long inner_tol sweep
        raise SystemExit(f"unknown refine impls: {sorted(bad)}")

    import jax

    jax.config.update("jax_enable_x64", True)
    from skellysim_tpu.utils.bootstrap import enable_compilation_cache

    enable_compilation_cache("auto")
    import jax.numpy as jnp
    import numpy as np

    import bench

    t0 = time.perf_counter()
    system, state = bench._walkthrough_state(args.shell_n, args.body_n,
                                             jnp.float64, args.tol, True)
    print(json.dumps({"backend": jax.default_backend(),
                      "shell_n": args.shell_n,
                      "setup_s": round(time.perf_counter() - t0, 1)}),
          flush=True)

    best = (None, float("inf"))
    for tol_s in args.tols.split(","):
        inner = float(tol_s)
        system.params = dataclasses.replace(system.params, inner_tol=inner)
        # params live on `self`, not in the jit signature: rebuild the jit
        # wrapper so the new inner_tol is baked into a fresh program
        out = bench._solve_rate(system, state, trials=args.trials)
        print(json.dumps({"inner_tol": inner, **out}), flush=True)
        if out["residual_true"] <= args.tol and out["wall_s"] < best[1]:
            best = (inner, out["wall_s"])

    if impls and best[0] is None:
        # no swept inner_tol validated against --tol: benching impls at an
        # arbitrary tolerance would misread as a validated winner
        print(json.dumps({"refine_impl_sweep": "skipped",
                          "reason": f"no inner_tol reached {args.tol}"}),
              flush=True)
        impls = []
    for impl in impls:
        system.params = dataclasses.replace(
            system.params, inner_tol=best[0], refine_pair_impl=impl)
        out = bench._solve_rate(system, state, trials=args.trials)
        print(json.dumps({"refine_pair_impl": impl,
                          "inner_tol": best[0], **out}), flush=True)


if __name__ == "__main__":
    main()
